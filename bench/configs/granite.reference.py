"""Plain float32 reference of the served granite-3-2b forward pass.

A pre-norm decoder as the configuration file states it: token embedding;
per layer RMSNorm, grouped-query attention with rotary positions
(half-split, theta from the file) and a causal mask, a residual add,
RMSNorm, a SiLU-gated MLP and a residual add; a final RMSNorm and logits
against the tied embedding over the published vocabulary.  Written from
those equations in plain ``jax.numpy`` at ``precision="highest"``, with no
cache, no batching tricks and no kernel; it reads the weights by their
names in the tree the benchmark made (``stack/body/0_attn/...``).

Departures of the served model from granite's published config (its
``*_multiplier`` and ``logits_scaling`` constants are 1 and its attention
scale is 1/sqrt(head_dim)) are the program's and are stated in the
configuration file; this reference follows the file.

``quantize`` (the control) rounds every matrix to float8 e4m3 with one
scale per output column before it is used.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F8_MAX = 448.0


def _fp8(w):
    """Round-trip a (..., d_in, d_out) matrix through float8 e4m3 with one
    scale per output column."""
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, D); positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params, tokens, cfg: dict, quantize: bool = False):
    """Logits (B, S, vocab) in float32 for token ids (B, S)."""
    q8 = _fp8 if quantize else (lambda w: w)
    hq, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    group = hq // hkv
    table = params["embed"]["table"].astype(jnp.float32)
    b, s = tokens.shape
    x = q8(table.T).T[tokens] if quantize else table[tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        h = _rmsnorm(x, f32(p["ln1"]["scale"]), eps)
        q = (h @ q8(f32(p["attn"]["q"]["kernel"]))).reshape(b, s, hq, dh)
        k = (h @ q8(f32(p["attn"]["k"]["kernel"]))).reshape(b, s, hkv, dh)
        v = (h @ q8(f32(p["attn"]["v"]["kernel"]))).reshape(b, s, hkv, dh)
        q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, group, axis=2)           # head i reads kv i//group
        v = jnp.repeat(v, group, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (dh ** -0.5)
        scores = jnp.where(causal, scores, -jnp.inf)
        attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = x + attn.reshape(b, s, hq * dh) @ q8(f32(p["attn"]["o"]["kernel"]))
        h = _rmsnorm(x, f32(p["ln2"]["scale"]), eps)
        ffn = p["ffn"]
        g = jax.nn.silu(h @ q8(f32(ffn["w_gate"]["kernel"])))
        u = h @ q8(f32(ffn["w_in"]["kernel"]))
        return x + (g * u) @ q8(f32(ffn["w_out"]["kernel"])), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, params["stack"]["body"]["0_attn"])
        h = _rmsnorm(x, params["final_norm"]["scale"].astype(jnp.float32),
                     eps)
        head = q8(table.T) if quantize else table.T
        return (h @ head)[..., : cfg["vocab_size"]]
