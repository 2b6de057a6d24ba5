"""Fused on-device decode loop (DESIGN.md §7.1, device half).

The serving engine used to pay one jitted dispatch — plus a full host
round-trip for sampling and token commit — per generated token.  This
module fuses up to ``decode_chunk`` decode steps into a single jitted
``lax.while_loop`` so the device stays busy while the host only does
coarse bookkeeping (admission, paging, deadline sweeps) once per chunk,
the same amortization move the paper's §3 RgCSR kernel makes by running
many row groups per grid launch.

Three pieces live here because host and device must share them exactly:

* :func:`sample_tokens` — the pure ``(logits, key) → tokens`` sampler
  (greedy / temperature / top-k via ``lax.top_k``).  ``Engine._sample``
  calls it on host with the engine's split key; the fused loop calls it
  in-trace with a key threaded through the carry, so both paths produce
  identical streams for a given key sequence.
* :func:`make_decode_step` — the one decode-step factory.  The engine's
  per-step jit, the fused loop body, and ``launch/steps.py`` all route
  through it, so there is exactly one definition of "one decode step".
* :func:`build_fused_decode` — the jitted chunk runner.

Carry layout (one ``lax.while_loop`` iteration = one decode step)::

    (step, caches, cur_tok, remaining, active, key, block)

    step      ()            int32   steps executed so far
    caches    pytree                KV caches (donated — updated in place)
    cur_tok   (n_slots, 1)  int32   last sampled token per slot
    remaining (n_slots,)    int32   decode budget left per slot
    active    (n_slots,)    bool    slot still generating
    key       (2,)          uint32  PRNG key (split once per step, exactly
                                    like the host sampler)
    block     (k_max, n)    int32   sampled tokens, row i = step i

The predicate is ``step < n_steps AND any(active)`` — the loop early-
exits as soon as every slot has hit EOS or exhausted its budget, so a
chunk never burns device steps on a finished batch.  ``n_steps`` is a
*traced* scalar (the host clamps it to ``k_max``): varying the chunk
length at runtime — drain tails, fault-split chunks — reuses one
compiled executable instead of recompiling per length.

Finished slots keep decoding harmlessly inside a chunk: their block-
table pages are still allocated (the host frees them only when it
commits the chunk), out-of-range paged lookups land on the null page,
and dense out-of-bounds scatters drop under jit — the host commit loop
is the single authority on which rows/slots count.

The caches argument is donated (``donate_argnums``), so each dispatch
updates the KV buffers in place — no per-chunk copy of the pool.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["sample_tokens", "make_decode_step", "build_fused_decode"]


def sample_tokens(logits, key, temperature: float, top_k: int) -> jax.Array:
    """Pure ``(logits, key) → tokens`` sampler shared by host and device.

    ``logits`` is ``(b, s, V)`` — the last position is sampled in fp32.
    ``temperature <= 0`` is greedy argmax and consumes no key (callers
    may pass ``key=None``); otherwise top-k filtering uses
    ``jax.lax.top_k`` (O(V log k), vs the old full ``jnp.sort``) with
    ``top_k`` clamped to the vocab: ``k >= vocab`` keeps every token,
    ``k <= 0`` disables filtering.
    """
    logits = logits[:, -1, :].astype(jnp.float32)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    k = min(int(top_k), logits.shape[-1])
    if 0 < k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, k)[0][:, -1][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def make_decode_step(model, shape_kind: str = "decode"):
    """The one decode-step factory: ``(params, caches, tokens) →
    (logits, caches)``.  Engine per-step jit, fused loop body, and the
    launcher dry-run all build their step from here."""
    def decode_step(params, caches, tokens):
        return model.decode_step(params, caches, tokens,
                                 shape_kind=shape_kind)
    return decode_step


def build_fused_decode(model, cfg, on_dispatch=None):
    """Build the jitted fused chunk runner for one engine config.

    ``on_dispatch`` (optional) is called with the full output tuple after
    every dispatch, *inside* the returned callable.  The engine passes
    none: a hook that reads the output waits on the device.

    Returns ``fused(params, caches, cur_tok, remaining, active, key,
    n_steps) → (block, steps_ran, cur_tok, key, caches, logit_ok)`` where
    ``block`` is the static ``(k_max, n_slots)`` token block (rows past
    ``steps_ran`` are zero-padding the host never reads) and ``logit_ok``
    is the matching ``(k_max, n_slots)`` bool block: row i is per-slot
    "every last-position logit at step i was finite" — the host's
    commit-time NaN/Inf screen (DESIGN.md §7.6) reads it to stop
    committing a poisoned stream at the exact step the poison appeared.
    ``logit_ok`` rides at the END of the tuple so existing consumers of
    positions 0–4 keep working.  Sampling parameters (temperature,
    top-k, EOS) are baked in from ``cfg`` — they are per-engine
    constants, and baking them keeps the loop body free of host
    branches.
    """
    eos = int(cfg.eos_id)
    temperature = float(cfg.temperature)
    top_k = int(cfg.top_k)
    k_max = max(1, int(cfg.decode_chunk))
    decode = make_decode_step(model)

    def fused(params, caches, cur_tok, remaining, active, key, n_steps):
        n = cur_tok.shape[0]

        def cond(c):
            step, _, _, _, act, _, _, _ = c
            return (step < n_steps) & jnp.any(act)

        def body(c):
            step, caches, tok, rem, act, key, block, ok = c
            logits, caches = decode(params, caches, tok)
            # per-slot finiteness of the sampled position's logits —
            # NaN/Inf here means the KV pages this slot read are poisoned
            fin = jnp.all(jnp.isfinite(logits[:, -1, :].astype(jnp.float32)),
                          axis=-1)
            if temperature > 0.0:
                # one split per decode step — the exact key-consumption
                # cadence of the host sampler, so device streams match
                # host streams key-for-key
                key, sub = jax.random.split(key)
                nxt = sample_tokens(logits, sub, temperature, top_k)
            else:
                nxt = sample_tokens(logits, None, temperature, top_k)
            block = block.at[step].set(nxt)
            ok = ok.at[step].set(fin)
            rem = jnp.where(act, rem - 1, rem)
            done = rem <= 0
            if eos >= 0:
                done = done | (nxt == eos)
            return (step + 1, caches, nxt[:, None], rem, act & ~done,
                    key, block, ok)

        init = (jnp.zeros((), jnp.int32), caches, cur_tok, remaining,
                active, key, jnp.zeros((k_max, n), jnp.int32),
                jnp.ones((k_max, n), jnp.bool_))
        step, caches, tok, _, _, key, block, ok = jax.lax.while_loop(
            cond, body, init)
        return block, step, tok, key, caches, ok

    jitted = jax.jit(fused, donate_argnums=(1,))
    if on_dispatch is None:
        return jitted

    def fused_with_hook(*args):
        out = jitted(*args)
        on_dispatch(out)
        return out

    return fused_with_hook
