"""Shared arithmetic of the per-layer readers.

The program names its jitted programs; the readers find the fused decode
loop and the prefill by those names in the device trace (a renamed
program leaves its metric out rather than miscounting it).  A metric that
several cells report has one reader file per cell, each naming its
function here.
"""
from __future__ import annotations

import peaks
import work

FUSED_DECODE = "jit_fused"        # serve/device_loop.build_fused_decode
PREFILL = "jit__lambda"           # Engine._prefill, a jitted lambda


def decode_seconds(run):
    t = run.trace
    if t is None or not run.layer.get("decode_steps"):
        return None
    s = t.module_seconds(lambda n: n == FUSED_DECODE)
    return s if s > 0 else None


def prefill_seconds(run):
    t = run.trace
    if t is None or not run.layer.get("prefill_lengths"):
        return None
    s = t.module_seconds(lambda n: n == PREFILL)
    return s if s > 0 else None


def decode_bytes(run) -> float:
    lay, cfg = run.layer, run.layer["model"]
    return (lay["decode_steps"] * work.decode_step_bytes(cfg, 0)
            + work.kv_bytes_per_token(cfg) * lay["decode_context_tokens"])


def model_flops(run) -> float:
    """Model FLOPs of every prefill and decoded token in the slice."""
    lay, cfg = run.layer, run.layer["model"]
    flops = sum(work.prefill_flops(cfg, n) for n in lay["prefill_lengths"])
    flops += 2.0 * work.lm_matmul_params(cfg) * lay["decode_tokens"]
    flops += work.attention_flops(cfg, lay["decode_context_tokens"])
    return flops


def decode_step_ms(run):
    """Device milliseconds per decode step: time of the fused decode
    programs in the traced slice over the decode steps they ran."""
    s = decode_seconds(run)
    return None if s is None else 1e3 * s / run.layer["decode_steps"]


def decode_hbm_roofline(run):
    """Share (%) of the HBM roofline reached by the decode steps: every
    weight read once at the model's published dtype (bf16) plus the K/V of
    every live token at 81,920 B, over the fused decode programs' device
    time.  Counted from the model, not from how the program stores it."""
    s = decode_seconds(run)
    if s is None:
        return None
    return peaks.hbm_roofline_pct(decode_bytes(run), s,
                                  run.layer["device_kind"])


def serve_mfu(run):
    """Model FLOP utilization (%) of the whole serving step: model FLOPs of
    all prefill and decoded tokens in the traced slice over the device's
    busy seconds at the bf16 peak."""
    t = run.trace
    if t is None or t.busy_s <= 0 or "decode_tokens" not in run.layer:
        return None
    return peaks.flops_pct(model_flops(run), t.busy_s,
                           run.layer["device_kind"])


def device_idle(run):
    """Share (%) of the traced slice in which no op ran on the device."""
    t = run.trace
    return None if t is None else 100.0 * t.idle_share
