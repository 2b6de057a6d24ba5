"""Operations and bytes that the work needs, counted from its sizes.

These counts are the yardstick: they depend on the problem (the matrix,
the model as published) and never on the format, kernel or storage type
that computes it, so a PR that changes those is judged against the same
count.
"""
from __future__ import annotations

# ------------------------------------------------------------------ HPCG


def hpcg_rows(m: int) -> int:
    """Rows of HPCG's 27-point operator on an m³ local grid."""
    return m ** 3


def hpcg_nnz(m: int) -> int:
    """Nonzeros of the 27-point operator on an m³ grid: along each axis a
    point couples to 3 neighbours except at the two faces, (3m − 2)³."""
    return (3 * m - 2) ** 3


def cg_flops_per_iteration(n: int, nnz: int) -> int:
    """One unpreconditioned CG iteration: an SpMV (2·nnz), two dot
    products (2·2n) and three axpys (3·2n)."""
    return 2 * nnz + 10 * n


def spmv_min_bytes(n_rows: int, n_cols: int, nnz: int,
                   value_bytes: int = 4, index_bytes: int = 4) -> int:
    """Least HBM traffic of y = A·x in CSR terms: every nonzero's value and
    column once, the row pointers, x read once and y written once."""
    return (nnz * (value_bytes + index_bytes) + (n_rows + 1) * index_bytes
            + n_cols * value_bytes + n_rows * value_bytes)


# ----------------------------------------------------------- dense models


def lm_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's projections and the (tied) output head over the published
    vocabulary.  Norm scales and the embedding lookup are not products."""
    d, f, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_layer = d * hq + 2 * d * hkv + hq * d + 3 * d * f
    return L * per_layer + cfg["vocab_size"] * d


def lm_params(cfg: dict) -> int:
    """All parameters at the published sizes (norm scales included; the
    tied head shares the embedding)."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    return lm_matmul_params(cfg) + L * 2 * d + d


def attention_flops(cfg: dict, context: float) -> float:
    """Scores and weighted values for one query over ``context`` keys."""
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    return 4.0 * cfg["num_hidden_layers"] * hq * context


def decode_flops(cfg: dict, context: float) -> float:
    """Model FLOPs of one decoded token that attends to ``context``."""
    return 2.0 * lm_matmul_params(cfg) + attention_flops(cfg, context)


def prefill_flops(cfg: dict, length: int) -> float:
    """Model FLOPs of a causal prefill of ``length`` tokens."""
    return 2.0 * lm_matmul_params(cfg) * length + attention_flops(
        cfg, length * (length + 1) / 2.0)


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    """K and V of one cached token over every layer."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * dtype_bytes)


def decode_step_bytes(cfg: dict, live_tokens: float) -> float:
    """Least HBM traffic of one decode step: every weight once at the
    published dtype (bf16), and the K/V of every live token once."""
    return (lm_params(cfg) * cfg["published_dtype_bytes"]
            + kv_bytes_per_token(cfg) * live_tokens)
