"""Plain reference for HPCG's operator and unpreconditioned CG.

HPCG's matrix (SAND2013-8752, §2) couples each point of an m³ grid to the
in-grid points of its 3×3×3 neighbourhood: 26 on the diagonal, −1 off it.
So A·u = 27·u − (sum of u over the 3×3×3 box, zero outside the grid),
which this module computes with shifted sums, not from any stored matrix.
``xp`` is ``numpy`` (float64: the reference) or ``jax.numpy`` (a lower
precision: the control).
"""
from __future__ import annotations

import numpy as np


def apply(u, m: int, xp=np):
    """A·u for HPCG's 27-point operator on an m³ grid (u flat, z-major)."""
    g = xp.pad(u.reshape(m, m, m), 1)
    s = g[:-2] + g[1:-1] + g[2:]
    s = s[:, :-2] + s[:, 1:-1] + s[:, 2:]
    s = s[:, :, :-2] + s[:, :, 1:-1] + s[:, :, 2:]
    return (27 * u.reshape(m, m, m) - s).reshape(-1)


def rhs(m: int, xp=np, dtype=np.float64):
    """b = A·1, so that the exact solution is all ones (HPCG §2)."""
    return apply(xp.ones(m ** 3, dtype), m, xp)


def cg(m: int, iterations: int, keep=(), xp=np, dtype=np.float64):
    """Unpreconditioned CG from x = 0 on A·x = b.

    Returns ``(resid, xs)``: ‖r_k‖ after each of ``iterations`` steps, and
    the iterate x_k for every k in ``keep``.  Every array and scalar is
    held in ``dtype``, so rounding happens in it after every operation.
    """
    def cast(a):          # no copy where the dtype already matches
        return xp.asarray(a, dtype=dtype)

    b = cast(rhs(m, xp, dtype))
    x = xp.zeros_like(b)
    r, p = b, b
    rr = xp.dot(r, r)
    resid, xs = [], {}
    for k in range(1, iterations + 1):
        ap = cast(apply(p, m, xp))
        alpha = cast(rr / xp.dot(p, ap))
        x = cast(x + alpha * p)
        r = cast(r - alpha * ap)
        rr_new = cast(xp.dot(r, r))
        p = cast(r + cast(rr_new / rr) * p)
        rr = rr_new
        resid.append(float(np.sqrt(np.float64(rr))))
        if k in keep:
            xs[k] = np.asarray(x, np.float64)
    return np.asarray(resid), xs
