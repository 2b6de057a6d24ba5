"""Jit'd public wrappers around the Pallas kernels + the plan/cache layer.

``RgCSRPlan`` is the device-resident execution plan built once per
(matrix, kernel config) — the analogue of a real framework's format-compile
step: the flat grouped storage reshaped into the ``(S, G)`` slot-major tile
the kernel consumes, plus the **step table** that drives the data-dependent
grid.  With ``chunks_per_step > 1`` every group's slot count is padded up to
a multiple of ``8·chunks_per_step`` so one grid step covers several 8-slot
chunks of the same group (DESIGN.md §3); the padding is exact zeros with
ghost column index 0, i.e. masked at plan time.

``PlanCache`` is the process-wide memo: SpMV-heavy paths (core dispatch, the
serving engine, the benchmark harness) fetch plans through ``get_plan``
instead of rebuilding host-side layouts per call.  Entries are keyed on
matrix identity + config and evicted when the matrix is garbage-collected.

On a TPU the kernels compile through Mosaic; on any other backend they run
in ``interpret=True`` mode — the kernel body executes in Python with
identical semantics (the CPU test path).  ``interpret=None`` resolves via
``jax.default_backend()``.
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import functools
import os
import threading
import weakref
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import ELLPACK, HybridEllCoo, RgCSR, ShardedRgCSR
from repro.kernels.ell_spmv import ell_spmv_pallas
from repro.kernels.rgcsr_spmm import rgcsr_spmm_pallas
from repro.kernels.rgcsr_spmv import (CHUNKS_PER_STEP_CHOICES, LANES,
                                      SUBLANES, merge_group_parts,
                                      rgcsr_spmv_pallas)

__all__ = ["RgCSRPlan", "make_plan", "rgcsr_spmv", "rgcsr_spmm",
           "EllPlan", "make_ell_plan", "ell_spmv", "hybrid_spmv",
           "default_interpret",
           "PlanCache", "PLAN_CACHE", "get_plan",
           "ShardedRgCSRPlan", "make_sharded_plan", "get_sharded_plan",
           "sharded_rgcsr_spmv", "sharded_rgcsr_spmm",
           "sharded_plan_cache_stats", "sharded_plan_placement",
           "plan_from_params", "warm_plans_from_params"]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class RgCSRPlan:
    """Kernel-ready layout for one RgCSR matrix at one kernel config.

    ``step_group``/``step_first`` form the coarsened step table: grid step
    ``s`` covers slot rows ``[R·s, R·(s+1))`` of ``values2d``/``columns2d``
    (``R = 8·chunks_per_step``) and belongs to group ``step_group[s]``.

    **Diagonal slot rows** (block plans, DESIGN.md §3.1): steps ``[0,
    diag_steps)`` hold rows whose lane ``l`` of group ``g`` is the entry at
    column ``g·G + l + d`` for one offset ``d`` per row (or an exact 0);
    ``diag_start`` gives each such row's start ``g·G + d + x_pad`` in x
    padded by ``x_pad`` zeros on both sides.  Steps ``[diag_steps,
    num_steps)`` hold the gathered rows.  Within each range a group's steps
    are consecutive and ``step_first`` marks its first.  ``columns2d`` stays
    valid on diagonal rows (true column, or an in-range column under a 0).

    **Adaptive plans** (``ordering='adaptive'``, DESIGN.md §5): groups hold
    length-sorted rows instead of consecutive ones, so the kernel's output
    lives in the *permuted* row space.  ``gather_idx``/``grouped_mask`` are
    the fused inverse-permutation map back to original rows, and rows longer
    than ``spill_threshold`` live in the COO tail (``spill_*``), combined
    with a segment-sum in the epilogue.  Block plans leave these ``None``.
    """

    values2d: Any       # (S, G)
    columns2d: Any      # (S, G) int32
    step_group: Any     # (num_steps,) int32
    step_first: Any     # (num_steps,) int32
    n_rows: int
    n_cols: int
    n_groups: int
    group_size: int
    chunks_per_step: int = 1
    # --- adaptive grouping (None/defaults on block plans) ---
    ordering: str = "block"        # "block" | "adaptive"
    spill_threshold: int = 0       # 0 = no spill
    nnz: int = -1                  # true nonzeros incl. spill (-1 = unknown)
    gather_idx: Any = None         # (n_rows,) int32: flat kernel-output index
    grouped_mask: Any = None       # (n_rows,) bool: False = row is spilled
    spill_values: Any = None       # (nnz_spill,)
    spill_rows: Any = None         # (nnz_spill,) int32 original row ids
    spill_columns: Any = None      # (nnz_spill,) int32
    # --- diagonal slot rows (0/None: every row is gathered) ---
    diag_steps: int = 0            # leading steps that hold diagonal rows
    diag_start: Any = None         # (diag_steps·R,) int32 slice starts
    # diag_start % G per step, the kernel's SMEM block: derived inside the
    # SpMV program instead, its relayout took ~10 s of TPU compile
    diag_shift: Any = None         # (diag_steps, 1, R) int32
    x_pad: int = 0                 # zeros on each side of the sliced x

    @property
    def num_steps(self) -> int:
        """Grid steps the SpMV kernel launches."""
        return int(self.step_group.shape[0])

    @property
    def num_chunks(self) -> int:
        """8-slot chunks covered (= num_steps · chunks_per_step)."""
        return self.num_steps * self.chunks_per_step

    @property
    def stored_slots(self) -> int:
        return int(self.values2d.shape[0])

    @property
    def n_spilled_elements(self) -> int:
        return 0 if self.spill_values is None else int(
            self.spill_values.shape[0])

    @property
    def diag_slot_fraction(self) -> float:
        """Share of stored slot rows whose x comes from contiguous slices
        (diagonal rows) instead of the scalar gather."""
        if self.stored_slots == 0:
            return 0.0
        return (self.diag_steps * self.chunks_per_step * SUBLANES
                / self.stored_slots)

    @property
    def stored_elements(self) -> int:
        """Grouped slots × lanes + COO tail (the format's byte footprint)."""
        return self.stored_slots * self.group_size + self.n_spilled_elements

    @property
    def padded_slot_fraction(self) -> float:
        """Fraction of stored elements that are padding (artificial zeros).

        The paper's fill-ratio metric normalized to stored bytes: on a
        memory-bound op this is directly the fraction of wasted HBM traffic.
        Requires ``nnz`` (set by ``make_plan``; -1 on raw param-view plans).
        """
        if self.nnz < 0 or self.stored_elements == 0:
            return 0.0
        return (self.stored_elements - self.nnz) / self.stored_elements


def make_plan(m: RgCSR, *, chunks_per_step: int = 1,
              ordering: str = "block",
              spill_threshold: int = 0) -> RgCSRPlan:
    """Host-side plan construction (format-compile).

    ``chunks_per_step`` coarsens the grid: each group's ``(K_g, G)`` tile is
    re-padded so ``K_g`` is a multiple of ``8·chunks_per_step`` and one grid
    step consumes the whole coarsened sub-tile.  The extra padding rows are
    exact zeros (ghost column 0), so in-kernel accumulation over them is a
    masked no-op — the paper's artificial-zeros accounting extended to the
    coarsened tile.  The trade (fewer grid steps vs more padded bytes) is
    what :mod:`repro.kernels.autotune` measures per matrix.

    ``ordering='adaptive'`` (DESIGN.md §5) regroups rows by descending
    length so same-length rows share groups (each group's slot count is its
    own max, not the max over an arbitrary consecutive window), and rows
    longer than ``spill_threshold`` (> 0) leave the grouped storage for a
    COO tail.  The kernel then computes in the permuted row space; the
    SpMV/SpMM wrappers fuse the inverse gather + tail back in.
    """
    if m.group_size % LANES != 0:
        raise ValueError(
            f"TPU plan needs group_size % {LANES} == 0, got {m.group_size} "
            f"(use group_size=128/256/512; smaller groups are modeled, not run "
            f"— DESIGN.md §2)")
    if m.slot_pad % SUBLANES != 0:
        raise ValueError(f"slot_pad must be a multiple of {SUBLANES}")
    if chunks_per_step not in CHUNKS_PER_STEP_CHOICES:
        raise ValueError(
            f"chunks_per_step must be one of {CHUNKS_PER_STEP_CHOICES}, "
            f"got {chunks_per_step}")
    if ordering not in ("block", "adaptive"):
        raise ValueError(
            f"ordering must be 'block' or 'adaptive', got {ordering!r}")
    if ordering == "adaptive":
        return _make_adaptive_plan(m, chunks_per_step=chunks_per_step,
                                   spill_threshold=int(spill_threshold))
    if spill_threshold:
        raise ValueError(
            "spill_threshold requires ordering='adaptive' (block grouping "
            "cannot drop rows without a permutation gather)")
    return _make_block_plan(m, chunks_per_step=chunks_per_step,
                            offset_slots=True)


def _make_block_plan(m: RgCSR, *, chunks_per_step: int,
                     offset_slots: bool) -> RgCSRPlan:
    """Block plan: group ``g`` holds rows ``[g·G, (g+1)·G)``.

    With ``offset_slots`` the groups whose rows share column offsets get
    diagonal slot rows (:func:`_offset_slots`); a matrix where none does
    keeps the CSR slotting of ``m`` as it is.
    """
    g = m.group_size
    rows_per_step = chunks_per_step * SUBLANES
    slots = np.asarray(m.slots_per_group).astype(np.int64)
    total_slots = int(slots.sum())
    values2d = np.asarray(m.values).reshape(total_slots, g)
    columns2d = np.asarray(m.columns).reshape(total_slots, g).astype(np.int32)
    base = dict(n_rows=m.shape[0], n_cols=m.shape[1], n_groups=m.n_groups,
                group_size=g, chunks_per_step=chunks_per_step, nnz=m.nnz)

    diag = _offset_slots(values2d, columns2d, slots,
                         np.asarray(m.row_lengths), group_size=g,
                         rows_per_step=rows_per_step,
                         n_cols=m.shape[1]) if offset_slots else None
    if diag is not None:
        sg_d, sf_d = _step_table(diag["diag_rows"], rows_per_step)
        sg_g, sf_g = _step_table(diag["gathered_rows"], rows_per_step)
        return RgCSRPlan(
            values2d=_stack(diag["values2d"]),
            columns2d=_stack(diag["columns2d"]),
            step_group=jnp.asarray(np.concatenate([sg_d, sg_g])),
            step_first=jnp.asarray(np.concatenate([sf_d, sf_g])),
            diag_steps=len(sg_d),
            diag_start=jnp.asarray(diag["diag_start"]),
            diag_shift=jnp.asarray((diag["diag_start"] % g).reshape(
                len(sg_d), 1, rows_per_step)),
            x_pad=diag["x_pad"], **base)

    values2d, columns2d, padded = _csr_rows(values2d, columns2d, slots,
                                            rows_per_step)
    step_group, step_first = _step_table(padded, rows_per_step)
    return RgCSRPlan(
        values2d=jnp.asarray(values2d),
        columns2d=jnp.asarray(columns2d),
        step_group=jnp.asarray(step_group),
        step_first=jnp.asarray(step_first),
        **base,
    )


def _stack(blocks):
    """Host blocks stacked on the device (one transfer each, no host copy)."""
    return jnp.concatenate([jnp.asarray(b) for b in blocks])


def _csr_rows(values2d, columns2d, slots, rows_per_step: int):
    """CSR-slotted storage with each group's rows re-padded to whole steps
    (exact zeros, ghost column 0); returns it and the padded row counts."""
    padded = _pad_to(slots, rows_per_step)
    if int(padded.sum()) == len(values2d):
        return values2d, columns2d, padded
    grown = padded - slots
    dst = np.arange(len(values2d)) + np.repeat(np.cumsum(grown) - grown,
                                               slots)
    vp = np.zeros((int(padded.sum()), values2d.shape[1]), values2d.dtype)
    cp = np.zeros(vp.shape, np.int32)
    vp[dst], cp[dst] = values2d, columns2d
    return vp, cp, padded


# Slot rows per host thread when re-slotting (up to 8 threads).
_SLOT_ROWS_PER_PART = 1 << 16


def _offset_slots(values2d, columns2d, slots, row_lens, *, group_size: int,
                  rows_per_step: int, n_cols: int):
    """Re-slot the groups whose rows share column offsets (DESIGN.md §3.1).

    ``values2d``/``columns2d``: the CSR-slotted ``(S, G)`` storage, group
    ``g`` owning ``slots[g]`` rows and lane ``l`` its row's entries in slots
    ``[0, row_lens[g·G + l])``.  Groups are independent, so runs of them
    are re-slotted on host threads (:func:`_offset_slots_part`).

    Returns None when no group takes a diagonal row, else the new storage
    as blocks to stack (diagonal rows of every group first, then gathered
    rows), each group's padded row counts in both parts, the diagonal
    rows' slice starts in x padded by ``x_pad`` on both sides, and
    ``x_pad``.
    """
    g = group_size
    n_groups = len(slots)
    lens = np.zeros(n_groups * g, np.int32)
    lens[: len(row_lens)] = row_lens
    lens = lens.reshape(n_groups, g)
    first = np.concatenate([[0], np.cumsum(slots)])
    # runs of groups of about equal storage, re-slotted on host threads
    n_parts = max(1, min(8, int(first[-1]) // _SLOT_ROWS_PER_PART))
    cut = np.unique(np.searchsorted(
        first, np.linspace(0, first[-1], n_parts + 1)[1:-1]))
    edges = [0, *cut[(cut > 0) & (cut < n_groups)].tolist(), n_groups]

    def part(lo, hi):
        rows = slice(first[lo], first[hi])
        return _offset_slots_part(
            values2d[rows], columns2d[rows], slots[lo:hi], lens[lo:hi],
            first_group=lo, rows_per_step=rows_per_step, n_cols=n_cols)

    with concurrent.futures.ThreadPoolExecutor(
            min(len(edges) - 1, os.cpu_count() or 1)) as pool:
        parts = list(pool.map(part, edges[:-1], edges[1:]))
    if all(p is None for p in parts):
        return None
    for i, (lo, hi) in enumerate(zip(edges[:-1], edges[1:])):
        if parts[i] is None:            # no diagonal row: CSR slotting
            rows = slice(first[lo], first[hi])
            vg, cg, padded = _csr_rows(values2d[rows], columns2d[rows],
                                       slots[lo:hi], rows_per_step)
            parts[i] = dict(vd=vg[:0], cd=cg[:0], start=np.zeros(0, np.int32),
                            vg=vg, cg=cg, diag_rows=np.zeros_like(padded),
                            gathered_rows=padded)
    start = np.concatenate([p["start"] for p in parts])
    x_pad = int(max(0, -int(start.min()), int(start.max()) + g - n_cols))
    return dict(values2d=[p["vd"] for p in parts] + [p["vg"] for p in parts],
                columns2d=[p["cd"] for p in parts] + [p["cg"] for p in parts],
                diag_rows=np.concatenate([p["diag_rows"] for p in parts]),
                gathered_rows=np.concatenate(
                    [p["gathered_rows"] for p in parts]),
                diag_start=start + np.int32(x_pad), x_pad=x_pad)


def _offset_slots_part(values2d, columns2d, slots, lens, *, first_group: int,
                       rows_per_step: int, n_cols: int):
    """Diagonal slot rows for a run of groups starting at ``first_group``
    (``lens``: its ``(n, G)`` row lengths).  Per group:

    1. candidates are the offsets ``d = column − row`` of its longest row;
       a lane's entry matches a candidate positionally (same slot, same
       offset) or, where rows differ in which offsets they have, by lookup;
    2. candidates are ranked by how many lanes they serve, and the group
       takes the ranked prefix of ``t`` diagonal rows that minimises its
       padded slot count ``pad_R(t) + pad_R(max leftover per lane)``,
       preferring more diagonal rows on ties.  ``t = 0`` is the CSR
       slotting, so no group grows.  A prefix qualifies only if every
       offset in it serves at least two lanes and the rows serve, on
       average, at least half of the group's non-empty lanes: offsets
       that rows share by chance (random and power-law rows) would leave
       the diagonal rows nearly empty;
    3. diagonal rows keep candidate (CSR) order, so for rows with ascending
       columns the summation order is unchanged but for added zeros; the
       leftover entries stay in CSR order in the group's gathered rows.

    Returns None when no group of the run takes a diagonal row, else its
    diagonal block (values, columns, slice starts ``g·G + d``) and
    gathered block, and each group's padded row counts in both.  int32
    on ``(S, G)`` arrays throughout.
    """
    g = values2d.shape[1]

    def pad(k):                                  # whole steps; 0 stays 0
        return _pad_to(k, rows_per_step)

    n_slots = values2d.shape[0]
    n_groups = len(slots)
    slots = np.asarray(slots, np.int64)
    first = np.zeros(n_groups, np.int64)
    first[1:] = np.cumsum(slots)[:-1]
    grp = np.repeat(np.arange(n_groups, dtype=np.int32), slots)
    k = np.arange(n_slots, dtype=np.int32) - first[grp].astype(np.int32)
    n_cand = lens.max(axis=1)
    group_row0 = (first_group + np.arange(n_groups, dtype=np.int32)) * g
    row0 = group_row0[grp]
    # few fresh (S, G) arrays, filled in place: each costs page faults
    offset = np.take(lens, grp, axis=0)
    valid = offset > k[:, None]
    np.subtract(columns2d, row0[:, None], out=offset)
    offset -= np.arange(g, dtype=np.int32)
    is_cand = k < n_cand[grp]
    cand = offset[np.arange(n_slots), lens.argmax(axis=1)[grp]]   # (S,)

    # hits[r, l]: lane l has an entry at candidate row r's offset
    positional = offset == cand[:, None]
    positional &= valid
    positional &= is_cand[:, None]
    hits = positional.copy()
    unmatched = np.greater(valid, positional)     # valid and not positional
    some = np.nonzero(unmatched.any(axis=1))[0]
    src_r, src_l = np.nonzero(unmatched[some])
    src_r = some[src_r]
    tgt_r = np.zeros(0, np.int64)
    if len(src_r):
        # look the rest up among the group's candidates: key (group, offset)
        shift = np.int64(first_group + n_groups) * g
        span = np.int64(n_cols) + shift + 1
        crow = np.nonzero(is_cand)[0]
        ckey = grp[crow].astype(np.int64) * span + cand[crow] + shift
        order = np.argsort(ckey, kind="stable")
        ckey, crow = ckey[order], crow[order]
        qkey = grp[src_r].astype(np.int64) * span + offset[src_r, src_l] \
            + shift
        at = np.minimum(np.searchsorted(ckey, qkey), len(ckey) - 1)
        tgt = crow[at]
        # a repeated column keeps its slot: one entry per (candidate, lane)
        found = np.nonzero((ckey[at] == qkey) & ~positional[tgt, src_l])[0]
        _, once = np.unique(tgt[found] * np.int64(g) + src_l[found],
                            return_index=True)
        found = found[once]
        src_r, src_l, tgt_r = src_r[found], src_l[found], tgt[found]
        hits[tgt_r, src_l] = True

    # rank candidates by lanes served; cost of each ranked prefix
    cover = np.count_nonzero(hits, axis=1).astype(np.int32)
    cover[~is_cand] = 0
    rank = np.lexsort((k, -cover, grp))          # stays within each group
    # most_left[p]: the longest lane's leftover once its group's ranked
    # candidates up to position p are diagonal rows; one pass per rank
    # position over the groups that have it
    most_left = np.empty(n_slots, np.int32)
    by_slots = np.argsort(-slots, kind="stable")
    n_with = np.searchsorted(-slots[by_slots], -np.arange(int(slots.max())),
                             side="left")
    left = lens[by_slots]
    for j, n_j in enumerate(n_with):
        at = first[by_slots[:n_j]] + j
        left[:n_j] -= hits[rank[at]]
        most_left[at] = left[:n_j].max(axis=1)
    t = k + 1                                    # prefix size at each rank
    served = np.cumsum(cover[rank], dtype=np.int64)
    served -= np.concatenate([[0], served[first[1:] - 1]])[grp]
    active = (lens > 0).sum(axis=1)
    ok = (cover[rank] >= 2) & (2 * served >= t * active[grp].astype(np.int64))
    cost = pad(t.astype(np.int64)) + pad(most_left.astype(np.int64))
    top = int(slots.max()) + 1
    never = np.iinfo(np.int64).max
    best = np.minimum.reduceat(np.where(ok, cost * top + (top - t), never),
                               first)
    cost0 = pad(np.maximum(n_cand, 1).astype(np.int64))
    take = (best != never) & (best // top <= cost0)
    if not take.any():
        return None
    n_diag = np.where(take, top - best % top, 0)
    chosen = np.zeros(n_slots, bool)
    chosen[rank] = k < n_diag[grp]

    # --- diagonal rows: the chosen candidates in candidate order
    diag_rows = pad(n_diag)
    left_at_take = most_left[first + np.maximum(n_diag, 1) - 1]
    gathered_rows = np.where(take, pad(left_at_take.astype(np.int64)),
                             pad(slots))       # as _csr_rows, if not taken
    vd = np.zeros((int(diag_rows.sum()), g), values2d.dtype)
    cd = np.empty(vd.shape, np.int32)
    dfirst = np.concatenate([[0], np.cumsum(diag_rows)[:-1]])
    sel = np.nonzero(chosen)[0]
    n_sel = np.cumsum(chosen)
    dst = dfirst[grp[sel]] + n_sel[sel] - 1 - np.concatenate(
        [[0], n_sel[first[1:] - 1]])[grp[sel]]
    start = np.repeat(group_row0, diag_rows)     # g·G + d; pad rows d = 0
    start[dst] += cand[sel]
    picked = np.take(values2d, sel, axis=0)
    np.copyto(picked, 0, where=~np.take(positional, sel, axis=0))
    vd[dst] = picked
    looked = chosen[tgt_r]
    where = np.zeros(n_slots, np.int64)
    where[sel] = dst
    vd[where[tgt_r[looked]], src_l[looked]] = \
        values2d[src_r[looked], src_l[looked]]
    np.add(start[:, None], np.arange(g, dtype=np.int32), out=cd)
    np.clip(cd, 0, max(n_cols - 1, 0), out=cd)

    # --- gathered rows: the rest, compacted per lane in CSR order
    vg = np.zeros((int(gathered_rows.sum()), g), values2d.dtype)
    cg = np.zeros(vg.shape, np.int32)
    if len(vg):
        used = positional & chosen[:, None]
        used[src_r[looked], src_l[looked]] = True
        rest = valid & ~used
        gfirst = np.concatenate([[0], np.cumsum(gathered_rows)[:-1]])
        upto = np.cumsum(rest, axis=0, dtype=np.int32)
        before = np.zeros((n_groups, g), np.int32)
        before[1:] = upto[first[1:] - 1]
        rr, ll = np.nonzero(rest)
        out = gfirst[grp[rr]] + upto[rr, ll] - 1 - before[grp[rr], ll]
        vg[out, ll] = values2d[rr, ll]
        cg[out, ll] = columns2d[rr, ll]
    return dict(vd=vd, cd=cd, start=start, vg=vg, cg=cg,
                diag_rows=diag_rows, gathered_rows=gathered_rows)


def _step_table(padded_slots: np.ndarray, rows_per_step: int):
    """(step_group, step_first) for per-group padded slot counts (a group
    with 0 slots gets no step)."""
    steps_per_group = (padded_slots // rows_per_step).astype(np.int64)
    n_groups = len(steps_per_group)
    step_group = np.repeat(np.arange(n_groups, dtype=np.int32),
                           steps_per_group)
    first_idx = np.cumsum(np.concatenate([[0], steps_per_group[:-1]]))
    step_first = np.zeros(len(step_group), dtype=np.int32)
    step_first[first_idx[steps_per_group > 0]] = 1
    return step_group, step_first


def _make_adaptive_plan(m: RgCSR, *, chunks_per_step: int,
                        spill_threshold: int) -> RgCSRPlan:
    """Length-aware regrouping + pathological-row spill (DESIGN.md §5).

    1. rows with nnz > ``spill_threshold`` (if > 0) leave for the COO tail;
    2. remaining rows are permuted by descending length (stable), so each
       group of ``G`` rows has near-uniform lengths and its slot count
       ``K_g = roundup(max len in group, 8·chunks_per_step)`` carries
       minimal padding under the alignment constraint;
    3. the kernel output is in permuted space — ``gather_idx`` maps original
       row ``r`` to its flat output lane, ``grouped_mask`` marks spilled
       rows (their value comes from the tail's segment-sum alone).
    """
    from repro.core.ordering import descending_from_lengths, split_spill_rows

    g = m.group_size
    rows_per_step = chunks_per_step * SUBLANES
    n_rows, n_cols = m.shape
    row_lens = np.asarray(m.row_lengths).astype(np.int64)
    csr_v, csr_c, row_ptr = m.to_csr_arrays()

    grouped_rows, spilled_rows = split_spill_rows(row_lens, spill_threshold)
    order = descending_from_lengths(row_lens[grouped_rows])
    perm = grouped_rows[order]                 # position p holds row perm[p]
    n_grouped = len(perm)
    n_groups = max(1, -(-n_grouped // g))

    # per-group slot counts: own max length, aligned to the step granularity
    slots = np.empty(n_groups, dtype=np.int64)
    for gi in range(n_groups):
        rows_g = perm[gi * g: (gi + 1) * g]
        k_g = int(row_lens[rows_g].max()) if len(rows_g) else 0
        slots[gi] = -(-max(k_g, 1) // rows_per_step) * rows_per_step
    offsets = np.concatenate([[0], np.cumsum(slots)[:-1]])

    values2d = np.zeros((int(slots.sum()), g), np.asarray(m.values).dtype)
    columns2d = np.zeros((int(slots.sum()), g), np.int32)
    for p in range(n_grouped):
        r = int(perm[p])
        gi, lane = p // g, p % g
        lo, hi = int(row_ptr[r]), int(row_ptr[r + 1])
        base = int(offsets[gi])
        values2d[base: base + (hi - lo), lane] = csr_v[lo:hi]
        columns2d[base: base + (hi - lo), lane] = csr_c[lo:hi]

    step_group, step_first = _step_table(slots, rows_per_step)

    gather_idx = np.zeros(n_rows, np.int32)
    grouped_mask = np.zeros(n_rows, bool)
    gather_idx[perm] = np.arange(n_grouped, dtype=np.int32)
    grouped_mask[perm] = True

    spill_sel = np.zeros(len(csr_v), bool)
    for r in spilled_rows:
        spill_sel[int(row_ptr[r]): int(row_ptr[r + 1])] = True
    spill_row_ids = np.repeat(
        spilled_rows.astype(np.int32),
        (row_ptr[spilled_rows + 1] - row_ptr[spilled_rows]).astype(np.int64)
        if len(spilled_rows) else np.empty(0, np.int64))

    return RgCSRPlan(
        values2d=jnp.asarray(values2d),
        columns2d=jnp.asarray(columns2d),
        step_group=jnp.asarray(step_group),
        step_first=jnp.asarray(step_first),
        n_rows=n_rows,
        n_cols=n_cols,
        n_groups=n_groups,
        group_size=g,
        chunks_per_step=chunks_per_step,
        ordering="adaptive",
        spill_threshold=spill_threshold,
        nnz=m.nnz,
        gather_idx=jnp.asarray(gather_idx),
        grouped_mask=jnp.asarray(grouped_mask),
        spill_values=jnp.asarray(csr_v[spill_sel]),
        spill_rows=jnp.asarray(spill_row_ids),
        spill_columns=jnp.asarray(csr_c[spill_sel].astype(np.int32)),
    )


# ---------------------------------------------------------------------------
# PlanCache — process-wide memo of (matrix identity, config) -> RgCSRPlan
# ---------------------------------------------------------------------------


class PlanCache:
    """LRU plan cache keyed on matrix identity + kernel config.

    Keys use ``id(matrix)`` plus every plan-shaping config field —
    ``(chunks_per_step, ordering, spill_threshold)`` — so a block plan and
    an adaptive plan of the same matrix (or two adaptive plans at different
    spill thresholds) can never shadow each other.  A ``weakref.finalize``
    hook evicts every config of a matrix when it is garbage-collected
    (CPython runs the finalizer during deallocation, before the id can be
    reused).  Thread-safe; plan *construction* happens outside the lock so
    concurrent misses on different matrices don't serialize.
    """

    def __init__(self, maxsize: int = 256):
        self.maxsize = maxsize
        self._plans: "collections.OrderedDict[tuple, RgCSRPlan]" = \
            collections.OrderedDict()
        self._finalized: set = set()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0

    def get(self, m: RgCSR, *, chunks_per_step: int = 1,
            ordering: str = "block", spill_threshold: int = 0) -> RgCSRPlan:
        key = (id(m), chunks_per_step, ordering, int(spill_threshold))
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self.hits += 1
                self._plans.move_to_end(key)
                return plan
        plan = make_plan(m, chunks_per_step=chunks_per_step,
                         ordering=ordering, spill_threshold=spill_threshold)
        with self._lock:
            if key not in self._plans:
                self.misses += 1
                self._plans[key] = plan
                if id(m) not in self._finalized:
                    self._finalized.add(id(m))
                    weakref.finalize(m, self._evict, id(m))
                while len(self._plans) > self.maxsize:
                    self._plans.popitem(last=False)
            else:
                self.hits += 1
                plan = self._plans[key]
        return plan

    def _evict(self, mid: int) -> None:
        with self._lock:
            self._finalized.discard(mid)
            for key in [k for k in self._plans if k[0] == mid]:
                del self._plans[key]

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()
            self._finalized.clear()
            self.hits = 0
            self.misses = 0

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "entries": len(self._plans)}

    def __len__(self) -> int:
        return len(self._plans)


PLAN_CACHE = PlanCache()


def get_plan(m: RgCSR, *, chunks_per_step: int = 1, ordering: str = "block",
             spill_threshold: int = 0) -> RgCSRPlan:
    """Fetch (or build and memoize) the kernel plan for ``m``."""
    return PLAN_CACHE.get(m, chunks_per_step=chunks_per_step,
                          ordering=ordering, spill_threshold=spill_threshold)


# ---------------------------------------------------------------------------
# SpMV / SpMM wrappers
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_rows", "has_spill"))
def _adaptive_finish_spmv(y_flat, x, gather_idx, grouped_mask,
                          spill_values, spill_rows, spill_columns,
                          *, n_rows: int, has_spill: bool):
    """Fused adaptive epilogue: inverse-permutation gather + COO tail.

    One jit region, no materialized scatter: original row ``r`` reads lane
    ``gather_idx[r]`` of the permuted kernel output (spilled rows masked to
    zero) and the pathological rows come back as a segment-sum over the COO
    tail — both fuse into a single gather/scatter pass over HBM.
    """
    out = jnp.where(grouped_mask, jnp.take(y_flat, gather_idx, axis=0),
                    jnp.zeros((), y_flat.dtype))
    if has_spill:
        prods = spill_values * jnp.take(x, spill_columns, axis=0)
        out = out + jax.ops.segment_sum(prods, spill_rows,
                                        num_segments=n_rows)
    return out


@functools.partial(jax.jit, static_argnames=("n_rows", "has_spill"))
def _adaptive_finish_spmm(y2d, x, gather_idx, grouped_mask,
                          spill_values, spill_rows, spill_columns,
                          *, n_rows: int, has_spill: bool):
    """SpMM twin of :func:`_adaptive_finish_spmv` (row gather over axis 0)."""
    out = jnp.where(grouped_mask[:, None],
                    jnp.take(y2d, gather_idx, axis=0),
                    jnp.zeros((), y2d.dtype))[:, : x.shape[1]]
    if has_spill:
        prods = jnp.take(x, spill_columns, axis=0) * spill_values[:, None]
        out = out + jax.ops.segment_sum(prods, spill_rows,
                                        num_segments=n_rows)
    return out


def rgcsr_spmv(plan: RgCSRPlan, x, *, interpret: bool | None = None):
    """y = A @ x via the Pallas kernel. x: (n_cols,) -> y: (n_rows,).

    Adaptive plans return through the fused epilogue (inverse gather +
    spill segment-sum); block plans slice the contiguous rows.
    """
    if interpret is None:
        interpret = default_interpret()
    x = jnp.asarray(x)
    y_flat = rgcsr_spmv_pallas(
        plan.step_group, plan.step_first, plan.values2d, plan.columns2d,
        x, plan.diag_start, plan.diag_shift, n_groups=plan.n_groups,
        group_size=plan.group_size, chunks_per_step=plan.chunks_per_step,
        diag_steps=plan.diag_steps, x_pad=plan.x_pad, interpret=interpret)
    if plan.ordering != "adaptive":
        return y_flat[: plan.n_rows]
    return _adaptive_finish_spmv(
        y_flat, x, plan.gather_idx, plan.grouped_mask,
        plan.spill_values, plan.spill_rows, plan.spill_columns,
        n_rows=plan.n_rows, has_spill=plan.n_spilled_elements > 0)


def rgcsr_spmm(plan: RgCSRPlan, x, *, d_tile: int = LANES,
               interpret: bool | None = None):
    """Y = A @ X via the Pallas kernel. X: (n_cols, d) -> Y: (n_rows, d).

    The kernel gathers X through ``columns2d`` for every slot row; a plan
    with both diagonal and gathered rows runs it once per step range."""
    if interpret is None:
        interpret = default_interpret()
    x = jnp.asarray(x)
    launch = functools.partial(
        rgcsr_spmm_pallas, x=x, n_groups=plan.n_groups,
        group_size=plan.group_size, d_tile=d_tile,
        chunks_per_step=plan.chunks_per_step, interpret=interpret)
    cut = plan.diag_steps
    if 0 < cut < plan.num_steps:
        r = cut * plan.chunks_per_step * SUBLANES
        head, tail = slice(None, cut), slice(cut, None)
        parts = [(launch(plan.step_group[st], plan.step_first[st],
                         plan.values2d[rows], plan.columns2d[rows]
                         ).reshape(plan.n_groups, -1), plan.step_group[st])
                 for st, rows in ((head, slice(None, r)),
                                  (tail, slice(r, None)))]
        y = merge_group_parts(parts, plan.n_groups).reshape(-1, x.shape[1])
    else:
        y = launch(plan.step_group, plan.step_first, plan.values2d,
                   plan.columns2d)
    if plan.ordering != "adaptive":
        return y[: plan.n_rows]
    return _adaptive_finish_spmm(
        y, x, plan.gather_idx, plan.grouped_mask,
        plan.spill_values, plan.spill_rows, plan.spill_columns,
        n_rows=plan.n_rows, has_spill=plan.n_spilled_elements > 0)


# ---------------------------------------------------------------------------
# Row-sharded multi-device SpMV/SpMM (DESIGN.md §11)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardedRgCSRPlan:
    """Stacked, device-major execution plan for a :class:`ShardedRgCSR`.

    Each shard's :class:`RgCSRPlan` (built by the single-device planner in
    CSR slotting, without diagonal rows — block or adaptive grouping
    applies *per shard*, at that shard's own
    tuned ``(chunks_per_step, ordering, spill_threshold)`` from
    ``shard_configs``) is padded to the across-shard maxima and stacked on
    a leading device axis, which is what ``shard_map`` needs: one SPMD
    program, per-device slices of uniform shape.  Padding rows are exact
    zeros; padding *steps* point at the shard's own last real group with
    ``step_first = 0``, so they accumulate zeros into an already-initialized
    output block (the Pallas revisit rule stays satisfied: padded steps
    extend the last group's consecutive run).  Because the SPMD kernel has
    one static ``chunks_per_step``, per-shard winners are reconciled at the
    table level: each shard's layout is padded at its *own* winner
    granularity and its step table is expanded to the common kernel
    ``chunks_per_step`` (the gcd of the winners — DESIGN.md §12).

    ``x_mode`` fixes how the dense vector is reconciled (arXiv:1112.5588's
    local/remote split):

    * ``'replicated'`` — x is replicated; columns keep global indices.
      Zero communication, D× x memory: the fast path while x fits.
    * ``'split'`` — x is row-sharded over the same axis
      (``cols_per_shard`` entries per device) and the exchange is a
      plan-driven **sparse collective** (DESIGN.md §12): grouped storage
      holds only the shard's *local*-column entries (columns remapped into
      ``[0, cols_per_shard)``), each shard's *remote* entries live in a COO
      remote tail (``rem_*``) indexed into the exchange receive buffer, and
      ``send_idx``/``edge_counts`` form the per-(src, dst) send schedule —
      padded to the static per-edge max ``e_max`` for jittability — that
      the run path executes as one ``all_to_all`` of only the remote x
      entries.  The kernel reads only the local slice, so the exchange
      overlaps the local-partial launch, and per-device exchange volume is
      exactly that shard's plan-time remote column count.
    """

    values3d: Any        # (D, S_pad, G)
    columns3d: Any       # (D, S_pad, G) int32 (global; local-only in split)
    step_group2d: Any    # (D, T_max) int32
    step_first2d: Any    # (D, T_max) int32
    n_rows: int
    n_cols: int
    n_shards: int
    rows_per_shard: int
    cols_per_shard: int          # x entries owned per device (split mode)
    n_groups: int                # max over shards (uniform kernel out shape)
    group_size: int
    chunks_per_step: int = 1     # kernel cps (gcd of per-shard winners)
    ordering: str = "block"      # 'adaptive' when ANY shard is adaptive
    spill_threshold: int = 0     # the broadcast arg only — per-shard truth
    #                              (incl. tuned thresholds) is shard_configs
    x_mode: str = "replicated"
    nnz: int = -1
    # per-shard (chunks_per_step, ordering, spill_threshold) actually built
    shard_configs: Tuple[Tuple[int, str, int], ...] = ()
    remote_cols: Any = None      # (D, R_max) int32 (split: plan-time sets)
    # --- sparse-exchange schedule (split mode with a non-empty exchange) ---
    send_idx: Any = None         # (D_src, D_dst, e_max) int32 local col idx
    edge_counts: Any = None      # (D_src, D_dst) int64 true edge sizes (host)
    e_max: int = 0               # static per-edge pad (0 = no exchange)
    rem_values: Any = None       # (D, E_t) remote-entry COO tail values
    rem_rows: Any = None         # (D, E_t) int32 local row ids
    rem_xidx: Any = None         # (D, E_t) int32 index into recv buffer
    gather_idx: Any = None       # (D, rows_per_shard) int32 (adaptive)
    grouped_mask: Any = None     # (D, rows_per_shard) bool (adaptive)
    spill_values: Any = None     # (D, E_max) (adaptive + spill)
    spill_rows: Any = None       # (D, E_max) int32 local row ids
    spill_columns: Any = None    # (D, E_max) int32 (local in split mode)
    # true per-shard figures, pre-stacking (the ~1/D acceptance numbers)
    shard_stored_slots: Tuple[int, ...] = ()
    shard_num_steps: Tuple[int, ...] = ()
    shard_remote_cols: Tuple[int, ...] = ()
    shard_remote_entries: Tuple[int, ...] = ()   # rem-tail nnz per shard
    shard_spill_counts: Tuple[int, ...] = ()     # spill-tail nnz per shard

    @property
    def num_steps_max(self) -> int:
        return int(self.step_group2d.shape[1])

    @property
    def stored_slots_max(self) -> int:
        """Per-device stored slot rows after stacking (= max over shards)."""
        return int(self.values3d.shape[1])

    @property
    def n_spilled_max(self) -> int:
        return 0 if self.spill_values is None else int(
            self.spill_values.shape[1])

    @property
    def stored_elements(self) -> int:
        """True (unstacked) grouped slots × lanes + COO tails, all shards —
        including split mode's remote exchange tails, which store one entry
        per remote nonzero (they are part of the format's footprint, and
        without them a mostly-remote matrix would show stored < nnz)."""
        spilled = sum(self.shard_spilled_elements)
        return (sum(self.shard_stored_slots) * self.group_size + spilled
                + sum(self.shard_remote_entries))

    @property
    def shard_spilled_elements(self) -> Tuple[int, ...]:
        """True spill-tail entries per shard — positional (recorded at
        build), never inferred from values: a stored spill value may
        legitimately be 0.0 (same rule as ``RgCSR.to_csr_arrays``)."""
        if self.spill_values is None:
            return (0,) * self.n_shards
        return self.shard_spill_counts or (0,) * self.n_shards

    @property
    def padded_slot_fraction(self) -> float:
        if self.nnz < 0 or self.stored_elements == 0:
            return 0.0
        return (self.stored_elements - self.nnz) / self.stored_elements

    # ------------------------------------------------- exchange accounting
    @property
    def has_exchange(self) -> bool:
        """Whether the run path executes the sparse collective at all."""
        return self.x_mode == "split" and self.e_max > 0

    @property
    def shard_exchange_recv_cols(self) -> Tuple[int, ...]:
        """x entries device d *receives* per the plan schedule — equals
        ``shard_remote_cols[d]`` by construction (the tentpole bound)."""
        if self.edge_counts is None:
            return (0,) * self.n_shards
        ec = np.asarray(self.edge_counts)
        return tuple(int(ec[:, d].sum()) for d in range(self.n_shards))

    @property
    def shard_exchange_send_cols(self) -> Tuple[int, ...]:
        """x entries device d *sends* per the plan schedule."""
        if self.edge_counts is None:
            return (0,) * self.n_shards
        ec = np.asarray(self.edge_counts)
        return tuple(int(ec[d, :].sum()) for d in range(self.n_shards))

    @property
    def shard_exchange_bytes(self) -> Tuple[int, ...]:
        """Exchange volume per device in bytes (received x entries ×
        itemsize) — the number the all_gather path paid ``n_cols ×
        itemsize`` for regardless of the remote set size.  Itemsize is the
        stored-values dtype; a run-time x of a different width scales the
        wire bytes accordingly (the recv *counts* are the exact figures)."""
        itemsize = jnp.dtype(self.values3d.dtype).itemsize
        return tuple(c * itemsize for c in self.shard_exchange_recv_cols)

    @property
    def exchange_padded_recv_cols(self) -> int:
        """Static recv-buffer width (D·e_max) — the jittability pad; the
        collective moves this many slots, only ``recv_cols`` are real."""
        return self.n_shards * self.e_max


def _normalize_shard_configs(shard_configs, n_shards: int,
                             chunks_per_step: int, ordering: str,
                             spill_threshold: int,
                             group_size: Optional[int] = None
                             ) -> Tuple[Tuple[int, str, int], ...]:
    """Per-shard (cps, ordering, spill) tuples; the global args broadcast
    when ``shard_configs`` is None.  Accepts TuneConfig-likes, dicts, or
    bare 3-tuples so tuner winners thread through without conversion.
    A config that *carries* a group size (TuneConfig/dict) must match the
    matrix's — winners measured at a different G would silently mis-tune
    the plan otherwise."""
    if shard_configs is None:
        return ((int(chunks_per_step), str(ordering),
                 int(spill_threshold)),) * n_shards
    norm = []
    for c in shard_configs:
        cfg_g = None
        if hasattr(c, "chunks_per_step"):          # autotune.TuneConfig
            cps, o, t = c.chunks_per_step, c.ordering, c.spill_threshold
            cfg_g = getattr(c, "group_size", None)
        elif isinstance(c, dict):
            # missing keys inherit the caller's broadcast globals, never
            # silently reset to the defaults
            cps = c.get("chunks_per_step", chunks_per_step)
            o = c.get("ordering", ordering)
            t = c.get("spill_threshold", spill_threshold)
            cfg_g = c.get("group_size")
        else:
            cps, o, t = c
        if group_size is not None and cfg_g is not None \
                and int(cfg_g) != int(group_size):
            raise ValueError(
                f"shard config tuned at group_size={cfg_g} cannot build a "
                f"plan for a group_size={group_size} matrix — re-tune at "
                f"the matrix's group size")
        norm.append((int(cps), str(o), int(t)))
    if len(norm) != n_shards:
        raise ValueError(f"shard_configs has {len(norm)} entries for "
                         f"{n_shards} shards")
    return tuple(norm)


def _exchange_schedule(remotes, cstride: int, d_sh: int):
    """Per-(src, dst) send schedule from the per-dst remote column sets.

    Edge (s → d) holds dst d's remote columns owned by src s, in sorted
    order; every edge is padded to the static across-edge max ``e_max`` so
    the run-time ``all_to_all`` buffer shape is jittable.  Returns
    ``(send_idx (D, D, e_max) local col offsets at the src,
    edge_counts (D, D) true sizes, e_max, xidx_lut)`` where ``xidx_lut[d]``
    maps a global remote column to its slot ``src·e_max + pos`` in dst d's
    flattened receive buffer.
    """
    edge_cols = [[None] * d_sh for _ in range(d_sh)]
    counts = np.zeros((d_sh, d_sh), np.int64)
    for dst, remote in enumerate(remotes):
        owner = remote // cstride
        for s in range(d_sh):
            ec = remote[owner == s]
            edge_cols[s][dst] = ec
            counts[s, dst] = len(ec)
    e_max = int(counts.max()) if counts.size else 0
    send_idx = np.zeros((d_sh, d_sh, e_max), np.int32)
    xidx_lut = []
    for dst in range(d_sh):
        lut = np.zeros(max(cstride * d_sh, 1), np.int32)
        for s in range(d_sh):
            ec = edge_cols[s][dst]
            send_idx[s, dst, : len(ec)] = ec - s * cstride
            lut[ec] = s * e_max + np.arange(len(ec), dtype=np.int32)
        xidx_lut.append(lut)
    return send_idx, counts, e_max, xidx_lut


def make_sharded_plan(sm: ShardedRgCSR, *, chunks_per_step: int = 1,
                      ordering: str = "block", spill_threshold: int = 0,
                      x_mode: str = "replicated",
                      shard_configs=None) -> ShardedRgCSRPlan:
    """Build per-shard plans via :func:`make_plan`, then pad + stack them.

    Reuses the whole single-device plan machinery per shard — the adaptive
    length-aware permutation, per-group slot sizing, and COO spill are each
    computed inside a shard's own row block.  ``shard_configs`` (one
    ``(chunks_per_step, ordering, spill_threshold)`` per shard, e.g. the
    per-shard autotune winners) lets each shard keep its own schedule: the
    grouped layout is padded at the shard's own winner granularity and its
    step table is expanded to the common kernel ``chunks_per_step`` (the
    gcd of the winners) so one SPMD program still runs everywhere.

    In ``x_mode='split'`` the grouped storage keeps only each shard's
    **local**-column entries (columns remapped into ``[0, cols_per_shard)``
    — exactly the shard's own slice of x, so the kernel never waits on the
    exchange); remote entries move to the ``rem_*`` COO tail indexed into
    the receive buffer of the plan-time ``send_idx`` exchange schedule.
    """
    if x_mode not in ("replicated", "split"):
        raise ValueError(
            f"x_mode must be 'replicated' or 'split', got {x_mode!r}")
    d_sh = sm.n_shards
    n_rows, n_cols = sm.shape
    g = sm.group_size
    cfgs = _normalize_shard_configs(shard_configs, d_sh, chunks_per_step,
                                    ordering, spill_threshold,
                                    group_size=g)
    for cps_d, o_d, _ in cfgs:
        if cps_d not in CHUNKS_PER_STEP_CHOICES:
            raise ValueError(
                f"chunks_per_step must be one of {CHUNKS_PER_STEP_CHOICES}, "
                f"got {cps_d}")
        if o_d not in ("block", "adaptive"):
            raise ValueError(f"ordering must be 'block' or 'adaptive', "
                             f"got {o_d!r}")
    # the SPMD kernel has one static cps; per-shard winners keep their own
    # padding granularity and expand their step tables down to the gcd
    # (powers of two, so gcd == min)
    kernel_cps = min(c[0] for c in cfgs)
    rows_per_step = kernel_cps * SUBLANES
    any_adaptive = any(c[1] == "adaptive" for c in cfgs)
    _, cstride = ShardedRgCSR.shard_layout(n_rows, n_cols, d_sh)

    # split mode: local/remote entry split + per-(src,dst) exchange schedule
    remotes = []
    rem_tails = []                      # (values, rows, global cols) per dst
    if x_mode == "split":
        sources = []
        for d, shard in enumerate(sm.shards):
            lo, hi = d * cstride, min((d + 1) * cstride, n_cols)
            # CSR-based split, no densification: local entries (columns
            # remapped into this shard's slice) build the grouped storage,
            # remote entries stay as index triplets for the exchange tail
            csr_v, csr_c, row_ptr = shard.to_csr_arrays()
            csr_r = np.repeat(np.arange(sm.rows_per_shard, dtype=np.int32),
                              np.diff(row_ptr))
            is_local = (csr_c >= lo) & (csr_c < hi)
            local_ptr = np.concatenate([[0], np.cumsum(np.bincount(
                csr_r[is_local], minlength=sm.rows_per_shard))])
            sources.append(RgCSR.from_csr(
                csr_v[is_local], csr_c[is_local] - lo, local_ptr,
                (sm.rows_per_shard, cstride), group_size=g,
                slot_pad=sm.slot_pad))
            rc = csr_c[~is_local].astype(np.int64)
            remotes.append(np.unique(rc))
            rem_tails.append((csr_v[~is_local], csr_r[~is_local], rc))
        send_idx, edge_counts, e_max, xidx_lut = _exchange_schedule(
            remotes, cstride, d_sh)
        e_tail = max(len(v) for v, _, _ in rem_tails)
        r_max = max(len(r) for r in remotes)
    else:
        sources = list(sm.shards)
        send_idx = edge_counts = None
        e_max = e_tail = r_max = 0

    # shards keep CSR slotting: the SPMD kernel runs one gathered step
    # table per shard
    plans = [_make_block_plan(src, chunks_per_step=c[0], offset_slots=False)
             if c[1] == "block" and not c[2] else
             make_plan(src, chunks_per_step=c[0], ordering=c[1],
                       spill_threshold=c[2])
             for src, c in zip(sources, cfgs)]
    # expand each shard's step table to the kernel cps: one coarse step of
    # cps_d chunks becomes cps_d/kernel_cps consecutive fine steps of the
    # same group (step_first only on the first — the revisit rule holds)
    tables = []
    for p, (cps_d, _, _) in zip(plans, cfgs):
        f = cps_d // kernel_cps
        sg = np.repeat(np.asarray(p.step_group), f)
        sf = np.zeros(len(sg), np.int32)
        if len(sg):
            sf[::f] = np.asarray(p.step_first)
        tables.append((sg, sf))
    n_groups = max(p.n_groups for p in plans)
    t_max = max(len(sg) for sg, _ in tables)
    s_pad = t_max * rows_per_step

    vals = np.zeros((d_sh, s_pad, g),
                    np.asarray(plans[0].values2d).dtype)
    cols = np.zeros((d_sh, s_pad, g), np.int32)
    sg2 = np.zeros((d_sh, t_max), np.int32)
    sf2 = np.zeros((d_sh, t_max), np.int32)
    remote_cols = np.zeros((d_sh, r_max), np.int32)
    rm_v = np.zeros((d_sh, e_tail), vals.dtype)
    rm_r = np.zeros((d_sh, e_tail), np.int32)
    rm_x = np.zeros((d_sh, e_tail), np.int32)
    sp_max = max(p.n_spilled_elements for p in plans) if any_adaptive else 0
    gidx = np.zeros((d_sh, sm.rows_per_shard), np.int32)
    gmask = np.zeros((d_sh, sm.rows_per_shard), bool)
    sp_v = np.zeros((d_sh, sp_max), vals.dtype)
    sp_r = np.zeros((d_sh, sp_max), np.int32)
    sp_c = np.zeros((d_sh, sp_max), np.int32)

    for d, p in enumerate(plans):
        s_d = p.stored_slots
        sg, sf = tables[d]
        t_d = len(sg)
        vals[d, :s_d] = np.asarray(p.values2d)
        cols[d, :s_d] = np.asarray(p.columns2d)
        sg2[d, :t_d] = sg
        # padding steps extend the shard's own last group (step_first = 0,
        # zero values): consecutive revisit of an initialized block
        sg2[d, t_d:] = int(sg[-1]) if t_d else 0
        sf2[d, :t_d] = sf
        if x_mode == "split":
            remote_cols[d, : len(remotes[d])] = remotes[d]
            rv, rr, rc = rem_tails[d]
            if len(rv):
                rm_v[d, : len(rv)] = rv
                rm_r[d, : len(rv)] = rr
                rm_x[d, : len(rv)] = xidx_lut[d][rc]
        if any_adaptive:
            if p.ordering == "adaptive":
                gidx[d] = np.asarray(p.gather_idx)
                gmask[d] = np.asarray(p.grouped_mask)
                e_d = p.n_spilled_elements
                if e_d:
                    sp_v[d, :e_d] = np.asarray(p.spill_values)
                    sp_r[d, :e_d] = np.asarray(p.spill_rows)
                    sp_c[d, :e_d] = np.asarray(p.spill_columns)
            else:
                # block shard inside a mixed stack: identity gather —
                # kernel output index of row r IS r for consecutive groups
                gidx[d] = np.arange(sm.rows_per_shard, dtype=np.int32)
                gmask[d] = True
    split = x_mode == "split"
    # host numpy on purpose: the plan is mesh-agnostic, and the run path
    # places each stacked array once per mesh, sharded on its device axis
    # (``_sharded_exec``) — never whole on one device
    return ShardedRgCSRPlan(
        values3d=vals,
        columns3d=cols,
        step_group2d=sg2,
        step_first2d=sf2,
        n_rows=n_rows, n_cols=n_cols, n_shards=d_sh,
        rows_per_shard=sm.rows_per_shard, cols_per_shard=cstride,
        n_groups=n_groups, group_size=g, chunks_per_step=kernel_cps,
        ordering="adaptive" if any_adaptive else "block",
        spill_threshold=int(spill_threshold),
        x_mode=x_mode, nnz=sm.nnz, shard_configs=cfgs,
        remote_cols=remote_cols if split else None,
        send_idx=send_idx if split and e_max else None,
        edge_counts=edge_counts,
        e_max=e_max,
        rem_values=rm_v if split and e_max else None,
        rem_rows=rm_r if split and e_max else None,
        rem_xidx=rm_x if split and e_max else None,
        gather_idx=gidx if any_adaptive else None,
        grouped_mask=gmask if any_adaptive else None,
        spill_values=sp_v if any_adaptive else None,
        spill_rows=sp_r if any_adaptive else None,
        spill_columns=sp_c if any_adaptive else None,
        shard_stored_slots=tuple(p.stored_slots for p in plans),
        shard_num_steps=tuple(len(sg) for sg, _ in tables),
        shard_remote_cols=tuple(len(r) for r in remotes) if remotes
        else (0,) * d_sh,
        shard_remote_entries=tuple(len(v) for v, _, _ in rem_tails)
        if rem_tails else (0,) * d_sh,
        shard_spill_counts=tuple(p.n_spilled_elements for p in plans),
    )


# sharded plan memo: (id(matrix), shard count, x_mode, per-shard configs)
# -> plan, GC-evicted like PLAN_CACHE.  Keys carry the shard/device count
# explicitly (not just matrix identity) so re-warming on a resized mesh can
# never reuse a stale stacked plan, and the full per-shard config tuple so
# per-shard-tuned plans coexist with uniform ones; x_mode is keyed because
# split mode stores local-only column indices + the exchange schedule.
_SHARDED_PLANS: "collections.OrderedDict[tuple, ShardedRgCSRPlan]" = \
    collections.OrderedDict()
_SHARDED_PLANS_MAX = 64
_SHARDED_LOCK = threading.RLock()
_SHARDED_FINALIZED: set = set()
_SHARDED_STATS = {"hits": 0, "misses": 0}


def get_sharded_plan(sm: ShardedRgCSR, *, chunks_per_step: int = 1,
                     ordering: str = "block", spill_threshold: int = 0,
                     x_mode: str = "replicated",
                     shard_configs=None) -> ShardedRgCSRPlan:
    """Fetch (or build and memoize) the stacked sharded plan for ``sm``."""
    cfgs = _normalize_shard_configs(shard_configs, sm.n_shards,
                                    chunks_per_step, ordering,
                                    spill_threshold,
                                    group_size=sm.group_size)
    key = (id(sm), sm.n_shards, x_mode, cfgs)
    with _SHARDED_LOCK:
        plan = _SHARDED_PLANS.get(key)
        if plan is not None:
            _SHARDED_STATS["hits"] += 1
            _SHARDED_PLANS.move_to_end(key)
            return plan
    plan = make_sharded_plan(sm, chunks_per_step=chunks_per_step,
                             ordering=ordering,
                             spill_threshold=spill_threshold, x_mode=x_mode,
                             shard_configs=cfgs)
    with _SHARDED_LOCK:
        if key not in _SHARDED_PLANS:
            _SHARDED_STATS["misses"] += 1
            _SHARDED_PLANS[key] = plan
            if id(sm) not in _SHARDED_FINALIZED:
                _SHARDED_FINALIZED.add(id(sm))
                weakref.finalize(sm, _evict_sharded, id(sm))
            while len(_SHARDED_PLANS) > _SHARDED_PLANS_MAX:
                _SHARDED_PLANS.popitem(last=False)
        else:
            _SHARDED_STATS["hits"] += 1
            plan = _SHARDED_PLANS[key]
    return plan


def _evict_sharded(mid: int) -> None:
    with _SHARDED_LOCK:
        _SHARDED_FINALIZED.discard(mid)
        for key in [k for k in _SHARDED_PLANS if k[0] == mid]:
            del _SHARDED_PLANS[key]


def sharded_plan_cache_stats() -> Dict[str, int]:
    with _SHARDED_LOCK:
        return {"hits": _SHARDED_STATS["hits"],
                "misses": _SHARDED_STATS["misses"],
                "entries": len(_SHARDED_PLANS)}


# memo of (jitted shard_map executable, plan arrays placed on the mesh) per
# (plan, mesh, axis, kind) — the shard_map wrapper must be a stable
# callable for jax's jit cache to hit, and the placement is done once
_SHARDED_EXEC: "collections.OrderedDict[tuple, Any]" = \
    collections.OrderedDict()
_SHARDED_EXEC_MAX = 32


def _sharded_args(plan: ShardedRgCSRPlan):
    """(args, per-arg PartitionSpec dim-count) in the inner-fn unpack order."""
    args = [plan.values3d, plan.columns3d, plan.step_group2d,
            plan.step_first2d]
    ndims = [3, 3, 2, 2]
    if plan.has_exchange:
        # send schedule is sharded on its *source* axis (each device gets
        # its own (D_dst, e_max) row); the remote tail on its dst axis
        args += [plan.send_idx, plan.rem_values, plan.rem_rows,
                 plan.rem_xidx]
        ndims += [3, 2, 2, 2]
    if plan.ordering == "adaptive":
        args += [plan.gather_idx, plan.grouped_mask]
        ndims += [2, 2]
        if plan.n_spilled_max > 0:
            args += [plan.spill_values, plan.spill_rows, plan.spill_columns]
            ndims += [2, 2, 2]
    return args, ndims


def _build_sharded_exec(plan: ShardedRgCSRPlan, kind: str, mesh, axis: str,
                        interpret: bool, d_tile: int):
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    split = plan.x_mode == "split"
    exchange = plan.has_exchange
    adaptive = plan.ordering == "adaptive"
    has_spill = adaptive and plan.n_spilled_max > 0
    # hoist every plan attribute the body needs into scalars: the closure
    # must NOT reference `plan` itself, or the cached jitted fn would pin
    # the stacked device arrays and the plan-death exec eviction
    # (weakref.finalize below) could never fire before LRU turnover
    rps = plan.rows_per_shard
    recv_width = plan.n_shards * plan.e_max
    n_groups, group_size = plan.n_groups, plan.group_size
    kernel_cps = plan.chunks_per_step
    empty_v = jnp.zeros((0,), plan.values3d.dtype)
    empty_i = jnp.zeros((0,), jnp.int32)

    def per_shard(*a):
        it = iter(a)
        vals, cols = next(it)[0], next(it)[0]            # (S_pad, G)
        sg, sf = next(it)[0], next(it)[0]                # (T_max,)
        sidx = next(it)[0] if exchange else None         # (D, e_max)
        rm_v = next(it)[0] if exchange else empty_v      # (E_t,)
        rm_r = next(it)[0] if exchange else empty_i
        rm_x = next(it)[0] if exchange else empty_i
        gi = next(it)[0] if adaptive else None
        gm = next(it)[0] if adaptive else None
        sv = next(it)[0] if has_spill else empty_v
        sr = next(it)[0] if has_spill else empty_i
        sc = next(it)[0] if has_spill else empty_i
        x_in = next(it)
        recv_flat = None
        if exchange:
            # plan-driven sparse collective (DESIGN.md §12): move ONLY the
            # remote x entries — each device sends its (D, e_max) schedule
            # rows, one all_to_all delivers recv[s] = what src s sent us.
            # Issued before the kernel, which reads only x_in: the two are
            # dataflow-independent, so the scheduler can overlap the
            # exchange with the local-partial launch.
            send = jnp.take(x_in, sidx, axis=0)    # (D, e_max[, d])
            recv = jax.lax.all_to_all(send, axis, split_axis=0,
                                      concat_axis=0, tiled=True)
            recv_flat = recv.reshape((recv_width,) + x_in.shape[1:])
        # split mode: grouped storage is local-column-only, so the kernel's
        # x working set is exactly this device's slice (cols_per_shard)
        if kind == "spmv":
            y_flat = rgcsr_spmv_pallas(
                sg, sf, vals, cols, x_in, n_groups=n_groups,
                group_size=group_size, chunks_per_step=kernel_cps,
                interpret=interpret)
            if adaptive:
                y_loc = _adaptive_finish_spmv(
                    y_flat, x_in, gi, gm, sv, sr, sc, n_rows=rps,
                    has_spill=has_spill)
            else:
                y_loc = y_flat[:rps]
            if recv_flat is None:
                return y_loc
            # remote contributions: COO tail over the received entries
            prods = rm_v * jnp.take(recv_flat, rm_x, axis=0)
            return y_loc + jax.ops.segment_sum(prods, rm_r,
                                               num_segments=rps)
        y = rgcsr_spmm_pallas(
            sg, sf, vals, cols, x_in, n_groups=n_groups,
            group_size=group_size, d_tile=d_tile,
            chunks_per_step=kernel_cps, interpret=interpret)
        if adaptive:
            y_loc = _adaptive_finish_spmm(
                y, x_in, gi, gm, sv, sr, sc, n_rows=rps,
                has_spill=has_spill)
        else:
            y_loc = y[:rps]
        if recv_flat is None:
            return y_loc
        prods = jnp.take(recv_flat, rm_x, axis=0) * rm_v[:, None]
        return y_loc + jax.ops.segment_sum(prods, rm_r, num_segments=rps)

    _, ndims = _sharded_args(plan)
    in_specs = [P(*((axis,) + (None,) * (nd - 1))) for nd in ndims]
    if kind == "spmv":
        in_specs.append(P(axis) if split else P())
        out_spec = P(axis)
    else:
        in_specs.append(P(axis, None) if split else P(None, None))
        out_spec = P(axis, None)
    fn = jax.jit(shard_map(per_shard, mesh=mesh,
                           in_specs=tuple(in_specs), out_specs=out_spec,
                           check_rep=False))
    return fn, in_specs[:-1]


# mesh-signature memo: a Mesh's topology is immutable, so the O(n_devices)
# signature walk runs once per mesh object instead of on every sharded
# dispatch (the weak keying preserves the resized-mesh aliasing guarantee:
# a dead mesh's entry vanishes with it, a rebuilt mesh recomputes)
_MESH_SIGS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _mesh_signature(mesh) -> tuple:
    """Value identity of a mesh (axis names/sizes + device ids) for cache
    keys — ``id(mesh)`` alone can alias a resized/rebuilt mesh after GC."""
    from repro.sharding.partitioner import mesh_signature
    try:
        sig = _MESH_SIGS.get(mesh)
        if sig is None:
            sig = mesh_signature(mesh)
            _MESH_SIGS[mesh] = sig
        return sig
    except TypeError:          # mesh not weakref-able/hashable: just compute
        return mesh_signature(mesh)


def _sharded_exec(plan: ShardedRgCSRPlan, kind: str, mesh, axis: str,
                  interpret: bool, d_tile: int = LANES):
    from jax.sharding import NamedSharding
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis!r}: {mesh.axis_names}")
    if mesh.shape[axis] != plan.n_shards:
        raise ValueError(
            f"plan built for {plan.n_shards} shards but mesh axis "
            f"{axis!r} has {mesh.shape[axis]} devices")
    key = (id(plan), kind, _mesh_signature(mesh), axis, interpret, d_tile)
    with _SHARDED_LOCK:
        entry = _SHARDED_EXEC.get(key)
        if entry is not None:
            _SHARDED_EXEC.move_to_end(key)
            return entry
    fn, specs = _build_sharded_exec(plan, kind, mesh, axis, interpret,
                                    d_tile)
    # each stacked plan array lives sharded on its device axis: device d
    # holds only its own shard, placed once here and reused every call
    args, _ = _sharded_args(plan)
    entry = (fn, [jax.device_put(a, NamedSharding(mesh, spec))
                  for a, spec in zip(args, specs)])
    with _SHARDED_LOCK:
        if key not in _SHARDED_EXEC:
            _SHARDED_EXEC[key] = entry
            weakref.finalize(plan, _evict_sharded_exec, id(plan))
            while len(_SHARDED_EXEC) > _SHARDED_EXEC_MAX:
                _SHARDED_EXEC.popitem(last=False)
        else:
            entry = _SHARDED_EXEC[key]
    return entry


def sharded_plan_placement(plan: ShardedRgCSRPlan, *, mesh, axis: str):
    """The plan's stacked arrays as the SpMV run path placed them on
    ``mesh``: each is sharded on its leading (device) axis over ``axis``."""
    return _sharded_exec(plan, "spmv", mesh, axis, default_interpret())[1]


def _evict_sharded_exec(pid: int) -> None:
    with _SHARDED_LOCK:
        for key in [k for k in _SHARDED_EXEC if k[0] == pid]:
            del _SHARDED_EXEC[key]


def sharded_rgcsr_spmv(plan: ShardedRgCSRPlan, x, *, mesh, axis: str,
                       interpret: bool | None = None):
    """y = A @ x over a 1-D mesh axis: one shard_map program, each device
    running the existing Pallas kernel on its row shard's local slice.

    ``x``: the full (n_cols,) vector; in ``'split'`` mode it is padded to
    ``n_shards · cols_per_shard`` and row-sharded over ``axis`` by GSPMD,
    in ``'replicated'`` mode it is broadcast.  Returns (n_rows,).
    """
    if interpret is None:
        interpret = default_interpret()
    fn, args = _sharded_exec(plan, "spmv", mesh, axis, interpret)
    x = jnp.asarray(x)
    if plan.x_mode == "split":
        xw = plan.n_shards * plan.cols_per_shard
        x = jnp.zeros((xw,), x.dtype).at[: plan.n_cols].set(x)
    y = fn(*args, x)
    return y[: plan.n_rows]


def sharded_rgcsr_spmm(plan: ShardedRgCSRPlan, x, *, mesh, axis: str,
                       d_tile: int = LANES, interpret: bool | None = None):
    """Y = A @ X over a 1-D mesh axis (X dense (n_cols, d)) -> (n_rows, d)."""
    if interpret is None:
        interpret = default_interpret()
    fn, args = _sharded_exec(plan, "spmm", mesh, axis, interpret, d_tile)
    x = jnp.asarray(x)
    if plan.x_mode == "split":
        xw = plan.n_shards * plan.cols_per_shard
        x = jnp.zeros((xw, x.shape[1]), x.dtype).at[: plan.n_cols].set(x)
    y = fn(*args, x)
    return y[: plan.n_rows, : x.shape[1]]


# ---------------------------------------------------------------------------
# Plans over SparseLinear parameter trees (serving path)
# ---------------------------------------------------------------------------

# Memo keyed on (id(columns2d), dtype, d_out, d_in, group_size) — the dims
# are part of the key so an entry built with different/misinferred dims can
# never shadow a caller's correct ones.  The stored strong reference to the
# source values array both validates the entry (values identity must match —
# a training step invalidates it) and keeps the id stable.
_PARAM_PLANS: "collections.OrderedDict[tuple, tuple]" = collections.OrderedDict()
_PARAM_PLANS_MAX = 64
_PARAM_PLANS_LOCK = threading.Lock()


def plan_from_params(params, dtype, *, d_out: int, d_in: int,
                     group_size: int) -> RgCSRPlan:
    """RgCSRPlan view over SparseLinear param arrays (no host repack —
    the params already live in the kernel's slot-major layout, cps=1).

    With concrete arrays (eager per-layer paths) the container is memoized
    so each layer's plan is built once per process (``Engine`` warms this at
    init); under jit tracing the memo is bypassed and the container is
    rebuilt per trace, which is free — the jit'd serving path never pays
    per-call host plan work by construction.
    """
    n_groups = -(-d_out // group_size)
    # either array traced means we're inside a transform (grad over values
    # closes over concrete structure buffers) — never memoize tracers
    tracing = (isinstance(params["columns2d"], jax.core.Tracer)
               or isinstance(params["values2d"], jax.core.Tracer))
    key = (id(params["columns2d"]), jnp.dtype(dtype).str, d_out, d_in,
           group_size)
    if not tracing:
        with _PARAM_PLANS_LOCK:
            entry = _PARAM_PLANS.get(key)
            if entry is not None and entry[0] is params["values2d"]:
                _PARAM_PLANS.move_to_end(key)
                return entry[1]
    values = params["values2d"]
    if values.dtype != jnp.dtype(dtype):   # avoid a same-dtype device copy
        values = values.astype(dtype)
    plan = RgCSRPlan(
        values2d=values,
        columns2d=params["columns2d"],
        step_group=params["chunk_group"],
        step_first=params["chunk_first"],
        n_rows=d_out, n_cols=d_in, n_groups=int(n_groups),
        group_size=group_size, chunks_per_step=1)
    if not tracing:
        with _PARAM_PLANS_LOCK:
            _PARAM_PLANS[key] = (params["values2d"], plan)
            while len(_PARAM_PLANS) > _PARAM_PLANS_MAX:
                _PARAM_PLANS.popitem(last=False)
    return plan


def param_plan_stats() -> Dict[str, int]:
    """Size of the SparseLinear param-plan memo (serving-path cache)."""
    with _PARAM_PLANS_LOCK:
        return {"entries": len(_PARAM_PLANS)}


def warm_plans_from_params(params, dtype=jnp.float32) -> int:
    """Pre-stage SpMM plans for every SparseLinear subtree in ``params``.

    Walks the parameter tree for the RgCSR layout signature
    (``values2d``/``columns2d``/``chunk_group``/``chunk_first``) and builds
    each layer's plan once so the first *eager* per-layer call pays no
    host-side plan work.  Scope limits, by construction:

    * the jit'd prefill/decode path assembles plan containers at trace time
      (free) and never consults this memo — warming helps eager paths only;
    * layer-stacked (3-D) sparse params are skipped — the stacked scan path
      only ever sees traced slices;
    * ``d_in``/``d_out`` are inferred from the buffers (max column + 1,
      ``n_groups·G``); an eager caller passing different exact dims simply
      misses this entry and builds its own (dims are part of the memo key —
      a misinferred warm entry can never shadow correct dims).

    Returns #plans warmed.
    """
    warmed = 0

    def visit(node) -> None:
        nonlocal warmed
        if not isinstance(node, dict):
            return
        if {"values2d", "columns2d", "chunk_group", "chunk_first"} <= set(node):
            if getattr(node["values2d"], "ndim", 0) == 2:
                g = int(node["columns2d"].shape[1])
                n_groups = int(np.asarray(node["chunk_group"])[-1]) + 1 \
                    if node["chunk_group"].shape[0] else 1
                d_in = int(np.asarray(node["columns2d"]).max()) + 1
                plan_from_params(node, dtype, d_out=n_groups * g,
                                 d_in=d_in, group_size=g)
                warmed += 1
            return
        for v in node.values():
            visit(v)

    visit(params)
    return warmed


# ---------------------------------------------------------------------------
# ELLPACK
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EllPlan:
    values2d: Any   # (K_pad, N_pad)
    columns2d: Any  # (K_pad, N_pad)
    n_rows: int
    n_cols: int


def make_ell_plan(m) -> EllPlan:
    """Kernel layout of an ELLPACK matrix or of a Hybrid's ELL part."""
    if isinstance(m, HybridEllCoo):
        vals, cols = np.asarray(m.ell_values), np.asarray(m.ell_columns)
    else:
        vals, cols = np.asarray(m.values), np.asarray(m.columns)
    cols = cols.astype(np.int32)
    k, n = vals.shape
    k_pad, n_pad = _pad_to(k, SUBLANES), _pad_to(n, LANES)
    vp = np.zeros((k_pad, n_pad), vals.dtype)
    cp = np.zeros((k_pad, n_pad), np.int32)
    vp[:k, :n] = vals
    cp[:k, :n] = cols
    return EllPlan(values2d=jnp.asarray(vp), columns2d=jnp.asarray(cp),
                   n_rows=m.shape[0], n_cols=m.shape[1])


def ell_spmv(plan: EllPlan, x, *, interpret: bool | None = None):
    if interpret is None:
        interpret = default_interpret()
    y = ell_spmv_pallas(plan.values2d, plan.columns2d, jnp.asarray(x),
                        interpret=interpret)
    return y[: plan.n_rows]


def hybrid_spmv(m, x, *, interpret: bool | None = None):
    """y = A @ x for ELLPACK, or Hybrid (ELL kernel + COO segment-sum)."""
    x = jnp.asarray(x)
    y = ell_spmv(make_ell_plan(m), x, interpret=interpret)
    if isinstance(m, HybridEllCoo) and m.coo_values.shape[0]:
        y = y + jax.ops.segment_sum(
            m.coo_values * jnp.take(x, m.coo_columns, axis=0), m.coo_rows,
            num_segments=m.shape[0])
    return y
