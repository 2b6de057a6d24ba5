"""Sparse-matrix storage formats from the paper, as JAX pytrees.

Implements every format the paper discusses (§3):

* :class:`CSR`            — common Compressed Sparse Rows (Fig. 1).
* :class:`COO`            — coordinate format (Fig. 4).
* :class:`ELLPACK`        — fixed-K padded format (Fig. 3), stored slot-major
                            ``(K, N)`` which is the TPU-lane-friendly layout.
* :class:`HybridEllCoo`   — Bell–Garland Hybrid (ELL + COO spill) [1].
* :class:`BlockedCSR`     — 4x4-style BSR (Fig. 2) [Buatois et al.].
* :class:`SlicedEllpack`  — Monakov et al. sliced ELLPACK (no rowLengths).
* :class:`RgCSR`          — the paper's Row-grouped CSR (Fig. 5): slot-major
                            groups + ``group_pointers`` + ``row_lengths``.

Construction happens on the host in numpy (as a real framework builds formats
at load time); the resulting containers hold ``jnp`` arrays and are registered
pytrees, so they can be passed through ``jax.jit`` boundaries, donated,
sharded and checkpointed like any other parameter tree.

TPU adaptation notes (DESIGN.md §2): within one RgCSR group of ``G`` rows the
data for slot ``k`` occupies ``G`` consecutive lanes — i.e. a group is a dense
``(K_g, G)`` tile in (sublane, lane) layout.  We additionally pad each group's
slot count to a multiple of ``slot_pad`` (default 8) so tiles are full VREGs.
The padding is *accounted* exactly like the paper's "artificial zeros".
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = Any

# Lane width of the TPU vector unit; RgCSR groups default to one lane-row.
TPU_LANES = 128
# Sublane packing: slots per group are padded to a multiple of this.
TPU_SUBLANES = 8

__all__ = [
    "CSR",
    "COO",
    "ELLPACK",
    "HybridEllCoo",
    "BlockedCSR",
    "SlicedEllpack",
    "RgCSR",
    "ShardedRgCSR",
    "from_dense",
    "FORMATS",
]


def _as_2d(dense: np.ndarray) -> np.ndarray:
    dense = np.asarray(dense)
    if dense.ndim != 2:
        raise ValueError(f"expected a 2-D matrix, got shape {dense.shape}")
    return dense


def _csr_arrays(dense: np.ndarray):
    """Host-side CSR triplet from a dense matrix (row-major nonzero walk)."""
    rows, cols = np.nonzero(dense)
    values = dense[rows, cols]
    n_rows = dense.shape[0]
    row_ptr = np.zeros(n_rows + 1, dtype=np.int32)
    np.add.at(row_ptr, rows + 1, 1)
    row_ptr = np.cumsum(row_ptr, dtype=np.int64).astype(np.int32)
    return values, cols.astype(np.int32), rows.astype(np.int32), row_ptr


def _tree_dataclass(cls):
    """Register a dataclass as a pytree: array fields dynamic, rest static."""
    cls = dataclasses.dataclass(frozen=True)(cls)
    array_fields = [f.name for f in dataclasses.fields(cls) if f.metadata.get("array")]
    static_fields = [f.name for f in dataclasses.fields(cls) if not f.metadata.get("array")]

    def flatten(obj):
        children = tuple(getattr(obj, n) for n in array_fields)
        aux = tuple(getattr(obj, n) for n in static_fields)
        return children, aux

    def unflatten(aux, children):
        kwargs = dict(zip(array_fields, children))
        kwargs.update(dict(zip(static_fields, aux)))
        return cls(**kwargs)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    cls._array_fields = array_fields
    cls._static_fields = static_fields
    return cls


def _arr():
    return dataclasses.field(metadata={"array": True})


def _static():
    return dataclasses.field(metadata={"array": False})


# ---------------------------------------------------------------------------
# CSR
# ---------------------------------------------------------------------------


@_tree_dataclass
class CSR:
    """Common CSR (paper §3.1). ``row_ids`` is a derived array used only by the
    vectorized jnp oracle (scalar-CSR has no data-parallel TPU analogue); it is
    NOT counted in the format's storage footprint."""

    values: Array = _arr()
    columns: Array = _arr()
    row_pointers: Array = _arr()
    row_ids: Array = _arr()  # derived: row index of each stored nonzero
    shape: Tuple[int, int] = _static()

    name: ClassVar[str] = "csr"

    @classmethod
    def from_dense(cls, dense: np.ndarray) -> "CSR":
        dense = _as_2d(dense)
        values, cols, rows, row_ptr = _csr_arrays(dense)
        return cls(
            values=jnp.asarray(values),
            columns=jnp.asarray(cols),
            row_pointers=jnp.asarray(row_ptr),
            row_ids=jnp.asarray(rows),
            shape=dense.shape,
        )

    @property
    def nnz(self) -> int:
        return int(self.values.shape[0])

    @property
    def stored_elements(self) -> int:
        return self.nnz

    def storage_bytes(self) -> int:
        """values + columns + rowPointers, per the paper's byte accounting."""
        itemsize = jnp.dtype(self.values.dtype).itemsize
        return self.nnz * itemsize + self.nnz * 4 + (self.shape[0] + 1) * 4

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=np.asarray(self.values).dtype)
        np.add.at(out, (np.asarray(self.row_ids), np.asarray(self.columns)),
                  np.asarray(self.values))
        return out


# ---------------------------------------------------------------------------
# COO
# ---------------------------------------------------------------------------


@_tree_dataclass
class COO:
    """Coordinate format (paper Fig. 4): fully explicit (row, col, value)."""

    values: Array = _arr()
    rows: Array = _arr()
    columns: Array = _arr()
    shape: Tuple[int, int] = _static()

    name: ClassVar[str] = "coo"

    @classmethod
    def from_dense(cls, dense: np.ndarray) -> "COO":
        dense = _as_2d(dense)
        values, cols, rows, _ = _csr_arrays(dense)
        return cls(
            values=jnp.asarray(values),
            rows=jnp.asarray(rows),
            columns=jnp.asarray(cols),
            shape=dense.shape,
        )

    @property
    def nnz(self) -> int:
        return int(self.values.shape[0])

    @property
    def stored_elements(self) -> int:
        return self.nnz

    def storage_bytes(self) -> int:
        itemsize = jnp.dtype(self.values.dtype).itemsize
        return self.nnz * (itemsize + 8)  # value + row idx + col idx

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=np.asarray(self.values).dtype)
        np.add.at(out, (np.asarray(self.rows), np.asarray(self.columns)),
                  np.asarray(self.values))
        return out


# ---------------------------------------------------------------------------
# ELLPACK
# ---------------------------------------------------------------------------


@_tree_dataclass
class ELLPACK:
    """ELLPACK (paper Fig. 3), stored slot-major ``(K, N)``.

    Slot-major is the coalesced/GPU layout and equally the TPU-lane layout:
    slot ``k`` of all rows is one contiguous vector.  ``columns`` padding uses
    the row's own index ("ghost index") so gathers stay in-bounds.
    """

    values: Array = _arr()   # (K, N)
    columns: Array = _arr()  # (K, N) int32
    shape: Tuple[int, int] = _static()

    name: ClassVar[str] = "ellpack"

    @classmethod
    def from_dense(cls, dense: np.ndarray) -> "ELLPACK":
        dense = _as_2d(dense)
        n_rows, _ = dense.shape
        row_lens = (dense != 0).sum(axis=1)
        k = int(row_lens.max()) if n_rows else 0
        k = max(k, 1)
        values = np.zeros((k, n_rows), dtype=dense.dtype)
        columns = np.zeros((k, n_rows), dtype=np.int32)
        for i in range(n_rows):
            cols_i = np.nonzero(dense[i])[0]
            values[: len(cols_i), i] = dense[i, cols_i]
            columns[: len(cols_i), i] = cols_i
        return cls(values=jnp.asarray(values), columns=jnp.asarray(columns),
                   shape=dense.shape)

    @property
    def nnz(self) -> int:
        return int((np.asarray(self.values) != 0).sum())

    @property
    def stored_elements(self) -> int:
        return int(np.prod(self.values.shape))

    def storage_bytes(self) -> int:
        itemsize = jnp.dtype(self.values.dtype).itemsize
        return self.stored_elements * (itemsize + 4)

    def to_dense(self) -> np.ndarray:
        k, n_rows = self.values.shape
        out = np.zeros(self.shape, dtype=np.asarray(self.values).dtype)
        vals = np.asarray(self.values)
        cols = np.asarray(self.columns)
        for slot in range(k):
            mask = vals[slot] != 0
            out[np.arange(n_rows)[mask], cols[slot][mask]] += vals[slot][mask]
        return out


# ---------------------------------------------------------------------------
# Hybrid (ELL + COO)
# ---------------------------------------------------------------------------


def _hybrid_split_k(row_lens: np.ndarray, relative_speed: float = 3.0,
                    breakeven_threshold: int = 4096) -> int:
    """Bell–Garland / CUSP heuristic for K1 (paper §3.3).

    Choose the largest K such that at least ``max(N/relative_speed,
    breakeven_threshold)`` rows still have >= K nonzeros — i.e. the ELL part
    stays mostly dense and the spill goes to COO.
    """
    n = len(row_lens)
    if n == 0:
        return 0
    hist = np.bincount(np.minimum(row_lens, row_lens.max()), minlength=row_lens.max() + 2)
    # rows_with_at_least[k] = number of rows with >= k nonzeros
    rows_with_at_least = n - np.cumsum(hist)[:-1]
    threshold = min(n, max(n / relative_speed, breakeven_threshold))
    ks = np.nonzero(rows_with_at_least >= threshold)[0]
    return int(ks.max()) if len(ks) else 0


@_tree_dataclass
class HybridEllCoo:
    """Hybrid format [Bell & Garland 2008] (paper §3.3): ELLPACK for the first
    ``k1`` nonzeros of each row, COO for the spill."""

    ell_values: Array = _arr()   # (K1, N)
    ell_columns: Array = _arr()  # (K1, N)
    coo_values: Array = _arr()
    coo_rows: Array = _arr()
    coo_columns: Array = _arr()
    shape: Tuple[int, int] = _static()
    k1: int = _static()

    name: ClassVar[str] = "hybrid"

    @classmethod
    def from_csr(cls, values, columns, row_ptr, shape: Tuple[int, int],
                 k1: int | None = None) -> "HybridEllCoo":
        """Build from a host CSR triplet (vectorized, no densification):
        each row's first ``k1`` entries go to the ELL part, the rest to
        the COO tail in CSR order."""
        row_ptr = np.asarray(row_ptr).astype(np.int64)
        nnz = int(row_ptr[-1])
        values = np.asarray(values)[:nnz]
        columns = np.asarray(columns)[:nnz].astype(np.int32)
        n_rows = int(shape[0])
        row_lens = np.diff(row_ptr)
        if k1 is None:
            k1 = _hybrid_split_k(row_lens)
        k1 = int(max(k1, 0))
        rows = np.repeat(np.arange(n_rows, dtype=np.int64), row_lens)
        slot = np.arange(nnz, dtype=np.int64) - np.repeat(row_ptr[:-1],
                                                          row_lens)
        head = slot < k1
        ell_values = np.zeros((max(k1, 1), n_rows), dtype=values.dtype)
        ell_columns = np.zeros((max(k1, 1), n_rows), dtype=np.int32)
        ell_values[slot[head], rows[head]] = values[head]
        ell_columns[slot[head], rows[head]] = columns[head]
        tail = ~head
        return cls(
            ell_values=jnp.asarray(ell_values),
            ell_columns=jnp.asarray(ell_columns),
            coo_values=jnp.asarray(values[tail]),
            coo_rows=jnp.asarray(rows[tail].astype(np.int32)),
            coo_columns=jnp.asarray(columns[tail]),
            shape=(n_rows, int(shape[1])),
            k1=k1,
        )

    @classmethod
    def from_dense(cls, dense: np.ndarray, k1: int | None = None) -> "HybridEllCoo":
        dense = _as_2d(dense)
        values, cols, _, row_ptr = _csr_arrays(dense)
        return cls.from_csr(values, cols, row_ptr, dense.shape, k1=k1)

    @property
    def nnz(self) -> int:
        return int((np.asarray(self.ell_values) != 0).sum()) + int(self.coo_values.shape[0])

    @property
    def stored_elements(self) -> int:
        return int(np.prod(self.ell_values.shape)) + int(self.coo_values.shape[0])

    def storage_bytes(self) -> int:
        itemsize = jnp.dtype(self.ell_values.dtype).itemsize
        ell = int(np.prod(self.ell_values.shape)) * (itemsize + 4)
        coo = int(self.coo_values.shape[0]) * (itemsize + 8)
        return ell + coo

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=np.asarray(self.ell_values).dtype)
        vals = np.asarray(self.ell_values)
        cols = np.asarray(self.ell_columns)
        n_rows = self.shape[0]
        for slot in range(vals.shape[0]):
            mask = vals[slot] != 0
            out[np.arange(n_rows)[mask], cols[slot][mask]] += vals[slot][mask]
        np.add.at(out, (np.asarray(self.coo_rows), np.asarray(self.coo_columns)),
                  np.asarray(self.coo_values))
        return out


# ---------------------------------------------------------------------------
# Blocked CSR (BSR)
# ---------------------------------------------------------------------------


@_tree_dataclass
class BlockedCSR:
    """Blocked CSR (paper §3.2, Fig. 2): dense ``bs x bs`` blocks of the matrix
    itself (not of the compressed rows) — the format the paper criticizes for
    low fill efficiency (27% in Fig. 2)."""

    values: Array = _arr()         # (n_blocks, bs, bs)
    block_columns: Array = _arr()  # (n_blocks,)
    block_row_pointers: Array = _arr()  # (n_block_rows + 1,)
    block_row_ids: Array = _arr()  # derived, for the jnp oracle
    shape: Tuple[int, int] = _static()
    block_size: int = _static()

    name: ClassVar[str] = "blocked_csr"

    @classmethod
    def from_dense(cls, dense: np.ndarray, block_size: int = 4) -> "BlockedCSR":
        dense = _as_2d(dense)
        n_rows, n_cols = dense.shape
        bs = block_size
        pr = (-n_rows) % bs
        pc = (-n_cols) % bs
        padded = np.pad(dense, ((0, pr), (0, pc)))
        nbr, nbc = padded.shape[0] // bs, padded.shape[1] // bs
        blocks = padded.reshape(nbr, bs, nbc, bs).transpose(0, 2, 1, 3)
        nz_block = (blocks != 0).any(axis=(2, 3))
        brows, bcols = np.nonzero(nz_block)
        values = blocks[brows, bcols]
        ptr = np.zeros(nbr + 1, dtype=np.int32)
        np.add.at(ptr, brows + 1, 1)
        ptr = np.cumsum(ptr).astype(np.int32)
        return cls(
            values=jnp.asarray(values),
            block_columns=jnp.asarray(bcols.astype(np.int32)),
            block_row_pointers=jnp.asarray(ptr),
            block_row_ids=jnp.asarray(brows.astype(np.int32)),
            shape=dense.shape,
            block_size=bs,
        )

    @property
    def nnz(self) -> int:
        return int((np.asarray(self.values) != 0).sum())

    @property
    def stored_elements(self) -> int:
        return int(np.prod(self.values.shape))

    def storage_bytes(self) -> int:
        itemsize = jnp.dtype(self.values.dtype).itemsize
        nb = int(self.values.shape[0])
        return self.stored_elements * itemsize + nb * 4 + (len(self.block_row_pointers)) * 4

    def to_dense(self) -> np.ndarray:
        bs = self.block_size
        nbr = len(np.asarray(self.block_row_pointers)) - 1
        nbc = (self.shape[1] + bs - 1) // bs
        out = np.zeros((nbr * bs, nbc * bs), dtype=np.asarray(self.values).dtype)
        vals = np.asarray(self.values)
        brows = np.asarray(self.block_row_ids)
        bcols = np.asarray(self.block_columns)
        for b in range(vals.shape[0]):
            r0, c0 = brows[b] * bs, bcols[b] * bs
            out[r0:r0 + bs, c0:c0 + bs] += vals[b]
        return out[: self.shape[0], : self.shape[1]]


# ---------------------------------------------------------------------------
# Row-grouped CSR — the paper's format — and Sliced ELLPACK
# ---------------------------------------------------------------------------


def _rgcsr_arrays(values, columns, row_ptr, n_rows: int, group_size: int,
                  slot_pad: int):
    """Build slot-major grouped arrays from a host CSR triplet (vectorized).
    Returns a dict of numpy arrays.

    Layout: group ``g`` covers rows ``[g*G, min((g+1)*G, N))``; its data is a
    dense ``(K_g, G)`` tile flattened into ``values``/``columns`` starting at
    ``group_pointers[g]``, where element ``(slot, r)`` sits at
    ``group_pointers[g] + slot*G + r``.  ``K_g`` = max row length in the group,
    rounded up to ``slot_pad`` (TPU sublane packing; paper pads to the max
    row length only — the extra pad is accounted as artificial zeros too).
    The last group is padded to a full ``G`` rows (lanes must be full).
    Row ``r``'s CSR entries fill slots ``0..len-1`` of its lane in CSR order.
    """
    values = np.asarray(values)
    columns = np.asarray(columns)
    row_ptr = np.asarray(row_ptr).astype(np.int64)
    if len(row_ptr) != n_rows + 1:
        raise ValueError(f"row_ptr has {len(row_ptr)} entries for "
                         f"{n_rows} rows")
    g_size = int(group_size)
    n_groups = max(1, -(-n_rows // g_size))
    row_lens = np.diff(row_ptr).astype(np.int32)

    lens_grid = np.zeros(n_groups * g_size, np.int64)
    lens_grid[:n_rows] = row_lens
    k_g = np.maximum(lens_grid.reshape(n_groups, g_size).max(axis=1), 1)
    if slot_pad > 1:
        k_g = -(-k_g // slot_pad) * slot_pad
    slots_per_group = k_g.astype(np.int32)
    group_ptr = np.concatenate([[0], np.cumsum(k_g * g_size)])
    total = int(group_ptr[-1])

    rows = np.repeat(np.arange(n_rows, dtype=np.int64), row_lens)
    slot = np.arange(len(rows), dtype=np.int64) - np.repeat(row_ptr[:-1],
                                                           row_lens)
    flat = group_ptr[rows // g_size] + slot * g_size + rows % g_size
    out_values = np.zeros(total, dtype=values.dtype)
    out_columns = np.zeros(total, dtype=np.int32)     # ghost index 0 on pad
    out_values[flat] = values[: len(rows)]
    out_columns[flat] = columns[: len(rows)]

    # derived (oracle only): row of every stored element; lanes past the
    # last real row default to their group's first row (values are 0)
    grp = np.repeat(np.arange(n_groups, dtype=np.int64), k_g * g_size)
    lane = (np.arange(total, dtype=np.int64) - group_ptr[grp]) % g_size
    row_of_element = grp * g_size + lane
    row_of_element = np.where(row_of_element < n_rows, row_of_element,
                              grp * g_size).astype(np.int32)
    return dict(
        values=out_values,
        columns=out_columns,
        group_pointers=group_ptr.astype(np.int32),
        row_lengths=row_lens,
        slots_per_group=slots_per_group,
        row_of_element=row_of_element,
        n_groups=n_groups,
    )


def _dense_rgcsr_arrays(dense, group_size: int, slot_pad: int):
    dense = _as_2d(dense)
    values, cols, _, row_ptr = _csr_arrays(dense)
    return _rgcsr_arrays(values, cols, row_ptr, dense.shape[0], group_size,
                         slot_pad)


@_tree_dataclass
class RgCSR:
    """Row-grouped CSR — the paper's contribution (§3.4, Fig. 5).

    ``values``/``columns``: flat slot-major grouped storage.
    ``group_pointers``:     offset of each group (paper's groupPointers).
    ``row_lengths``:        true nnz per row (paper's rowLengths — the delta
                            vs sliced ELLPACK: lets the kernel skip padding).
    ``slots_per_group``:    K_g per group (derivable from group_pointers; kept
                            for the chunk table used by the Pallas kernel).
    ``row_of_element``:     derived row index per stored element — used only by
                            the vectorized jnp oracle, excluded from storage
                            accounting (a CUDA thread derives it from its id).
    """

    values: Array = _arr()
    columns: Array = _arr()
    group_pointers: Array = _arr()
    row_lengths: Array = _arr()
    slots_per_group: Array = _arr()
    row_of_element: Array = _arr()
    shape: Tuple[int, int] = _static()
    group_size: int = _static()
    slot_pad: int = _static()

    name: ClassVar[str] = "rgcsr"

    @classmethod
    def from_csr(cls, values, columns, row_ptr, shape: Tuple[int, int],
                 group_size: int = TPU_LANES,
                 slot_pad: int = TPU_SUBLANES) -> "RgCSR":
        """Build from a host CSR triplet without densifying — the one
        construction path (``from_dense`` goes through it), so matrices
        far larger than a dense host array can reach the kernel."""
        shape = (int(shape[0]), int(shape[1]))
        arrs = _rgcsr_arrays(values, columns, row_ptr, shape[0], group_size,
                             slot_pad)
        return cls(
            values=jnp.asarray(arrs["values"]),
            columns=jnp.asarray(arrs["columns"]),
            group_pointers=jnp.asarray(arrs["group_pointers"]),
            row_lengths=jnp.asarray(arrs["row_lengths"]),
            slots_per_group=jnp.asarray(arrs["slots_per_group"]),
            row_of_element=jnp.asarray(arrs["row_of_element"]),
            shape=shape,
            group_size=int(group_size),
            slot_pad=int(slot_pad),
        )

    @classmethod
    def from_dense(cls, dense: np.ndarray, group_size: int = TPU_LANES,
                   slot_pad: int = TPU_SUBLANES) -> "RgCSR":
        dense = _as_2d(dense)
        values, cols, _, row_ptr = _csr_arrays(dense)
        return cls.from_csr(values, cols, row_ptr, dense.shape,
                            group_size=group_size, slot_pad=slot_pad)

    @property
    def n_groups(self) -> int:
        return int(self.slots_per_group.shape[0])

    @property
    def nnz(self) -> int:
        return int(np.asarray(self.row_lengths).sum())

    @property
    def stored_elements(self) -> int:
        return int(self.values.shape[0])

    def fill_ratio(self) -> float:
        """Paper's "artificial zeros" metric: pad/nnz as a percentage.
        100% = as many artificial zeros as true nonzeros."""
        nnz = self.nnz
        if nnz == 0:
            return 0.0
        return 100.0 * (self.stored_elements - nnz) / nnz

    def storage_bytes(self) -> int:
        itemsize = jnp.dtype(self.values.dtype).itemsize
        n_rows = self.shape[0]
        return (self.stored_elements * (itemsize + 4)
                + (self.n_groups + 1) * 4 + n_rows * 4)

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=np.asarray(self.values).dtype)
        vals = np.asarray(self.values)
        cols = np.asarray(self.columns)
        rows = np.asarray(self.row_of_element)
        mask = vals != 0
        np.add.at(out, (rows[mask], cols[mask]), vals[mask])
        return out

    def to_csr_arrays(self):
        """Host CSR triplet ``(values, columns, row_ptr)`` recovered from the
        grouped slot-major storage — no densification.

        Used by the adaptive planner (kernels/ops, ordering='adaptive') to
        regroup rows by length.  Extraction is *positional*: row ``r`` owns
        slots ``[0, row_lengths[r])`` of its lane, i.e. flat indices
        ``group_pointers[r // G] + slot·G + (r % G)``.  Selecting by stored
        value (``!= 0``) would misalign every subsequent row if a true
        element happens to equal 0.0 (e.g. a trained value crossing zero),
        so positions — not values — define membership.
        """
        vals = np.asarray(self.values)
        cols = np.asarray(self.columns)
        g = self.group_size
        row_lens = np.asarray(self.row_lengths).astype(np.int64)
        gp = np.asarray(self.group_pointers).astype(np.int64)
        row_ptr = np.concatenate([[0], np.cumsum(row_lens)])
        total = int(row_ptr[-1])
        rows = np.repeat(np.arange(len(row_lens), dtype=np.int64), row_lens)
        slot = np.arange(total, dtype=np.int64) - np.repeat(
            row_ptr[:-1], row_lens)
        flat = gp[rows // g] + slot * g + (rows % g)
        return vals[flat], cols[flat], row_ptr


@_tree_dataclass
class SlicedEllpack:
    """Sliced ELLPACK [Monakov et al. 2010] (paper §3.4): same grouped
    slot-major layout as RgCSR but WITHOUT ``row_lengths`` — every row in a
    group performs K_g multiply-adds including the padding (the paper's
    "meaningless arithmetic").  Storage equals RgCSR minus the rowLengths
    array; compute is modeled accordingly in :mod:`repro.core.analyze`."""

    values: Array = _arr()
    columns: Array = _arr()
    group_pointers: Array = _arr()
    slots_per_group: Array = _arr()
    row_of_element: Array = _arr()
    shape: Tuple[int, int] = _static()
    group_size: int = _static()
    slot_pad: int = _static()

    name: ClassVar[str] = "sliced_ellpack"

    @classmethod
    def from_dense(cls, dense: np.ndarray, group_size: int = TPU_LANES,
                   slot_pad: int = TPU_SUBLANES) -> "SlicedEllpack":
        arrs = _dense_rgcsr_arrays(dense, group_size, slot_pad)
        return cls(
            values=jnp.asarray(arrs["values"]),
            columns=jnp.asarray(arrs["columns"]),
            group_pointers=jnp.asarray(arrs["group_pointers"]),
            slots_per_group=jnp.asarray(arrs["slots_per_group"]),
            row_of_element=jnp.asarray(arrs["row_of_element"]),
            shape=_as_2d(dense).shape,
            group_size=int(group_size),
            slot_pad=int(slot_pad),
        )

    @property
    def nnz(self) -> int:
        return int((np.asarray(self.values) != 0).sum())

    @property
    def stored_elements(self) -> int:
        return int(self.values.shape[0])

    def storage_bytes(self) -> int:
        itemsize = jnp.dtype(self.values.dtype).itemsize
        n_groups = int(self.slots_per_group.shape[0])
        return self.stored_elements * (itemsize + 4) + (n_groups + 1) * 4

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape, dtype=np.asarray(self.values).dtype)
        vals = np.asarray(self.values)
        cols = np.asarray(self.columns)
        rows = np.asarray(self.row_of_element)
        mask = vals != 0
        np.add.at(out, (rows[mask], cols[mask]), vals[mask])
        return out


# ---------------------------------------------------------------------------
# Row-sharded RgCSR — one RgCSR per device shard (multi-device SpMV)
# ---------------------------------------------------------------------------


@_tree_dataclass
class ShardedRgCSR:
    """RgCSR partitioned by rows over a 1-D mesh axis (DESIGN.md §11).

    The canonical distributed-SpMV decomposition (Kreutzer et al.,
    arXiv:1112.5588): shard ``d`` owns the contiguous row block
    ``[d·rows_per_shard, (d+1)·rows_per_shard)`` and stores it as its own
    :class:`RgCSR` — so block/adaptive grouping, slot padding and the step
    table all apply *per shard*, and per-device stored slots and grid steps
    shrink ~1/D.  Columns keep their **global** indices here; the local /
    remote split (columns owned by this device vs. columns whose x-entries
    must be communicated) is computed at plan time
    (:func:`repro.kernels.ops.make_sharded_plan`) because it depends on the
    execution mode.

    Every shard is built over exactly ``rows_per_shard`` rows (the trailing
    shard is padded with empty rows), so all shards have the *same* group
    count — the uniformity `shard_map` needs for SPMD execution.
    """

    shards: Tuple[RgCSR, ...] = _arr()   # pytree children (one per device)
    shape: Tuple[int, int] = _static()
    n_shards: int = _static()
    rows_per_shard: int = _static()
    group_size: int = _static()
    slot_pad: int = _static()

    name: ClassVar[str] = "sharded_rgcsr"

    @staticmethod
    def shard_layout(n_rows: int, n_cols: int,
                     n_shards: int) -> Tuple[int, int]:
        """``(rows_per_shard, cols_per_shard)`` ceil-div layout.

        The single source of the shard geometry — plan construction
        (``ops.make_sharded_plan``) and per-shard tuning
        (``autotune.shard_row_blocks``) derive their blocks from this, so
        a layout change here cannot silently desynchronize them.
        """
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        return (max(1, -(-n_rows // n_shards)),
                max(1, -(-n_cols // n_shards)))

    @classmethod
    def from_csr(cls, values, columns, row_ptr, shape: Tuple[int, int],
                 n_shards: int, group_size: int = TPU_LANES,
                 slot_pad: int = TPU_SUBLANES) -> "ShardedRgCSR":
        """Row-shard a host CSR triplet (no densification): shard ``d``
        gets rows ``[d·rps, (d+1)·rps)`` as its own :class:`RgCSR`."""
        values = np.asarray(values)
        columns = np.asarray(columns)
        row_ptr = np.asarray(row_ptr).astype(np.int64)
        n_rows, n_cols = int(shape[0]), int(shape[1])
        rps, _ = cls.shard_layout(n_rows, n_cols, n_shards)
        shards = []
        for d in range(n_shards):
            lo, hi = min(d * rps, n_rows), min((d + 1) * rps, n_rows)
            ptr = np.zeros(rps + 1, np.int64)
            ptr[1: hi - lo + 1] = row_ptr[lo + 1: hi + 1] - row_ptr[lo]
            ptr[hi - lo + 1:] = ptr[hi - lo]       # trailing empty rows
            sel = slice(int(row_ptr[lo]), int(row_ptr[hi]))
            shards.append(RgCSR.from_csr(values[sel], columns[sel], ptr,
                                         (rps, n_cols),
                                         group_size=group_size,
                                         slot_pad=slot_pad))
        return cls(shards=tuple(shards), shape=(n_rows, n_cols),
                   n_shards=int(n_shards), rows_per_shard=rps,
                   group_size=int(group_size), slot_pad=int(slot_pad))

    @classmethod
    def from_dense(cls, dense: np.ndarray, n_shards: int,
                   group_size: int = TPU_LANES,
                   slot_pad: int = TPU_SUBLANES) -> "ShardedRgCSR":
        dense = _as_2d(dense)
        values, cols, _, row_ptr = _csr_arrays(dense)
        return cls.from_csr(values, cols, row_ptr, dense.shape, n_shards,
                            group_size=group_size, slot_pad=slot_pad)

    @property
    def nnz(self) -> int:
        return sum(s.nnz for s in self.shards)

    @property
    def stored_elements(self) -> int:
        return sum(s.stored_elements for s in self.shards)

    def storage_bytes(self) -> int:
        return sum(s.storage_bytes() for s in self.shards)

    def shard_rows(self, d: int) -> Tuple[int, int]:
        """(lo, hi) global row range truly owned by shard ``d`` (unpadded)."""
        lo = d * self.rows_per_shard
        return lo, min(lo + self.rows_per_shard, self.shape[0])

    def to_dense(self) -> np.ndarray:
        out = np.zeros(self.shape,
                       dtype=np.asarray(self.shards[0].values).dtype)
        for d, s in enumerate(self.shards):
            lo, hi = self.shard_rows(d)
            if hi > lo:
                out[lo:hi] = s.to_dense()[: hi - lo]
        return out


FORMATS = {
    "csr": CSR,
    "coo": COO,
    "ellpack": ELLPACK,
    "hybrid": HybridEllCoo,
    "blocked_csr": BlockedCSR,
    "sliced_ellpack": SlicedEllpack,
    "rgcsr": RgCSR,
}


def from_dense(dense: np.ndarray, fmt: str = "rgcsr", **kwargs):
    """Build any of the paper's formats from a dense matrix."""
    try:
        cls = FORMATS[fmt]
    except KeyError:
        raise ValueError(f"unknown format {fmt!r}; options: {sorted(FORMATS)}")
    return cls.from_dense(dense, **kwargs)
