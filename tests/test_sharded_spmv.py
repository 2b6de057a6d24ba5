"""Row-sharded multi-device RgCSR SpMV/SpMM (DESIGN.md §11/§12).

Two layers of coverage:

* in-process tests validate the host-side machinery on the single real CPU
  device — ShardedRgCSR construction, stacked-plan invariants, the §12
  sparse-exchange schedule (send_idx/edge_counts reconstruct x[remote]
  exactly; per-device exchange volume == plan-time remote count), its edge
  cases (empty remote set, all-remote shard, single-device degrade),
  per-shard-config stacking at the gcd kernel cps, and plan-cache keying
  on (x_mode, per-shard configs, shard count — the resized-mesh guard);
* subprocess tests run the actual ``shard_map`` execution path on 8 fake
  host devices (``--xla_force_host_platform_device_count=8`` must live only
  in the child, mirroring tests/test_distributed.py) and assert oracle
  equivalence for ragged, empty-shard, powerlaw and spill-bearing matrices
  × {replicated, split} × uniform/per-shard configs, the ~1/D per-shard
  stored-slots/grid-steps shrink, and the exchange-volume bound on the
  live all_to_all path.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import from_dense
from repro.core.formats import ShardedRgCSR
from repro.core.spmv import spmv
from repro.kernels import ops as kops
from repro.kernels.rgcsr_spmv import rgcsr_spmv_pallas
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rand(seed, n, m, density):
    rng = np.random.default_rng(seed)
    a = (rng.uniform(size=(n, m)) < density).astype(np.float32)
    a *= rng.uniform(0.5, 1.5, size=(n, m)).astype(np.float32)
    return a


# ------------------------------------------------------------- construction


def test_sharded_rgcsr_construction_covers_rows():
    a = _rand(0, 300, 280, 0.05)                   # 300/8 → ragged last shard
    sm = ShardedRgCSR.from_dense(a, n_shards=8)
    assert sm.n_shards == 8 and sm.rows_per_shard == 38
    assert sm.nnz == int((a != 0).sum())
    assert all(s.shape == (38, 280) for s in sm.shards)
    np.testing.assert_array_equal(sm.to_dense(), a)
    lo, hi = sm.shard_rows(7)
    assert (lo, hi) == (266, 300)                  # unpadded true range


def test_sharded_rgcsr_empty_trailing_shard():
    a = _rand(1, 20, 64, 0.2)
    sm = ShardedRgCSR.from_dense(a, n_shards=8)    # rps=3: shard 7 is empty
    assert sm.rows_per_shard == 3
    lo, hi = sm.shard_rows(7)
    assert hi <= lo                                # owns no real rows
    assert sm.shards[7].nnz == 0
    np.testing.assert_array_equal(sm.to_dense(), a)


def test_sharded_rgcsr_rejects_bad_shards():
    with pytest.raises(ValueError):
        ShardedRgCSR.from_dense(_rand(2, 16, 16, 0.2), n_shards=0)


# ------------------------------------------------------------ plan stacking


def test_sharded_plan_uniform_stacking():
    a = _rand(3, 300, 280, 0.05)
    sm = ShardedRgCSR.from_dense(a, n_shards=4)
    plan = kops.make_sharded_plan(sm, chunks_per_step=2)
    d, s_pad, g = plan.values3d.shape
    assert (d, g) == (4, 128)
    assert s_pad == plan.num_steps_max * 2 * 8     # S_pad = T_max·R
    assert plan.step_group2d.shape == (4, plan.num_steps_max)
    assert len(plan.shard_stored_slots) == 4
    # true per-shard slots never exceed the stacked (padded) slot count
    assert max(plan.shard_stored_slots) <= s_pad
    # per-shard padding steps carry no accumulator-init flags
    sf = np.asarray(plan.step_first2d)
    for i, t in enumerate(plan.shard_num_steps):
        assert (sf[i, t:] == 0).all()


def test_sharded_plan_split_remote_cols_disjoint_from_local():
    a = _rand(4, 256, 256, 0.04)
    sm = ShardedRgCSR.from_dense(a, n_shards=4)
    plan = kops.make_sharded_plan(sm, x_mode="split")
    assert plan.cols_per_shard == 64
    rc = np.asarray(plan.remote_cols)
    for d in range(4):
        lo, hi = d * 64, (d + 1) * 64
        real = rc[d, : plan.shard_remote_cols[d]]
        assert ((real < lo) | (real >= hi)).all()  # remote = not owned
        assert len(np.unique(real)) == len(real)
    # grouped storage is local-only: the kernel's x working set is exactly
    # this device's slice — remote entries live in the rem_* exchange tail
    assert int(np.asarray(plan.columns3d).max()) < plan.cols_per_shard


def test_exchange_schedule_matches_remote_sets():
    """The tentpole bound: the plan-time send schedule moves exactly each
    shard's remote column set — per-device exchange volume == remote count
    — and the schedule's (src, dst) edges reconstruct x[remote] verbatim."""
    a = _rand(11, 256, 256, 0.04)
    sm = ShardedRgCSR.from_dense(a, n_shards=4)
    plan = kops.make_sharded_plan(sm, x_mode="split")
    assert plan.has_exchange
    ec = np.asarray(plan.edge_counts)
    # received entries per dst == that shard's plan-time remote count
    assert plan.shard_exchange_recv_cols == plan.shard_remote_cols
    assert tuple(ec.sum(axis=0)) == plan.shard_remote_cols
    assert int(ec.max()) <= plan.e_max
    # the schedule delivers exactly x[remote] to every dst: edge (s → d)
    # holds d's remote columns owned by s in sorted order, and send_idx
    # addresses them inside s's own slice
    cstride = plan.cols_per_shard
    x = np.random.default_rng(12).standard_normal(
        plan.n_shards * cstride).astype(np.float32)
    sidx = np.asarray(plan.send_idx)
    for d in range(plan.n_shards):
        remote = np.asarray(plan.remote_cols)[d, : plan.shard_remote_cols[d]]
        for s in range(plan.n_shards):
            edge = remote[(remote >= s * cstride)
                          & (remote < (s + 1) * cstride)]
            local_idx = sidx[s, d, : len(edge)]
            assert (local_idx < cstride).all()
            np.testing.assert_array_equal(
                x[s * cstride: (s + 1) * cstride][local_idx], x[edge])


def _emulate_shard(plan, d, x):
    """Run one device's slice of the stacked plan directly (no shard_map):
    local kernel over the owned x slice, plus the emulated sparse-exchange
    remote tail in split mode."""
    cstride = plan.cols_per_shard
    if plan.x_mode == "split":
        xw = plan.n_shards * cstride
        x_glob = np.zeros(xw, np.float32)
        x_glob[: plan.n_cols] = x
        x_use = x_glob[d * cstride: (d + 1) * cstride]
    else:
        x_use = x
    y = rgcsr_spmv_pallas(
        plan.step_group2d[d], plan.step_first2d[d], plan.values3d[d],
        plan.columns3d[d], jnp.asarray(x_use), n_groups=plan.n_groups,
        group_size=plan.group_size, chunks_per_step=plan.chunks_per_step,
        interpret=True)
    y = np.asarray(y).reshape(-1)[: plan.rows_per_shard].copy()
    if plan.x_mode == "split" and plan.has_exchange:
        # emulate the all_to_all: recv[s·e_max + e] = x_src[send_idx[s, d, e]]
        recv = np.zeros(plan.n_shards * plan.e_max, np.float32)
        sidx = np.asarray(plan.send_idx)
        for s in range(plan.n_shards):
            recv[s * plan.e_max: (s + 1) * plan.e_max] = \
                x_glob[s * cstride: (s + 1) * cstride][sidx[s, d]]
        rv = np.asarray(plan.rem_values)[d]
        rr = np.asarray(plan.rem_rows)[d]
        rx = np.asarray(plan.rem_xidx)[d]
        np.add.at(y, rr, rv * recv[rx])
    return y


@pytest.mark.parametrize("x_mode", ["replicated", "split"])
def test_sharded_plan_per_device_slices_match_blocks(x_mode):
    """Each device's stacked slice × its compact x equals the dense row
    block — the remap/local-remote split is exercised without any mesh."""
    a = _rand(5, 200, 190, 0.06)
    sm = ShardedRgCSR.from_dense(a, n_shards=4)
    plan = kops.make_sharded_plan(sm, chunks_per_step=2, x_mode=x_mode)
    x = np.random.default_rng(6).standard_normal(190).astype(np.float32)
    for d in range(4):
        lo, hi = sm.shard_rows(d)
        y_d = _emulate_shard(plan, d, x)
        np.testing.assert_allclose(y_d[: hi - lo], a[lo:hi] @ x,
                                   rtol=1e-4, atol=1e-4)


def test_split_empty_remote_set_skips_exchange():
    """Block-diagonal matrix: every shard references only its own columns,
    so the plan carries no exchange at all and still matches the oracle."""
    a = np.zeros((256, 256), np.float32)
    for d in range(4):
        a[d * 64: (d + 1) * 64, d * 64: (d + 1) * 64] = \
            _rand(20 + d, 64, 64, 0.2)
    sm = ShardedRgCSR.from_dense(a, n_shards=4)
    plan = kops.make_sharded_plan(sm, x_mode="split")
    assert plan.e_max == 0 and not plan.has_exchange
    assert plan.send_idx is None and plan.rem_values is None
    assert plan.shard_remote_cols == (0, 0, 0, 0)
    assert plan.shard_exchange_bytes == (0, 0, 0, 0)
    x = np.random.default_rng(21).standard_normal(256).astype(np.float32)
    for d in range(4):
        np.testing.assert_allclose(
            _emulate_shard(plan, d, x), a[d * 64: (d + 1) * 64] @ x,
            rtol=1e-4, atol=1e-4)


def test_split_all_remote_shard():
    """A shard whose every referenced column is owned elsewhere: its local
    grouped plan is empty and the remote tail carries the whole row block."""
    a = _rand(22, 128, 128, 0.06)
    a[:32, :32] = 0.0                  # shard 0 owns cols [0, 32): zero them
    a[:32, 100] = 1.5                  # …but keep remote references
    sm = ShardedRgCSR.from_dense(a, n_shards=4)
    plan = kops.make_sharded_plan(sm, x_mode="split")
    assert plan.shard_remote_cols[0] > 0
    assert np.asarray(plan.values3d)[0, :, :].max() == 0  # no local entries
    x = np.random.default_rng(23).standard_normal(128).astype(np.float32)
    for d in range(4):
        lo, hi = sm.shard_rows(d)
        np.testing.assert_allclose(_emulate_shard(plan, d, x),
                                   a[lo:hi] @ x, rtol=1e-4, atol=1e-4)


def test_split_single_device_degrades_to_local_only():
    """n_shards=1: the shard owns every column, split mode has no exchange,
    and the real shard_map path runs on the one physical CPU device."""
    import jax
    a = _rand(24, 128, 96, 0.08)
    sm = ShardedRgCSR.from_dense(a, n_shards=1)
    plan = kops.get_sharded_plan(sm, x_mode="split")
    assert plan.n_shards == 1 and not plan.has_exchange
    assert plan.shard_remote_cols == (0,)
    mesh = make_mesh((1,), ("model",))
    x = np.random.default_rng(25).standard_normal(96).astype(np.float32)
    y = np.asarray(spmv(sm, jnp.asarray(x), mesh=mesh, mesh_axis="model",
                        x_mode="split"))
    np.testing.assert_allclose(y, a @ x, rtol=1e-4, atol=1e-4)


def test_per_shard_configs_stack_at_gcd_cps():
    """Mixed per-shard winners: each shard keeps its own padding
    granularity/ordering/spill, step tables expand to the gcd kernel cps,
    and every device slice still reproduces its dense row block."""
    a = _rand(26, 200, 190, 0.06)
    a[7, :150] = 1.0                               # heavy row in shard 0
    sm = ShardedRgCSR.from_dense(a, n_shards=4)
    cfgs = [(1, "adaptive", 8), (4, "block", 0), (2, "block", 0),
            (2, "adaptive", 0)]
    plan = kops.make_sharded_plan(sm, x_mode="split", shard_configs=cfgs)
    assert plan.chunks_per_step == 1               # gcd of {1, 4, 2, 2}
    assert plan.shard_configs == ((1, "adaptive", 8), (4, "block", 0),
                                  (2, "block", 0), (2, "adaptive", 0))
    assert plan.ordering == "adaptive"             # any shard adaptive
    assert sum(plan.shard_spilled_elements) > 0    # shard 0 spilled
    # emulation needs the adaptive gather; go through the real shard_map
    # path on a 1-D mesh only in the subprocess tests — here verify the
    # block shards' slices directly and the table expansion invariants
    sf = np.asarray(plan.step_first2d)
    sg = np.asarray(plan.step_group2d)
    for d, (cps_d, _, _) in enumerate(cfgs):
        t_d = plan.shard_num_steps[d]
        f = cps_d // plan.chunks_per_step
        # init flags only ever sit on coarse-step boundaries, so the
        # expanded fine steps of one coarse step accumulate consecutively
        assert all(j % f == 0 for j in np.flatnonzero(sf[d, :t_d]))
        assert (np.diff(sg[d, :t_d]) >= 0).all()   # groups stay ordered
        assert (sf[d, t_d:] == 0).all()            # padding steps never init


def test_sharded_plan_cache_keys_on_x_mode_config_and_shards():
    sm = ShardedRgCSR.from_dense(_rand(7, 128, 128, 0.05), n_shards=4)
    p1 = kops.get_sharded_plan(sm)
    p2 = kops.get_sharded_plan(sm, x_mode="split")
    p3 = kops.get_sharded_plan(sm, ordering="adaptive", spill_threshold=8)
    per_shard = [(2, "block", 0), (1, "adaptive", 8), (1, "block", 0),
                 (2, "adaptive", 0)]
    p4 = kops.get_sharded_plan(sm, x_mode="split", shard_configs=per_shard)
    assert p1 is not p2 and p2 is not p3 and p3 is not p4
    assert kops.get_sharded_plan(sm) is p1                 # repeat: hit
    assert kops.get_sharded_plan(sm, x_mode="split") is p2
    assert kops.get_sharded_plan(sm, x_mode="split",
                                 shard_configs=per_shard) is p4
    # a uniform shard_configs list is the same key as the broadcast args
    assert kops.get_sharded_plan(
        sm, shard_configs=[(1, "block", 0)] * 4) is p1
    stats = kops.sharded_plan_cache_stats()
    assert stats["hits"] >= 3 and stats["misses"] >= 4


def test_harmonize_shard_winners_respects_bottleneck():
    """The stacked pick is structural-first: grid steps at the candidate
    kernel cps (a deterministic plan property) outrank measured µs, so a
    light shard's marginal cps=1 µs win cannot drag the kernel cps down,
    and host jitter between near-tie candidates cannot flip the heavy
    shard's spill win between runs."""
    from repro.kernels.autotune import (TuneConfig, TuneResult,
                                        harmonize_shard_winners)

    def res(rows):
        timings = tuple((cfg, us) for cfg, us, _ in rows)
        return TuneResult(config=min(timings, key=lambda t: t[1])[0],
                          us_per_call=min(us for _, us in timings),
                          timings=timings, signature=(),
                          plan_stats=tuple(s for _, _, s in rows))

    # rows: (config, measured µs, (stored_slots, stored_elements, spilled))
    light = res([(TuneConfig(1, 128, 128, "block", 0), 100.0,
                  (16, 2048, 0)),
                 (TuneConfig(4, 128, 128, "block", 0), 101.0,
                  (32, 4096, 0)),
                 (TuneConfig(8, 128, 128, "block", 0), 150.0,
                  (64, 8192, 0))])
    heavy = res([(TuneConfig(1, 128, 128, "block", 0), 900.0,
                  (96, 12288, 0)),
                 # µs noise puts block cps4 marginally AHEAD of the spill
                 # config; the spill config's smaller grid must still win
                 (TuneConfig(4, 128, 128, "block", 0), 310.0,
                  (96, 12288, 0)),
                 (TuneConfig(4, 128, 128, "adaptive", 8), 315.0,
                  (32, 4500, 400))])
    picks = harmonize_shard_winners([light, heavy, light])
    # heavy keeps the structurally smaller spill plan despite the µs tie
    assert picks[1] == TuneConfig(4, 128, 128, "adaptive", 8)
    assert all(p.chunks_per_step >= 4 for p in picks)
    # all-identical shards degenerate to the plain independent winners
    same = harmonize_shard_winners([light, light])
    assert all(p.ordering == "block" for p in same)
    # deterministic: re-running with the same tables gives the same picks
    assert harmonize_shard_winners([light, heavy, light]) == picks


def test_engine_warm_sharded_replaces_rewarm_keeps_distinct(
        deterministic_autotune):
    """The engine's warm-plan retention is keyed on exact matrix content:
    re-warming the same matrix replaces its entry (no unbounded growth),
    while two distinct matrices sharing a coarse tuner-signature bucket
    both stay warmed."""
    import jax
    from repro.configs import get_smoke
    from repro.serve import Engine, ServeConfig
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = Engine(get_smoke("granite-3-2b"), ServeConfig(max_seq=32))
    a = _rand(40, 256, 256, 0.05)
    b = _rand(41, 256, 256, 0.05)      # same log2 signature bucket as a
    eng.warm_spmv_plans([a, b], repeats=1, mesh=mesh, x_mode="split")
    assert len(eng._warm_sharded) == 2
    eng.warm_spmv_plans([a], repeats=1, mesh=mesh, x_mode="split")
    assert len(eng._warm_sharded) == 2
    assert eng.sharded_spmv_plans_warmed == 3


def test_sharded_exec_memo_evicts_on_plan_gc():
    """The cached shard_map executable must not pin its plan: the closure
    captures hoisted scalars only, so when the plan dies its exec entries
    are evicted by the finalizer instead of lingering until LRU turnover
    (each would otherwise hold the full stacked device arrays)."""
    import gc
    import jax
    sm = ShardedRgCSR.from_dense(_rand(30, 64, 64, 0.1), n_shards=1)
    plan = kops.make_sharded_plan(sm, x_mode="split")
    mesh = make_mesh((1,), ("model",))
    kops._sharded_exec(plan, "spmv", mesh, "model", True)
    pid = id(plan)
    with kops._SHARDED_LOCK:
        assert any(k[0] == pid for k in kops._SHARDED_EXEC)
    del plan
    gc.collect()
    with kops._SHARDED_LOCK:
        assert not any(k[0] == pid for k in kops._SHARDED_EXEC)


def test_sharded_plan_cache_keys_on_shard_count():
    """Resized-mesh safety: plans for the same dense matrix at different
    shard counts are distinct entries — a re-warm on a resized mesh can
    never be answered with the stale stacked plan."""
    a = _rand(9, 128, 128, 0.05)
    sm4 = ShardedRgCSR.from_dense(a, n_shards=4)
    sm2 = ShardedRgCSR.from_dense(a, n_shards=2)
    p4 = kops.get_sharded_plan(sm4, x_mode="split")
    p2 = kops.get_sharded_plan(sm2, x_mode="split")
    assert p4 is not p2
    assert p4.n_shards == 4 and p2.n_shards == 2
    # the key carries the shard count explicitly, not just matrix identity
    with kops._SHARDED_LOCK:
        keys = [k for k in kops._SHARDED_PLANS
                if k[0] in (id(sm4), id(sm2))]
    assert all(len(k) == 4 and k[1] in (2, 4) for k in keys)


def test_sharded_spmv_requires_mesh():
    sm = ShardedRgCSR.from_dense(_rand(8, 64, 64, 0.1), n_shards=2)
    with pytest.raises(ValueError, match="mesh"):
        spmv(sm, jnp.zeros(64))


def test_partitioner_resolves_sparse_rows_axis():
    import jax
    from repro.sharding import Partitioner
    mesh = make_mesh((1, 1), ("data", "model"))
    for kind in ("train", "decode"):
        part = Partitioner(mesh, kind)
        assert part.spmv_shard_axis() == "model"
        assert part.spmv_shard_count() == 1


# ---------------------------------------------- shard_map on 8 fake devices


def _run(code: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0 and "OK" in out.stdout, \
        (out.stdout[-1500:], out.stderr[-3000:])


def test_sharded_spmv_matches_oracle_on_8_devices():
    """The acceptance sweep: ragged, empty-shard, powerlaw and
    spill-bearing matrices × {replicated, split} × {block, adaptive},
    SpMV and SpMM, all equal to the jnp oracle up to fp reassociation —
    plus the ~1/D per-shard stored-slots / grid-steps shrink."""
    _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.formats import RgCSR, ShardedRgCSR
        from repro.core.spmv import spmv, spmm
        from repro.core.suite import generate
        from repro.kernels import ops as kops

        mesh = make_mesh((8,), ("model",))
        rng = np.random.default_rng(0)

        def check(a, **kw):
            sm = ShardedRgCSR.from_dense(a, n_shards=8)
            x = rng.standard_normal(a.shape[1]).astype(np.float32)
            y = np.asarray(spmv(sm, jnp.asarray(x), mesh=mesh, **kw))
            np.testing.assert_allclose(y, a @ x, rtol=1e-4, atol=1e-4)

        def rand(seed, n, m, density):
            r = np.random.default_rng(seed)
            a = (r.uniform(size=(n, m)) < density).astype(np.float32)
            return a * r.uniform(0.5, 1.5, (n, m)).astype(np.float32)

        ragged = rand(1, 300, 280, 0.05)           # 300 = 7·38 + 34
        tiny = rand(2, 20, 64, 0.2)                # shard 7 empty
        power = generate("powerlaw", 256, seed=0)
        skew = rand(3, 256, 240, 0.02)
        for r in np.random.default_rng(4).choice(256, 3, replace=False):
            skew[r, :200] = 1.0                    # spill-bearing rows
        for a in (ragged, tiny, power, skew):
            for x_mode in ("replicated", "split"):
                check(a, x_mode=x_mode)
                check(a, x_mode=x_mode, ordering="adaptive")
        # split mode groups only each shard's LOCAL entries (the remote
        # ones ride the exchange tail), so per-row local lengths deflate
        # by ~1/D — the spill threshold must sit below them to fire
        check(skew, ordering="adaptive", spill_threshold=8, x_mode="split")
        sm = ShardedRgCSR.from_dense(skew, n_shards=8)
        plan = kops.get_sharded_plan(sm, ordering="adaptive",
                                     spill_threshold=8, x_mode="split")
        assert sum(plan.shard_spilled_elements) > 0

        # SpMM on the same sharded plans
        X = rng.standard_normal((280, 9)).astype(np.float32)
        smr = ShardedRgCSR.from_dense(ragged, n_shards=8)
        for x_mode in ("replicated", "split"):
            Y = np.asarray(spmm(smr, jnp.asarray(X), mesh=mesh,
                                mesh_axis="model", x_mode=x_mode,
                                ordering="adaptive"))
            np.testing.assert_allclose(Y, ragged @ X, rtol=1e-4, atol=1e-4)

        # ~1/D: per-shard stored slots and grid steps vs the single-device
        # plan of the same matrix/config (uniform profile: no padding floor)
        big = rand(5, 1024, 512, 0.05)
        single = kops.make_plan(RgCSR.from_dense(big), chunks_per_step=2)
        sm8 = ShardedRgCSR.from_dense(big, n_shards=8)
        p8 = kops.get_sharded_plan(sm8, chunks_per_step=2)
        assert max(p8.shard_stored_slots) <= single.stored_slots / 8 * 1.5
        assert max(p8.shard_num_steps) <= single.num_steps / 8 * 1.5
        x = rng.standard_normal(512).astype(np.float32)
        y = np.asarray(kops.sharded_rgcsr_spmv(p8, jnp.asarray(x),
                                               mesh=mesh, axis="model"))
        np.testing.assert_allclose(y, big @ x, rtol=1e-4, atol=1e-4)

        # §12 sparse collective: per-device exchange volume equals the
        # shard's plan-time remote column count (the acceptance bound),
        # and is far below the all_gather's n_cols-per-device traffic
        psplit = kops.get_sharded_plan(sm8, chunks_per_step=2,
                                       x_mode="split")
        assert psplit.shard_exchange_recv_cols == psplit.shard_remote_cols
        assert max(psplit.shard_exchange_recv_cols) < psplit.n_cols
        y2 = np.asarray(kops.sharded_rgcsr_spmv(psplit, jnp.asarray(x),
                                                mesh=mesh, axis="model"))
        np.testing.assert_allclose(y2, big @ x, rtol=1e-4, atol=1e-4)

        # per-shard winners that differ across shards: split == replicated
        # == oracle under a mixed (cps, ordering, spill) assignment
        cfgs = [(4, "block", 0) if d % 2 else (1, "adaptive", 8)
                for d in range(8)]
        for xm in ("replicated", "split"):
            ym = np.asarray(spmv(sm8, jnp.asarray(x), mesh=mesh,
                                 x_mode=xm, shard_configs=cfgs))
            np.testing.assert_allclose(ym, big @ x, rtol=1e-4, atol=1e-4)
        print("OK")
    """)


def test_sharded_engine_warmup_and_partitioner_routing_on_8_devices():
    """Engine.warm_spmv_plans with a mesh: autotuned winner config applied
    per shard, sharded plan staged + stats recorded; core.spmv resolves the
    mesh axis through the partitioner's sparse_rows rule."""
    _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.configs import get_smoke
        from repro.core.formats import ShardedRgCSR
        from repro.core.spmv import spmv
        from repro.core.suite import generate
        from repro.serve import Engine, ServeConfig
        from repro.sharding import Partitioner

        mesh = make_mesh((2, 4), ("data", "model"))
        part = Partitioner(mesh, "decode")
        assert part.spmv_shard_axis() == "model"
        assert part.spmv_shard_count() == 4

        eng = Engine(get_smoke("granite-3-2b"), ServeConfig(max_seq=32))
        mats = [generate("banded", 256, seed=4)]
        winners = eng.warm_spmv_plans(mats, repeats=1, mesh=mesh,
                                      x_mode="split")
        assert len(winners) == 1
        stats = eng.plan_cache_stats()
        assert stats["sharded_spmv_plans_warmed"] == 1
        assert stats["sharded_plan_cache"]["entries"] >= 1
        shard_stats = eng.sharded_spmv_shard_stats[0]
        assert shard_stats["n_shards"] == 4
        assert len(shard_stats["stored_slots"]) == 4
        # per-shard tuning + §12 exchange accounting in the warm stats
        assert len(shard_stats["shard_winners"]) == 4
        assert all(len(w) == 3 for w in shard_stats["shard_winners"])
        assert shard_stats["exchange_recv_cols"] == \
            shard_stats["remote_cols"]
        assert len(shard_stats["exchange_bytes"]) == 4
        assert shard_stats["kernel_chunks_per_step"] >= 1

        # re-warming on a RESIZED mesh must build a fresh stacked plan
        # (plan-cache keys carry the shard count), never reuse the stale one
        mesh8 = make_mesh((1, 8), ("data", "model"))
        eng.warm_spmv_plans(mats, repeats=1, mesh=mesh8, x_mode="split")
        assert eng.sharded_spmv_shard_stats[1]["n_shards"] == 8
        assert eng.plan_cache_stats()["sharded_plan_cache"]["entries"] >= 2
        assert eng.sharded_spmv_shard_stats[0]["mesh"] != \
            eng.sharded_spmv_shard_stats[1]["mesh"]

        # dispatch: mesh_axis defaults to the sparse_rows rule ('model')
        a = generate("uniform", 256, seed=1)
        sm = ShardedRgCSR.from_dense(a, n_shards=4)
        x = np.random.default_rng(2).standard_normal(
            a.shape[1]).astype(np.float32)
        y = np.asarray(spmv(sm, jnp.asarray(x), mesh=mesh))
        np.testing.assert_allclose(y, a @ x, rtol=1e-4, atol=1e-4)
        print("OK")
    """)
