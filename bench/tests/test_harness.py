"""Tests of the yardstick: trace arithmetic, work counts, traffic, lookup.

Run with ``JAX_PLATFORMS=cpu python -m pytest -q bench/tests``.
"""
import json
import os
import shutil

import numpy as np
import pytest

import devtrace
import harness
import peaks
import run
import work

BENCH = harness.BENCH
cg = harness.load_module(os.path.join(BENCH, "runners", "cg.py"))
serve = harness.load_module(os.path.join(BENCH, "runners", "serve.py"))
hpcg_csr, plan_requests = cg.hpcg_csr, serve.plan_requests
GRANITE = harness.load_json(os.path.join(BENCH, "configs",
                                         "granite-3-2b.json"))


# ------------------------------------------------------------------ trace


def _tiny_trace():
    E = devtrace.Event
    ops = [E("%a = f32[] fusion(x)", 10, 20), E("%w = while(x)", 30, 60),
           E("%b.1 = f32[] add(x)", 35, 45), E("%c = copy(x)", 70, 75)]
    mods = [E("jit_kernel(1)", 10, 20), E("jit_loop(2)", 30, 60),
            E("jit_bench_x(3)", 70, 75)]
    host = [E(devtrace.TRACED_SPAN, 0, 100), E("bench.step", 18, 40),
            E("bench.wait", 58, 80)]
    return devtrace.Trace([devtrace.Chip(ops, mods)], host)


def test_busy_union_idle_and_scoped_time_by_hand():
    r = devtrace.reduce(_tiny_trace())
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(45e-9)          # 10 + 30 + 5
    assert r.idle_share == pytest.approx(0.55)
    assert r.module_seconds(lambda n: n == "jit_loop") == pytest.approx(
        30e-9)
    assert r.module_seconds(lambda n: not n.startswith("jit_bench")) \
        == pytest.approx(40e-9)
    top = dict(r.top_ops())
    assert top["jit_loop/w"] == pytest.approx(20e-9)  # self time
    assert top["jit_loop/b.1"] == pytest.approx(10e-9)
    gaps = r.idle_gaps()
    assert gaps[0] == ["no bench span", pytest.approx(25e-9)]
    assert ["bench.step", pytest.approx(10e-9)] in gaps
    assert ["bench.wait", pytest.approx(10e-9)] in gaps


def test_recorded_chip_trace_reduces():
    """A CG trace recorded on a TPU v5e (32³ grid, three iterations)."""
    t = devtrace.load_json(os.path.join(BENCH, "data", "trace_small.json"))
    r = devtrace.reduce(t)
    assert 0 < r.busy_s <= r.window_s
    spmv = r.module_seconds(lambda n: n == "jit_rgcsr_spmv_pallas")
    update = r.module_seconds(lambda n: n == "jit_bench_cg_update")
    assert spmv > 100 * update > 0
    assert sum(devtrace.module_short(m.name) == "jit_bench_cg_update"
               for m in t.chips[0].modules) == 3
    assert r.top_ops()[0][0] == "jit_rgcsr_spmv_pallas/fusion"
    assert len(r.breakdown()["idle_gaps"]) <= 10


def test_hpcg_readers_on_the_recorded_trace():
    t = devtrace.reduce(devtrace.load_json(
        os.path.join(BENCH, "data", "trace_small.json")))
    lay = {"spmv_calls_traced": 3, "own_programs": cg.OWN_PROGRAMS,
           "spmv_bytes": work.spmv_min_bytes(32 ** 3, 32 ** 3,
                                             work.hpcg_nnz(32)),
           "device_kind": "TPU v5 lite"}
    out = harness.RunOutput({}, 3, 0, [], lay, {}, trace=t)
    cell = harness.find_cell(harness.load_spec(), "hpcg-168.cg-rgcsr")
    got = {k: v["value"] for k, v in harness.read_per_layer(cell,
                                                            out).items()}
    spmv_s = t.module_seconds(lambda n: n == "jit_rgcsr_spmv_pallas") / 3
    assert got["spmv_hbm_roofline.rgcsr"] == pytest.approx(
        100 * lay["spmv_bytes"] / 819e9 / spmv_s)
    assert 99.0 < got["spmv_share.hpcg"] < 100.0
    assert got["device_idle.hpcg"] == pytest.approx(100 * t.idle_share)


def test_serving_readers_by_hand():
    E = devtrace.Event
    ms = 1e6                                         # ns per ms
    mods = [E("jit__lambda(1)", 0, 20 * ms), E("jit_fused(2)", 20 * ms,
                                                  420 * ms)]
    ops = [E("%f = fusion(x)", 0, 20 * ms), E("%w = while(x)", 20 * ms,
                                              420 * ms)]
    host = [E(devtrace.TRACED_SPAN, 0, 500 * ms)]
    t = devtrace.reduce(devtrace.Trace([devtrace.Chip(ops, mods)], host))
    lay = {"model": GRANITE, "device_kind": "TPU v5 lite", "decode_steps": 8,
           "decode_tokens": 64 * 8, "decode_context_tokens": 10_000,
           "prefill_lengths": [100, 100], "queue_s": [0.1, 0.3, 0.2]}
    out = harness.RunOutput({}, 0, 0, [], lay, {}, trace=t)
    cell = harness.find_cell(harness.load_spec(), "granite-3-2b.chat")
    got = {k: v["value"] for k, v in harness.read_per_layer(cell,
                                                            out).items()}
    assert got["decode_step_ms.chat"] == pytest.approx(50.0)
    assert got["prefill_ms_per_ktok.chat"] == pytest.approx(100.0)
    assert got["queue_ms_p50.chat"] == pytest.approx(200.0)
    assert got["device_idle.chat"] == pytest.approx(16.0)
    step_bytes = 8 * 2 * 2_533_531_648 + 81_920 * 10_000
    assert got["decode_hbm_roofline.chat"] == pytest.approx(
        100 * step_bytes / 819e9 / 0.4)
    flops = 2 * work.prefill_flops(GRANITE, 100) + 512 * 2 * 2_533_365_760 \
        + work.attention_flops(GRANITE, 10_000)
    assert got["serve_mfu.chat"] == pytest.approx(
        100 * flops / (0.42 * 197e12))


# ------------------------------------------------------------------- work


def test_hpcg_counts_by_hand():
    values, columns, row_ptr, shape = hpcg_csr(4)
    assert shape == (64, 64)
    assert len(values) == work.hpcg_nnz(4) == 10 ** 3
    assert work.cg_flops_per_iteration(64, 1000) == 2 * 1000 + 10 * 64
    dense = np.zeros(shape)
    rows = np.repeat(np.arange(64), np.diff(row_ptr))
    dense[rows, columns] = values
    assert np.allclose(np.diag(dense), 26.0)
    assert (dense.sum(axis=1) >= 0).all() and dense[21].sum() == 0.0


def test_spmv_bytes_from_the_matrix_not_the_format():
    from repro.core.formats import HybridEllCoo, RgCSR
    values, columns, row_ptr, shape = hpcg_csr(5)
    counts = []
    for fmt in (RgCSR, HybridEllCoo):
        m = fmt.from_csr(values, columns, row_ptr, shape)
        dense = np.asarray(m.to_dense())
        counts.append(work.spmv_min_bytes(shape[0], shape[1],
                                          int(np.count_nonzero(dense))))
    assert counts[0] == counts[1] == 8 * work.hpcg_nnz(5) + 12 * 125 + 4


def test_granite_flops_and_bf16_weight_bytes():
    assert work.lm_matmul_params(GRANITE) == 2_533_365_760
    assert work.lm_params(GRANITE) == 2_533_531_648
    assert work.decode_step_bytes(GRANITE, 0) == 2 * 2_533_531_648
    assert work.kv_bytes_per_token(GRANITE) == 81_920
    assert work.decode_step_bytes(GRANITE, 1000) == \
        2 * 2_533_531_648 + 81_920_000
    per_tok = work.decode_flops(GRANITE, 0)
    assert per_tok == 2 * 2_533_365_760
    assert work.attention_flops(GRANITE, 100) == 4 * 40 * 2048 * 100
    assert work.prefill_flops(GRANITE, 2) == 2 * per_tok + \
        work.attention_flops(GRANITE, 3)


def test_peaks_table_and_shares():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert peaks.hbm_roofline_pct(819e9, 2.0, "TPU v5 lite") == \
        pytest.approx(50.0)
    assert peaks.flops_pct(197e12, 4.0, "TPU v5 lite") == pytest.approx(25)
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


# ---------------------------------------------------------------- traffic


def _traffic(name):
    return harness.load_json(os.path.join(BENCH, "traffic", name + ".json"))


def test_traffic_reproduces_from_the_seed():
    chat = _traffic("chat")
    a = plan_requests(chat, 2 ** 31 + 9, 20.0, 1000)
    b = plan_requests(chat, 2 ** 31 + 9, 20.0, 1000)
    c = plan_requests(chat, 5, 20.0, 1000)
    key = lambda ps: [(p.due, p.max_new, p.prompt.tolist()) for p in ps]  # noqa: E731
    assert key(a) == key(b) and key(a) != key(c)


def test_chat_draws_prompt_lengths_only_from_its_table():
    chat = _traffic("chat")
    table = set(chat["prompt_len_table"])
    plans = plan_requests(chat, 3, 30.0, 1000)
    assert {len(p.prompt) for p in plans} <= table
    win = [p for p in plans if p.in_window]
    assert len(win) == round(chat["rate_per_s"] * 30.0)
    lo, hi = chat["lead_s"], chat["lead_s"] + 30.0
    assert all(lo <= p.due < hi for p in win)


@pytest.mark.parametrize("mix", ["chat", "offline"])
def test_length_tables_follow_the_stated_lognormals(mix):
    """Each table entry is the mean of one of 16 equal-probability bins of
    the lognormal with the file's mean and sigma; answers keep the stated
    mean until ``max_total`` cuts them."""
    from statistics import NormalDist
    t = _traffic(mix)
    mean, sigma = t["prompt_len"]["mean"], t["prompt_len"]["sigma"]
    n = len(t["prompt_len_table"])
    phi = NormalDist().cdf
    z = [NormalDist().inv_cdf(i / n) if 0 < i < n else (-1e9 if i == 0
                                                          else 1e9)
         for i in range(n + 1)]
    want = [round(n * mean * (phi(z[i + 1] - sigma) - phi(z[i] - sigma)))
            for i in range(n)]
    assert t["prompt_len_table"] == want
    rng = np.random.default_rng(0)
    answers = serve._lognormal_lengths(rng, t["output_len"], 200_000)
    assert answers.mean() == pytest.approx(t["output_len"]["mean"],
                                           rel=0.01)
    plans = plan_requests(t, 3, 50.0, 1000)
    assert all(len(p.prompt) + p.max_new <= t["output_len"]["max_total"]
               for p in plans)


def test_every_seed_serves_the_same_work_at_the_same_moments():
    chat = _traffic("chat")
    sizes, dues, orders = [], [], []
    for seed in (1, 2, 2 ** 31 + 3):
        plans = plan_requests(chat, seed, 20.0, 1000)
        win = [p for p in plans if p.in_window]
        sizes.append(sorted((len(p.prompt), p.max_new) for p in win))
        dues.append([p.due for p in plans])
        orders.append([p.max_new for p in win])
    assert sizes[0] == sizes[1] == sizes[2]
    assert dues[0] == dues[1] == dues[2]
    assert orders[0] != orders[1]


def test_offline_queue_is_due_before_the_window():
    off = _traffic("offline")
    plans = plan_requests(off, 4, 50.0, 1000)
    assert len(plans) == off["queue_requests"]
    assert all(p.due == 0.0 for p in plans)


# ------------------------------------------------------------ the harness


def test_harness_finds_a_cell_mix_and_metric_added_as_files(tmp_path):
    """A later PR adds a cell by adding files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    (root / "bench" / "traffic" / "cg-new.json").write_text(json.dumps(
        dict(_traffic("cg-rgcsr"), iterations_per_set=7)))
    (root / "bench" / "metrics" / "new_metric.hpcg.py").write_text(
        "def read(run):\n    return 42.0\n")
    for m in spec["end_to_end"]:
        if m["name"] == "cg_gflops":
            m["workloads"].append("hpcg-168.cg-new")
    spec["workloads"].append({"name": "hpcg-168.cg-new", "config":
                              "hpcg-168", "traffic": "cg-new", "chips": 1,
                              "why": "a new mix"})
    spec["per_layer"].append({"name": "new_metric.hpcg", "unit": "%",
                              "better": "higher", "source": "device_trace",
                              "layer": "kernels", "moves": "cg_gflops",
                              "workloads": ["hpcg-168.cg-new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.find_cell(harness.load_spec(str(root)), "hpcg-168.cg-new",
                             bench_dir=str(root / "bench"))
    assert cell.traffic["iterations_per_set"] == 7
    assert [m["name"] for m in cell.per_layer] == ["new_metric.hpcg"]
    assert [m["name"] for m in cell.end_to_end] == ["cg_gflops", "setup_s"]
    out = harness.RunOutput({}, 0, 0, [], {}, {})
    assert harness.read_per_layer(cell, out) == {
        "new_metric.hpcg": {"value": 42.0, "unit": "%"}}
    with pytest.raises(harness.SetupError):
        harness.find_cell(harness.load_spec(str(root)), "no.such.cell")


def test_every_cell_resolves_and_reports_what_it_moves():
    spec = harness.load_spec()
    for w in spec["workloads"]:
        cell = harness.find_cell(spec, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               m["name"] + ".py"))


def test_a_run_without_a_tpu_exits_non_zero(capsys):
    rc = run.main(["--workload", "hpcg-168.cg-rgcsr", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""
