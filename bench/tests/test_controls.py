"""The comparison that decides ``correct`` must fail when it should.

Each test drives a whole run on the CPU at a small size (the harness's
look for a chip skipped) with the timed path broken underneath, and sees
``correct`` come out false; the controls (the reference in a lower
precision, put in the program's place) read above their limits.  The
faults a cell can have: a step that returns its state unchanged, and an
answer or token altered where it is produced.  The cells run on one chip
with no batch split across chips, so the faults of a left-out half batch
or a left-out exchange do not arise.
"""
import dataclasses
import json
import os

import pytest

import calibrate
import harness
import run

BENCH = harness.BENCH


def _last_json(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# --------------------------------------------------------------------- CG


def _cg_hook(cell):
    cell.config["local_grid"] = 12
    cell.traffic["iterations_per_set"] = 8


def _run_cg(capsys):
    rc = run.main(["--workload", "hpcg-168.cg-rgcsr", "--seed", "3",
                   "--seconds", "1", "--trace", "0"],
                  allow_cpu=True, cell_hook=_cg_hook)
    assert rc == 0
    return _last_json(capsys)


def test_cg_sound_run_is_correct(capsys):
    line = _run_cg(capsys)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_cg_fault_is_not_correct(capsys, monkeypatch, fault):
    import repro.core as core
    orig = core.spmv

    def broken(a, x, **kw):
        if fault == "state_unchanged":
            return x                      # the SpMV hands back its input
        return orig(a, x, **kw).at[5].add(1.0)   # one term dropped

    monkeypatch.setattr(core, "spmv", broken)
    line = _run_cg(capsys)
    assert line["correct"] is False


def test_cg_control_fails_its_limits():
    cell = harness.find_cell(harness.load_spec(), "hpcg-168.cg-rgcsr")
    _cg_hook(cell)
    cg = cell.runner()
    reading = cg.control(cell, 8)
    lim = cell.traffic["limits"]
    assert any(reading[k] > lim[k] for k in lim), (reading, lim)


# ---------------------------------------------------------------- serving


def _small_model(monkeypatch):
    import repro.configs as rc
    from repro.configs.base import scale_down
    small = scale_down(rc.get_config("granite-3-2b"), n_layers=8,
                       d_model=256, n_heads=8, n_kv_heads=2, d_head=32,
                       d_ff=1024, vocab=4096)
    monkeypatch.setattr(rc, "get_config",
                        lambda name: dataclasses.replace(small, name=name))
    return small


def _serve_hook(cell):
    c = cell.config
    c.update(hidden_size=256, intermediate_size=1024, num_hidden_layers=8,
             num_attention_heads=8, num_key_value_heads=2, head_dim=32,
             vocab_size=4096)
    c["serve"].update(n_slots=8, max_seq=128, page_size=8, n_pages=0)
    t = cell.traffic
    t.update(prompt_len_table=[4, 6, 9, 12], lead_s=0.5, trace_seconds=1,
             output_len={"mean": 45, "sigma": 0.5, "min": 3,
                         "max_total": 100},
             sample={"min_served_tokens": 600, "max_requests": 16,
                     "batch": 4})
    if t["mode"] == "open_loop":
        t.update(rate_per_s=4.0, tail_s=10, drain_limit_s=30)
    else:
        t["queue_requests"] = 3000


def _offline_hook(cell):
    """The chat cell driven by the offline mix (``traffic/offline.json``),
    which waits for chip runs before it becomes a cell of its own."""
    cell.traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                                  "offline.json"))
    _serve_hook(cell)


def _run_serve(capsys, workload):
    hook = _offline_hook if workload == "offline" else _serve_hook
    rc = run.main(["--workload", "granite-3-2b.chat", "--seed", "5",
                   "--seconds", "2", "--trace", "0"], allow_cpu=True,
                  cell_hook=hook)
    assert rc == 0
    return _last_json(capsys)


CELLS = ["granite-3-2b.chat", "offline"]


@pytest.mark.parametrize("workload", CELLS)
def test_serve_sound_run_is_correct(capsys, monkeypatch, workload):
    _small_model(monkeypatch)
    line = _run_serve(capsys, workload)
    assert line["correct"] is True and line["failed"] == 0


def _broken_fused(fault):
    import jax
    import jax.numpy as jnp
    from repro.serve import device_loop
    orig = device_loop.build_fused_decode

    def build(model, cfg, on_dispatch=None):
        fused = orig(model, cfg, on_dispatch)

        def run_chunk(params, caches, *rest):
            if fault == "state_unchanged":
                kept = jax.tree_util.tree_map(jnp.copy, caches)
                out = fused(params, caches, *rest)
                return out[:4] + (kept,) + out[5:]
            block, *others = fused(params, caches, *rest)
            vocab = model.cfg.vocab
            return (block.at[:, 0].set((block[:, 0] + 1) % vocab),
                    *others)
        return run_chunk
    return build


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_serve_fault_is_not_correct(capsys, monkeypatch, workload, fault):
    from repro.serve import device_loop
    _small_model(monkeypatch)
    monkeypatch.setattr(device_loop, "build_fused_decode",
                        _broken_fused(fault))
    line = _run_serve(capsys, workload)
    assert line["correct"] is False


@pytest.mark.parametrize("workload", CELLS)
def test_serve_control_fails_its_limit(capsys, monkeypatch, workload):
    _small_model(monkeypatch)
    hook = _offline_hook if workload == "offline" else _serve_hook
    rc = calibrate.main(["--workload", "granite-3-2b.chat", "--seeds", "5",
                         "6", "--seconds", "2"], allow_cpu=True,
                        cell_hook=hook)
    assert rc == 0
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith("{")]
    traffic = "chat" if workload != "offline" else "offline"
    limit = harness.load_json(os.path.join(BENCH, "traffic", traffic
                                           + ".json"))["limits"][
        "max_logit_gap"]
    assert all(r["control_max_logit_gap"] > limit for r in rows), rows
    assert all(r["program_max_logit_gap"] <= limit for r in rows), rows
