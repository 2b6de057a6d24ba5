"""chip_smoke.py on the CPU: its phases at smoke sizes (kernels in interpret
mode), and its refusal to run without a TPU."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

from repro.configs import get_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(extra, PYTHONPATH=os.path.join(REPO, "src"))
    return env


def test_cpu_run_exits_nonzero_before_any_phase():
    out = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, env=_cpu_env(), timeout=300)
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout
    assert '"platform": "tpu"' not in out.stdout
    assert not any(line.startswith(("spmv:", "spmm:", "serve:", "sharded:"))
                   for line in out.stdout.splitlines())
    last = out.stdout.strip().splitlines()[-1]
    assert not last.startswith("{")


def test_spmv_phase_small():
    res = _smoke().spmv_phase(6, on_tpu=False)
    assert res["rgcsr"] <= 1e-5 and res["hybrid"] <= 1e-5


def test_spmm_phase_small():
    res = _smoke().spmm_phase(256, 128, 16, on_tpu=False)
    assert res["float32"] <= 1e-5 and res["bfloat16"] <= 1e-2


def test_serve_phase_small():
    res = _smoke().serve_phase(get_smoke("granite-3-2b"), n_requests=3,
                               prompt_len=8, max_new=6, n_slots=2,
                               max_seq=32)
    assert res["statuses"] == {"ok": 3}
    assert res["compiles_in_window"] == 0
    assert res["max_gap_served"] <= _smoke().LOGIT_TOL


def test_sharded_phase_on_4_devices():
    code = textwrap.dedent(f"""
        import importlib.util, json
        spec = importlib.util.spec_from_file_location("chip_smoke", {SCRIPT!r})
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        print(json.dumps(mod.sharded_phase((4, 8, 8), 4)))
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["replicated"] <= 1e-5 and res["split"] <= 1e-5
