"""Distribution tests — run in subprocesses with a fake 8-device host so the
main pytest process keeps its single real CPU device (assignment
requirement: the 512-device flag must live ONLY in launch/dryrun.py).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=560)
    assert out.returncode == 0 and "OK" in out.stdout, \
        (out.stdout[-1500:], out.stderr[-3000:])


def test_partitioner_rules_resolve():
    _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from repro.sharding import Partitioner
        from repro.models.spec import P as Spec
        mesh = make_mesh((2, 4), ("data", "model"))
        part = Partitioner(mesh, "train")
        # divisible dims shard; non-divisible fall back to replicated
        s = part._leaf_spec(Spec((16, 8), ("embed", "mlp")))
        assert s == P("data", "model"), s
        s = part._leaf_spec(Spec((15, 9), ("embed", "mlp")))
        assert s == P(None, None), s
        # one mesh axis never used twice in a leaf
        s = part._leaf_spec(Spec((8, 8), ("mlp", "mlp2")))
        assert s[0] == "model" and s[1] is None, s
        # serve rules: whole-mesh EP with fallback
        part2 = Partitioner(mesh, "decode")
        s = part2._leaf_spec(Spec((8, 4, 4), ("experts", "embed", "mlp")))
        assert s[0] == ("data", "model"), s
        print("OK")
    """)


def test_train_step_compiles_on_mesh_and_runs():
    """End-to-end SPMD: real (tiny) train step on a (2,4) mesh, executed."""
    _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import get_smoke
        from repro.sharding import Partitioner
        from repro.launch.steps import make_train_step
        from repro.train.optimizer import OptimizerConfig
        from repro.models import LanguageModel
        from repro.train.data import DataConfig, make_batch

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = dataclasses.replace(
            get_smoke("granite-3-2b"), act_shard=True,
            attn_shard_mode="repeat", mesh_batch_axes=("data",),
            d_model=64, n_heads=4, n_kv_heads=2, d_ff=128)
        model = LanguageModel(cfg)
        part = Partitioner(mesh, "train")
        spec = model.spec()
        p_sh = part.param_shardings(spec)
        o_sh = part.opt_shardings(spec, "adamw")
        step, opt_init = make_train_step(model, OptimizerConfig(lr=1e-3), 2)
        params = jax.device_put(model.init(jax.random.PRNGKey(0)), p_sh)
        opt = jax.device_put(opt_init(params), o_sh)
        dc = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4)
        batch = make_batch(dc, 0)
        with mesh:
            fn = jax.jit(step, in_shardings=(p_sh, o_sh, None),
                         out_shardings=(p_sh, o_sh, None))
            params, opt, metrics = fn(params, opt, batch)
            params, opt, metrics = fn(params, opt, make_batch(dc, 1))
        assert np.isfinite(float(metrics["loss"]))
        print("OK")
    """)


def test_elastic_reshard_checkpoint():
    """Save on a (2,4) layout, restore onto (1,8) — elastic restart."""
    _run("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train.checkpoint import save, restore_sharded
        mesh_a = make_mesh((2, 4), ("data", "model"))
        mesh_b = make_mesh((1, 8), ("data", "model"))
        w = jax.device_put(np.arange(64, dtype=np.float32).reshape(8, 8),
                           NamedSharding(mesh_a, P("data", "model")))
        with tempfile.TemporaryDirectory() as d:
            save(d, 1, {"w": w})
            restored, _ = restore_sharded(
                d, {"w": np.zeros((8, 8), np.float32)},
                {"w": NamedSharding(mesh_b, P("data", "model"))})
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(w))
        assert restored["w"].sharding.mesh.shape["model"] == 8
        print("OK")
    """)


def test_dryrun_single_cell_subprocess():
    """The actual dry-run entry point on the production mesh (256 fake
    devices) for one small cell — proves the documented launch path."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "granite-moe-1b-a400m", "--shape", "decode_32k"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=560)
    assert "1/1 cells OK" in out.stdout, (out.stdout[-1500:],
                                          out.stderr[-1500:])
