"""Validation of the loop-aware HLO cost model (launch/hlo_cost.py).

The key check: XLA's own cost_analysis counts while-loop bodies once; ours
multiplies by trip count and matches hand-derived flops exactly on plain,
scanned, nested-scan and SPMD-sharded modules.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_cost import analyze_hlo


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_plain_matmul_matches_xla():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    c = _compile(lambda x, w: jnp.tanh(x @ w), x, w)
    ca = c.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    mine = analyze_hlo(c.as_text(), 1)
    assert mine.flops == ca["flops"] == 2 * 128 * 256 * 512


def test_scan_flops_multiplied_by_trip_count():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    c = _compile(f, x, w)
    ca = c.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    mine = analyze_hlo(c.as_text(), 1)
    expected = 10 * 2 * 128 * 256 * 256
    assert mine.flops == expected
    assert ca["flops"] < expected  # XLA's known single-visit undercount
    assert 10 in mine.loops.values()


def test_nested_scan():
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def h(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            y, _ = jax.lax.scan(inner, c, None, length=4)
            return y, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    c = _compile(h, x, w)
    mine = analyze_hlo(c.as_text(), 1)
    assert mine.flops == 12 * 2 * 128 * 256 * 256


def test_collectives_counted_inside_loops():
    """A psum inside a scan must be multiplied by the trip count."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_cost import analyze_hlo
        mesh = make_mesh((2, 4), ("data", "model"))
        def f(ws, x):
            def body(c, w):
                return c @ w, None
            y, _ = jax.lax.scan(body, x, ws)
            return y.sum()
        ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
        x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
        with mesh:
            fn = jax.jit(f, in_shardings=(
                NamedSharding(mesh, P(None, None, "model")),
                NamedSharding(mesh, P("data", None))))
            c = fn.lower(ws, x).compile()
        res = analyze_hlo(c.as_text(), 8)
        expected = 5 * 2 * 4 * 64 * 16
        assert res.flops == expected, (res.flops, expected)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=_env())
    assert "OK" in out.stdout, out.stderr[-2000:]


def _env():
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    env.pop("XLA_FLAGS", None)
    return env
