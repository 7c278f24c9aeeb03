"""HLO cost model: loop multipliers, flops and bytes vs XLA ground truth."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline import hlo_cost
from repro.roofline.analysis import collective_bytes, model_flops, roofline


def _rms(x):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype)


def _body(x, w):
    return x + _rms(x) @ w, None


def test_scan_flops_corrected():
    """cost_analysis counts a while body once; the cost model multiplies by
    the trip count (the whole reason this module exists)."""
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)

    def scanned(x, ws):
        y, _ = jax.lax.scan(_body, x, ws)
        return y

    c = jax.jit(scanned).lower(x, ws).compile()
    xla = c.cost_analysis()["flops"]
    hc = hlo_cost.analyze(c.as_text(), 1)
    expected = 8 * 2 * 128 ** 3
    assert xla < expected / 4                   # XLA undercounts
    np.testing.assert_allclose(hc.flops, expected, rtol=0.02)
    assert any(v == 8.0 for v in hc.loops.values())


def test_matches_xla_on_unrolled_grad():
    """On an unrolled model (no while) both flops and bytes must agree with
    XLA's own cost analysis."""
    x = jax.ShapeDtypeStruct((64, 256), jnp.bfloat16)
    ws = jax.ShapeDtypeStruct((6, 256, 256), jnp.bfloat16)

    def loss(x, ws):
        y, _ = jax.lax.scan(jax.checkpoint(_body), x, ws, unroll=6)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    c = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, ws).compile()
    ca = c.cost_analysis()
    hc = hlo_cost.analyze(c.as_text(), 1)
    assert 0.8 <= hc.flops / ca["flops"] <= 1.05       # dots only
    np.testing.assert_allclose(hc.bytes_accessed_xla, ca["bytes accessed"],
                               rtol=0.05)
    # the HBM approximation only ever discounts the visitor accounting
    assert hc.bytes_accessed <= hc.bytes_accessed_xla


def test_scan_equals_unrolled_through_cost_model():
    """The corrected scan cost must equal the unrolled XLA cost."""
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 128, 128), jnp.float32)

    def f_scan(x, ws):
        return jax.lax.scan(_body, x, ws)[0].sum()

    def f_unroll(x, ws):
        return jax.lax.scan(_body, x, ws, unroll=5)[0].sum()

    c_scan = jax.jit(jax.grad(f_scan, argnums=(0, 1))).lower(x, ws).compile()
    c_un = jax.jit(jax.grad(f_unroll, argnums=(0, 1))).lower(x, ws).compile()
    hc = hlo_cost.analyze(c_scan.as_text(), 1)
    xla_unrolled = c_un.cost_analysis()["flops"]
    np.testing.assert_allclose(hc.flops, xla_unrolled, rtol=0.15)


@pytest.mark.slow        # 8-device subprocess + fresh compile
def test_collective_parse_on_psum():
    """Collectives inside an 8-step scan are multiplied by the trip count."""
    import subprocess, sys, os, textwrap, json
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import jax, jax.numpy as jnp, json
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        from repro.roofline import hlo_cost
        mesh = jax.make_mesh((8,), ("d",), axis_types=(AxisType.Auto,))
        def body(x, w):
            y = jax.lax.psum(x @ w, "d")          # (16, 64) all-reduce
            i = jax.lax.axis_index("d")
            return jax.lax.dynamic_slice(y, (0, i * 8), (16, 8)), None
        def f(x, ws):
            return jax.lax.scan(body, x, ws)[0]
        sm = jax.shard_map(f, mesh=mesh,
                           in_specs=(P(None, "d"), P(None, "d", None)),
                           out_specs=P(None, None), check_vma=False)
        x = jax.ShapeDtypeStruct((16, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((8, 64, 64), jnp.float32)
        with mesh:
            c = jax.jit(sm).lower(x, ws).compile()
        hc = hlo_cost.analyze(c.as_text(), 8)
        print(json.dumps({"ar": hc.collective["all-reduce"]}))
    """ % os.path.join(os.path.dirname(__file__), "..", "src"))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    ar = json.loads(proc.stdout.strip().splitlines()[-1])["ar"]
    # 8 iterations x all-reduce of (16, 64) f32 = 4096 B result each,
    # ring model: 2 * 4096 * 7/8 -> x8 steps
    expected = 8 * 2 * (16 * 64 * 4) * 7 / 8
    np.testing.assert_allclose(ar, expected, rtol=0.3)


def test_roofline_terms_and_dominance():
    from repro.roofline.analysis import V5E
    r = roofline(flops_per_chip=1.97e14, bytes_per_chip=819e9,
                 coll_bytes_per_chip=100e9, n_chips=4,
                 model_flops_global=4 * 1.97e14 * 0.5, device_kind=V5E)
    assert np.isclose(r.compute_s, 1.0)
    assert np.isclose(r.memory_s, 1.0)
    assert np.isclose(r.collective_s, 2.0)
    assert r.dominant == "collective"
    assert np.isclose(r.useful_ratio, 0.5)


def test_peaks_are_keyed_by_device_kind():
    """The v5e has published peaks; any other kind (the CPU included) has
    no roofline, and asking for one raises instead of defaulting."""
    from repro.roofline.analysis import V5E, peaks
    assert peaks(V5E)["hbm_bw"] == 819e9
    r = roofline(197e12, 0.0, 0.0, 1, 197e12, device_kind=V5E)
    assert r.dominant == "compute" and r.bound_s == pytest.approx(1.0)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks(jax.devices("cpu")[0].device_kind)
    with pytest.raises(KeyError):
        roofline(1.0, 1.0, 0.0, 1, 1.0, device_kind="cpu")
