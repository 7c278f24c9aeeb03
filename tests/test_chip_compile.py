"""The Pallas kernels compile for a TPU v5e at deployment size.

Interpret mode accepts block shapes and in-kernel indexing that the chip's
compiler refuses, so every kernel of the ``kernel``, ``kernel-sell`` and
``kernel-fcoo`` executors (and the ``shard-sell`` mesh path) is compiled
here ahead of time for a *described* ``v5e:2x2`` topology — no chip is
attached, nothing runs.  Shapes are those of the bring-up subject of
``chip_smoke.py``: 50,000 PROB fibers over a 52^3 grid, Ntheta = Na = 96,
about 1.0e6 coefficients.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import dsc, fcoo, ops, wc

N_VOXELS, N_FIBERS, N_COEFFS = 52 ** 3, 50_000, 1_000_000
N_ATOMS, T_PAD = 96, 128                   # Ntheta = 96 padded to the lanes
C_TILE, ROW_TILE = 256, 8
# a tile plan has at most one partial tile per visited row block
DSC_TILES = N_COEFFS // C_TILE + N_VOXELS // ROW_TILE
WC_TILES = N_COEFFS // C_TILE + N_FIBERS // ROW_TILE
# SELL: the longest row rounded to the slot tile; rows to the lane width
SELL_WIDTH, SLOT_TILE = 64, 16
SELL_VOXELS = -(-N_VOXELS // dsc.LANES) * dsc.LANES
SELL_FIBERS = -(-N_FIBERS // dsc.LANES) * dsc.LANES
# F-COO: one chunk per c_tile coefficients, K segments per chunk
CHUNKS, K_DSC, K_WC = N_COEFFS // C_TILE, 192, 32

DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _kernel_cases():
    """(name, fn, operand shapes) per kernel; storage dtype ``sd`` is the
    dictionary / Phi-value dtype, accumulation stays fp32."""
    f32, i32 = jnp.float32, jnp.int32
    out = dict(out_dtype=f32)
    return {
        "kernel-dsc": (
            lambda rb, a, s, l, d: dsc.dsc_pallas(
                rb, a, s, l, d, row_tile=ROW_TILE,
                n_row_blocks=N_VOXELS // ROW_TILE, **out),
            lambda sd: [((DSC_TILES,), i32), ((DSC_TILES, C_TILE), i32),
                        ((DSC_TILES, C_TILE), f32), ((DSC_TILES, C_TILE), i32),
                        ((N_ATOMS, T_PAD), sd)]),
        "kernel-wc": (
            lambda rb, a, y, v, l, d: wc.wc_pallas(
                rb, a, y, v, l, d, fib_tile=ROW_TILE,
                n_fib_blocks=N_FIBERS // ROW_TILE, **out),
            lambda sd: [((WC_TILES,), i32), ((WC_TILES, C_TILE), i32),
                        ((WC_TILES, C_TILE, T_PAD), f32),
                        ((WC_TILES, C_TILE), sd), ((WC_TILES, C_TILE), i32),
                        ((N_ATOMS, T_PAD), sd)]),
        "kernel-sell-dsc": (
            lambda a, s, d: dsc.dsc_sell_pallas(a, s, d, slot_tile=SLOT_TILE,
                                                **out),
            lambda sd: [((SELL_WIDTH, SELL_VOXELS), i32),
                        ((SELL_WIDTH, SELL_VOXELS), f32),
                        ((N_ATOMS, T_PAD), sd)]),
        "kernel-sell-wc": (
            lambda a, y, v, d: wc.wc_sell_pallas(a, y, v, d,
                                                 slot_tile=SLOT_TILE, **out),
            lambda sd: [((SELL_WIDTH, SELL_FIBERS), i32),
                        ((SELL_WIDTH, SELL_FIBERS, T_PAD), f32),
                        ((SELL_WIDTH, SELL_FIBERS), sd),
                        ((N_ATOMS, T_PAD), sd)]),
        "kernel-fcoo-dsc": (
            lambda a, r, s, d: fcoo.dsc_fcoo_pallas(a, r, s, d, seg_k=K_DSC,
                                                    **out),
            lambda sd: [((CHUNKS, C_TILE), i32), ((CHUNKS, C_TILE), i32),
                        ((CHUNKS, C_TILE), f32), ((N_ATOMS, T_PAD), sd)]),
        "kernel-fcoo-wc": (
            lambda a, r, v, y, d: fcoo.wc_fcoo_pallas(a, r, v, y, d,
                                                      seg_k=K_WC, **out),
            lambda sd: [((CHUNKS, C_TILE), i32), ((CHUNKS, C_TILE), i32),
                        ((CHUNKS, C_TILE), sd),
                        ((CHUNKS, C_TILE, T_PAD), f32),
                        ((N_ATOMS, T_PAD), sd)]),
    }


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("kernel", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(kernel, dtype, one_chip):
    fn, shapes = _kernel_cases()[kernel]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes(DTYPES[dtype])]
    compiled = jax.jit(fn).lower(*args).compile()
    print(kernel, dtype, compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("op", ["dsc", "wc"])
def test_shard_sell_compiles_on_described_mesh(op, topo):
    """shard-sell at mesh=(2, 2): per-cell SELL kernels under shard_map,
    compiled (not interpreted) because the mesh is made of TPU devices."""
    from repro.distributed import life_shard as LS
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2),
                ("data", "model"))
    nv_l, nf_l = -(-N_VOXELS // 2), -(-N_FIBERS // 2)
    rows = -(-(nv_l if op == "dsc" else nf_l) // dsc.LANES) * dsc.LANES
    dsc_fn, wc_fn = LS.make_sharded_sell_ops(
        mesh, dict(nv_local=nv_l, nf_local=nf_l, n_theta=96),
        slot_tile=SLOT_TILE, out_dtype=jnp.float32)
    spec = lambda shape, dt, *p: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, P(*p)))
    cell = lambda dt: spec((2, 2, SELL_WIDTH // 2, rows), dt,
                           "data", "model", None, None)
    d = spec((N_ATOMS, T_PAD), jnp.float32)
    if op == "dsc":
        args = (cell(jnp.int32), cell(jnp.int32), cell(jnp.float32), d,
                spec((2 * nf_l,), jnp.float32, "model"))
        fn = dsc_fn
    else:
        args = (cell(jnp.int32), cell(jnp.int32), cell(jnp.float32), d,
                spec((2 * nv_l, T_PAD), jnp.float32, "data"))
        fn = wc_fn
    compiled = jax.jit(fn).lower(*args).compile()
    print(op, compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["kernel", "kernel-sell", "kernel-fcoo"])
def test_kernel_executors_interpret_on_cpu(name, tiny_problem):
    """Off-TPU the Pallas executors run in the interpreter (no Mosaic
    custom call in their program), and only there."""
    from repro.core.life import LifeConfig
    from repro.core.registry import create_for_format
    fmt = {"kernel": "coo", "kernel-sell": "sell", "kernel-fcoo": "fcoo"}
    assert jax.default_backend() == "cpu"
    assert ops.interpret_mode() and not ops.interpret_mode("tpu")
    ex = create_for_format(tiny_problem.phi, tiny_problem,
                           LifeConfig(executor=name, format=fmt[name],
                                      plan_cache_dir=""))
    w = jnp.ones((tiny_problem.phi.n_fibers,), jnp.float32)
    hlo = jax.jit(lambda f, x: f(x)).lower(ex.matvec, w).as_text()
    assert "tpu_custom_call" not in hlo
    assert ex.matvec(w).shape == tiny_problem.b.shape
