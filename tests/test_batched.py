"""BatchedLifeEngine: cohort results must match per-subject engines."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batched import BatchedLifeEngine, _pad_sorted, ladder_size
from repro.core.life import LifeConfig, LifeEngine
from repro.core.registry import REGISTRY
from repro.core.restructure import sort_by_host
from repro.data.dmri import synth_cohort


@pytest.fixture(scope="module")
def cohort():
    return synth_cohort(3, base_seed=10, n_fibers=64, n_theta=16,
                        n_atoms=24, grid=(10, 10, 10))


@pytest.mark.parametrize("executor,extra", [
    pytest.param("naive", {}, id="naive"),
    pytest.param("opt", {}, id="opt"),
    pytest.param("opt-paper", {}, id="opt-paper"),
    pytest.param("opt", {"compute_dtype": "bf16"}, id="opt-bf16"),
    pytest.param("opt", {"format": "alto"}, id="alto"),
])
def test_batched_matches_per_subject(cohort, executor, extra):
    cfg = LifeConfig(executor=executor, n_iters=12, plan_cache_dir="",
                     **extra)
    beng = BatchedLifeEngine(cohort, cfg)
    W, losses = beng.run()
    assert W.shape == (3, cohort[0].phi.n_fibers)
    assert losses.shape == (3, 12)
    for s, p in enumerate(cohort):
        w_ref, l_ref = LifeEngine(p, cfg).run()
        np.testing.assert_allclose(np.asarray(W[s]), np.asarray(w_ref),
                                   rtol=1e-5, atol=1e-6,
                                   err_msg=f"{executor} subject {s}")
        np.testing.assert_allclose(losses[s], l_ref, rtol=1e-5)


def test_batched_auto_uses_one_tuned_recipe(cohort, tmp_path):
    cfg = LifeConfig(executor="auto", n_iters=10,
                     plan_cache_dir=str(tmp_path))
    beng = BatchedLifeEngine(cohort, cfg)
    W, _ = beng.run()
    # auto tunes on subject 0 through the persistent cache
    assert beng.cache.stats.misses == 2
    # per-subject results still close to the reference executor
    ref_cfg = LifeConfig(executor="opt", n_iters=10, plan_cache_dir="")
    for s, p in enumerate(cohort):
        w_ref, _ = LifeEngine(p, ref_cfg).run()
        np.testing.assert_allclose(np.asarray(W[s]), np.asarray(w_ref),
                                   rtol=1e-3, atol=1e-4)


def test_padding_is_inert():
    """A padded subject must produce bit-comparable results to unpadded."""
    from repro.core import spmv
    [p] = synth_cohort(1, base_seed=3, n_fibers=32, n_theta=8, n_atoms=12,
                       grid=(8, 8, 8))
    phi_v, _ = sort_by_host(p.phi, "voxel")
    padded = _pad_sorted(phi_v, phi_v.n_coeffs + 37, "voxel", True)
    assert padded.n_coeffs == phi_v.n_coeffs + 37
    assert not np.any(np.diff(np.asarray(padded.voxels)) < 0)  # still sorted
    w = jnp.asarray(np.random.default_rng(0).uniform(size=32), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(spmv.dsc(padded, p.dictionary, w)),
        np.asarray(spmv.dsc(phi_v, p.dictionary, w)),
        rtol=1e-6, atol=1e-7)


def test_rejects_non_vmappable_executor(cohort):
    for executor in ("kernel", "shard"):
        with pytest.raises(ValueError, match="not vmappable"):
            BatchedLifeEngine(
                cohort, LifeConfig(executor=executor, plan_cache_dir=""))


def test_rejects_mismatched_geometry(cohort):
    small = synth_cohort(1, base_seed=99, n_fibers=32, n_theta=16,
                         n_atoms=24, grid=(10, 10, 10))
    with pytest.raises(ValueError, match="geometry"):
        BatchedLifeEngine(cohort + small, LifeConfig(plan_cache_dir=""))


def test_registry_names_cover_ladder():
    for name in ("naive", "opt", "opt-paper", "kernel", "auto", "shard"):
        assert name in REGISTRY
    with pytest.raises(ValueError, match="executor must be one of"):
        REGISTRY.create("nope", None, None, None)


# ----------------------------------------------------------------------------
# mesh placement (DESIGN.md §9.4): subjects over `data`, Phi slots over
# `model`.  The multi-device variant executes in the CI multi-device lane
# (8 forced host devices); on one device it validates the error surface.
# ----------------------------------------------------------------------------

def test_batched_mesh_rejects_oversized_mesh(cohort):
    import jax
    n = len(jax.devices())
    cfg = LifeConfig(executor="opt", n_iters=4, plan_cache_dir="",
                     shard_rows=n + 1, shard_cols=2)
    with pytest.raises(ValueError, match="devices"):
        BatchedLifeEngine(cohort, cfg)


def _mesh_skip(n_needed):
    import jax
    return pytest.mark.skipif(
        len(jax.devices()) < n_needed,
        reason=f"needs {n_needed} devices")


@pytest.mark.parametrize("R,C", [
    pytest.param(2, 2, marks=_mesh_skip(4)),
    pytest.param(4, 2, marks=_mesh_skip(8)),
])
def test_batched_mesh_placement_matches_unplaced(cohort, R, C):
    """Device-placing the stacked cohort (subjects x slots over the mesh)
    never changes results — GSPMD repartitions, the math is identical."""
    base = LifeConfig(executor="opt", n_iters=10, plan_cache_dir="")
    W0, L0 = BatchedLifeEngine(cohort, base).run()
    import dataclasses
    eng = BatchedLifeEngine(
        cohort, dataclasses.replace(base, shard_rows=R, shard_cols=C))
    assert eng.mesh is not None
    W1, L1 = eng.run()
    np.testing.assert_allclose(np.asarray(W1), np.asarray(W0),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(L1, L0, rtol=1e-4)


# ----------------------------------------------------------------------------
# the Nc ladder and the shared runner (DESIGN.md §6.2)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("nc", [0, 1, 7, 8, 15, 16, 17, 31, 127, 128, 143,
                                144, 145, 271, 272, 273, 1000, 4095, 4096,
                                4097, 2 ** 20 - 1, 2 ** 20, 2 ** 20 + 1,
                                2 ** 20 + 129, 1_000_007, 1_016_359,
                                3 * 2 ** 30 + 5])
def test_ladder_size(nc):
    size = ladder_size(nc)
    assert size >= nc
    assert size - nc <= nc / 8 and (nc == 0 or size - nc < nc / 8)
    assert ladder_size(size) == size                 # a ladder value is fixed
    assert ladder_size(max(0, nc - 1)) <= size <= ladder_size(nc + 1)
    base = size - 128                                # m * 2**e, m in 8..15
    if base >= 16:
        e = base.bit_length() - 4
        assert base % 2 ** e == 0 and 8 <= base >> e <= 15
    else:
        assert size == nc
    if nc > 8192 + 128:               # never a multiple of 1024 (v5e layout)
        assert size % 1024 == 128


def test_ladder_size_monotone_with_eight_sizes_an_octave():
    sizes = [ladder_size(n) for n in range(1, 5000)]
    assert all(a <= b for a, b in zip(sizes, sizes[1:]))
    for lo in (16, 256, 2048):
        assert len({s - 128 for s in sizes if lo <= s - 128 < 2 * lo}) == 8


def _subset(p, n, scale):
    """A different subject with the first ``n`` coefficients of ``p``."""
    phi = jax.tree_util.tree_map(lambda a: a[:n], p.phi)
    return dataclasses.replace(
        p, phi=dataclasses.replace(phi, values=phi.values * scale))


def test_runner_is_shared_across_subjects_in_one_bucket(cohort, monkeypatch):
    """A second engine on another subject whose Nc lands on the same ladder
    size builds and steps without compiling; a subject on another ladder
    size compiles; an engine built after the solver step is replaced gets
    a runner of its own."""
    import jax.monitoring as mon

    import repro.core.batched as batched

    p = cohort[0]
    nc = p.phi.n_coeffs
    same = _subset(p, nc - 3, 0.5)
    other = _subset(p, nc // 2, 2.0)
    assert ladder_size(same.phi.n_coeffs) == ladder_size(nc)
    assert ladder_size(other.phi.n_coeffs) != ladder_size(nc)
    cfg = LifeConfig(executor="opt", n_iters=4, plan_cache_dir="")
    compiles = []

    def count(event, *_, **__):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    def build_and_step(problem):
        del compiles[:]
        eng = BatchedLifeEngine([problem], cfg)
        states, _ = eng.step(eng.init_states(), 3)
        jax.block_until_ready(states)
        return eng, states, len(compiles)

    mon.register_event_duration_secs_listener(count)
    try:
        first, _, _ = build_and_step(p)
        second, states, n_same = build_and_step(same)
        _, _, n_other = build_and_step(other)
        monkeypatch.setattr(batched, "sbbnnls_step",
                            lambda mv, rmv, b, state: state)
        patched, frozen, n_patched = build_and_step(p)
    finally:
        mon.unregister_event_duration_listener(count)
    assert second._runner is first._runner
    assert n_same == 0
    assert n_other >= 1
    assert patched._runner is not first._runner and n_patched >= 1
    np.testing.assert_array_equal(np.asarray(frozen.w), 1.0)
    # the shared runner solved the second subject, not the first
    w_ref, _ = LifeEngine(same, cfg).run(n_iters=3)
    np.testing.assert_allclose(np.asarray(states.w[0]), np.asarray(w_ref),
                               rtol=1e-5, atol=1e-6)
    monkeypatch.undo()
    assert BatchedLifeEngine([same], cfg)._runner is first._runner
