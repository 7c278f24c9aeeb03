"""Persistent plan cache: round-trips, content addressing, engine wiring."""
import dataclasses

import numpy as np
import pytest

from repro.core import plan_cache as PC
from repro.core.inspector import plan_tiles
from repro.core.life import LifeConfig, LifeEngine
from repro.core.plan_cache import PlanCache, spmv_plan_key, tile_plan_key
from repro.core.restructure import SpmvPlan


def _ids(n=300, rows=40, seed=0):
    return np.sort(np.random.default_rng(seed).integers(0, rows, n)), rows


def test_tile_plan_roundtrip(tmp_path):
    ids, rows = _ids()
    plan = plan_tiles(ids, rows, c_tile=32, row_tile=8)
    cache = PlanCache(str(tmp_path))
    key = tile_plan_key(ids, rows, c_tile=32, row_tile=8)
    assert cache.get_tile_plan(key) is None          # cold
    cache.put_tile_plan(key, plan)
    got = cache.get_tile_plan(key)
    assert got is not None
    np.testing.assert_array_equal(got.sel, plan.sel)
    np.testing.assert_array_equal(got.row_block, plan.row_block)
    np.testing.assert_array_equal(got.local_row, plan.local_row)
    assert (got.n_tiles, got.c_tile, got.row_tile, got.n_rows_padded) == \
        (plan.n_tiles, plan.c_tile, plan.row_tile, plan.n_rows_padded)
    assert cache.stats.hits == 1 and cache.stats.misses == 1


def test_spmv_plan_roundtrip(tmp_path):
    cache = PlanCache(str(tmp_path))
    plan = SpmvPlan(op="dsc", restructure="voxel", partition="voxel",
                    order=np.arange(17, dtype=np.int64)[::-1].copy())
    key = spmv_plan_key("dsc", *(np.arange(5),) * 3)
    cache.put_spmv_plan(key, plan)
    got = cache.get_spmv_plan(key)
    assert (got.op, got.restructure, got.partition) == \
        ("dsc", "voxel", "voxel")
    np.testing.assert_array_equal(got.order, plan.order)


def test_key_is_content_addressed():
    ids, rows = _ids()
    base = tile_plan_key(ids, rows, c_tile=32, row_tile=8)
    # same content, different buffer -> same key
    assert tile_plan_key(ids.copy(), rows, c_tile=32, row_tile=8) == base
    # any input change -> different key
    assert tile_plan_key(ids, rows + 1, c_tile=32, row_tile=8) != base
    assert tile_plan_key(ids, rows, c_tile=64, row_tile=8) != base
    assert tile_plan_key(ids, rows, c_tile=32, row_tile=4) != base
    bumped = ids.copy()
    bumped[0] = min(bumped[0] + 1, rows - 1)
    if not np.array_equal(bumped, ids):
        assert tile_plan_key(bumped, rows, c_tile=32, row_tile=8) != base


def test_disabled_cache_never_touches_disk(tmp_path):
    cache = PlanCache("")
    assert not cache.enabled
    ids, rows = _ids()
    plan = plan_tiles(ids, rows, c_tile=32, row_tile=8)
    key = tile_plan_key(ids, rows, c_tile=32, row_tile=8)
    cache.put_tile_plan(key, plan)
    assert cache.get_tile_plan(key) is None
    assert list(tmp_path.iterdir()) == []


def test_corrupt_entry_degrades_to_miss(tmp_path):
    cache = PlanCache(str(tmp_path))
    ids, rows = _ids()
    key = tile_plan_key(ids, rows, c_tile=32, row_tile=8)
    (tmp_path / (key + ".npz")).write_bytes(b"not an npz")
    assert cache.get_tile_plan(key) is None


def test_cache_hit_skips_plan_tiles(tmp_path, tiny_problem, monkeypatch):
    """Second kernel-engine construction must not call plan_tiles at all."""
    cfg = LifeConfig(executor="kernel", n_iters=2, c_tile=64, row_tile=8,
                     plan_cache_dir=str(tmp_path))
    eng1 = LifeEngine(tiny_problem, cfg)
    assert eng1.cache_stats.misses == 2 and eng1.cache_stats.hits == 0

    def boom(*a, **k):
        raise AssertionError("plan_tiles called despite cache hit")

    from repro.core import registry
    monkeypatch.setattr(registry, "plan_tiles", boom)
    eng2 = LifeEngine(tiny_problem, cfg)
    assert eng2.cache_stats.hits == 2 and eng2.cache_stats.misses == 0
    # and the cached plans still produce the same results
    import jax.numpy as jnp
    w = jnp.ones((tiny_problem.phi.n_fibers,), jnp.float32)
    np.testing.assert_allclose(np.asarray(eng1.matvec(w)),
                               np.asarray(eng2.matvec(w)),
                               rtol=1e-6, atol=1e-6)


def test_second_planning_time_drops(tmp_path):
    """The amortization claim: a warm plan lookup beats re-running the
    O(Nc) host tiling loop.  Sized so the margin is decisive (200k coeffs:
    the python loop takes orders of magnitude longer than one np.load)."""
    import time
    from repro.core.registry import planned_tiles
    ids = np.sort(np.random.default_rng(1).integers(0, 5000, 200_000))
    cache = PlanCache(str(tmp_path))
    t0 = time.perf_counter()
    cold = planned_tiles(ids, 5000, c_tile=128, row_tile=8, cache=cache)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = planned_tiles(ids, 5000, c_tile=128, row_tile=8, cache=cache)
    t_warm = time.perf_counter() - t0
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    assert t_warm < t_cold
    np.testing.assert_array_equal(warm.sel, cold.sel)
    np.testing.assert_array_equal(warm.row_block, cold.row_block)


def test_size_cap_prunes_oldest(tmp_path):
    """Writes past max_bytes evict oldest entries; newest always survives."""
    import os
    import time
    cache = PlanCache(str(tmp_path), max_bytes=6000)
    keys = []
    for i in range(12):
        ids = np.sort(np.random.default_rng(i).integers(0, 40, 300))
        plan = plan_tiles(ids, 40, c_tile=32, row_tile=8)
        key = tile_plan_key(ids, 40, c_tile=32, row_tile=8)
        cache.put_tile_plan(key, plan)
        keys.append(key)
        os.utime(tmp_path / (key + ".npz"),
                 (time.time() - 100 + i, time.time() - 100 + i))
    files = list(tmp_path.glob("*.npz"))
    total = sum(f.stat().st_size for f in files)
    assert total <= 6000
    assert len(files) < 12                        # something was evicted
    assert cache.get_tile_plan(keys[-1]) is not None   # newest survives
    assert cache.get_tile_plan(keys[0]) is None        # oldest evicted


def test_cap_below_one_entry_keeps_newest(tmp_path):
    """A cap smaller than a single entry must not disable the cache."""
    cache = PlanCache(str(tmp_path), max_bytes=1)
    ids, rows = _ids()
    key = tile_plan_key(ids, rows, c_tile=32, row_tile=8)
    cache.put_tile_plan(key, plan_tiles(ids, rows, c_tile=32, row_tile=8))
    assert cache.get_tile_plan(key) is not None


def test_no_cap_keeps_everything(tmp_path):
    cache = PlanCache(str(tmp_path))              # max_bytes=None
    for i in range(5):
        ids = np.sort(np.random.default_rng(100 + i).integers(0, 40, 300))
        cache.put_tile_plan(tile_plan_key(ids, 40, c_tile=32, row_tile=8),
                            plan_tiles(ids, 40, c_tile=32, row_tile=8))
    assert len(list(tmp_path.glob("*.npz"))) == 5


def test_max_bytes_env_default(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_CACHE_MAX_BYTES", "12345")
    assert PlanCache(str(tmp_path)).max_bytes == 12345
    monkeypatch.setenv("REPRO_PLAN_CACHE_MAX_BYTES", "not-an-int")
    assert PlanCache(str(tmp_path)).max_bytes is None


def test_compaction_changes_key_and_misses(tmp_path, tiny_problem):
    """Compacted phi has different index content -> clean cache miss."""
    from repro.core.restructure import compact_by_weight
    import jax.numpy as jnp
    cfg = LifeConfig(executor="kernel", n_iters=2, c_tile=64, row_tile=8,
                     plan_cache_dir=str(tmp_path))
    eng = LifeEngine(tiny_problem, cfg)
    w = np.zeros(tiny_problem.phi.n_fibers, np.float32)
    w[: len(w) // 2] = 1.0
    compacted = compact_by_weight(tiny_problem.phi, jnp.asarray(w))
    assert compacted.n_coeffs < tiny_problem.phi.n_coeffs
    problem2 = dataclasses.replace(tiny_problem, phi=compacted)
    eng2 = LifeEngine(problem2, cfg)
    assert eng2.cache_stats.misses == 2        # no false sharing


# ----------------------------------------------------------------------------
# ShardPlan: partition cuts keyed by mesh topology (DESIGN.md §9)
# ----------------------------------------------------------------------------

def test_shard_plan_roundtrip_and_warm_hit(tmp_path, tiny_problem,
                                           monkeypatch):
    """A warm cache hit rebuilds the partition without re-partitioning:
    the second partition_cuts never calls shard_boundaries."""
    from repro.formats import shard as FS
    cache = PlanCache(str(tmp_path))
    plan = FS.partition_cuts(tiny_problem.phi, 3, 2, cache=cache)
    assert cache.stats.misses == 1
    calls = []
    orig = FS.shard_boundaries
    monkeypatch.setattr(FS, "shard_boundaries",
                        lambda *a, **k: (calls.append(1), orig(*a, **k))[1])
    warm = FS.partition_cuts(tiny_problem.phi, 3, 2, cache=cache)
    assert cache.stats.hits == 1 and calls == []
    assert (warm.R, warm.C) == (plan.R, plan.C)
    np.testing.assert_array_equal(warm.voxel_cuts, plan.voxel_cuts)
    np.testing.assert_array_equal(warm.fiber_cuts, plan.fiber_cuts)


def test_shard_plan_key_includes_mesh_and_devices():
    """Regression (ISSUE 4): a sharded plan written on one topology must
    miss cleanly on another — the key covers the mesh shape, the device
    count, and the inner cell format."""
    from repro.core.plan_cache import shard_plan_key
    ids = (np.arange(10), np.arange(10) % 4, np.arange(10) % 3)
    base = dict(sizes=(8, 4, 3), R=4, C=2, cell_format="coo", n_devices=8)
    key = shard_plan_key(*ids, **base)
    assert shard_plan_key(*ids, **base) == key                   # stable
    for change in (dict(R=2), dict(C=1), dict(n_devices=1),
                   dict(cell_format="sell"), dict(sizes=(8, 4, 4))):
        assert shard_plan_key(*ids, **{**base, **change}) != key, change


def test_shard_plan_mesh_shape_mismatch_is_clean_miss(tmp_path,
                                                      tiny_problem):
    """Full-stack: the same dataset partitioned for a different mesh shape
    misses (and re-partitions) instead of loading the wrong cuts."""
    from repro.formats.shard import partition_cuts
    cache = PlanCache(str(tmp_path))
    partition_cuts(tiny_problem.phi, 4, 2, cache=cache)
    assert (cache.stats.hits, cache.stats.misses) == (0, 1)
    plan = partition_cuts(tiny_problem.phi, 2, 1, cache=cache)
    assert (cache.stats.hits, cache.stats.misses) == (0, 2)
    assert (plan.R, plan.C) == (2, 1)
    # and the original topology still hits its own entry
    partition_cuts(tiny_problem.phi, 4, 2, cache=cache)
    assert cache.stats.hits == 1


@pytest.mark.parametrize("env", [None, "/some/shared/jax-cache"])
def test_compile_cache_dir(monkeypatch, env):
    """$JAX_COMPILATION_CACHE_DIR is left to JAX; without it the cache is
    the fixed <checkout>/.jax_cache (no temp dir, pid or time in it)."""
    import jax
    from pathlib import Path
    from repro import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    path = compile_cache.enable_compile_cache()
    if env is None:
        checkout = Path(__file__).resolve().parents[1]
        assert path == str(checkout / ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", path)]
    else:
        assert path == env and updates == []
