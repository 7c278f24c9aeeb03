import os
import sys

# NOTE: no XLA_FLAGS here — tests run on the single real device; only
# launch/dryrun.py forces 512 placeholder devices.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", False)

# CI runs the fast lane under two values of $REPRO_TEST_SEED to flush
# seed-dependent flakiness; fixtures offset their PRNG seeds by it.
TEST_SEED = int(os.environ.get("REPRO_TEST_SEED", "0"))


@pytest.fixture(autouse=True)
def _obs_disabled_and_clean():
    """Observability starts disabled and empty for every test — a test
    that enables it (tests/test_obs.py, serving counter checks) cannot
    leak instrument state or the enabled switch into the next test."""
    from repro import obs
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.fixture(autouse=True)
def _isolated_plan_cache(tmp_path, monkeypatch):
    """Route all plan caching to a per-test tmpdir and clear the in-process
    autotune memo, so no test's outcome depends on suite ordering or on a
    warm on-disk cache left by an earlier run (or by the developer's own
    engines writing to ~/.cache)."""
    from repro.core import restructure
    from repro.learn import clear_load_memo, refine
    monkeypatch.setenv("REPRO_PLAN_CACHE", str(tmp_path / "plan-cache"))
    monkeypatch.delenv("REPRO_PLAN_CACHE_MAX_BYTES", raising=False)
    restructure.clear_plan_cache()
    refine.QUEUE.clear()
    clear_load_memo()
    yield
    restructure.clear_plan_cache()
    refine.QUEUE.clear()
    clear_load_memo()


@pytest.fixture(scope="session")
def tiny_problem():
    from repro.data.dmri import synth_connectome
    return synth_connectome(n_fibers=64, n_theta=16, n_atoms=24,
                            grid=(10, 10, 10), seed=1 + TEST_SEED)


@pytest.fixture(scope="session")
def tiny_dense(tiny_problem):
    from repro.core.std import materialize_dense
    return materialize_dense(tiny_problem.phi, tiny_problem.dictionary)


@pytest.fixture()
def rng():
    return np.random.default_rng(TEST_SEED)


@pytest.fixture(scope="session")
def tiny_cohort():
    """Three small subjects sharing one acquisition (serving fixtures)."""
    from repro.data.dmri import synth_cohort
    return synth_cohort(3, base_seed=10 + TEST_SEED, n_fibers=64, n_theta=16,
                        n_atoms=24, grid=(10, 10, 10))
