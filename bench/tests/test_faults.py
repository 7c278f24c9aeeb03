"""Runs of each cell at a tiny size on the CPU, past the harness's look for
a chip: the program as configured is correct; its bf16 control and each
fault a cell can have, planted in the timed path, are not, under the
default layout and under ``format="auto"`` (``stn96-prob50k.auto``)
alike.  The open-loop
cell ``roi-prob5k.served`` is made from its files (it is not in
``BENCHMARK.json``: PERF.md), so that its path stays checked, batches of
several jobs and each member's share of the batch's state included.

On the CPU the program's own float32 loss (a long dot product) is off by
about 1e-4, above the chip's ``loss_gap`` limits, so a fault is shown by
the number it moves, ``obj_gap``, read above its limit."""
import collections
import dataclasses
import time

import numpy as np
import pytest

from bench import harness

CELLS = ["stn96-prob50k.solve", "stn96-prob50k.lesion",
         "stn96-prob50k.auto", "roi-prob5k.served"]


def tiny(name):
    try:
        cell = harness.load_cell(name)
    except KeyError:
        cell = harness.cell_from_files(name)
    mix = dict(cell.mix)
    if "lesion" in mix:
        mix["lesion"] = dict(mix["lesion"], bundle_fibers=20, bundles=3)
    if mix["loop"] == "open":
        mix.update(warmup_s=1.0, rate_per_s=2.0)
    return dataclasses.replace(
        cell, config=dict(cell.config, n_fibers=400, grid=[12, 12, 12]),
        mix=mix)


def run(name, control=False):
    return harness.run(tiny(name), 2 ** 31 + 99, 2.0, False,
                       time.perf_counter(), control=control)["result"]


def fails(result):
    obj = result["checks"]["obj_gap"]
    return not result["correct"] and obj["value"] > obj["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_bf16_control_fails_and_reads_above_the_program(name):
    program, control = run(name), run(name, control=True)
    assert program["failed"] == 0 and program["attempted"] >= 1
    assert list(program)[-1] == "checks"
    assert not control["correct"]
    # the limits come from chip readings; on the CPU the program's own
    # float32 loss (a long dot product) is off by about 1e-4, so only the
    # answers' objectives are compared between the two runs here
    assert (3 * program["checks"]["obj_gap"]["value"]
            < control["checks"]["obj_gap"]["value"])


def _unchanged_step(monkeypatch):
    import repro.core.batched as batched
    monkeypatch.setattr(batched, "sbbnnls_step",
                        lambda mv, rmv, b, state: state)


def _half_left_out(monkeypatch):
    import dataclasses as dc
    import jax.numpy as jnp
    import repro.core.batched as batched
    stack = batched._stack_phis

    def half(phis):
        def cut(p):
            keep = jnp.arange(p.n_coeffs) < p.n_coeffs // 2
            return dc.replace(p, values=jnp.where(keep, p.values, 0.0))
        return stack([cut(p) for p in phis])

    monkeypatch.setattr(batched, "_stack_phis", half)


def _answer_altered(monkeypatch):
    from repro.serve import scheduler
    result = scheduler.Job.result

    def altered(self):
        w, losses = result(self)
        w = np.array(w)
        w[int(np.argmin(w))] = 2.0 * np.abs(w).max()
        return w, losses

    monkeypatch.setattr(scheduler.Job, "result", altered)


@pytest.mark.parametrize("fault", [_unchanged_step, _half_left_out,
                                   _answer_altered])
@pytest.mark.parametrize("name", CELLS)
def test_fault_fails(name, fault, monkeypatch):
    fault(monkeypatch)
    assert fails(run(name))


def _batch_sizes(monkeypatch):
    """Counts the jobs of each batched step (a spy, not a fault)."""
    from repro.core.batched import BatchedLifeEngine
    step, sizes = BatchedLifeEngine.step, collections.Counter()

    def spy(self, states, k):
        sizes[int(states.w.shape[0])] += 1
        return step(self, states, k)

    monkeypatch.setattr(BatchedLifeEngine, "step", spy)
    return sizes


def _members_swapped(monkeypatch):
    """Each member of a batch is handed the next member's weights, as a
    slice of the batch's state taken at the wrong offset would."""
    import jax.numpy as jnp
    from repro.core.batched import BatchedLifeEngine
    step = BatchedLifeEngine.step

    def swapped(self, states, k):
        new, losses = step(self, states, k)
        return new._replace(w=jnp.roll(new.w, 1, axis=0)), losses

    monkeypatch.setattr(BatchedLifeEngine, "step", swapped)


OPEN_CELLS = [n for n in CELLS if tiny(n).mix["loop"] == "open"]


@pytest.mark.parametrize("name", OPEN_CELLS)
def test_open_run_batches_several_jobs(name, monkeypatch):
    """The tiny open run is sound and has steps of more than one job: what
    the swapped-members fault below has to act on."""
    sizes = _batch_sizes(monkeypatch)
    result = run(name)
    assert result["failed"] == 0
    assert result["checks"]["obj_gap"]["value"] < (
        result["checks"]["obj_gap"]["limit"])
    assert max(sizes) > 1, sizes


@pytest.mark.parametrize("name", OPEN_CELLS)
def test_batch_members_swapped_fails(name, monkeypatch):
    sizes = _batch_sizes(monkeypatch)
    _members_swapped(monkeypatch)
    assert fails(run(name))
    assert max(sizes) > 1, sizes


class _Stop(Exception):
    pass


@pytest.mark.parametrize("traced", [True, False])
@pytest.mark.parametrize("name", CELLS)
def test_run_turns_obs_on_inside_the_window(name, traced, monkeypatch,
                                            tmp_path):
    """``harness.run`` runs the window of either loop with the program's
    tracing on in a traced run and off in an untraced one, and leaves it
    off after the window."""
    import jax
    from repro import obs
    cell = tiny(name)
    seen = []

    def window(fe, wl):
        seen.append(obs.enabled())
        raise _Stop

    monkeypatch.setitem(harness.WINDOWS, cell.mix["loop"], window)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda *a, **k: None)
    monkeypatch.setattr(harness.tempfile, "mkdtemp",
                        lambda **_: str(tmp_path))
    with pytest.raises(_Stop):
        harness.run(cell, 2 ** 31 + 99, 2.0, traced, time.perf_counter())
    assert seen == [traced] and not obs.enabled()
