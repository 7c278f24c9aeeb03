"""Runs of each cell at a tiny size on the CPU, past the harness's look for
a chip: the program as configured is correct; its bf16 control and each
fault a cell can have, planted in the timed path, are not."""
import dataclasses
import time

import numpy as np
import pytest

from bench import harness

CELLS = ["stn96-prob50k.solve", "stn96-prob50k.lesion"]


def tiny(name):
    cell = harness.load_cell(name)
    mix = dict(cell.mix)
    if "lesion" in mix:
        mix["lesion"] = dict(mix["lesion"], bundle_fibers=20, bundles=3)
    return dataclasses.replace(
        cell, config=dict(cell.config, n_fibers=400, grid=[12, 12, 12]),
        mix=mix)


def run(name, control=False):
    return harness.run(tiny(name), 2 ** 31 + 99, 2.0, False,
                       time.perf_counter(), control=control)["result"]


@pytest.mark.parametrize("name", CELLS)
def test_bf16_control_fails_and_reads_above_the_program(name):
    program, control = run(name), run(name, control=True)
    assert program["failed"] == 0 and program["attempted"] >= 1
    assert list(program)[-1] == "checks"
    assert not control["correct"]
    # the limits come from chip readings; on the CPU the program's own
    # float32 loss (a long dot product) is off by about 1e-4, so only the
    # weights are compared between the two runs here
    assert (3 * program["checks"]["fit_gap"]["value"]
            < control["checks"]["fit_gap"]["value"])


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
    assert not run(name)["correct"]
