"""A mix's ``format`` and ``mesh``: what ``traffic.load`` accepts, that
every job and the front end's configuration get them, that the isolated
calls time the executor the served jobs ran, and that each run starts
with a plan cache of its own.  The tiny runs are on the CPU; there the
program's float32 loss reads above the chip's ``loss_gap`` limit
(``test_faults.py``), so a sound run is one whose ``obj_gap`` is under its
limit and that misses no job."""
import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import harness, peaks, traffic

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 99
SOLVE = {"loop": "closed", "subjects": 5, "n_iters": 100, "checked": 3}


def _write(tmp_path, mix):
    path = tmp_path / "mix.json"
    path.write_text(json.dumps(mix))
    return path


@pytest.mark.parametrize("bad", [
    {"format": "csr"}, {"format": 3}, {"format": None},
    {"mesh": [2]}, {"mesh": [2, 2, 1]}, {"mesh": [0, 4]}, {"mesh": [2, -2]},
    {"mesh": [2.0, 2]}, {"mesh": [True, 2]}, {"mesh": "2x2"},
    {"mesh": [2, 2], "format": "auto"}, {"mesh": [2, 2], "format": "alto"},
    {"mesh": [2, 2], "format": "fcoo"}])
def test_load_refuses_a_bad_format_or_mesh(tmp_path, bad):
    with pytest.raises(ValueError):
        traffic.load(_write(tmp_path, dict(SOLVE, **bad)))


@pytest.mark.parametrize("extra, options, chips", [
    ({}, {}, 1),
    ({"format": "auto"}, {"format": "auto"}, 1),
    ({"format": "sell"}, {"format": "sell"}, 1),
    ({"mesh": [1, 1]}, {"mesh": (1, 1)}, 1),
    ({"mesh": [2, 2]}, {"mesh": (2, 2)}, 4),
    ({"mesh": [1, 4], "format": "sell"}, {"mesh": (1, 4), "format": "sell"},
     4)])
def test_load_accepts_format_and_mesh(tmp_path, extra, options, chips):
    mix = traffic.load(_write(tmp_path, dict(SOLVE, **extra)))
    assert traffic.job_options(mix) == options
    assert traffic.chips(mix) == chips


def test_formats_are_the_programs():
    """The names a mix may give are those the scheduler accepts, and those
    it may put on a mesh are those with a sharded executor."""
    from repro.core.registry import REGISTRY
    from repro.serve import scheduler
    assert set(traffic.FORMATS) == set(scheduler.BATCHABLE_FORMATS
                                       + scheduler._SOLO_FORMATS)
    assert set(traffic.MESH_FORMATS) == {
        f for f in traffic.FORMATS
        if f != "auto" and REGISTRY.mesh_executor_for(f)}


def test_a_mix_without_them_runs_as_before():
    """No ``format``/``mesh``: the front end's default configuration, and
    jobs submitted with nothing but their problem, iterations and id."""
    from repro.core.life import LifeConfig
    assert harness.life_config(False, SOLVE) == LifeConfig()
    assert harness.life_config(True, SOLVE) == LifeConfig(
        compute_dtype="bf16")
    cell = _tiny("stn96-prob50k.solve")
    wl = harness.Workload(cell, SEED, 1.0)
    seen = []

    class Frontend:
        def submit_async(self, problem, **kwargs):
            seen.append(kwargs)

    wl.submit(Frontend(), traffic.Request(subject=1, n_iters=7), "j")
    assert seen == [{"n_iters": 7, "job_id": "j"}]


def test_every_job_gets_the_mix_format_and_mesh():
    """Warm-up and window jobs, solve and lesion mixes alike."""
    seen = []

    class Frontend:
        def submit_async(self, problem, **kwargs):
            seen.append(kwargs)

            class Handle:
                def result(self, timeout=None):
                    return problem.w_true, None
            return Handle()

    extra = {"format": "sell", "mesh": [1, 1]}
    for name in ("stn96-prob50k.solve", "stn96-prob50k.lesion"):
        cell = _tiny(name, **extra)
        wl = harness.Workload(cell, SEED, 1.0)
        wl.warm_up(Frontend())
        wl.submit(Frontend(), next(traffic.closed_requests(cell.mix, SEED)))
    assert len(seen) == 5
    assert all(k["format"] == "sell" and k["mesh"] == (1, 1) for k in seen)
    cfg = harness.life_config(True, dict(SOLVE, format="sell", mesh=[1, 4]))
    assert (cfg.format, cfg.shard_rows, cfg.shard_cols, cfg.compute_dtype) \
        == ("sell", 1, 4, "bf16")


def test_cell_from_files_takes_its_chips_from_the_mesh(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(
        (ROOT / "BENCHMARK.json").read_text())
    (bench / "configs" / "c.json").write_text("{}")
    for name, extra in (("one", {}), ("four", {"mesh": [2, 2]})):
        (bench / "traffic" / f"{name}.json").write_text(
            json.dumps(dict(SOLVE, **extra)))
        (bench / "limits" / f"c.{name}.json").write_text('{"missing": 0}')
    assert harness.cell_from_files("c.one", tmp_path).chips == 1
    assert harness.cell_from_files("c.four", tmp_path).chips == 4


def test_peaks_of_several_chips_add_up():
    one, four = peaks.peaks("TPU v5 lite"), peaks.peaks("TPU v5 lite", 4)
    assert {k: 4 * v for k, v in one.items()} == four


def test_each_run_gets_an_empty_plan_cache_of_its_own(monkeypatch):
    monkeypatch.setenv(harness.PLAN_CACHE_ENV, "/nonexistent/before")
    with harness.fresh_plan_cache() as a:
        assert os.environ[harness.PLAN_CACHE_ENV] == a
        assert os.path.isdir(a) and not os.listdir(a)
        Path(a, "plan").write_text("x")
    with harness.fresh_plan_cache() as b:
        assert b != a and not os.listdir(b)
    assert not os.path.exists(a) and not os.path.exists(b)
    assert os.environ[harness.PLAN_CACHE_ENV] == "/nonexistent/before"


def _tiny(name, **extra):
    try:
        cell = harness.load_cell(name)
    except KeyError:
        cell = harness.cell_from_files(name)
    mix = dict(cell.mix, **extra)
    if "lesion" in mix:
        mix["lesion"] = dict(mix["lesion"], bundle_fibers=20, bundles=3)
    return dataclasses.replace(
        cell, config=dict(cell.config, n_fibers=400, grid=[12, 12, 12]),
        mix=mix)


def _with_isolated(cell):
    """The cell with one metric whose reader needs the isolated calls, so
    that an untraced run builds them (the reader reads nothing untraced)."""
    return dataclasses.replace(cell, metrics=[
        {"name": "dsc_roofline", "unit": "%"}])


def _sound(result):
    obj = result["checks"]["obj_gap"]
    return result["failed"] == 0 and obj["value"] < obj["limit"]


def _engine_formats(monkeypatch):
    """The format each batched engine ran, by its first subject's
    coefficient count (a spy)."""
    from repro.core.batched import BatchedLifeEngine
    resolve, ran = BatchedLifeEngine._resolve_recipe, {}

    def spy(self):
        out = resolve(self)
        plan = self.format_plan
        ran[self.problems[0].phi.n_coeffs] = (
            "coo" if plan is None else plan.format)
        return out

    monkeypatch.setattr(BatchedLifeEngine, "_resolve_recipe", spy)
    return ran


@pytest.mark.parametrize("extra, executor", [
    ({}, "opt"), ({"format": "sell"}, "kernel-sell"),
    ({"format": "alto"}, "alto"), ({"format": "auto"}, None)])
def test_isolated_calls_follow_the_mix(extra, executor, monkeypatch):
    """The isolated calls build the executor of the format subject 0's
    jobs ran: the mix's own, or for ``auto`` the batched engine's choice
    on subject 0, read back from the plan cache; their outputs match the
    reference."""
    ran = _engine_formats(monkeypatch)
    out = harness.run(_with_isolated(_tiny("stn96-prob50k.solve", **extra)),
                      SEED, 2.0, False, time.perf_counter())
    result, info = out["result"], out["info"]
    assert _sound(result)
    for gap in ("dsc_gap", "wc_gap"):
        assert result["checks"][gap]["value"] < result["checks"][gap]["limit"]
    if executor is None:            # auto: whatever subject 0's engine chose
        fmt = ran[info["n_coeffs"][0]]
        assert info["formats"]["0"] == fmt
        executor = {"coo": "opt", "alto": "alto"}[fmt]
    assert info["isolated"]["executor"] == executor


def test_a_sell_mix_runs_the_sell_executor(monkeypatch):
    from repro.core.registry import REGISTRY
    create, made = REGISTRY.create, []

    def spy(name, *args, **kwargs):
        made.append(name)
        return create(name, *args, **kwargs)

    monkeypatch.setattr(REGISTRY, "create", spy)
    result = harness.run(_tiny("stn96-prob50k.solve", format="sell"), SEED,
                         2.0, False, time.perf_counter())["result"]
    assert _sound(result) and result["attempted"] >= 1
    assert set(made) == {"kernel-sell"}


MESH_RUN = """
import dataclasses, json, sys, time
sys.path[:0] = [{root!r}, {root!r} + "/src"]
import jax
from bench import harness
from repro.core.registry import REGISTRY
create, made = REGISTRY.create, []
def spy(name, *a, **k):
    made.append(name)
    return create(name, *a, **k)
REGISTRY.create = spy
cell = harness.load_cell("stn96-prob50k.solve")
cell = dataclasses.replace(
    cell, chips=4, config=dict(cell.config, n_fibers=400, grid=[12, 12, 12]),
    mix=dict(cell.mix, mesh=[2, 2]),
    metrics=[{{"name": "dsc_roofline", "unit": "%"}}])
out = harness.run(cell, {seed}, 2.0, False, time.perf_counter())
print(json.dumps({{"result": out["result"], "isolated": out["info"]["isolated"],
                  "made": made, "devices": len(jax.devices())}}))
"""


def test_a_four_device_mesh_cell_runs_on_the_shard_executor():
    """A tiny cell whose mix asks for a 2x2 mesh, on four host CPU devices
    (a process of its own: the device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", MESH_RUN.format(root=str(ROOT), seed=SEED)],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["devices"] == 4
    assert _sound(got["result"])
    assert set(got["made"]) == {"shard"}
    assert got["isolated"] == {"format": "coo", "executor": "shard"}
    for gap in ("dsc_gap", "wc_gap"):
        check = got["result"]["checks"][gap]
        assert check["value"] < check["limit"]
