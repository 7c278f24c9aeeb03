"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
resolves to its files under bench/."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and \
        "\n" not in text and "\t" not in text


def test_top_level():
    assert set(SPEC) == KEYS
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its time limit
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        # every cut is a key of the file, with its reason beside it
        assert set(cfg["reduced"]) <= set(cfg) & set(cfg.get("reduced_why", {}))
        assert cfg["source"] == c["source"]
        assert cfg["n_voxels"] == cfg["grid"][0] * cfg["grid"][1] * \
            cfg["grid"][2]


def chips_fit_the_mix(chips, mix) -> bool:
    """A cell takes 1 chip or 4, as many as its mix's mesh holds: a
    four-chip cell's mix carries a mesh of 4 devices, a one-chip cell's
    none or one of 1."""
    from bench import traffic
    return chips in (1, 4) and chips == traffic.chips(mix)


def test_workloads_resolve():
    from bench import check, traffic
    names = [w["name"] for w in SPEC["workloads"]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert _line(w["why"])
        mix = traffic.load(ROOT / "bench" / "traffic"
                           / f"{w['traffic']}.json")
        assert chips_fit_the_mix(w["chips"], mix)
        limits = check.load_limits(ROOT / "bench" / "limits"
                                   / f"{w['name']}.json")
        assert {"loss_gap", "obj_gap", "missing"} <= set(limits)
    # at most half of the cells, rounded down, take four chips; one may
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(names) // 2)


@pytest.mark.parametrize("chips, mesh, fits", [
    (1, None, True), (1, [1, 1], True), (4, [2, 2], True),
    (4, [1, 4], True), (4, None, False), (4, [1, 1], False),
    (4, [1, 2], False), (1, [2, 2], False), (2, [1, 2], False),
    (8, [2, 4], False)])
def test_chips_follow_the_mesh(chips, mesh, fits):
    mix = {"loop": "closed"} if mesh is None else {"loop": "closed",
                                                   "mesh": mesh}
    assert chips_fit_the_mix(chips, mix) is fits


def test_metrics():
    from bench import harness
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and _line(m["layer"])
        assert set(m["workloads"]) <= cells
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", [cell])
        layers.setdefault(m["layer"], set()).add(m["name"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert harness.reader_path(m["name"], ROOT).is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_each_cell_reports_enough(cell):
    from bench import harness
    c = harness.load_cell(cell)
    names = {m["name"] for m in c.metrics}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


def test_run_refuses_without_a_tpu():
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, cwd=ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_reader_falls_back_to_the_metric_family(tmp_path):
    from bench import harness
    metrics = tmp_path / "bench" / "metrics"
    metrics.mkdir(parents=True)
    (metrics / "idle_share.py").write_text("")
    assert harness.reader_path("idle_share.solve", tmp_path).name == \
        "idle_share.py"
    (metrics / "idle_share.solve.py").write_text("")
    assert harness.reader_path("idle_share.solve", tmp_path).name == \
        "idle_share.solve.py"
