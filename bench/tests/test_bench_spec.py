"""BENCHMARK.json against the benchmark's contract, the files each cell
is found by, and that a cell, a mix and a metric are added by files
alone."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = harness.load_spec(ROOT)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_lines():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in SPEC["workloads"]]:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])


def test_metrics_sources_bounds_and_moves():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    layers = {}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and _line(m["layer"])
        for w in m.get("workloads", cells):
            assert w in cells
            assert "workloads" not in e2e[m["moves"]] or \
                w in e2e[m["moves"]]["workloads"]
        if m["name"].endswith("_roofline") or ".roofline" in m["name"]:
            assert m["unit"] == "%"
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


@pytest.mark.parametrize("cell", sorted(w["name"] for w in SPEC["workloads"]))
def test_every_cell_resolves_to_its_files(cell):
    c = harness.resolve(SPEC, cell)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and len(c.per_layer) >= 1
    harness.phase_module(c)
    for m in c.per_layer:
        assert callable(harness.metric_reader(c, m["name"]))
    assert c.limits and all(isinstance(v, (int, float))
                            for v in c.limits.values())
    cfg_file = next(x["file"] for x in SPEC["configs"]
                    if x["name"] == c.config["name"])
    assert os.path.exists(os.path.join(ROOT, cfg_file))


def test_four_chip_cells_within_share():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_a_cell_mix_and_metric_register_from_files_alone(tmp_path):
    """A copy of the benchmark with a new config, a new traffic mix, new
    limits and a new per-layer metric, added as files and entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    cfg = json.load(open(os.path.join(ROOT, "bench/configs/sift1m.json")))
    cfg.update(name="tiny", n=1024, k=16, components=16, lists=16)
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/graph-small.json").write_text(json.dumps(
        {"phase": "graph", "sample_rows": 32, "checked_builds": 1}))
    (root / "bench/limits/tiny.graph.json").write_text(json.dumps(
        {"dist_err": 1e-3, "recall_short": 0.9, "bad_slots": 0}))
    (root / "bench/metrics/calls.graph.py").write_text(
        "def read(ctx):\n    return ctx.counts.get('calls') or None\n")
    spec["configs"].append({"name": "tiny", "source": "a test",
                            "file": "bench/configs/tiny.json",
                            "reduced": ["n"], "why": "a test"})
    spec["workloads"].append({"name": "tiny.graph", "config": "tiny",
                              "traffic": "graph-small", "chips": 1,
                              "why": "a test"})
    spec["end_to_end"][0]["workloads"].append("tiny.graph")
    spec["per_layer"].append({"name": "calls.graph", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "harness", "moves": "graph_rows_per_s",
                              "workloads": ["tiny.graph"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.resolve(harness.load_spec(str(root)), "tiny.graph",
                           bench_dir=str(root / "bench"))
    assert cell.config["n"] == 1024 and cell.phase == "graph"
    assert [m["name"] for m in cell.per_layer] == ["calls.graph"]
    read = harness.metric_reader(cell, "calls.graph")

    class Ctx:
        counts = {"calls": 3}
    assert read(Ctx()) == 3
    out = harness.run_cell(cell, 5, 0.2, False, t_process=0.0,
                           require_tpu=False)
    assert out["correct"] and "graph_rows_per_s" in out["metrics"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sift1m.graph",
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_cpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_command_fails_without_the_program(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert not p.stdout.strip()
