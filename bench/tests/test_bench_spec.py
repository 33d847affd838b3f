"""BENCHMARK.json against the files it names, the benchmark's contract on
names and keys, and a throwaway cell added as new files only."""
import json
import re
import shutil
import subprocess
import sys

import pytest

from bench import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_resolves(workload):
    cell = spec.resolve(workload)
    assert cell.config["name"] == cell.config_name
    assert cell.reference().dims(cell.config)["layers"] >= 1
    for m in cell.per_layer:
        assert callable(cell.metric_reader(m["name"]).read)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    # each per-layer metric moves an end-to-end metric the cell reports
    for m in cell.per_layer:
        assert m["moves"] in names, (workload, m["name"])
    for key in ("serving", "pipeline", "correctness"):
        assert key in cell.cell


@pytest.mark.parametrize("workload", WORKLOADS)
def test_open_loop_window_is_whole_periods(workload):
    """An open loop's window of ``run_seconds`` holds its traffic's fixed
    period a whole number of times, so every seed meets the same work."""
    cell = spec.resolve(workload)
    if cell.traffic["loop"] != "open":
        return
    periods = cell.cell["rate"] * BENCH["run_seconds"] / cell.traffic["levels"]
    assert periods >= 1 and abs(periods - round(periods)) < 1e-9


def test_names_units_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "layer", "moves", "workloads"}
        for w in m.get("workloads", []):
            assert w in WORKLOADS
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert not k.endswith(("_dim", "_rank", "_size"))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(NAME.match(w) for w in WORKLOADS)
    assert 1 <= BENCH["run_seconds"] <= 51


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        spec.resolve("no-such-cell")


def test_a_new_cell_is_new_files_only(tmp_path):
    """A configuration, mix, cell and metric added in another root, with
    nothing under the existing bench/ edited."""
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "internlm2-1.8b.json").read_text())
    cfg["name"] = "throwaway"
    (b / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    (b / "traffic" / "burst.json").write_text(
        (b / "traffic" / "chat-shared.json").read_text())
    (b / "cells" / "throwaway.burst.json").write_text(
        (b / "cells" / "internlm2-1.8b.reasoning.json").read_text())
    (b / "metrics" / "always_one.py").write_text(
        "def read(record):\n    return 1.0\n")
    bench["configs"].append({"name": "throwaway", "source": "x",
                             "file": "bench/configs/throwaway.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway.burst",
                               "config": "throwaway", "traffic": "burst",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "always_one", "unit": "rows",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler",
                               "moves": "output_tokens_per_s",
                               "workloads": ["throwaway.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.resolve("throwaway.burst", root=tmp_path)
    assert cell.config_name == "throwaway"
    assert [m["name"] for m in cell.per_layer][-1] == "always_one"
    assert cell.metric_reader("always_one").read({}) == 1.0
    assert "always_one" not in {
        m["name"] for m in spec.resolve(WORKLOADS[0], tmp_path).per_layer}


def _run(args, cwd, env):
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_means_no_result(monkeypatch):
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(spec.ROOT)}
    r = _run(["--workload", WORKLOADS[0], "--seed", str(2**33),
              "--seconds", "1", "--trace", "0"], spec.ROOT, env)
    assert r.returncode != 0
    assert "correct" not in r.stdout


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(tmp_path)}
    r = _run(["--workload", WORKLOADS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"], tmp_path, env)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
