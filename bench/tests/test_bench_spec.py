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


#: a reference that is new files only: dense GQA, but with its own counts
#: (a doubled FFN) and a reader of one of its program's scopes
DOUBLED_FFN = """
from pathlib import Path
from bench import roofline, spec
_dense = spec.load_module(Path(__file__).with_name("dense_gqa.py"))
dims, make_params, logits = _dense.dims, _dense.make_params, _dense.logits


def _wide(dm):
    return dict(dm, ff=2 * dm["ff"])


def count_decode_tick(dm, pump, events):
    return roofline.decode_tick(_wide(dm), pump.rows, pump.context)


def count_prefill(dm, pump, events):
    return [roofline.prefill(_wide(dm), [seg]) for seg in pump.segments]


def count_decode_attention(dm, pump, events):
    return tuple(2 * x for x in
                 roofline.decode_attention(dm, pump.rows, pump.context))
"""
FFN_SHARE = """
def read(record):
    scopes = record["device"]["scopes"].get("jit_tick", {})
    return 100.0 * scopes.get("ffn", 0.0) / sum(scopes.values()) \\
        if scopes else None
"""


def test_a_new_cell_is_new_files_only(tmp_path):
    """A configuration, mix, cell and metric added in another root, with
    nothing under the existing bench/ edited; a second configuration
    whose reference brings its own counts is priced by them in the
    standing readers, and a new reader reads one of its scopes."""
    from bench import roofline
    from bench.harness import Pump
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "internlm2-1.8b.json").read_text())
    cfg["name"] = "throwaway"
    (b / "configs" / "throwaway.json").write_text(json.dumps(cfg))
    (b / "configs" / "wide.json").write_text(
        json.dumps(dict(cfg, name="wide", reference="doubled_ffn")))
    (b / "references" / "doubled_ffn.py").write_text(DOUBLED_FFN)
    (b / "metrics" / "ffn_share.py").write_text(FFN_SHARE)
    (b / "cells" / "wide.burst.json").write_text(
        (b / "cells" / "internlm2-1.8b.reasoning.json").read_text())
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
    bench["configs"].append({"name": "wide", "source": "x",
                             "file": "bench/configs/wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "wide.burst", "config": "wide",
                               "traffic": "burst", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "ffn_share", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "kernels", "moves": "itl_p95_ms",
                               "workloads": ["wide.burst"]})
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
    # the wide configuration's counts price its decode ticks, the dense
    # one's the same pumps; everything else in the record is the same
    pumps = [Pump(0.0, 0.05, 16, 13_000, True, [], 0, 0, 0),
             Pump(0.05, 0.3, 0, 0, False, [(1500, 0)], 1, 0, 0)]
    device = {"programs": {"jit_tick": 0.05, "jit_pf": 0.3},
              "scopes": {"jit_tick": {"paged_gather": 0.01, "ffn": 0.03,
                                      "attention/gqa_grouped": 0.01}}}
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    read = {}
    for workload in ("throwaway.burst", "wide.burst"):
        c = spec.resolve(workload, root=tmp_path)
        ref = c.reference()
        dm = ref.dims(c.config)
        rec = {"dims": dm, "peaks": peaks, "pumps": pumps, "device": device,
               "counts": spec.Counts(ref, dm, [])}
        read[workload] = {m["name"]: c.metric_reader(m["name"]).read(rec)
                          for m in c.per_layer
                          if m["name"] in ("decode_mfu", "ffn_share",
                                           "decode_step_roofline",
                                           "decode_attention_roofline")}
    dense, wide = read["throwaway.burst"], read["wide.burst"]
    dm = spec.resolve("wide.burst", root=tmp_path).reference().dims(cfg)
    wide_dm = dict(dm, ff=2 * dm["ff"])
    assert wide["decode_mfu"] / dense["decode_mfu"] == pytest.approx(
        roofline.decode_tick(wide_dm, 16, 13_000)[0] /
        roofline.decode_tick(dm, 16, 13_000)[0], rel=1e-12)
    assert wide["decode_step_roofline"] > dense["decode_step_roofline"]
    assert wide["decode_attention_roofline"] == pytest.approx(
        2 * dense["decode_attention_roofline"], rel=1e-12)
    assert "ffn_share" not in dense and wide["ffn_share"] == \
        pytest.approx(60.0)
    # nothing that was under bench/ changed
    assert all(p.read_bytes() == data for p, data in before.items())


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
