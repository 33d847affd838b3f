"""A throwaway benchmark root with a tiny configuration, for CPU tests.

``make(tmp, ...)`` writes ``BENCHMARK.json`` and the files of one cell
(``tiny.<mix>``) under ``tmp``, next to copies of the real metric
readers and references, so the whole run path can be driven on the CPU
at a size a test run holds.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, intermediate_size=128,
            vocab_size=256)


def tiny_config(name: str, **over) -> dict:
    """The real configuration file ``name`` at smoke widths."""
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg.update(over)
    return cfg


def make(tmp: Path, mix_name: str = "chat-shared",
         config: str = "internlm2-1.8b", **cell_over) -> str:
    """Write a tiny cell for ``mix_name``; returns its workload name."""
    bench = tmp / "bench"
    for d in ("metrics", "references"):
        shutil.copytree(ROOT / "bench" / d, bench / d)
    for d in ("configs", "cells", "traffic"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    with open(ROOT / "bench" / "traffic" / f"{mix_name}.json") as f:
        mix = json.load(f)
    mix["levels"] = 4
    if mix.get("shared_prefixes"):
        mix["shared_prefixes"]["length"] = 32
    mix["prompt"].update(min=4, max=40, median=10)
    mix["output"].update(min=8, max=24, median=12)
    (bench / "traffic" / "tinymix.json").write_text(json.dumps(mix))
    (bench / "configs" / "tiny.json").write_text(
        json.dumps(tiny_config(config)))
    cell = {"rate": 10.0, "clients": 3, "lead_in_s": 1, "drain_s": 20,
            "trace_s": 1,
            "serving": {"max_slots": 4, "cap_new": 64,
                        "seq_buckets": [16, 32, 64, 128],
                        "batch_buckets": [1], "num_blocks": 65,
                        "block_size": 16,
                        "prefix_cache": bool(mix.get("shared_prefixes"))},
            "pipeline": {"max_batch_size": 1},
            "correctness": {"min_tokens": 48, "max_requests": 4,
                            "worst_gap_sigma": 0.05}}
    cell.update(cell_over)
    name = "tiny." + mix_name
    (bench / "cells" / f"{name}.json").write_text(json.dumps(cell))
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "tiny", "source": "smoke widths",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": name, "config": "tiny",
                          "traffic": "tinymix", "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return name
