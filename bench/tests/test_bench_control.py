"""The comparison that decides ``correct``, driven through a whole run on
the CPU at a tiny size: sound runs pass, the float8 control fails, and a
run whose served tokens are altered where they are produced fails.

The chip's own look for a TPU is skipped (``run_cell`` is called
directly); everything else is the run the benchmark makes.
"""
import time
from collections import Counter

import jax.numpy as jnp
import pytest

from bench import harness, runner, spec
from bench.tests import tiny_root

PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture(scope="module")
def events():
    ev = Counter()
    harness.count_compiles(ev)
    return ev


def run(tmp_path, events, mix, seed, **kw):
    name = tiny_root.make(tmp_path, mix)
    cell = spec.resolve(name, tmp_path)
    return runner.run_cell(cell, seed, 2.0, trace=False, peaks=PEAKS,
                           events=events, t_start=time.perf_counter(),
                           **kw)


@pytest.mark.parametrize("mix", ["chat-shared", "reasoning"])
def test_sound_run_passes_and_control_fails(tmp_path, events, mix):
    out = run(tmp_path, events, mix, 2**33 + 17, control=True)
    check = out["extra"]["check"]
    limit = out["checks"]["worst_gap_sigma"]["limit"]
    assert check["tokens"] >= 8
    assert out["correct"], check
    assert check["worst_gap_sigma"] <= limit
    # the float8 reference in the program's place reads past the limit
    assert check["control_gap_sigma"] > limit, check
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(out)[-2:] == ["checks", "extra"]


def test_altered_token_fails(tmp_path, events, monkeypatch):
    """A decode tick whose emitted token is changed where it is made."""
    from repro.runtime import engine
    tick = engine.InferenceEngine.decode_step_batch

    def altered(self, state):
        st = tick(self, state)
        rows = jnp.arange(st.emitted.shape[0])
        at = jnp.maximum(st.counts - 1, 0)
        bad = (st.emitted[rows, at] + 1) % self.cfg.vocab_size
        return engine.replace(st, emitted=st.emitted.at[rows, at].set(bad))
    monkeypatch.setattr(engine.InferenceEngine, "decode_step_batch",
                        altered)
    out = run(tmp_path, events, "chat-shared", 23)
    assert out["extra"]["check"]["tokens"] >= 8
    assert not out["correct"], out["extra"]["check"]


def test_stalled_step_fails(tmp_path, events, monkeypatch):
    """A decode tick that returns its state unchanged: nothing finishes,
    nothing is compared, and the run is not correct."""
    from repro.runtime import engine
    drive = harness.drive

    def stalled_drive(*args, **kw):
        # the warm-up runs sound; the lead-in and the window stall
        monkeypatch.setattr(engine.InferenceEngine, "decode_step_batch",
                            lambda self, state: state)
        return drive(*args, **kw)
    monkeypatch.setattr(harness, "drive", stalled_drive)
    name = tiny_root.make(tmp_path, "code-completion", drain_s=2)
    out = runner.run_cell(spec.resolve(name, tmp_path), 29, 2.0,
                          trace=False, peaks=PEAKS, events=events,
                          t_start=time.perf_counter())
    assert out["extra"]["check"]["tokens"] == 0
    assert not out["correct"]
