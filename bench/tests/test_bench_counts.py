"""The counts a configuration's reference owns, and the readers that price
a run by them: every reference exports the counts; on one fixed record
per cell the roofline and utilization readers read what
``bench/roofline.py``'s dense counts give when called directly; the new
``decode_attention_roofline`` against a hand count; a count sees the
program's events of its own pump."""
import pytest

from bench import roofline, spec
from bench.harness import Pump

BENCH = spec.load_benchmark()
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
REFERENCES = sorted((spec.BENCH / "references").glob("*.py"))


def reader(name):
    return spec.load_module(spec.BENCH / "metrics" / f"{name}.py")


def pump(t0, dur, rows=0, context=0, decoded=False, segments=()):
    return Pump(t0, t0 + dur, rows, context, decoded, list(segments),
                len(segments), 0, 0)


#: decode ticks of several sizes, lone prefills, and a pump doing both
PUMPS = [pump(0.00, 0.050, 16, 13_000, True),
         pump(0.05, 0.040, 3, 4_100, True),
         pump(0.10, 0.300, 0, 0, False, [(1500, 0)]),
         pump(0.40, 0.200, 0, 0, False, [(700, 16), (2048, 512)]),
         pump(0.60, 0.350, 2, 900, True, [(3000, 0)]),
         pump(0.95, 0.045, 5, 2_600, True)]


def record(workload, pumps=PUMPS, events=()):
    cell = spec.resolve(workload)
    ref = cell.reference()
    dm = ref.dims(cell.config)
    return dm, {"dims": dm, "peaks": PEAKS, "pumps": pumps,
                "counts": spec.Counts(ref, dm, list(events)),
                "device": {"programs": {"jit_tick(3)": 0.61,
                                        "jit_pf(7)": 0.93},
                           "scopes": {"jit_tick": {
                               "paged_gather": 0.11,
                               "attention/gqa_grouped": 0.07,
                               "ffn": 0.2, "(unscoped)": 0.23}}}}


# the parent's readers, with bench/roofline.py's dense counts called
# directly: what reading the counts from the record must not change
def dense_decode_mfu(dm, rec):
    ops = secs = 0.0
    for p in rec["pumps"]:
        if p.decoded and not p.segments:
            ops += roofline.decode_tick(dm, p.rows, p.context)[0]
            secs += p.t1 - p.t0
    return 100.0 * ops / (secs * rec["peaks"]["flops_per_s"])


def dense_prefill_mfu(dm, rec):
    ops = secs = 0.0
    for p in rec["pumps"]:
        if p.segments and not p.decoded:
            ops += roofline.prefill(dm, p.segments)[0]
            secs += p.t1 - p.t0
    return 100.0 * ops / (secs * rec["peaks"]["flops_per_s"])


def dense_decode_step_roofline(dm, rec):
    need = sum(roofline.least_time(*roofline.decode_tick(
        dm, p.rows, p.context), rec["peaks"])
        for p in rec["pumps"] if p.decoded)
    return 100.0 * need / rec["device"]["programs"]["jit_tick(3)"]


def dense_prefill_step_roofline(dm, rec):
    need = sum(roofline.least_time(*roofline.prefill(dm, [seg]),
                                   rec["peaks"])
               for p in rec["pumps"] for seg in p.segments)
    return 100.0 * need / rec["device"]["programs"]["jit_pf(7)"]


DENSE = {"decode_mfu": dense_decode_mfu, "prefill_mfu": dense_prefill_mfu,
         "decode_step_roofline": dense_decode_step_roofline,
         "prefill_step_roofline": dense_prefill_step_roofline}


@pytest.mark.parametrize("path", REFERENCES, ids=lambda p: p.stem)
def test_every_reference_exports_the_counts(path):
    ref = spec.load_module(path)
    for name in spec.COUNTS:
        assert callable(getattr(ref, f"count_{name}", None)), \
            (path.name, name)


def test_a_reference_without_counts_is_refused(tmp_path):
    (tmp_path / "bench" / "references").mkdir(parents=True)
    (tmp_path / "bench" / "references" / "bare.py").write_text(
        (spec.BENCH / "references" / "dense_gqa.py").read_text()
        .replace("def count_decode_attention", "def _gone"))
    cell = spec.resolve(WORKLOADS[0])
    cell.root, cell.config = tmp_path, dict(cell.config, reference="bare")
    with pytest.raises(AttributeError, match="count_decode_attention"):
        cell.reference()


@pytest.mark.parametrize("name", sorted(DENSE))
@pytest.mark.parametrize("workload", WORKLOADS)
def test_readers_read_what_the_dense_counts_give(workload, name):
    dm, rec = record(workload)
    got = reader(name).read(rec)
    assert got is not None and got > 0
    assert got == DENSE[name](dm, rec)


def test_decode_attention_counts_by_hand():
    # d 8, 2 heads of 4, 1 KV head, 2 layers (as test_bench_roofline)
    dm = {"layers": 2, "d": 8, "heads": 2, "kv": 1, "dh": 4, "ff": 16,
          "vocab": 10, "norm": "rmsnorm", "gated": True}
    ops, nbytes = roofline.decode_attention(dm, rows=3, context=20)
    # 4 * (20 + 3) keys * dh 4 * 2 heads * 2 layers
    assert ops == 4 * 23 * 4 * 2 * 2
    # K and V of 23 tokens (2 * 2 layers * 1 head * 4 * 2 B each), and q
    # and the output of 3 rows (2 heads of 4, bf16) in each of 2 layers
    assert nbytes == 23 * 32 + 2 * 3 * 8 * 2 * 2
    # the attention part of the decode tick
    assert ops == roofline.decode_tick(dm, 3, 20)[0] - \
        2 * 3 * (2 * roofline.layer_matmul_params(dm) + 8 * 10)


def test_decode_attention_roofline_by_hand():
    """internlm2-1.8b (24 layers, 16 heads of 128, 8 KV heads): two decode
    ticks, 16 rows over 13,000 tokens and 3 over 4,100, both bound by
    bytes; device time of the attention scopes 0.11 + 0.07 s."""
    workload = next(w for w in WORKLOADS if w.startswith("internlm2"))
    _, rec = record(workload, PUMPS[:2] + PUMPS[2:4])
    kv = 2 * 24 * 8 * 128 * 2                       # 96 KiB a token
    qo = 2 * 24 * 16 * 128 * 2                      # q and out a row
    need = ((13_016 * kv + 16 * qo) + (4_103 * kv + 3 * qo)) / 819e9
    assert 4 * 13_016 * 128 * 16 * 24 / 197e12 < 13_016 * kv / 819e9
    got = reader("decode_attention_roofline").read(rec)
    assert got == pytest.approx(100.0 * need / 0.18, rel=1e-12)
    # the scopes are the ones read: ffn and the unscoped ops are not
    rec["device"]["scopes"]["jit_tick"]["attention/expand_kv"] = 0.18
    assert reader("decode_attention_roofline").read(rec) == \
        pytest.approx(got / 2, rel=1e-12)


@pytest.mark.parametrize("device", [None, {"programs": {}, "scopes": {}}])
def test_decode_attention_roofline_reads_nothing_without_scopes(device):
    _, rec = record(WORKLOADS[0])
    rec["device"] = device
    assert reader("decode_attention_roofline").read(rec) is None


def test_a_count_gets_the_events_of_its_own_pump():
    seen = []

    class Ref:
        @staticmethod
        def count_decode_tick(dm, p, events):
            seen.append([e["name"] for e in events])
            return 1.0, 1.0
    events = [{"name": n, "ts": ts} for n, ts in
              [("c", 0.07), ("a", 0.01), ("b", 0.05), ("d", 0.2)]]
    counts = spec.Counts(Ref, {}, events)
    counts("decode_tick", PUMPS[0])          # [0.00, 0.05)
    counts("decode_tick", PUMPS[1])          # [0.05, 0.09)
    assert seen == [["a"], ["b", "c"]]


def test_a_traced_run_prices_its_pumps_by_the_reference(tmp_path):
    """A whole ``--trace 1`` run at a tiny size on the CPU (the chip's own
    look for a TPU skipped): the record's counts are the reference's, the
    host-clock readers read them, and the breakdown names scopes. The
    CPU's trace has no TPU plane, so the device readers read nothing."""
    import time
    from collections import Counter

    from bench import harness, runner
    from bench.tests import tiny_root
    events = Counter()
    harness.count_compiles(events)
    name = tiny_root.make(tmp_path, "reasoning")
    cell = spec.resolve(name, tmp_path)
    seen = []
    ref = cell.reference()
    count = ref.count_decode_tick

    def counted(dm, p, evs):
        seen.append((p, evs))
        return count(dm, p, evs)
    ref.count_decode_tick = counted
    cell.reference = lambda: ref
    out = runner.run_cell(cell, 2**33 + 5, 2.0, trace=True, peaks=PEAKS,
                          events=events, t_start=time.perf_counter(),
                          trace_dir=tmp_path / "trace")
    metrics = out["metrics"]
    assert metrics["decode_mfu"]["value"] > 0
    assert "decode_attention_roofline" not in metrics
    assert out["breakdown"]["scopes"] == []
    assert out["correct"], out["extra"]["check"]
    # each decode pump was priced with the program's tick event in it
    decoded = [(p, evs) for p, evs in seen if p.decoded]
    assert decoded
    assert all(any(e["name"].endswith("decode") for e in evs)
               for _, evs in decoded)
