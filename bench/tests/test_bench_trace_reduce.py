"""Trace reduction on a small recorded trace: busy union, idle share,
time per program, idle gaps labelled by the host span around them."""
import pytest

from bench import trace_reduce as tr

MS = 1_000_000

# a 100 ms window: two decode ticks (overlapping ops), one prefill, host
# spans around pumps and one wait for an arrival
TRACE = {
    "ops": [("fusion.1", 10 * MS, 5 * MS), ("fusion.2", 12 * MS, 6 * MS),
            ("scatter", 30 * MS, 2 * MS), ("fusion.1", 40 * MS, 5 * MS),
            ("fusion.9", 70 * MS, 20 * MS), ("early", -5 * MS, 7 * MS)],
    "modules": [("jit_tick(1)", 10 * MS, 8 * MS),
                ("jit_tick(1)", 40 * MS, 5 * MS),
                ("jit_pf(2)", 70 * MS, 20 * MS),
                ("jit_scatter", 30 * MS, 2 * MS)],
    "host": [("bench.window", 0, 100 * MS), ("bench.pump", 8 * MS, 30 * MS),
             ("bench.pump", 39 * MS, 10 * MS),
             ("bench.wait", 50 * MS, 19 * MS),
             ("bench.pump", 69 * MS, 25 * MS)],
}


def test_window_of():
    assert tr.window_of(TRACE) == (0, 100 * MS)
    with pytest.raises(ValueError):
        tr.window_of({"host": []})


def test_union_merges_overlaps():
    assert tr.union([("a", 0, 10), ("b", 5, 10), ("c", 20, 5)]) == \
        [(0, 15), (20, 25)]


def test_reduce_busy_idle_programs_and_gaps():
    out = tr.reduce(TRACE, 0, 100 * MS)
    # busy: [0,2) clipped early op, [10,18), [30,32), [40,45), [70,90)
    assert out["busy_s"] == pytest.approx((2 + 8 + 2 + 5 + 20) * 1e-3)
    assert out["window_s"] == pytest.approx(0.1)
    assert out["programs"]["jit_tick(1)"] == pytest.approx(13e-3)
    assert out["programs"]["jit_pf(2)"] == pytest.approx(20e-3)
    names = [n for n, _ in out["device_ops"]]
    assert names[0] == "fusion.9"
    gaps = out["idle_gaps"]
    # the longest gap [45, 70) is covered by pump then wait; its middle
    # (57.5 ms) sits in the wait
    assert gaps[0] == ["wait", pytest.approx(25e-3)]
    assert gaps[1] == ["pump", pytest.approx(12e-3)]     # [18, 30)
    assert sum(s for _, s in gaps) == pytest.approx(0.1 - out["busy_s"])


def test_reduce_keeps_top_entries_only():
    out = tr.reduce(TRACE, 0, 100 * MS, top=2)
    assert len(out["device_ops"]) == 2 and len(out["idle_gaps"]) == 2


# a decode tick (a container op, ops in no model scope) and a prefill
# beside it, each op with its head, and the name paths the programs'
# HLO gives the heads
TICK = "jit(tick)/while/body/closed_call/"
SCOPED = {
    "ops": [("%while.3", 10 * MS, 30 * MS, "%while.3 = (s32[])"),
            ("%fusion.1", 10 * MS, 4 * MS, "%fusion.1 = bf16[8]"),
            ("%fusion.2", 14 * MS, 3 * MS, "%fusion.2 = bf16[8,2]"),
            ("%fusion.3", 17 * MS, 1 * MS, "%fusion.3 = f32[8]"),
            ("%fusion.4", 18 * MS, 6 * MS, "%fusion.4 = bf16[8]"),
            ("%copy.5", 24 * MS, 5 * MS, "%copy.5 = bf16[9]"),
            ("%copy.6", 29 * MS, 2 * MS, "%copy.6 = bf16[9]"),
            ("%fusion.7", 31 * MS, 2 * MS, "%fusion.7 = bf16[8]"),
            ("%fusion.1", 50 * MS, 8 * MS, "%fusion.1 = bf16[512]"),
            ("%fusion.9", 58 * MS, 2 * MS, "%fusion.9 = bf16[512]"),
            ("%fusion.1", 90 * MS, 1 * MS, "%fusion.1 = bf16[8]")],
    "modules": [("jit_tick(1)", 10 * MS, 30 * MS),
                ("jit_pf(2)", 50 * MS, 10 * MS)],
    "host": [("bench.window", 0, 100 * MS)],
}
HLO = {"jit_tick": {
    "%while.3 = (s32[])": TICK[:-1] + "/while",
    "%fusion.1 = bf16[8]": TICK + "paged_gather/gather",
    "%fusion.2 = bf16[8,2]": TICK + "attention/gqa_grouped/bkgd,bskd->bkgs/dot",
    "%fusion.3 = f32[8]": "jit(tick)/jit(_decode)/while/body/attention/"
                          "gqa_grouped/jit(_softmax)/exp",
    "%fusion.4 = bf16[8]": TICK + "ffn/dot_general",
    "%copy.5 = bf16[9]": "jit(tick)/while/body/dynamic_slice",
    "%fusion.7 = bf16[8]": "squeeze;jit(tick)/lm_head/bsd,kdv->bksv/dot"},
    "jit_pf": {"%fusion.1 = bf16[512]": "jit(pf)/while/body/ffn/dot",
               "%fusion.9 = bf16[512]": "jit(pf)/while/body/qkv/mul"}}


@pytest.mark.parametrize("path,scope", [
    (TICK + "attention/gqa_grouped/dot_general", "attention/gqa_grouped"),
    ("jit(tick)/jit(main)/while/cond/lt", tr.UNSCOPED),
    ("jit(pf)/branch_1/closed_call/kv_write/scatter", "kv_write"),
    ("jit(f)/vmap(jit(_gumbel))/sample/mul", "sample"),
    ("jit(tick)/vmap(vmap())/scatter", tr.UNSCOPED),
    (TICK + "ffn/bsf,fd->bsd/dot_general", "ffn"),
    ("squeeze;jit(pf)/lm_head/squeeze;jit(pf)/lm_head/bsd,kdv->bksv/dot",
     "lm_head"),
    ("sample/jit(sample_pallas)/pallas_call", "sample"),
    ("add", tr.UNSCOPED), ("", tr.UNSCOPED)])
def test_scope_of_strips_wrappers_and_the_primitive(path, scope):
    assert tr.scope_of(path) == scope


def test_scopes_per_program_sum_to_its_op_time():
    out = tr.reduce(SCOPED, 0, 100 * MS, hlo=HLO)
    tick = out["scopes"]["jit_tick"]
    assert tick == pytest.approx({
        "paged_gather": 4e-3, "attention/gqa_grouped": 4e-3, "ffn": 6e-3,
        "lm_head": 2e-3, tr.UNSCOPED: 7e-3})
    # the container holds the others and is not counted
    ops = {n: t for n, t in tr.per_name(tr.clip(SCOPED["ops"], 10 * MS,
                                                 40 * MS)).items()
           if not n.startswith(tr.CONTAINERS)}
    assert sum(tick.values()) == pytest.approx(sum(ops.values()))
    # the prefill's %fusion.1 is its own program's, not the tick's
    assert out["scopes"]["jit_pf"] == pytest.approx({"ffn": 8e-3,
                                                     "qkv": 2e-3})
    # an op that ran outside every program has a bucket of its own
    assert out["scopes"]["(none)"] == pytest.approx({tr.UNSCOPED: 1e-3})
    total = sum(t for v in out["scopes"].values() for t in v.values())
    assert total == pytest.approx(sum(t for _, t in out["device_ops"]))
    # the costliest scopes, named program:scope
    assert out["top_scopes"][0] == ["jit_pf:ffn", pytest.approx(8e-3)]
    assert out["top_scopes"][1] == [f"jit_tick:{tr.UNSCOPED}",
                                    pytest.approx(7e-3)]


def test_scopes_are_cut_to_the_window():
    out = tr.reduce(SCOPED, 12 * MS, 16 * MS, top=1, hlo=HLO)
    assert out["scopes"] == {"jit_tick": pytest.approx(
        {"paged_gather": 2e-3, "attention/gqa_grouped": 2e-3})}
    assert len(out["top_scopes"]) == 1


def test_without_the_programs_hlo_every_op_is_unscoped():
    """Ops of a program whose HLO is not known (or events without a
    head) are counted, all in one bucket per program."""
    out = tr.reduce(SCOPED, 0, 100 * MS)
    assert set(out["scopes"]["jit_tick"]) == {tr.UNSCOPED}
    assert tr.reduce(TRACE, 0, 100 * MS)["scopes"]["jit_tick"] == \
        pytest.approx({tr.UNSCOPED: 16e-3})


@pytest.mark.parametrize("text,want", [
    ("%fusion.2 = bf16[16,2048]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[92544,"
     "2048]{1,0:T(8,128)(2,1)} %bitcast), kind=kCustom, calls=%f.2",
     "%fusion.2 = bf16[16,2048]{1,0:T(8,128)(2,1)S(1)}"),
    ("%copy-start.12 = (s32[16,1]{0,1:T(1,128)}, u32[]{:S(2)}) copy-start("
     "s32[16,1]{0,1:T(1,128)S(1)} %gte.591)",
     "%copy-start.12 = (s32[16,1]{0,1:T(1,128)}, u32[]{:S(2)})"),
    ('  ROOT fusion.7 = f32[8]{0} fusion(p), kind=kLoop, metadata={op_name='
     '"jit(f)/ffn/add"}', "%fusion.7 = f32[8]{0}"),
    ("%while.3", "%while.3")])
def test_head_is_name_and_type(text, want):
    assert tr.head(text) == want


def test_hlo_paths_leave_out_what_two_variants_disagree_on():
    greedy = ('  %fusion.1 = f32[2]{0} fusion(x), metadata={op_name='
              '"jit(tick)/while/body/ffn/add"}\n'
              '  %fusion.2 = f32[4]{0} fusion(y), metadata={op_name='
              '"jit(tick)/sample/argmax"}\n  %p = f32[2]{0} parameter(0)\n'
              '  %copy.3 = f32[2]{0} copy(p), metadata={op_name='
              '"jit(tick)/ffn/copy"}')
    sampled = ('  ROOT %fusion.2 = f32[9]{0} fusion(y), metadata={op_name='
               '"jit(tick)/while/body/attention/dot"}\n'
               '  %copy.3 = f32[2]{0} copy(q), metadata={op_name='
               '"jit(tick)/qkv/copy"}')
    paths = tr.hlo_paths([greedy, sampled])
    assert paths == {
        "%fusion.1 = f32[2]{0}": "jit(tick)/while/body/ffn/add",
        # one name, two ops of different types: each known by its head
        "%fusion.2 = f32[4]{0}": "jit(tick)/sample/argmax",
        "%fusion.2 = f32[9]{0}": "jit(tick)/while/body/attention/dot"}
    # %copy.3 has one head and two paths, %p no metadata: neither is known


def test_programs_give_each_ops_scope_from_the_compiled_hlo():
    """On the CPU: the executables JAX makes while ``Programs`` runs are
    kept, and their optimized HLO names each op's scope."""
    import jax
    import jax.numpy as jnp

    from bench import harness

    @jax.named_scope("ffn")
    def ffn(x):
        return jnp.tanh(x @ x.T)

    @jax.jit
    def probe_tick(x):
        with jax.named_scope("attention"):
            x = jax.nn.softmax(x, axis=-1)
        return ffn(x).sum()
    programs = harness.Programs()
    programs.start()
    try:
        probe_tick(jnp.ones((8, 16))).block_until_ready()
    finally:
        programs.stop()
    texts = programs.hlo_texts("jit_probe_tick")
    assert len(texts) == 1
    scopes = {tr.scope_of(p) for p in tr.hlo_paths(texts).values()}
    assert {"attention", "ffn"} <= scopes
    # stopped: what JAX makes now is not kept
    made = len(programs.made)
    jax.jit(lambda x: x + 1)(jnp.ones(3)).block_until_ready()
    assert len(programs.made) == made
