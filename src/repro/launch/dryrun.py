import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count at first initialization. Do not move or reorder.

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from functools import partial  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (ARCH_IDS, SHAPES, get_config,   # noqa: E402
                           shapes_for)
from repro.configs.base import ModelConfig, ShapeConfig    # noqa: E402
from repro.distributed import plan as dplan                # noqa: E402
from repro.distributed.sharding import make_rules, sharding_rules  # noqa: E402,E501
from repro.launch.hlo_analysis import analyze as hlo_analyze  # noqa: E402
from repro.launch.mesh import make_production_mesh         # noqa: E402
from repro.models import (ModelRuntime, decode_step,       # noqa: E402
                          prefill)
from repro.models import io as mio                         # noqa: E402
from repro.models.transformer import init_params           # noqa: E402
from repro.training import (OptimizerConfig, TrainConfig,  # noqa: E402
                            init_state, make_train_step)

# -----------------------------------------------------------------------
# Per-arch training knobs (memory-driven; see EXPERIMENTS.md §Dry-run).
# grad_accum splits the 256-sequence global batch into microbatches;
# seq_shard shards the residual-stream carry over 'model' (Megatron SP).
# -----------------------------------------------------------------------
TRAIN_TUNING: Dict[str, Dict[str, Any]] = {
    # kv_dh_shard off: at tp=16 the 405B weights can't go
    # weight-stationary, and dh-sharded caches + per-layer FSDP gathers
    # blow the decode working set; sequence-sharded caches are better
    # in this regime (real deployments serve 405B at tp>=64).
    "llama3-405b": dict(grad_accum=16, seq_shard=True,
                        optimizer="adafactor", grad_dtype="bfloat16",
                        param_dtype="bfloat16", kv_dh_shard=False),
    "qwen3-32b": dict(grad_accum=16, seq_shard=False,
                      optimizer="adafactor"),
    "starcoder2-15b": dict(grad_accum=8, seq_shard=True,
                           optimizer="adafactor"),
    "phi3.5-moe-42b-a6.6b": dict(grad_accum=8, seq_shard=False,
                                 optimizer="adafactor"),
    # uneven: GSPMD-padded activation sharding for the 28-head attention
    # (28 % 16 != 0 would otherwise replicate scores; §Perf: 11.6x)
    "qwen2-vl-7b": dict(grad_accum=8, seq_shard=False, optimizer="adamw",
                        uneven=True),
    "falcon-mamba-7b": dict(grad_accum=8, seq_shard=False,
                            optimizer="adamw"),
    "olmoe-1b-7b": dict(grad_accum=2, seq_shard=False, optimizer="adamw"),
    "internlm2-1.8b": dict(grad_accum=2, seq_shard=False,
                           optimizer="adamw"),
    # mamba_ssd: SSD block-matmul form of Mamba-2 (§Perf cell D: 9.4x on
    # the dominant memory term vs the associative scan)
    "zamba2-1.2b": dict(grad_accum=4, seq_shard=False, optimizer="adamw",
                        mamba_ssd=True),
    "musicgen-large": dict(grad_accum=4, seq_shard=False,
                           optimizer="adamw"),
}

# v5e constants for the roofline report
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

COLLECTIVE_RE = re.compile(
    r"=\s*(?P<result>[^=]*?)\s*\b(?P<op>all-reduce|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute)"
    r"(?P<start>-start)?\(")
SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    nb = _DTYPE_BYTES.get(dtype)
    if nb is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * nb


def collective_stats(hlo_text: str) -> Dict[str, Any]:
    """Per-device collective *operand* bytes by type, parsed from the
    post-SPMD optimized HLO. Operand shapes are elided in the printed
    text, so we derive them from the result shape + replica-group size:
      all-reduce / all-to-all / collective-permute : operand == result
      all-gather   : operand == result / group
      reduce-scatter : operand == result * group
    Async '-done' ops are skipped (their '-start' is already counted)."""
    by_type: Dict[str, int] = {}
    counts: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m:
            continue
        op = m.group("op")
        shapes = SHAPE_RE.findall(m.group("result"))
        if not shapes:
            continue
        # async '-start' results are tuples (operand, result): take last
        result_bytes = _shape_bytes(*shapes[-1])
        group = 1
        g = GROUPS_RE.search(line)
        if g:
            group = int(g.group(2))
        else:
            g2 = GROUPS_BRACE_RE.search(line)
            if g2:
                group = len([x for x in g2.group(1).split(",") if
                             x.strip() != ""])
        if op == "all-gather":
            nbytes = result_bytes // max(group, 1)
        elif op == "reduce-scatter":
            nbytes = result_bytes * max(group, 1)
        else:
            nbytes = result_bytes
        by_type[op] = by_type.get(op, 0) + nbytes
        counts[op] = counts.get(op, 0) + 1
    return {"bytes_by_type": by_type, "counts": counts,
            "total_bytes": sum(by_type.values())}


def _optimizer_for(arch: str, overrides=None) -> OptimizerConfig:
    tun = dict(TRAIN_TUNING.get(arch, {}))
    tun.update(overrides or {})
    return OptimizerConfig(name=tun.get("optimizer", "adamw"))


def _runtime_for(cfg: ModelConfig, shape: ShapeConfig, arch: str,
                 overrides: Optional[Dict[str, Any]] = None) -> ModelRuntime:
    tun = dict(TRAIN_TUNING.get(arch, {}))
    tun.update(overrides or {})
    seq_shard = bool(tun.get("seq_shard", False)) and shape.kind == "train"
    if shape.kind == "train":
        default_attn = "chunked_train" if shape.seq_len >= 2048 else "naive"
    else:
        default_attn = "chunked" if shape.seq_len >= 2048 else "naive"
    # §Perf iteration: larger attention tiles amortize KV re-reads
    # (q_block 512->1024 / kv_block 1024->4096: 3.5x memory-term
    # reduction on 32k prefill)
    default_qb = 1024 if shape.kind != "train" else 512
    default_kb = 4096 if shape.kind != "train" else 1024
    return ModelRuntime(
        attn_impl=str(tun.get("attn_impl", default_attn)),
        q_block=int(tun.get("q_block", default_qb)),
        kv_block=int(tun.get("kv_block", default_kb)),
        remat=str(tun.get("remat",
                          "full" if shape.kind == "train" else "none")),
        seq_shard=seq_shard,
        unroll_decode=bool(tun.get("unroll_decode",
                                   shape.kind == "decode")))


def build_cell(arch: str, shape_name: str, mesh,
               overrides: Optional[Dict[str, Any]] = None):
    """Returns (lower_fn,) — a thunk that lowers + compiles the cell and
    returns the record dict."""
    import dataclasses
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    tun = dict(TRAIN_TUNING.get(arch, {}))
    tun.update(overrides or {})
    if tun.get("mamba_ssd") and cfg.ssm and cfg.ssm.variant == "mamba2":
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, ssd_matmul=True))
    rule_overrides = {}
    if "kv_dh_shard" in tun:
        rule_overrides["kv_dh_shard"] = bool(tun["kv_dh_shard"])
    if tun.get("ep_cap_data"):
        rule_overrides["exp_cap"] = "data"
    rules = make_rules(mesh, overrides=rule_overrides or None,
                       uneven=bool(tun.get("uneven", False)))
    rt = _runtime_for(cfg, shape, arch, overrides)

    with sharding_rules(rules):
        if shape.kind == "train":
            # keep >= 1 sequence per data shard per microbatch: more DP
            # ways (the pod axis) means fewer accumulation steps
            dp_ways = 1
            for ax in ("pod", "data"):
                dp_ways *= mesh.shape.get(ax, 1)
            ga = int(tun.get("grad_accum", 1))
            ga = max(1, min(ga, shape.global_batch // dp_ways))
            tc = TrainConfig(
                optimizer=_optimizer_for(arch, overrides),
                grad_accum=ga,
                param_dtype=str(tun.get("param_dtype", "float32")),
                compute_dtype="bfloat16",
                grad_dtype=str(tun.get("grad_dtype", "float32")))
            abstract_state = jax.eval_shape(
                partial(init_state, cfg, tc, 0))
            state_sh = dplan.to_shardings(
                rules, dplan.state_specs(rules, abstract_state))
            batch_abs = mio.train_input_specs(cfg, shape)
            batch_sh = dplan.to_shardings(
                rules, dplan.batch_specs(rules, batch_abs))
            step = make_train_step(cfg, tc, rt)
            repl = NamedSharding(mesh, P())
            metrics_sh = {"loss": repl, "aux_loss": repl,
                          "perplexity": repl, "grad_norm": repl}
            jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, metrics_sh),
                             donate_argnums=0)
            args = (abstract_state, batch_abs)
        elif shape.kind == "prefill":
            specs = mio.prefill_input_specs(cfg, shape)
            in_sh = dplan.to_shardings(
                rules, dplan.batch_specs(rules, specs))
            abstract_params = jax.eval_shape(
                partial(init_params, cfg, jax.random.key(0), "bfloat16"))
            p_sh = dplan.to_shardings(
                rules, dplan.param_specs(rules, abstract_params))

            def pf(params, batch):
                return prefill(
                    cfg, params, batch["tokens"], max_len=shape.seq_len,
                    rt=rt, embeds_override=batch.get("embeds_override"))

            cache_abs = jax.eval_shape(
                partial(mio.transformer.make_cache, cfg,
                        shape.global_batch, shape.seq_len))
            cache_sp, _ = dplan.decode_specs(
                rules, cfg, cache_abs,
                jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32))
            cache_sh = dplan.to_shardings(rules, cache_sp)
            logits_sh = NamedSharding(
                mesh, dplan._fit(
                    rules,
                    (shape.global_batch, cfg.vocab_size)
                    if not cfg.num_codebooks else
                    (shape.global_batch, cfg.num_codebooks, cfg.vocab_size),
                    "batch", *((None,) if not cfg.num_codebooks
                               else (None, None))))
            jitted = jax.jit(pf, in_shardings=(p_sh, in_sh),
                             out_shardings=(logits_sh, cache_sh))
            args = (abstract_params, specs)
        else:  # decode
            specs = mio.decode_input_specs(cfg, shape)
            abstract_params = jax.eval_shape(
                partial(init_params, cfg, jax.random.key(0), "bfloat16"))
            # weight-stationary decode: FSDP gathers per token would cost
            # a full param pass each step; replicate over 'data' instead
            # (params already TP-sharded over 'model') — but only when the
            # TP-sharded weights actually fit (~<6GB/device). 405B-class
            # models keep FSDP sharding and eat the per-step gathers.
            tp = mesh.shape.get("model", 1)
            ws_bytes = cfg.param_count() * 2 / tp
            if ws_bytes < 6e9:
                decode_rules = make_rules(
                    mesh,
                    overrides={**(rule_overrides or {}), "fsdp": None},
                    uneven=rules.uneven)
            else:
                decode_rules = rules
            p_sh = dplan.to_shardings(
                decode_rules, dplan.param_specs(decode_rules,
                                                abstract_params))
            cache_sp, tok_sp = dplan.decode_specs(
                rules, cfg, specs["cache"], specs["tokens_t"])
            cache_sh = dplan.to_shardings(rules, cache_sp)
            tok_sh = dplan.to_shardings(rules, tok_sp)
            logits_shape = (shape.global_batch, cfg.vocab_size) \
                if not cfg.num_codebooks else \
                (shape.global_batch, cfg.num_codebooks, cfg.vocab_size)
            logits_sh = NamedSharding(
                mesh, dplan._fit(rules, logits_shape, "batch",
                                 *([None] * (len(logits_shape) - 1))))

            def serve_step(params, cache, tokens_t):
                return decode_step(cfg, params, cache, tokens_t, rt=rt)[:2]

            jitted = jax.jit(serve_step,
                             in_shardings=(p_sh, cache_sh, tok_sh),
                             out_shardings=(logits_sh, cache_sh),
                             donate_argnums=1)
            args = (abstract_params, specs["cache"], specs["tokens_t"])

    def run(hlo_path: Optional[str] = None) -> Dict[str, Any]:
        # tracing happens inside .lower(): the logical-axis rules context
        # must be active HERE, not just at jit-construction time.
        with sharding_rules(rules):
            t0 = time.time()
            lowered = jitted.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            ma = compiled.memory_analysis()
            ca = compiled.cost_analysis() or {}
            hlo_text = compiled.as_text()
            if hlo_path:
                import zstandard
                with open(hlo_path, "wb") as f:
                    f.write(zstandard.ZstdCompressor(level=6).compress(
                        hlo_text.encode()))
            prof = hlo_analyze(hlo_text)
            colls = {
                "bytes_by_type": prof.collective_by_type,
                "counts": prof.collective_counts,
                "total_bytes": prof.collective_bytes,
            }
            chips = int(np.prod(list(mesh.shape.values())))
            # loop-aware static profile (XLA's cost_analysis counts while
            # bodies once; see hlo_analysis.py) — raw values kept below.
            flops_dev = float(prof.flops)
            bytes_dev = float(prof.hbm_bytes)
            coll_dev = float(prof.collective_bytes)
            n = cfg.param_count()
            n_active = cfg.active_param_count()
            if shape.kind == "train":
                model_flops = 6.0 * n_active * shape.global_batch * \
                    shape.seq_len
            elif shape.kind == "prefill":
                model_flops = 2.0 * n_active * shape.global_batch * \
                    shape.seq_len
            else:
                model_flops = 2.0 * n_active * shape.global_batch
            t_comp = flops_dev / PEAK_FLOPS
            t_mem = bytes_dev / HBM_BW
            t_coll = coll_dev / ICI_BW
            dominant = max((t_comp, "compute"), (t_mem, "memory"),
                           (t_coll, "collective"))[1]
            rec = {
                "arch": arch, "shape": shape_name,
                "mesh": "x".join(f"{k}={v}" for k, v in mesh.shape.items()),
                "chips": chips,
                "params": n, "active_params": n_active,
                "lower_s": round(t1 - t0, 2),
                "compile_s": round(t2 - t1, 2),
                "memory": {
                    "argument_bytes": ma.argument_size_in_bytes,
                    "output_bytes": ma.output_size_in_bytes,
                    "temp_bytes": ma.temp_size_in_bytes,
                    "alias_bytes": ma.alias_size_in_bytes,
                    "peak_bytes": getattr(ma, "peak_memory_in_bytes", 0),
                },
                "fits_hbm16": (ma.argument_size_in_bytes +
                               ma.temp_size_in_bytes +
                               ma.output_size_in_bytes -
                               ma.alias_size_in_bytes) < 16e9,
                "cost": {"flops_per_device": flops_dev,
                         "bytes_per_device": bytes_dev,
                         "xla_flops_raw": float(ca.get("flops", 0.0)),
                         "xla_bytes_raw": float(
                             ca.get("bytes accessed", 0.0))},
                "collectives": colls,
                "roofline": {
                    "t_compute_s": t_comp, "t_memory_s": t_mem,
                    "t_collective_s": t_coll, "dominant": dominant,
                    "model_flops": model_flops,
                    "hlo_flops_global": flops_dev * chips,
                    "useful_flops_ratio": (model_flops /
                                           (flops_dev * chips)
                                           if flops_dev else 0.0),
                    "step_time_bound_s": max(t_comp, t_mem, t_coll),
                    "roofline_fraction": (
                        min(1.0, (model_flops / chips / PEAK_FLOPS) /
                            max(t_comp, t_mem, t_coll))
                        if max(t_comp, t_mem, t_coll) > 0 else 0.0),
                },
            }
            return rec

    return run


def cells(archs, shape_names):
    for arch in archs:
        cfg = get_config(arch)
        valid = {s.name for s in shapes_for(cfg)}
        for sn in shape_names:
            if sn in valid:
                yield arch, sn
            else:
                yield arch, sn + ":SKIP"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--overrides", default="",
                    help="JSON dict of tuning overrides (perf iterations)")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shape_names = list(SHAPES) if args.shape == "all" \
        else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    overrides = json.loads(args.overrides) if args.overrides else None

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for multi_pod in meshes:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
        for arch, sn in cells(archs, shape_names):
            if sn.endswith(":SKIP"):
                print(f"SKIP  {arch} {sn.split(':')[0]} {mesh_tag} "
                      f"(long-context needs sub-quadratic attention)")
                continue
            tag = f"{arch}_{sn}_{mesh_tag}_{args.variant}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path) and not args.force:
                print(f"CACHED {tag}")
                continue
            print(f"RUN   {tag} ...", flush=True)
            try:
                rec = build_cell(arch, sn, mesh, overrides)(
                    hlo_path=os.path.join(args.out, tag + ".hlo.zst"))
                rec["variant"] = args.variant
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                r = rec["roofline"]
                print(f"  ok: compile={rec['compile_s']}s "
                      f"mem_temp={rec['memory']['temp_bytes']/1e9:.2f}GB "
                      f"args={rec['memory']['argument_bytes']/1e9:.2f}GB "
                      f"dominant={r['dominant']} "
                      f"frac={r['roofline_fraction']:.3f}", flush=True)
            except Exception as e:   # noqa: BLE001
                failures.append((tag, str(e)))
                print(f"  FAIL {tag}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e[:200])
        raise SystemExit(1)
    print("\nALL CELLS OK")


if __name__ == "__main__":
    main()
