"""Distribution tests.

Multi-device behaviour runs in a SUBPROCESS with
--xla_force_host_platform_device_count=8 (the main pytest process must
keep the default single device). The subprocess checks:
 - sharded train_step == single-device train_step numerically,
 - param/state specs divide or replicate every leaf,
 - mesh construction and the dry-run lowering path on a small config.
"""
import os
import subprocess
import sys
import textwrap


SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        AUTO = jax.sharding.AxisType.Auto
        from functools import partial
        from repro.configs import get_smoke_config
        from repro.distributed import plan as dplan
        from repro.distributed.sharding import make_rules, sharding_rules
        from repro.models import ModelRuntime
        from repro.models.io import synthetic_train_batch
        from repro.training import (OptimizerConfig, TrainConfig,
                                    init_state, make_train_step)

        cfg = get_smoke_config("internlm2-1.8b")
        tc = TrainConfig(optimizer=OptimizerConfig(learning_rate=1e-3),
                         compute_dtype="float32", grad_accum=2)
        batch = synthetic_train_batch(cfg, jax.random.key(1), 4, 32)
        state = init_state(cfg, tc, 0)
        step = make_train_step(cfg, tc, ModelRuntime())

        # single device reference
        s_ref, m_ref = jax.jit(step)(state, batch)

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AUTO, AUTO))
        rules = make_rules(mesh)
        with sharding_rules(rules):
            astate = jax.eval_shape(lambda: init_state(cfg, tc, 0))
            s_sh = dplan.to_shardings(rules,
                                      dplan.state_specs(rules, astate))
            b_sh = dplan.to_shardings(
                rules, dplan.batch_specs(
                    rules, jax.eval_shape(lambda: batch)))
            state_p = jax.device_put(state, s_sh)
            batch_p = jax.device_put(batch, b_sh)
            s_new, m = jax.jit(step, in_shardings=(s_sh, b_sh))(
                state_p, batch_p)
        err = abs(float(m["loss"]) - float(m_ref["loss"]))
        assert err < 1e-4, (float(m["loss"]), float(m_ref["loss"]))
        # params agree
        for a, b in zip(jax.tree.leaves(s_new["params"]),
                        jax.tree.leaves(s_ref["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)
        print("SHARDED_OK", float(m["loss"]))
    """)
    out = run_sub(code)
    assert "SHARDED_OK" in out


def test_decode_sharded_matches_single_device():
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        AUTO = jax.sharding.AxisType.Auto
        from repro.configs import get_smoke_config
        from repro.distributed import plan as dplan
        from repro.distributed.sharding import make_rules, sharding_rules
        from repro.models import (ModelRuntime, decode_step, init_params,
                                  prefill)
        from repro.models.io import synthetic_prompts

        cfg = get_smoke_config("qwen3-32b")
        params = init_params(cfg, jax.random.key(0))
        pr = synthetic_prompts(cfg, jax.random.key(2), 4, 16)
        logits, cache = prefill(cfg, params, pr["tokens"], max_len=32,
                                cache_dtype=jnp.float32)
        nxt = jnp.argmax(logits, -1)
        ref, _, _ = decode_step(cfg, params, dict(cache), nxt)

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AUTO, AUTO))
        rules = make_rules(mesh)
        with sharding_rules(rules):
            p_sh = dplan.to_shardings(
                rules, dplan.param_specs(
                    rules, jax.eval_shape(lambda: params)))
            cache_sp, tok_sp = dplan.decode_specs(
                rules, cfg, jax.eval_shape(lambda: cache),
                jax.eval_shape(lambda: nxt))
            c_sh = dplan.to_shardings(rules, cache_sp)
            t_sh = dplan.to_shardings(rules, tok_sp)
            params_p = jax.device_put(params, p_sh)
            cache_p = jax.device_put(cache, c_sh)
            nxt_p = jax.device_put(nxt, t_sh)
            got, _, _ = jax.jit(
                lambda p, c, t: decode_step(cfg, p, c, t),
                in_shardings=(p_sh, c_sh, t_sh))(params_p, cache_p, nxt_p)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        print("DECODE_SHARDED_OK")
    """)
    out = run_sub(code)
    assert "DECODE_SHARDED_OK" in out


def test_production_mesh_shapes():
    code = textwrap.dedent("""
        import jax
        # 8 host devices: validate the mesh helper with a debug mesh and
        # the production constructor's axis naming on a sliced config
        from repro.launch.mesh import make_debug_mesh
        m = make_debug_mesh(2, 4)
        assert m.shape == {"data": 2, "model": 4}
        print("MESH_OK")
    """)
    assert "MESH_OK" in run_sub(code)


def test_elastic_reshard_across_mesh_sizes():
    """Elastic scaling: train on a (2,2) mesh, checkpoint, reload onto a
    (2,4) mesh with new shardings, and verify the resharded step matches
    a continuation on the original mesh (loss parity)."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        AUTO = jax.sharding.AxisType.Auto
        from functools import partial
        from repro.configs import get_smoke_config
        from repro.distributed import plan as dplan
        from repro.distributed.sharding import make_rules, sharding_rules
        from repro.models import ModelRuntime
        from repro.models.io import synthetic_train_batch
        from repro.runtime import checkpoint as ckpt
        from repro.training import (OptimizerConfig, TrainConfig,
                                    init_state, make_train_step)

        cfg = get_smoke_config("internlm2-1.8b")
        tc = TrainConfig(optimizer=OptimizerConfig(learning_rate=1e-3),
                         compute_dtype="float32")
        step = make_train_step(cfg, tc, ModelRuntime())
        batch = synthetic_train_batch(cfg, jax.random.key(1), 4, 32)

        def run_on(mesh_shape, state_tree, n):
            mesh = jax.make_mesh(mesh_shape, ("data", "model"),
                                 axis_types=(AUTO, AUTO))
            rules = make_rules(mesh)
            with sharding_rules(rules):
                astate = jax.eval_shape(lambda: init_state(cfg, tc, 0))
                s_sh = dplan.to_shardings(
                    rules, dplan.state_specs(rules, astate))
                b_sh = dplan.to_shardings(
                    rules, dplan.batch_specs(
                        rules, jax.eval_shape(lambda: batch)))
                state_p = jax.device_put(state_tree, s_sh)
                batch_p = jax.device_put(batch, b_sh)
                fn = jax.jit(step, in_shardings=(s_sh, b_sh))
                m = None
                for _ in range(n):
                    state_p, m = fn(state_p, batch_p)
                return state_p, m

        state = init_state(cfg, tc, 0)
        state, _ = run_on((2, 2), state, 2)
        d = tempfile.mkdtemp()
        ckpt.save(d, 2, jax.tree.map(lambda x: np.asarray(x), state))

        # continuation on the SAME mesh (reference)
        _, m_ref = run_on((2, 2), state, 1)
        # elastic: reload and continue on a LARGER mesh
        _, loaded, _ = ckpt.load_latest(d)
        loaded = jax.tree.map(
            lambda r, l: jnp.asarray(l, r.dtype), state, loaded)
        _, m_new = run_on((2, 4), loaded, 1)
        err = abs(float(m_ref["loss"]) - float(m_new["loss"]))
        assert err < 1e-4, (float(m_ref["loss"]), float(m_new["loss"]))
        print("ELASTIC_OK", float(m_new["loss"]))
    """)
    out = run_sub(code)
    assert "ELASTIC_OK" in out


def test_param_specs_always_divide():
    code = textwrap.dedent("""
        import jax
        AUTO = jax.sharding.AxisType.Auto
        from functools import partial
        from repro.configs import ARCH_IDS, get_config
        from repro.distributed import plan as dplan
        from repro.distributed.sharding import make_rules
        from repro.models.transformer import init_params

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AUTO, AUTO))
        rules = make_rules(mesh)
        sizes = dict(mesh.shape)
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            ap = jax.eval_shape(partial(init_params, cfg,
                                        jax.random.key(0), "bfloat16"))
            specs = dplan.param_specs(rules, ap)
            flat_a = jax.tree_util.tree_leaves_with_path(ap)
            flat_s = jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec))
            for (path, leaf), spec in zip(flat_a, flat_s):
                for dim, part in zip(leaf.shape, spec):
                    if part is None:
                        continue
                    n = 1
                    for ax in (part if isinstance(part, tuple)
                               else (part,)):
                        n *= sizes[ax]
                    assert dim % n == 0, (arch, path, leaf.shape, spec)
        print("SPECS_OK")
    """)
    assert "SPECS_OK" in run_sub(code)
