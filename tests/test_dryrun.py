"""Dry-run machinery on a forced-8-device CPU mesh (subprocess so the main
pytest process keeps its single real device).

Full production meshes (256/512 devices x full configs) run via
``python -m repro.launch.dryrun --all`` — results in EXPERIMENTS.md.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, timeout=1200) -> str:
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
def test_tiny_mesh_train_lower_compile_smoke_arch():
    """Smoke config x (data=4, model=2) mesh: lower+compile a sharded train
    step, run the analyzer, and execute one real step on the 8 fake devices
    (numerics + shardings actually work, not just compile)."""
    out = run_py("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import registry
        from repro.models import lm
        from repro.optim import adamw
        from repro.sharding import rules
        from repro.train import loop as tl
        from repro.launch import hlo_analysis
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = registry.smoke_config("llama3-8b")
        model = lm.build(cfg)
        ocfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=0)
        fn = tl.make_train_fn(model, ocfg, n_micro=2)
        params = model.init(jax.random.PRNGKey(0))
        state = adamw.init(ocfg, params)
        pshard = rules.params_shardings(params, mesh)
        sshard = tl.state_shardings(ocfg, params, mesh)
        batch = {"tokens": jnp.ones((8, 32), jnp.int32)}
        bshard = rules.batch_shardings(batch, mesh)
        step = jax.jit(fn, in_shardings=(pshard, sshard, bshard),
                       out_shardings=(pshard, sshard, None))
        with mesh:
            lowered = step.lower(
                jax.eval_shape(lambda: params),
                jax.eval_shape(lambda: state),
                jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                             batch))
            compiled = lowered.compile()
            an = hlo_analysis.analyze(compiled.as_text())
            # actually run it
            pp = jax.device_put(params, pshard)
            ss = jax.device_put(state, sshard)
            bb = jax.device_put(batch, bshard)
            p2, s2, m = step(pp, ss, bb)
        print(json.dumps({
            "flops": an["flops_per_dev"],
            "coll_bytes": an["collective_bytes_per_dev"],
            "n_coll": {k: v["count"] for k, v in an["collectives"].items()},
            "loss": float(m["loss"]),
            "step": int(jax.device_get(s2.step)),
        }))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["step"] == 1
    assert r["loss"] > 0 and r["loss"] < 20
    assert r["flops"] > 1e6
    # TP matmuls + DP grad sync must produce collectives
    assert r["coll_bytes"] > 0, r


@pytest.mark.slow
def test_tiny_mesh_decode_and_elastic_restore():
    """Decode path on a mesh + elastic checkpoint restore onto a DIFFERENT
    mesh shape (4x2 -> 2x4)."""
    out = run_py("""
        import json, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import registry
        from repro.models import lm
        from repro.sharding import rules
        from repro.train import checkpoint as ckpt
        mesh1 = jax.make_mesh((4, 2), ("data", "model"),
                              axis_types=(jax.sharding.AxisType.Auto,) * 2)
        mesh2 = jax.make_mesh((2, 4), ("data", "model"),
                              axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = registry.smoke_config("llama3-8b")
        model = lm.build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        p1 = jax.device_put(params, rules.params_shardings(params, mesh1))
        d = tempfile.mkdtemp()
        ckpt.save(d, 1, {"params": p1})
        # elastic restore onto mesh2
        sh2 = rules.params_shardings(params, mesh2)
        got = ckpt.restore(d, 1, {"params": params},
                           shardings={"params": sh2})
        ok = all(jax.tree.leaves(jax.tree.map(
            lambda a, b: bool(np.allclose(np.asarray(a), np.asarray(b))),
            params, got["params"])))
        # decode on mesh2
        with mesh2:
            caches = model.init_cache(4, 16)
            cshard = rules.cache_shardings(caches, mesh2)
            toks = jnp.ones((4,), jnp.int32)
            logits, caches2 = jax.jit(model.decode)(got["params"], caches,
                                                    toks)
        print(json.dumps({"restore_ok": ok,
                          "logits_finite": bool(jnp.isfinite(logits).all()),
                          "len": int(jax.device_get(caches2["len"]))}))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    assert r == {"restore_ok": True, "logits_finite": True, "len": 1}


@pytest.mark.slow
def test_moe_expert_parallel_tiny_mesh():
    """MoE with E=8 experts on (data=2, model=4): E % (data*model) == 0
    triggers expert sharding over both axes; forward must stay exact vs
    single-device run."""
    out = run_py("""
        import json
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import registry
        from repro.models import lm
        from repro.sharding import rules
        import dataclasses
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = dataclasses.replace(registry.smoke_config("mixtral-8x7b"),
                                  moe_groups=2)
        model = lm.build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((4, 16), jnp.int32)}
        loss_1dev, _ = model.loss(params, batch)        # replicated reference
        pshard = rules.params_shardings(params, mesh)
        bshard = rules.batch_shardings(batch, mesh)
        with mesh:
            pp = jax.device_put(params, pshard)
            bb = jax.device_put(batch, bshard)
            loss_mesh, _ = jax.jit(model.loss)(pp, bb)
        print(json.dumps({"ref": float(loss_1dev),
                          "mesh": float(loss_mesh)}))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    assert abs(r["ref"] - r["mesh"]) < 1e-2, r


def test_dryrun_cells_cover_assignment():
    """40 assigned cells: 10 archs x 4 shapes, with long_500k lowered only
    for sub-quadratic archs (the skip rule) — 32 runnable cells."""
    from repro.configs import registry
    total_assigned = 10 * 4
    runnable = sum(len(registry.shapes_for(a)) for a in registry.ALIASES)
    assert total_assigned == 40
    assert runnable == 32
    for arch in registry.ALIASES:
        assert "train_4k" in registry.shapes_for(arch)
        assert "prefill_32k" in registry.shapes_for(arch)
        assert "decode_32k" in registry.shapes_for(arch)


def test_plans_exist_for_all_archs():
    import importlib
    dr = importlib.import_module("repro.launch.dryrun")
    from repro.configs import registry
    for arch in registry.ALIASES:
        assert arch in dr.PLANS
