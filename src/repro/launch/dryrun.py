import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.
# The 512 placeholder CPU devices exist ONLY for this dry-run; smoke tests and
# benchmarks see the single real device.
#
# Multi-pod dry-run: .lower().compile() every (architecture x input shape) on
# the production meshes and extract the roofline terms:
#   compute_s    = HLO_FLOPs / (chips * 197e12)          [bf16 MXU peak]
#   memory_s     = HLO_bytes / (chips * 819e9)           [HBM bandwidth]
#   collective_s = collective_bytes / (chips * 50e9)     [ICI per-link]
# cost_analysis() on the SPMD-partitioned module reports PER-DEVICE flops and
# bytes, so term = per_device / peak. Collective bytes are parsed from the
# post-optimization HLO with ring-algorithm multipliers (see _collectives).
#
# Usage:
#   python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
#   python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.launch import hlo_analysis, specs
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.optim import adamw
from repro.sharding import rules
from repro.train import loop as train_loop

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12     # bf16
HBM_BW = 819e9          # bytes/s
LINK_BW = 50e9          # bytes/s per ICI link

VOCAB_TP = True

# Per-arch dry-run options. fsdp: shard params over data too (needed when
# bf16 params exceed HBM at TP=16). quantized: int8 AdamW moments.
# n_micro: gradient-accumulation microbatches for the train_4k cell.
# attn_impl / ep_axes / grad_dtype / constrain_grads: §Perf optimizations
# (EXPERIMENTS.md) — the baseline PLANS keep the paper-faithful einsum path.
@dataclasses.dataclass(frozen=True)
class Plan:
    fsdp: bool = False
    quantized: bool = False
    n_micro: int = 1
    attn_impl: str = "einsum"
    ep_axes: tuple | None = None
    grad_dtype: str | None = None
    constrain_grads: bool = False


PLANS: dict[str, Plan] = {
    "mixtral-8x7b": Plan(n_micro=2),
    "deepseek-v3-671b": Plan(quantized=True, n_micro=8, fsdp=True),
    "deepseek-coder-33b": Plan(n_micro=4),
    "gemma-7b": Plan(n_micro=2),
    "minitron-8b": Plan(n_micro=2),
    "llama3-8b": Plan(n_micro=2),
    "zamba2-7b": Plan(n_micro=2),
    "rwkv6-1.6b": Plan(n_micro=1),
    "llama-3.2-vision-90b": Plan(fsdp=True, quantized=True, n_micro=8),
    "whisper-base": Plan(n_micro=1),
}

# §Perf optimized plans (--opt): grouped-GQA attention is already the
# default model path (iteration 1); these add grad-accumulator sharding
# constraints, two-level EP dispatch for deepseek-v3, and bf16 accumulators
# for the 100B+ archs. attn_impl="flash" (the Pallas kernel via shard_map)
# was evaluated and REFUTED for the 4k/32k cells on the CPU-derived
# roofline (EXPERIMENTS.md §Perf iteration 3) — the kernels remain as the
# validated TPU path, selectable per arch.
OPT_PLANS: dict[str, Plan] = dict(PLANS)
# grad-accumulator sharding constraints were hillclimbed per arch: they fix
# deepseek-v3's 20 TB/dev scan-backward resharding but CAUSE recompute on
# the dense archs (llama3 train compute +76% — §Perf it.6, refuted there).
OPT_PLANS["deepseek-v3-671b"] = dataclasses.replace(
    OPT_PLANS["deepseek-v3-671b"], ep_axes=("data", "model"),
    grad_dtype="bfloat16", fsdp=False, constrain_grads=True)
OPT_PLANS["llama-3.2-vision-90b"] = dataclasses.replace(
    OPT_PLANS["llama-3.2-vision-90b"], grad_dtype="bfloat16")


def _batch_groups(mesh, global_batch: int) -> int:
    """Number of MoE dispatch groups = number of batch shards."""
    ba = rules._batch_axes_for(mesh, global_batch)
    n = 1
    for a in ba:
        n *= mesh.shape[a]
    return max(n, 1)


# --- per-cell programs ----------------------------------------------------------
def build_cell(arch: str, shape: str, mesh, plan: Plan):
    """Returns (jitted_fn, abstract_args) for the cell's step program."""
    c = specs.cell(arch, shape)
    cfg = registry.config(arch)
    cfg = dataclasses.replace(
        cfg,
        moe_groups=_batch_groups(
            mesh, c.global_batch if c.kind != "train"
            else c.global_batch // plan.n_micro),
        attn_impl=plan.attn_impl,
        ep_axes=plan.ep_axes)
    model = lm.build(cfg)
    pspecs = specs.params_specs(model)
    pshard = rules.params_shardings(pspecs, mesh, fsdp=plan.fsdp)

    if c.kind == "train":
        ocfg = adamw.AdamWConfig(quantized_state=plan.quantized)
        sspecs = specs.opt_state_specs(ocfg, pspecs)
        sshard = train_loop.state_shardings(ocfg, pspecs, mesh,
                                            fsdp=plan.fsdp)
        batch = specs.model_inputs(cfg, c)
        bshard = rules.batch_shardings(batch, mesh)
        gspecs = (jax.tree.map(lambda s: s.spec, pshard)
                  if plan.constrain_grads else None)
        gdt = jnp.dtype(plan.grad_dtype) if plan.grad_dtype else None
        fn = train_loop.make_train_fn(model, ocfg, plan.n_micro,
                                      grad_specs=gspecs, grad_dtype=gdt)
        jitted = jax.jit(fn, in_shardings=(pshard, sshard, bshard),
                         out_shardings=(pshard, sshard, None),
                         donate_argnums=(0, 1))
        return jitted, (pspecs, sspecs, batch), cfg, c

    if c.kind == "prefill":
        batch = specs.model_inputs(cfg, c)
        bshard = rules.batch_shardings(batch, mesh)
        cspecs = specs.cache_specs(model, c.global_batch, c.seq_len)
        cshard = rules.cache_shardings(cspecs, mesh)
        ba = rules._batch_axes_for(mesh, c.global_batch)
        lshard = NamedSharding(mesh, P(
            ba if ba else None,
            "model" if VOCAB_TP and cfg.vocab % mesh.shape["model"] == 0
            else None))

        def prefill(p, b):
            return model.prefill(p, b, max_len=c.seq_len)

        jitted = jax.jit(prefill, in_shardings=(pshard, bshard),
                         out_shardings=(lshard, cshard))
        return jitted, (pspecs, batch), cfg, c

    # decode: one new token against a seq_len KV cache
    cspecs = specs.cache_specs(model, c.global_batch, c.seq_len)
    cshard = rules.cache_shardings(cspecs, mesh)
    toks = specs.decode_token_specs(c)
    ba = rules._batch_axes_for(mesh, c.global_batch)
    tshard = NamedSharding(mesh, P(ba if ba else None))
    lshard = NamedSharding(mesh, P(
        ba if ba else None,
        "model" if VOCAB_TP and cfg.vocab % mesh.shape["model"] == 0
        else None))
    jitted = jax.jit(model.decode,
                     in_shardings=(pshard, cshard, tshard),
                     out_shardings=(lshard, cshard),
                     donate_argnums=(1,))
    return jitted, (pspecs, cspecs, toks), cfg, c


def run_cell(arch: str, shape: str, mesh_kind: str, hlo_dir: str | None = None,
             opt: bool = False) -> dict:
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = int(np.prod(list(mesh.shape.values())))
    plan = (OPT_PLANS if opt else PLANS)[arch]
    rec: dict = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                 "chips": chips, "opt": opt, "plan": dataclasses.asdict(plan)}
    try:
        t0 = time.time()
        jax.set_mesh(mesh)   # ambient mesh for shard_map'd Pallas kernels
        with mesh:
            jitted, args, cfg, c = build_cell(arch, shape, mesh, plan)
            lowered = jitted.lower(*args)
            rec["lower_s"] = round(time.time() - t0, 1)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)
        ca = hlo_analysis.xla_cost_analysis(compiled)
        ma = compiled.memory_analysis()
        # XLA's cost_analysis counts while bodies ONCE (no trip
        # multiplication) — recorded for reference only; the roofline uses
        # the trip-adjusted numbers from hlo_analysis.
        rec["xla_cost"] = {"flops_per_dev": ca.get("flops", 0.0),
                           "bytes_per_dev": ca.get("bytes accessed", 0.0)}
        rec["memory"] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes": (ma.argument_size_in_bytes
                           + ma.output_size_in_bytes
                           + ma.temp_size_in_bytes
                           - ma.alias_size_in_bytes),
        }
        hlo = compiled.as_text()
        an = hlo_analysis.analyze(hlo)
        rec["cost"] = {"flops_per_dev": an["flops_per_dev"],
                       "bytes_per_dev": an["bytes_per_dev"]}
        rec["collectives"] = dict(an["collectives"],
                                  total_bytes=an["collective_bytes_per_dev"])
        if hlo_dir:
            os.makedirs(hlo_dir, exist_ok=True)
            with open(os.path.join(
                    hlo_dir, f"{arch}__{shape}__{mesh_kind}.hlo"), "w") as f:
                f.write(hlo)
        # roofline terms (seconds)
        fl = rec["cost"]["flops_per_dev"]
        by = rec["cost"]["bytes_per_dev"]
        cb = an["collective_bytes_per_dev"]
        rec["roofline"] = {
            "compute_s": fl / PEAK_FLOPS,
            "memory_s": by / HBM_BW,
            "collective_s": cb / LINK_BW,
        }
        dom = max(rec["roofline"], key=rec["roofline"].get)
        rec["roofline"]["dominant"] = dom
        # model flops: 6 * N_active * tokens (train has fwd+bwd = 3x fwd;
        # decode/prefill are fwd-only = 2 * N_active * tokens)
        n_active = cfg.active_param_count()
        tokens = (c.global_batch * c.seq_len if c.kind != "decode"
                  else c.global_batch)
        factor = 6.0 if c.kind == "train" else 2.0
        rec["model_flops_total"] = factor * n_active * tokens
        hlo_total = fl * chips
        rec["useful_flops_frac"] = (rec["model_flops_total"] / hlo_total
                                    if hlo_total else 0.0)
        rec["ok"] = True
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def cells_to_run(args) -> list[tuple[str, str]]:
    cells = []
    for arch in registry.ALIASES:
        if args.arch and arch != args.arch:
            continue
        for shape in registry.shapes_for(arch):
            if args.shape and shape != args.shape:
                continue
            cells.append((arch, shape))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--hlo", default=None, help="dir to dump HLO text")
    ap.add_argument("--opt", action="store_true",
                    help="use OPT_PLANS (flash attention, EP dispatch, ...)")
    args = ap.parse_args()
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    os.makedirs(args.out, exist_ok=True)
    cells = cells_to_run(args)
    n_fail = 0
    for arch, shape in cells:
        for mk in meshes:
            path = os.path.join(args.out, f"{arch}__{shape}__{mk}.json")
            rec = run_cell(arch, shape, mk, hlo_dir=args.hlo, opt=args.opt)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            if rec["ok"]:
                r = rec["roofline"]
                print(f"OK   {arch:22s} {shape:12s} {mk:6s} "
                      f"lower={rec['lower_s']:6.1f}s "
                      f"compile={rec['compile_s']:6.1f}s "
                      f"comp={r['compute_s']:.3e} mem={r['memory_s']:.3e} "
                      f"coll={r['collective_s']:.3e} dom={r['dominant']} "
                      f"useful={rec['useful_flops_frac']:.2f}",
                      flush=True)
            else:
                n_fail += 1
                print(f"FAIL {arch:22s} {shape:12s} {mk:6s} {rec['error']}",
                      flush=True)
    print(f"done: {len(cells) * len(meshes) - n_fail} ok, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
