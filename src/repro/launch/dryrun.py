"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces, with zero device allocation:
  * proof the distribution config is coherent (SPMD partitioning succeeds),
  * memory_analysis()  -> per-device bytes (fits-in-HBM evidence),
  * cost_analysis()    -> HLO FLOPs / bytes for the roofline terms,
  * collective op bytes parsed from the post-partitioning HLO.

Results are cached as JSON under ``benchmarks/out/dryrun/`` and consumed by
``benchmarks/roofline.py`` and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmoe-1b-7b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
"""
import argparse
import json
import os
import pathlib
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

import repro.configs as C
from repro.distributed import sharding as SH
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh
from repro.models import model as mdl
from repro.train import optim, step as tstep

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "out" / "dryrun"

# Per-arch training knobs (documented in EXPERIMENTS.md SDry-run): the
# 100B+ configs need bf16 optimizer state + gradient accumulation to fit
# 16 GB/chip HBM.
TRAIN_OVERRIDES = {
    "nemotron-4-340b": dict(state_dtype="bfloat16", accum=8),
    "deepseek-v3-671b": dict(state_dtype="bfloat16", accum=8),
    "qwen3-14b": dict(accum=2),
    "stablelm-12b": dict(accum=2),
    "gemma3-12b": dict(accum=4),
    "paligemma-3b": dict(accum=2),
    "musicgen-large": dict(accum=2),
    "olmoe-1b-7b": dict(accum=4),
    "xlstm-1.3b": dict(accum=4),
    "recurrentgemma-2b": dict(accum=2),
}

_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[^\]]*\]\S*))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", )
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|s32|u32|s16|u16|s8|u8|pred|c64)"
                       r"\[([0-9,]*)\]")
_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4,
          "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8}


def collective_bytes(hlo_text: str):
    """Sum output bytes of every collective op in post-SPMD HLO, by kind."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    for m in _COLL_RE.finditer(hlo_text):
        shapes, kind = m.group(1), m.group(2)
        n = 0
        for sm in _SHAPE_RE.finditer(shapes):
            dims = sm.group(2)
            cnt = int(np.prod([int(d) for d in dims.split(",") if d])) if dims \
                else 1
            n += cnt * _BYTES[sm.group(1)]
        out[kind] += n
    return out


def _batch_shardings(bspecs, mesh, step_kind="train"):
    rules = SH.act_rules_for(step_kind)

    def one(name, sds):
        names = {"tokens": ("batch", "seq"), "targets": ("batch", "seq"),
                 "extra_embeds": ("batch", "seq", "embed"),
                 "cond": ("batch", "seq", "embed"),
                 "cur_pos": ("batch",)}[name]
        return NamedSharding(mesh, SH._resolve(names, sds.shape, rules, mesh))
    return {k: one(k, v) for k, v in bspecs.items()}


def lower_cell(arch: str, shape: str, multi_pod: bool, verbose: bool = True,
               cost_variant: bool = False):
    """Lower+compile one cell.  cost_variant=True unrolls the layer and
    grad-accum loops so HLO cost analysis (which counts while-loop bodies
    once) reports trip-count-correct FLOPs and collective bytes; the deploy
    variant (scan+accum) is what memory analysis and the shardability proof
    use."""
    import dataclasses as _dc
    c = SP.cell(arch, shape)
    if cost_variant:
        c = _dc.replace(c, cfg=_dc.replace(c.cfg, unroll_layers=True))
    mesh = make_production_mesh(multi_pod=multi_pod)
    shard = SH.make_shard_fn(mesh, rules=SH.act_rules_for(c.step_kind))
    ov = dict(TRAIN_OVERRIDES.get(arch, {}))
    if cost_variant:
        ov["accum"] = 1
    t0 = time.time()

    if c.step_kind == "train":
        ocfg = optim.OptConfig(state_dtype=ov.get("state_dtype", "float32"))
        shapes, sspecs = SP.state_specs_shapes(c.cfg, ocfg)
        state_sh = SH.tree_shardings(sspecs, shapes, mesh)
        bspecs = SP.batch_specs(c)
        batch_sh = _batch_shardings(bspecs, mesh)
        pspecs_model = mdl.init_specs_only(c.cfg)
        step = tstep.make_train_step(c.cfg, ocfg, mesh=mesh, shard=shard,
                                     accum_steps=ov.get("accum", 1),
                                     param_specs=pspecs_model,
                                     cast_params=ov.get("cast_params", True))
        metric_sh = {"loss": NamedSharding(mesh, P()),
                     "grad_norm": NamedSharding(mesh, P()),
                     "lr": NamedSharding(mesh, P())}
        fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, metric_sh))
        lowered = fn.lower(shapes, bspecs)
    elif c.step_kind == "prefill":
        pspecs = mdl.init_specs_only(c.cfg)
        pshapes = jax.eval_shape(
            lambda: mdl.init(jax.random.PRNGKey(0), c.cfg)[0])
        param_sh = SH.tree_shardings(pspecs, pshapes, mesh)
        bspecs = SP.batch_specs(c)
        batch_sh = _batch_shardings(bspecs, mesh)

        pshard = SH.make_param_shard_fn(mesh)

        def prefill_fn(params, batch):
            params = tstep.cast_params_tree(params)
            return mdl.prefill(params, c.cfg, batch["tokens"],
                               extra_embeds=batch.get("extra_embeds"),
                               cond=batch.get("cond"), mesh=mesh, shard=shard,
                               param_specs=pspecs, pshard=pshard)

        fn = jax.jit(prefill_fn, in_shardings=(param_sh, batch_sh))
        lowered = fn.lower(pshapes, bspecs)
    else:  # decode
        pspecs = mdl.init_specs_only(c.cfg)
        pshapes = jax.eval_shape(
            lambda: mdl.init(jax.random.PRNGKey(0), c.cfg)[0])
        param_sh = SH.tree_shardings(pspecs, pshapes, mesh)
        dsp = SP.decode_specs(c)
        cache_sh = SH.tree_shardings(mdl.cache_specs(c.cfg), dsp["cache"],
                                     mesh, rules=SH.ACT_RULES)
        tok_sh = NamedSharding(mesh, SH._resolve(("batch", "seq"),
                                                 dsp["tokens"].shape,
                                                 SH.ACT_RULES, mesh))
        pos_sh = NamedSharding(mesh, SH._resolve(("batch",),
                                                 dsp["cur_pos"].shape,
                                                 SH.ACT_RULES, mesh))
        cond_spec = dsp.get("cond")

        def decode_fn(params, cache, tokens, cur_pos, cond=None):
            params = tstep.cast_params_tree(params)
            return mdl.decode_step(params, c.cfg, cache, tokens, cur_pos,
                                   cond=cond, mesh=mesh, shard=shard)

        in_sh = [param_sh, cache_sh, tok_sh, pos_sh]
        args = [pshapes, dsp["cache"], dsp["tokens"], dsp["cur_pos"]]
        if cond_spec is not None:
            in_sh.append(NamedSharding(mesh, SH._resolve(
                ("batch", "seq", "embed"), cond_spec.shape, SH.ACT_RULES,
                mesh)))
            args.append(cond_spec)
        fn = jax.jit(decode_fn, in_shardings=tuple(in_sh),
                     out_shardings=(None, cache_sh))
        lowered = fn.lower(*args)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    n_dev = int(np.prod(list(mesh.shape.values())))
    rec = {
        "arch": arch, "shape": shape, "variant":
            "cost" if cost_variant else "deploy",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev,
        "step_kind": c.step_kind,
        "flops": float(cost.get("flops", -1.0)),
        "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
        "peak_bytes_per_device": int(mem.argument_size_in_bytes
                                     + mem.temp_size_in_bytes
                                     + mem.output_size_in_bytes
                                     - mem.alias_size_in_bytes),
        "collective_bytes": coll,
        "collective_bytes_total": int(sum(coll.values())),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "model_params": int(SP.cell(arch, shape).cfg.param_count()),
        "active_params": int(SP.cell(arch, shape).cfg.active_param_count()),
        "tokens_per_step": (c.global_batch * c.seq_len
                            if c.step_kind != "decode" else c.global_batch),
    }
    if verbose:
        print(f"[{arch} x {shape} x {rec['mesh']}] "
              f"flops={rec['flops']:.3e} temp={rec['temp_bytes']/1e9:.2f}GB "
              f"args={rec['argument_bytes']/1e9:.2f}GB "
              f"coll={rec['collective_bytes_total']/1e9:.2f}GB "
              f"compile={t_compile:.0f}s")
        print("  memory_analysis:", mem)
    return rec


def run_cell(arch, shape, mesh_mode, force=False):
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    tag = "multi" if mesh_mode == "multi" else "single"
    path = OUT_DIR / f"{arch}__{shape}__{tag}.json"
    if path.exists() and not force:
        print(f"[skip cached] {path.name}")
        return json.loads(path.read_text())
    rec = lower_cell(arch, shape, multi_pod=(mesh_mode == "multi"))
    if mesh_mode == "single":
        # trip-count-correct FLOPs/collectives for the roofline table
        crec = lower_cell(arch, shape, multi_pod=False, cost_variant=True)
        rec["cost_variant"] = {k: crec[k] for k in
                               ("flops", "bytes_accessed", "collective_bytes",
                                "collective_bytes_total", "compile_s")}
    path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    # 512 fake host devices for the production meshes; set before the
    # first device query initialises the CPU backend
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = (C.cells() if args.all
             else [(args.arch, args.shape)])
    failures = []
    for arch, shape in cells:
        for m in meshes:
            try:
                run_cell(arch, shape, m, force=args.force)
            except Exception as e:  # noqa: BLE001 - report all failures
                traceback.print_exc()
                failures.append((arch, shape, m, str(e)[:200]))
    if failures:
        print("\nFAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nAll requested dry-run cells compiled.")


if __name__ == "__main__":
    main()
