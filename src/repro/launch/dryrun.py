import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the *real* step function (the distillation train
step for train shapes — the paper's training step — or the serve step for
prefill/decode shapes), lowers it with ShapeDtypeStruct inputs under the
production mesh sharding rules, compiles it, and records:

  * memory_analysis()  — proves the cell fits per-device HBM,
  * cost_analysis()    — FLOPs / bytes for §Roofline,
  * collective bytes   — parsed from the SPMD HLO (launch/roofline.py),
  * the three roofline terms + dominant bottleneck.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
      --shape train_4k --mesh single          # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out experiments/dryrun                # the full table
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED, get_config
from repro.core.distill import DistillConfig
from repro.distributed import sharding as SH
from repro.distributed.constraints import activation_mesh
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import adam
from repro.train import steps as TS


def use_fsdp(cfg: ModelConfig, *, train: bool) -> bool:
    """FSDP only when (params + optimizer state)/TP exceeds ~2 GB/chip —
    small models replicate across data and skip every FSDP all-gather."""
    tp = 16
    params = M.param_count(cfg)
    if train:
        trainable = (params if cfg.trainable == "all"
                     else M.trainable_param_count(cfg))
        per_chip = (2 * params + 8 * trainable) / tp
    else:
        per_chip = 2 * params / tp
    return per_chip > 2e9


def _named(tree, mesh, fsdp: bool = True):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, SH.param_spec(path, leaf, mesh, fsdp_enabled=fsdp)),
        tree)


def abstract_train_state(cfg: ModelConfig, opt_cfg, mesh, fsdp: bool = True):
    """ShapeDtypeStruct state for the distill step + its shardings."""
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)

    def build(key):
        teacher = M.init_params(jax.random.PRNGKey(0), cfg)
        student = M.student_subset(cfg, teacher)
        return {"teacher": teacher, "student": student,
                "opt": adam.init(student, opt_cfg),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.eval_shape(lambda _: build(None), key)
    sh = {
        "teacher": _named(state["teacher"], mesh, fsdp),
        "student": _named(state["student"], mesh, fsdp),
        "opt": {
            "mu": _named(state["opt"]["mu"], mesh, fsdp),
            "nu": _named(state["opt"]["nu"], mesh, fsdp),
            "count": NamedSharding(mesh, P()),
        },
        "step": NamedSharding(mesh, P()),
    }
    return state, sh


def abstract_pretrain_state(cfg: ModelConfig, opt_cfg, mesh):
    def build(_):
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        return {"params": params, "opt": adam.init(params, opt_cfg),
                "step": jnp.zeros((), jnp.int32)}

    state = jax.eval_shape(build, 0)
    sh = {
        "params": _named(state["params"], mesh),
        "opt": {"mu": _named(state["opt"]["mu"], mesh),
                "nu": _named(state["opt"]["nu"], mesh),
                "count": NamedSharding(mesh, P())},
        "step": NamedSharding(mesh, P()),
    }
    return state, sh


def batch_shardings(specs: dict, mesh, global_batch: int):
    return SH.batch_spec(specs, mesh, global_batch=global_batch)


def default_grad_accum(shape: M.ShapeSpec, mesh) -> int:
    """Bound activation transients to ~2 sequences per chip per microbatch."""
    data = SH.axis_size(mesh, SH.batch_axes(mesh))
    per_replica = max(shape.global_batch // max(data, 1), 1)
    accum = max(per_replica // 2, 1)
    while per_replica % accum:
        accum -= 1
    return accum


def lower_train(cfg: ModelConfig, shape: M.ShapeSpec, mesh, *,
                grad_accum: int | None = None,
                threshold_method: str | None = None):
    opt_cfg = adam.AdamWConfig(
        state_dtype="bfloat16" if cfg.trainable == "attention" or
        M.param_count(cfg) > 5e10 else "float32")
    distill = bool(cfg.had.enabled and cfg.has_attention)
    specs = M.input_specs(cfg, shape)
    b_sh = batch_shardings(specs, mesh, shape.global_batch)
    accum = default_grad_accum(shape, mesh) if grad_accum is None else grad_accum
    step_cfg = TS.StepConfig(grad_accum=accum)
    fsdp = use_fsdp(cfg, train=True)
    if distill:
        dcfg = DistillConfig()
        state, st_sh = abstract_train_state(cfg, opt_cfg, mesh, fsdp)
        step_fn = TS.build_distill_step(cfg, dcfg, opt_cfg, step_cfg,
                                        topn=cfg.had.topn(shape.seq_len),
                                        threshold_method=threshold_method)
    else:
        state, st_sh = abstract_pretrain_state(cfg, opt_cfg, mesh)
        step_fn = TS.build_pretrain_step(cfg, opt_cfg, lambda s: 1e-5,
                                         step_cfg)

    with mesh, activation_mesh(mesh):
        lowered = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                          out_shardings=(st_sh, None)).lower(state, specs)
    return lowered, {"distill": distill, "grad_accum": accum}


def lower_serve(cfg: ModelConfig, shape: M.ShapeSpec, mesh):
    binary = bool(cfg.had.enabled and cfg.has_attention)
    specs = M.input_specs(cfg, shape)
    b_sh = batch_shardings(specs, mesh, shape.global_batch)
    n = cfg.had.topn(shape.seq_len) if binary else 0
    caches = jax.eval_shape(
        lambda _: M.init_caches(cfg, shape.global_batch, shape.seq_len,
                                binary=binary), 0)
    cache_sh = SH.cache_shardings(caches, mesh,
                                  global_batch=shape.global_batch)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def serve_fn(params, batch, caches, pos):
        return M.serve_step(params, batch, caches, cfg=cfg, pos=pos, n=n,
                            binary=binary, logits_mode="last")

    params = jax.eval_shape(lambda _: M.init_params(jax.random.PRNGKey(0),
                                                    cfg), 0)
    p_sh = _named(params, mesh, use_fsdp(cfg, train=False))
    with mesh, activation_mesh(mesh):
        lowered = jax.jit(
            serve_fn,
            in_shardings=(p_sh, b_sh, cache_sh, NamedSharding(mesh, P())),
            out_shardings=(None, cache_sh),
        ).lower(params, specs, caches, pos)
    return lowered, {"binary": binary, "topn": n}


_Q_BLOCK_OVERRIDE = None
# CLI-scoped top-N threshold algorithm, threaded explicitly into the step
# builders (core.topn no longer has a mutable process-global).
_THRESHOLD_METHOD = None


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             keep_hlo: bool = False) -> dict:
    cfg = get_config(arch)
    if _Q_BLOCK_OVERRIDE:
        cfg = get_config(arch, q_block=_Q_BLOCK_OVERRIDE)
    shape = M.SHAPES[shape_name]
    ok, why = M.shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    try:
        if shape.kind == "train":
            lowered, extra = lower_train(cfg, shape, mesh,
                                         threshold_method=_THRESHOLD_METHOD)
        else:
            lowered, extra = lower_serve(cfg, shape, mesh)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        f32_copies = None
        terms = RL.terms_from_compiled(compiled, hlo, chips)
        from repro.launch import hlo_cost as HC
        coll = {k: v for k, v in HC.module_cost(hlo).collective.items() if v}
        mf = RL.model_flops(cfg, shape,
                            distill=extra.get("distill", False))
        from repro.launch.hlo_cost import f32_param_copy_bytes
        f32_copies = f32_param_copy_bytes(hlo)
        mem_d = _mem_dict(mem, chips)
        if f32_copies:
            mem_d["cpu_f32_weight_copy_gb"] = round(f32_copies / 2**30, 3)
            mem_d["per_device_total_gb_tpu_corrected"] = round(
                mem_d["per_device_total_gb"] - f32_copies / 2**30, 3)
        rec.update(
            status="ok", lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1), **extra,
            memory=mem_d,
            roofline=terms.as_dict(),
            collectives=coll,
            xla_reference=RL.xla_reference_cost(compiled),
            model_flops=mf,
            useful_flop_ratio=(mf / terms.global_flops
                               if terms.flops else None),
        )
        if keep_hlo:
            rec["hlo_len"] = len(hlo)
    except Exception as e:  # a failing cell is a bug — surface it loudly
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    return rec


def _mem_dict(mem, chips) -> dict:
    if mem is None:
        return {}
    out = {}
    for name in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "generated_code_size_in_bytes",
                 "peak_memory_in_bytes"):
        v = getattr(mem, name, None)
        if v is not None:
            out[name] = int(v)
    # memory_analysis is per-device post-SPMD (validated in roofline.py)
    args = out.get("argument_size_in_bytes", 0)
    temp = out.get("temp_size_in_bytes", 0)
    out["per_device_total_gb"] = round((args + temp) / 2**30, 3)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(M.SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--threshold", default="sort", choices=["sort", "bisect"])
    ap.add_argument("--attn-dtype", default="f32", choices=["f32", "bf16"])
    ap.add_argument("--carry", default="sp", choices=["sp", "dp"])
    ap.add_argument("--q-block", type=int, default=None)
    args = ap.parse_args()
    if args.carry == "dp":
        from repro.models import transformer as _T
        _T.set_carry_pattern("b..")
    global _Q_BLOCK_OVERRIDE, _THRESHOLD_METHOD
    _Q_BLOCK_OVERRIDE = args.q_block
    _THRESHOLD_METHOD = args.threshold
    if args.attn_dtype == "bf16":
        from repro.core import attention as _A
        _A.set_attn_compute_dtype(jnp.bfloat16)

    archs = ASSIGNED if args.all or args.arch is None else [args.arch]
    shapes = list(M.SHAPES) if args.shape is None else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    print(f"roofline terms model one {RL.MODELED_KIND!r} chip's "
          f"published peaks (analytic, not measured)", flush=True)
    records = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, multi_pod=mp)
                records.append(rec)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    mm = rec["memory"]
                    shown = mm.get("per_device_total_gb_tpu_corrected",
                                   mm.get("per_device_total_gb", "?"))
                    extra = (f"dom={r['dominant']} "
                             f"tc={r['t_compute_s']:.3e} "
                             f"tm={r['t_memory_s']:.3e} "
                             f"tx={r['t_collective_s']:.3e} "
                             f"mem/dev={shown}GB "
                             f"compile={rec['compile_s']}s")
                elif status == "error":
                    extra = rec["error"][:200]
                else:
                    extra = rec["reason"]
                print(f"[{status:7s}] {arch:24s} {shape:12s} "
                      f"{rec['mesh']:8s} {extra}", flush=True)
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    fn = f"{arch}__{shape}__{rec['mesh']}.json"
                    with open(os.path.join(args.out, fn), "w") as f:
                        json.dump(rec, f, indent=1)
    n_err = sum(r["status"] == "error" for r in records)
    print(f"\n{len(records)} cells: "
          f"{sum(r['status'] == 'ok' for r in records)} ok, "
          f"{sum(r['status'] == 'skipped' for r in records)} skipped, "
          f"{n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
