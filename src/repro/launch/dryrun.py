import os
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512"
    ).strip()

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture × input shape × mesh) cell this driver

  1. builds abstract (ShapeDtypeStruct, zero-allocation) stand-ins for all
     step inputs — train state + batch, or params + request batch + cache;
  2. ``jax.jit(step, in_shardings=…).lower(...).compile()`` on the
     production mesh (16×16 single pod / 2×16×16 multi-pod);
  3. records ``memory_analysis()`` (bytes per device — proves it fits
     16 GiB HBM), ``cost_analysis()`` and the loop-aware HLO cost model
     (FLOPs / HBM bytes / collective bytes) for the roofline.

Any sharding mismatch, compile-time OOM, or unsupported collective fails
the cell — those are bugs in the system, not in the harness.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod --out runs/dryrun
"""
import argparse
import json
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, ArchSpec, Shape, get_config, list_archs
from repro.data import batch_specs
from repro.launch.hlo import analyze_hlo
from repro.launch.mesh import HW, make_production_mesh
from repro.models.config import ModelConfig
from repro.models.model import decode_step, init_decode_state, init_model, prefill_step
from repro.optim import make_optimizer
from repro.runtime.shardings import (
    batch_specs_for_mesh,
    decode_state_specs,
    named,
    param_specs,
    state_specs,
)
from repro.runtime.train import TrainState, make_train_step

__all__ = ["run_cell", "input_specs", "main"]


def _abstract(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree
    )


def input_specs(cfg: ModelConfig, shape: Shape) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    return batch_specs(cfg, shape.seq_len, shape.global_batch)


def _train_cell(spec: ArchSpec, shape: Shape, mesh):
    cfg = spec.model
    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(lambda r: init_model(r, cfg), key)
    opt_init, opt_update = make_optimizer(spec.optimizer, 1e-4)
    opt_s = jax.eval_shape(opt_init, params_s)
    state_s = TrainState(params_s, opt_s)
    batch_s = input_specs(cfg, shape)

    grouped = cfg.shared_attn_every > 0
    p_specs = param_specs(params_s, mesh, grouped_blocks=grouped)
    o_specs = type(opt_s)(
        jax.sharding.PartitionSpec(),
        state_specs(opt_s.inner, mesh, grouped_blocks=grouped),
    )
    st_specs = TrainState(p_specs, o_specs)
    b_specs = batch_specs_for_mesh(batch_s, mesh)

    # cap microbatches so each microbatch's batch dim still shards over
    # every data axis (pod included): B/mb must divide pod·data
    import numpy as _np
    dp = int(_np.prod([mesh.shape[a] for a in mesh.axis_names if a != "model"]))
    mb = spec.train_microbatches
    B = shape.global_batch
    while mb > 1 and (B // mb) % dp:
        mb //= 2
    step = make_train_step(
        cfg, opt_update, vocab_chunk=512,
        microbatches=mb, grad_dtype=spec.grad_dtype,
        grad_shardings=named(mesh, p_specs),
    )
    metric_sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    out_metrics = {k: metric_sh for k in ("ce", "aux", "tokens", "loss", "grad_norm")}
    jitted = jax.jit(
        step,
        in_shardings=(named(mesh, st_specs), named(mesh, b_specs)),
        out_shardings=(named(mesh, st_specs), out_metrics),
        donate_argnums=(0,),
    )
    return jitted, (state_s, batch_s)


def _decode_cell(spec: ArchSpec, shape: Shape, mesh):
    cfg = spec.model
    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(lambda r: init_model(r, cfg), key)
    B = shape.global_batch
    cache_s = jax.eval_shape(
        lambda: init_decode_state(cfg, B, shape.seq_len)
    )
    grouped = cfg.shared_attn_every > 0
    p_specs = param_specs(params_s, mesh, grouped_blocks=grouped)
    c_specs = decode_state_specs(cache_s, mesh)

    if cfg.n_codebooks:
        tok_s = jax.ShapeDtypeStruct((B, cfg.n_codebooks, 1), jnp.int32)
        cond_s = jax.ShapeDtypeStruct((B, cfg.n_cond_tokens, cfg.d_model), jnp.float32)

        def step(params, tokens, cache, cond):
            return decode_step(params, cfg, tokens, cache, cond_embeds=cond)

        args = (params_s, tok_s, cache_s, cond_s)
        dp = batch_specs_for_mesh({"t": tok_s, "c": cond_s}, mesh)
        in_sh = (
            named(mesh, p_specs),
            named(mesh, dp["t"]),
            named(mesh, c_specs),
            named(mesh, dp["c"]),
        )
    else:
        tok_s = jax.ShapeDtypeStruct((B, 1), jnp.int32)

        def step(params, tokens, cache):
            return decode_step(params, cfg, tokens, cache)

        args = (params_s, tok_s, cache_s)
        dp = batch_specs_for_mesh({"t": tok_s}, mesh)
        in_sh = (named(mesh, p_specs), named(mesh, dp["t"]), named(mesh, c_specs))

    jitted = jax.jit(step, in_shardings=in_sh, donate_argnums=(2,))
    return jitted, args


def _prefill_cell(spec: ArchSpec, shape: Shape, mesh):
    cfg = spec.model
    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(lambda r: init_model(r, cfg), key)
    batch_s = input_specs(cfg, shape)
    batch_s.pop("labels", None)
    grouped = cfg.shared_attn_every > 0
    p_specs = param_specs(params_s, mesh, grouped_blocks=grouped)
    b_specs = batch_specs_for_mesh(batch_s, mesh)

    def step(params, batch):
        kwargs = {}
        if "img_embeds" in batch:
            kwargs["img_embeds"] = batch["img_embeds"]
        if "cond_embeds" in batch:
            kwargs["cond_embeds"] = batch["cond_embeds"]
        return prefill_step(params, cfg, batch["tokens"], **kwargs)

    jitted = jax.jit(step, in_shardings=(named(mesh, p_specs), named(mesh, b_specs)))
    return jitted, (params_s, batch_s)


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    mesh=None,
    collect_text_cost: bool = True,
) -> Dict[str, Any]:
    """Lower + compile one cell; return the analysis record."""
    spec = get_config(arch)
    shape = next(s for s in SHAPES if s.name == shape_name)
    if not spec.applicable(shape):
        return {
            "arch": arch, "shape": shape_name, "status": "skipped",
            "reason": spec.skip_notes.get(shape_name, "inapplicable"),
        }
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    t0 = time.time()
    if shape.kind == "train":
        jitted, args = _train_cell(spec, shape, mesh)
    elif shape.kind == "decode":
        jitted, args = _decode_cell(spec, shape, mesh)
    else:
        jitted, args = _prefill_cell(spec, shape, mesh)

    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": list(mesh.devices.shape),
        "axes": list(mesh.axis_names),
        "devices": int(n_dev),
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            # live bytes per device at peak ≈ args + temps (aliased args
            # are donated so not double counted)
            "per_device_bytes": int(
                mem.argument_size_in_bytes
                + mem.temp_size_in_bytes
                + mem.output_size_in_bytes
                - mem.alias_size_in_bytes
            ),
            "hbm_bytes": HW.HBM_BYTES,
        },
        "xla_cost": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        },
    }
    rec["memory"]["fits_hbm"] = rec["memory"]["per_device_bytes"] <= HW.HBM_BYTES
    # XLA:CPU's buffer assignment double-buffers while-loop carries that the
    # TPU memory-aware scheduler aliases in place (verified: the largest
    # temp allocation contains a second copy of the loop-carried state —
    # decode caches / gradient accumulators).  Report a corrected bound
    # that removes ONE duplicate of the donated carry (= output bytes).
    corrected = rec["memory"]["per_device_bytes"] - min(
        rec["memory"]["temp_bytes"], rec["memory"]["output_bytes"]
    )
    rec["memory"]["tpu_corrected_bytes"] = int(corrected)
    rec["memory"]["fits_hbm_corrected"] = corrected <= HW.HBM_BYTES
    if collect_text_cost:
        cost = analyze_hlo(compiled.as_text())
        rec["hlo_cost"] = {
            "flops": cost.flops,                    # per device, loop-aware
            "hbm_bytes": cost.bytes,
            "collectives": {k: float(v) for k, v in cost.collectives.items()},
            "collective_bytes": cost.collective_bytes,
        }
    cfg = spec.model
    rec["model"] = {
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "tokens_per_step": shape.global_batch
        * (shape.seq_len if shape.kind in ("train", "prefill") else 1),
    }
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--no-text-cost", action="store_true")
    args = ap.parse_args()

    cells = []
    archs = list_archs() if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in SHAPES] if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for mp in meshes:
        mesh = make_production_mesh(multi_pod=mp)
        for arch in archs:
            for shape in shapes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                try:
                    rec = run_cell(
                        arch, shape, multi_pod=mp, mesh=mesh,
                        collect_text_cost=not args.no_text_cost,
                    )
                except Exception as e:  # a cell failure is a system bug
                    rec = {
                        "arch": arch, "shape": shape, "status": "FAILED",
                        "mesh": "multi" if mp else "single",
                        "error": f"{type(e).__name__}: {e}",
                        "trace": traceback.format_exc()[-2000:],
                    }
                    failures += 1
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=2)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    gb = rec["memory"]["per_device_bytes"] / (1 << 30)
                    gbc = rec["memory"]["tpu_corrected_bytes"] / (1 << 30)
                    extra = (
                        f" mem/dev={gb:.2f}GiB (corr {gbc:.2f}) "
                        f"fits={rec['memory']['fits_hbm_corrected']}"
                        f" compile={rec['compile_s']}s"
                    )
                print(f"[{tag}] {status}{extra}", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
