import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this script:

1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod) over
   512 forced host devices,
2. constructs ShapeDtypeStruct stand-ins for params / optimizer state /
   batch / decode cache (``jax.eval_shape`` — nothing is allocated),
3. ``jax.jit(step, in_shardings=…, out_shardings=…).lower(...).compile()``,
4. records ``memory_analysis()``, ``cost_analysis()`` and the collective
   bytes parsed from the compiled (SPMD-partitioned, per-device) HLO,
5. writes a JSON artifact under artifacts/dryrun/ for the roofline report.

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the framework — the CI gate for "would this run at scale".

Usage::

    python -m repro.launch.dryrun --arch gemma_2b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out artifacts/dryrun]
"""
import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, SHAPES, get_config, input_specs
from repro.configs.base import ArchConfig, ShapeSpec
from repro.distributed import sharding as sh
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.optim.optimizer import AdamWConfig, init_opt_state
from repro.training.trainer import make_train_step

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(f32|f16|bf16|s32|u32|s8|u8|pred|s64|f64)\[([\d,]*)\]")
_BYTES = {"f32": 4, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "f64": 8}


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum per-device operand bytes of every collective op in the HLO."""
    totals = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(r"=\s+\S+\s+([a-z0-9-]+)\(", stripped)
        if not m:
            continue
        op = m.group(1)
        if op.endswith("-start"):
            op = op[: -len("-start")]
        if op not in _COLLECTIVES:
            continue
        # operand shapes appear inside the call parens; take them, falling
        # back to the output shape when operands carry no inline types.
        paren = stripped[stripped.index("(") :]
        shapes = _SHAPE_RE.findall(paren)
        if not shapes:
            shapes = _SHAPE_RE.findall(stripped)[:1]
        nbytes = 0
        for dt, dims in shapes:
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _BYTES[dt]
        totals[op] += nbytes
        counts[op] += 1
    return {"bytes_per_device": totals, "counts": counts,
            "total_bytes_per_device": sum(totals.values())}


def _spec_or_none(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


def build_cell(cfg: ArchConfig, shape: ShapeSpec, mesh,
               microbatches: int = 1):
    """Returns (jitted step, abstract args)."""
    batch_tree = input_specs(cfg, shape)
    params_shape = jax.eval_shape(
        lambda: model_lib.init_params(jax.random.PRNGKey(0), cfg))
    p_spec = sh.param_specs(cfg, params_shape, mesh)
    p_shard = sh.named_shardings(mesh, p_spec)
    b_shard = sh.named_shardings(mesh, sh.batch_specs(mesh, batch_tree))

    if shape.kind == "train":
        opt_shape = jax.eval_shape(init_opt_state, params_shape)
        o_spec = {"m": p_spec, "v": p_spec, "step": jax.sharding.PartitionSpec()}
        o_shard = sh.named_shardings(mesh, o_spec)
        opt_cfg = AdamWConfig()
        step_fn = make_train_step(cfg, opt_cfg, microbatches=microbatches)
        metric_shard = sh.named_shardings(
            mesh, jax.tree.map(lambda _: jax.sharding.PartitionSpec(),
                               {"loss": 0, "ce": 0, "aux": 0, "tokens": 0,
                                "grad_norm": 0, "lr": 0}))
        jitted = jax.jit(step_fn,
                         in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, metric_shard),
                         donate_argnums=(0, 1))
        args = (params_shape, opt_shape, batch_tree)
        return jitted, args

    logits_shape = (shape.global_batch, cfg.vocab)
    logits_shard = jax.sharding.NamedSharding(
        mesh, sh.fit_spec(mesh, [sh.batch_axes(mesh), "model"], logits_shape))

    if shape.kind == "prefill":
        def prefill_fn(params, batch):
            return model_lib.prefill(params, batch, cfg)

        cache_shape = jax.eval_shape(
            lambda: model_lib.init_cache(cfg, shape.global_batch,
                                         shape.seq_len))
        c_shard = sh.named_shardings(mesh, sh.cache_specs(cfg, mesh,
                                                          cache_shape))
        jitted = jax.jit(prefill_fn, in_shardings=(p_shard, b_shard),
                         out_shardings=(logits_shard, c_shard))
        return jitted, (params_shape, batch_tree)

    # decode
    def decode_fn(params, batch, cache):
        return model_lib.decode(params, batch, cache, cfg)

    cache_shape = jax.eval_shape(
        lambda: model_lib.init_cache(cfg, shape.global_batch, shape.seq_len))
    c_shard = sh.named_shardings(mesh, sh.cache_specs(cfg, mesh, cache_shape))
    jitted = jax.jit(decode_fn,
                     in_shardings=(p_shard, b_shard, c_shard),
                     out_shardings=(logits_shard, c_shard),
                     donate_argnums=(2,))
    return jitted, (params_shape, batch_tree, cache_shape)


def _compile_and_measure(cfg, shape, mesh,
                         microbatches: int = 1) -> Dict[str, Any]:
    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted, args = build_cell(cfg, shape, mesh, microbatches)
        lowered = jitted.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    return {
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "memory_analysis": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
        "cost_analysis": {
            "flops_per_device": cost.get("flops"),
            "bytes_per_device": cost.get("bytes accessed"),
            "transcendentals": cost.get("transcendentals"),
        },
        "collectives": collective_bytes(hlo),
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Optional[str] = None,
             cfg_overrides: Optional[dict] = None,
             scan_correction: bool = True,
             microbatches: int = 1,
             tag: Optional[str] = None) -> Dict[str, Any]:
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]

    if shape_name == "long_500k" and not cfg.is_subquadratic:
        rec = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
               "status": "skipped",
               "reason": "full-attention arch: O(S^2) at 512k "
                         "(see DESIGN.md §Arch-applicability)"}
        _dump(rec, out_dir, arch, shape_name, multi_pod)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "n_devices": mesh.devices.size,
    }
    record["microbatches"] = microbatches
    try:
        full = _compile_and_measure(cfg, shape, mesh, microbatches)
        record.update(full)
        record["status"] = "ok"
        record["model_params"] = cfg.n_params()
        record["model_active_params"] = cfg.n_active_params()

        if scan_correction and cfg.scan_layers and cfg.n_layers > cfg.period:
            # XLA's HloCostAnalysis counts a while (scan) body ONCE, not
            # trip-count times, so the full compile undercounts the layer
            # stack.  Measure two *unrolled* reduced-depth variants (no
            # while loop): group_cost = cost(2 periods) - cost(1 period);
            # corrected total = outside + group_cost · (n_layers / period).
            c1 = _compile_and_measure(
                dataclasses.replace(cfg, n_layers=cfg.period,
                                    scan_layers=False), shape, mesh,
                microbatches)
            c2 = _compile_and_measure(
                dataclasses.replace(cfg, n_layers=2 * cfg.period,
                                    scan_layers=False), shape, mesh,
                microbatches)
            n_units = cfg.n_layers / cfg.period  # fractional tail counted

            def corrected(path_fn):
                v1, v2 = path_fn(c1) or 0, path_fn(c2) or 0
                group = max(0.0, v2 - v1)
                outside = max(0.0, v1 - group)
                return outside + group * n_units, group

            flops_t, flops_g = corrected(
                lambda c: c["cost_analysis"]["flops_per_device"])
            bytes_t, bytes_g = corrected(
                lambda c: c["cost_analysis"]["bytes_per_device"])
            coll_t, coll_g = corrected(
                lambda c: c["collectives"]["total_bytes_per_device"])
            record["scan_corrected"] = {
                "n_groups": cfg.n_layers // cfg.period,
                "flops_per_device": flops_t,
                "bytes_per_device": bytes_t,
                "collective_bytes_per_device": coll_t,
                "group_flops_per_device": flops_g,
                "group_bytes_per_device": bytes_g,
                "group_collective_bytes_per_device": coll_g,
            }
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        record.update({"status": "failed", "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]})
    _dump(record, out_dir, arch, shape_name, multi_pod, tag)
    return record


def _dump(record, out_dir, arch, shape_name, multi_pod, tag=None):
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        base = f"{arch}.{shape_name}.{'multipod' if multi_pod else 'pod'}"
        if tag:
            base += f".{tag}"
        with open(os.path.join(out_dir, base + ".json"), "w") as f:
            json.dump(record, f, indent=2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    cells = []
    archs = ARCH_NAMES if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    for a, s, mp in cells:
        rec = run_cell(a, s, mp, out_dir=args.out)
        status = rec["status"]
        extra = ""
        if status == "ok":
            c = rec.get("scan_corrected", None)
            flops = (c["flops_per_device"] if c
                     else rec["cost_analysis"]["flops_per_device"])
            coll = (c["collective_bytes_per_device"] if c
                    else rec["collectives"]["total_bytes_per_device"])
            extra = (f" flops/dev={flops:.3e}"
                     f" coll/dev={coll:.3e}B"
                     f" compile={rec['compile_s']}s")
        elif status == "failed":
            extra = " " + rec["error"][:160]
        print(f"[{status:>7}] {a} × {s} × "
              f"{'2x16x16' if mp else '16x16'}{extra}", flush=True)


if __name__ == "__main__":
    main()
