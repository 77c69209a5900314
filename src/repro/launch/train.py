"""Training launcher.

Composes: config → mesh → sharded init (or elastic checkpoint restore) →
jit'd train step (donated buffers) → data pipeline → async checkpointing →
watchdog + supervisor fault handling.

Examples::

    # CPU-scale smoke training (reduced config):
    PYTHONPATH=src python -m repro.launch.train --arch gemma_2b --reduced \
        --steps 50 --batch 8 --seq 128

    # Supervised run with restart-on-failure:
    PYTHONPATH=src python -m repro.launch.train --arch gemma_2b --reduced \
        --steps 200 --supervise --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticDataset
from repro.distributed import sharding as sh
from repro.distributed.fault import StepWatchdog, supervise
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_elastic_mesh
from repro.models import model as model_lib
from repro.optim.optimizer import AdamWConfig, init_opt_state
from repro.training.trainer import (
    make_train_step, plan_cache_snapshot, restore_plan_cache,
)

__all__ = ["train_loop", "main"]


def train_loop(cfg, *, steps: int, batch: int, seq: int, lr: float = 3e-4,
               microbatches: int = 1, ckpt_dir=None, ckpt_every: int = 50,
               step_timeout_s: float = 600.0, mesh=None, log=print,
               seed: int = 0):
    mesh = mesh or make_elastic_mesh(model_parallel=1)
    opt_cfg = AdamWConfig(lr=lr, total_steps=steps)
    data = SyntheticDataset(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                       global_batch=batch, seed=seed))
    ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None

    with jax.set_mesh(mesh):
        params_shape = jax.eval_shape(
            lambda: model_lib.init_params(jax.random.PRNGKey(seed), cfg))
        p_spec = sh.param_specs(cfg, params_shape, mesh)
        p_shard = sh.named_shardings(mesh, p_spec)
        # Adam's moments are laid out like the parameters.  The compiler
        # cannot infer that for zeros, so it is stated.
        o_shard = sh.named_shardings(
            mesh, {"m": p_spec, "v": p_spec,
                   "step": jax.sharding.PartitionSpec()})

        start_step = 0
        if ckpt and ckpt.latest_step() is not None:
            opt_shape = jax.eval_shape(init_opt_state, params_shape)
            params, opt_state, manifest = ckpt.restore(
                None, (params_shape, opt_shape), (p_shard, o_shard))
            start_step = int(manifest["step"])
            data = SyntheticDataset.restore(
                data.cfg, manifest["extra"].get("data", data.state()))
            n_plans = restore_plan_cache(manifest.get("gemm_plans"))
            log(f"[train] restored step {start_step} "
                f"(elastic mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}"
                f"{f', {n_plans} warm GEMM plans' if n_plans else ''})")
        else:
            init_fn = jax.jit(
                lambda key: model_lib.init_params(key, cfg),
                out_shardings=p_shard)
            params = init_fn(jax.random.PRNGKey(seed))
            opt_state = jax.jit(init_opt_state,
                                out_shardings=o_shard)(params)

        step_fn = jax.jit(make_train_step(cfg, opt_cfg, microbatches),
                          donate_argnums=(0, 1))
        watchdog = StepWatchdog(step_timeout_s)

        losses = []
        gemm_plans = None
        for step in range(start_step, steps):
            watchdog.check()
            watchdog.arm()
            batch_data = data.batch(step)
            t0 = time.time()
            params, opt_state, metrics = step_fn(params, opt_state,
                                                 batch_data)
            loss = float(metrics["loss"])
            watchdog.disarm()
            losses.append(loss)
            if gemm_plans is None:
                # The first executed step traced every GEMM in the model,
                # so the plan cache now holds the full per-(shape, format)
                # training plan set — snapshot once, persist with every
                # checkpoint.
                gemm_plans = plan_cache_snapshot() or {}
            if step % 10 == 0 or step == steps - 1:
                log(f"[train] step {step} loss {loss:.4f} "
                    f"gnorm {float(metrics['grad_norm']):.3f} "
                    f"({time.time() - t0:.2f}s)")
            if np.isnan(loss):
                raise FloatingPointError(f"NaN loss at step {step}")
            if ckpt and (step + 1) % ckpt_every == 0:
                ckpt.save_async(step + 1, params, opt_state,
                                extra={"data": data.state()},
                                gemm_plans=gemm_plans or None)
        if ckpt:
            ckpt.save(steps, params, opt_state,
                      extra={"data": data.state()},
                      gemm_plans=gemm_plans or None)
            ckpt.wait()
        watchdog.stop()
        return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--supervise", action="store_true")
    ap.add_argument("--gemm-backend", default=None,
                    choices=[None, "xla", "pallas"])
    ap.add_argument("--format-policy", default=None,
                    choices=[None, "fp32", "bf16", "bf16acc", "int8"])
    ap.add_argument("--no-graph", action="store_true",
                    help="eager per-GEMM dispatch instead of compiled "
                         "repro.graph programs (debugging escape hatch; "
                         "compiled is the default)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.gemm_backend:
        cfg = dataclasses.replace(cfg, gemm_backend=args.gemm_backend)
    if args.format_policy:
        cfg = dataclasses.replace(cfg, format_policy=args.format_policy)
    if args.no_graph:
        cfg = dataclasses.replace(cfg, use_graph=False)

    def run(attempt: int):
        train_loop(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                   lr=args.lr, microbatches=args.microbatches,
                   ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)

    if args.supervise:
        supervise(run)
    else:
        run(0)


if __name__ == "__main__":
    main()
