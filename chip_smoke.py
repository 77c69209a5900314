"""Smoke run of the main path on a TPU, at gemma-2b's published width.

Run from the root of the repository, on a machine with a TPU::

    python3 chip_smoke.py               # one chip: the serving path
    python3 chip_smoke.py --four-chips  # four chips: sharded training only

One chip.  The script builds the serving engine the way
``repro.launch.serve`` does (``get_config`` -> ``init_serving_params`` ->
``ServingEngine`` -> ``submit`` -> ``run``) for gemma-2b at full width
(18 layers, d_model 2048, MQA with head_dim 256, d_ff 16384, vocab
256000, bf16 format policy, random weights from ``--seed``), twice in
one process over one set of parameters:

- phase ``xla``: the config as shipped (``gemm_backend="xla"``);
- phase ``kernels``: ``gemm_backend="pallas"`` -- graph programs, the plan
  cache, split-K, grouped q/k/v and paged flash-decode, all compiled to
  Mosaic.

Each phase serves 8 seeded requests (prompts of 64 to 256 tokens, 32
greedy output tokens each) on 4 slots with a 256-token prefill window in
128-token chunks, 16-token pages and a 512-token cache.

Both phases' prefill logits for one prompt (``models.model.prefill``)
and the logits of their first decode step (the engine's own decode
program: split-K, grouped q/k/v and paged flash-decode in the kernel
phase) are compared.

Four chips.  Three training steps of gemma-2b at full width through
``repro.launch.train.train_loop`` on a (data=2, model=2) mesh, against
the same steps on (data=1, model=4): the losses, the parameters after
the last step, and where the training state sits on the chips.

The seconds printed include compilation: this is a smoke run, not a
benchmark.  The script fails (non-zero exit, no ``"ok"`` line) when no
TPU is found, when a request ends other than ok with 32 tokens, when the
kernel phase's decode program holds no Mosaic kernel, or when a
comparison below exceeds its tolerance.  The last line of a passing run
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_REQUESTS = 8
PROMPT_LEN = (64, 256)          # inclusive range of prompt lengths
MAX_TOKENS = 32
SLOTS = 4                       # the launcher's default
PREFILL_LEN = 256
PREFILL_CHUNK = 128
PAGE_SIZE = 16
CACHE_LEN = 512

# Both phases run bf16 operands with f32 accumulation and round every
# activation to bf16 (one ulp is 2**-8, 0.4%).  They differ in
# accumulation order (split-K partials, block shapes, XLA's fused dots),
# so the same activation can round to neighbouring bf16 values, and over
# 18 layers these one-ulp flips compound.  Run at a tiny width with 18
# layers on the CPU, the two phases differ by 1.57e-2 of the largest
# logit in prefill and 1.21e-2 in the first decode step; with paged
# flash-decode's sequence mask dropped, the decode gap is 5.7e-2.  At
# full width on a TPU v5e the sound gaps are 7.1e-3 and 7.2e-3.
LOGIT_RTOL = 3e-2

TRAIN_STEPS = 3
TRAIN_BATCH = 2
TRAIN_SEQ = 256
# The two meshes compute the same steps with different reduction orders
# in bf16 activations, and Adam's normalised first steps turn those
# last-bit differences into whole updates of some weights.  On a TPU v5e
# (2x2), a sound run's losses differ by 1.49e-4 relative and its
# parameters by 4.3e-2 of the update.  With the gradient of the first
# data shard only (the exchange over the data axis left out), they
# differ by 1.81e-2 and 7.7e-1.  Each tolerance sits between the two.
LOSS_RTOL = 1.5e-3
UPDATE_RTOL = 2e-1


class CompileClock:
    """Seconds JAX spends lowering and compiling programs.

    Tracing is left out: a jitted function traced inside another reports
    its own trace time within the outer one's, so trace times overlap.
    """

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event in self.EVENTS:
            self.seconds += duration
            self.programs += event == self.EVENTS[-1]


def make_prompts(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    lengths = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1, size=N_REQUESTS)
    return [rng.integers(0, vocab, size=int(n), dtype=np.int32)
            for n in lengths]


def peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def decode_program_text(engine) -> str:
    """HLO text of the engine's compiled decode+sample program."""
    import jax.numpy as jnp
    table = np.full((engine.slots, engine.sched.max_pages_per_seq), -1,
                    np.int32)
    active = np.zeros(engine.slots, bool)
    batch = engine._make_batch(
        engine._last_tok, pos=engine.slot_pos, table=table,
        row_valid=active if engine._stateful_rows else None)
    lowered = engine._decode_step.lower(
        engine.params, batch, engine.cache, engine._zero_key,
        jnp.zeros(engine.slots, jnp.float32), jnp.asarray(active))
    return lowered.compile().as_text()


def prefill_logits(cfg, params, prompt) -> np.ndarray:
    """Last-position logits of ``models.model.prefill`` over the engine's
    left-padded prefill window of ``prompt``."""
    import jax
    from repro.models import model as model_lib
    window = np.pad(prompt[-PREFILL_LEN:],
                    (PREFILL_LEN - min(len(prompt), PREFILL_LEN), 0))
    fn = jax.jit(lambda p, t: model_lib.prefill(p, {"tokens": t}, cfg)[0])
    return np.asarray(fn(params, window[None]), np.float32)[0]


def record_first_decode(engine):
    """Wrap the engine's decode program so that its first launch keeps
    its input tokens, positions and active rows and its fp32 logits.
    Returns the dict they go into and a function that unwraps."""
    seen = {}
    step = engine._decode_step

    def recording(params, batch, cache, key, temps, active):
        out = step(params, batch, cache, key, temps, active)
        if not seen:
            seen.update(tokens=np.asarray(batch["tokens"]),
                        pos=np.asarray(batch["pos"]),
                        active=np.asarray(active),
                        logits=np.asarray(out[2], np.float32))
        return out

    engine._decode_step = recording
    return seen, lambda: setattr(engine, "_decode_step", step)


def serve_phase(label, cfg, params, prompts, clock, device):
    """Serve ``prompts`` on a fresh engine; returns (engine, outputs,
    first decode step, ok)."""
    from repro.core import autotune
    from repro.serving.engine import Request, ServingEngine

    c0, n0, t0 = clock.seconds, clock.programs, time.perf_counter()
    engine = ServingEngine(params, cfg, slots=SLOTS, cache_len=CACHE_LEN,
                           prefill_len=PREFILL_LEN,
                           prefill_chunk=PREFILL_CHUNK, page_size=PAGE_SIZE)
    first, unwrap = record_first_decode(engine)
    for rid, prompt in enumerate(prompts):
        engine.submit(Request(rid=rid, prompt=prompt, max_tokens=MAX_TOKENS))
    out = engine.run()
    unwrap()
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    print(f"[{label}] smoke timing, not a benchmark: {wall:.1f} s in all, "
          f"{compile_s:.1f} s of it lowering and compiling "
          f"{clock.programs - n0} programs, {wall - compile_s:.1f} s the rest")
    print(f"[{label}] peak_bytes_in_use {peak_bytes(device)}")
    ok = len(out) == len(prompts)
    for rid in range(len(prompts)):
        r = out.get(rid)
        status, n = (r.status, len(r)) if r is not None else ("missing", 0)
        good = status == "ok" and n == MAX_TOKENS
        ok &= good
        print(f"[{label}] req {rid}: prompt {len(prompts[rid])} tokens, "
              f"status {status}, {n} tokens{'' if good else '  <-- FAIL'}")
    print(f"[{label}] plan cache {autotune.cache_stats().as_dict()}")
    return engine, out, first, ok


def logits_agree(what, got, want) -> bool:
    """Print the relative max difference of ``got`` from ``want``; True
    when it is finite and within ``LOGIT_RTOL``."""
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    ok = bool(np.isfinite(rel)) and rel <= LOGIT_RTOL
    print(f"{what}: relative max difference kernels vs xla {rel:.3e} "
          f"(tolerance {LOGIT_RTOL:.0e}){'' if ok else '  <-- FAIL'}")
    return ok


def first_decode_agrees(xla: dict, kernels: dict) -> bool:
    """Compare the two phases' first decode step over its active rows.
    Both phases admit and prefill the same requests in the same order,
    so that step has the same inputs in both unless prefill diverged."""
    same_inputs = bool(xla) and bool(kernels) and all(
        np.array_equal(xla[k], kernels[k]) for k in ("tokens", "pos",
                                                      "active"))
    if not same_inputs:
        print("first decode step: the phases reached it with different "
              "inputs  <-- FAIL")
        return False
    rows = xla["active"]
    print(f"first decode step: {int(rows.sum())} active rows of "
          f"{rows.size}, positions {xla['pos'][rows].tolist()}")
    return logits_agree("first decode step logits",
                        kernels["logits"][rows], xla["logits"][rows])


def serve_smoke(seed: int, clock, device) -> bool:
    import jax
    from repro.configs import get_config
    from repro.models import model as model_lib
    from repro.serving.engine import init_serving_params

    cfg_xla = get_config("gemma_2b")
    cfg_kernels = dataclasses.replace(cfg_xla, gemm_backend="pallas")
    # One set of parameters for both phases, built as the launcher
    # builds them: straight into bf16 GEMM weights, since the fp32 tree
    # would take 10 GB of the chip's 16 GB.
    params = init_serving_params(jax.random.PRNGKey(seed), cfg_xla)
    n_params = model_lib.param_count(params)
    print(f"gemma_2b: {n_params} parameters, "
          f"{sum(x.nbytes for x in jax.tree.leaves(params))} bytes held")
    prompts = make_prompts(cfg_xla.vocab, seed)

    engine, out_xla, first_xla, ok_xla = serve_phase(
        "xla", cfg_xla, params, prompts, clock, device)
    del engine
    gc.collect()
    logits_xla = prefill_logits(cfg_xla, params, prompts[0])

    engine, out_kernels, first_kernels, ok_kernels = serve_phase(
        "kernels", cfg_kernels, params, prompts, clock, device)
    mosaic = "tpu_custom_call" in decode_program_text(engine)
    print(f"[kernels] decode program contains tpu_custom_call: {mosaic}")
    del engine
    gc.collect()
    logits_kernels = prefill_logits(cfg_kernels, params, prompts[0])

    prefill_ok = logits_agree("prefill logits, request 0", logits_kernels,
                              logits_xla)
    decode_ok = first_decode_agrees(first_xla, first_kernels)
    same = sum(list(out_xla.get(rid, ())) == list(out_kernels.get(rid, ()))
               for rid in range(len(prompts)))
    print(f"greedy outputs identical in both phases: {same}/{len(prompts)} "
          f"requests (informational)")
    return ok_xla and ok_kernels and mosaic and prefill_ok and decode_ok


def state_bytes(before: dict, devices):
    """Bytes held on each of ``devices``, and in all, by the arrays live
    now that were not live in ``before`` (id -> array)."""
    import jax
    held = {d: 0 for d in devices}
    whole = 0
    for a in jax.live_arrays():
        if id(a) not in before:
            whole += a.nbytes
            for shard in a.addressable_shards:
                held[shard.device] += shard.data.nbytes
    return [held[d] for d in devices], whole


def update_gap(cfg, seed, mesh, a, b):
    """||a - b|| / ||b - p0|| over the whole tree, where ``p0`` is the
    initialisation both runs started from: how far the two meshes'
    parameters part, against how far training moved them."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as model_lib

    shardings = jax.tree.map(lambda x: x.sharding, b)
    a = jax.device_put(a, shardings)
    with jax.set_mesh(mesh):
        p0 = jax.jit(lambda key: model_lib.init_params(key, cfg),
                     out_shardings=shardings)(jax.random.PRNGKey(seed))

    def norm(xs):
        return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in xs))

    @jax.jit
    def gap(a, b, p0):
        a, b, p0 = (jax.tree.leaves(t) for t in (a, b, p0))
        return (norm(x - y for x, y in zip(a, b))
                / norm(y - z for y, z in zip(b, p0)))

    return float(gap(a, b, p0))


def four_chip_train(seed: int, clock, devices) -> bool:
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train_loop

    cfg = get_config("gemma_2b")
    losses, params = {}, {}
    ok = True
    for data, model in ((2, 2), (1, 4)):
        label = f"data={data},model={model}"
        mesh = make_host_mesh(data, model)
        before = {id(a): a for a in jax.live_arrays()}
        state = {}

        def log(msg, label=label, before=before, state=state):
            print(f"[{label}] {msg}")
            if not state:
                # Logged after step 0, while the parameters and both of
                # Adam's moments are live.
                state["held"], state["whole"] = state_bytes(before, devices)

        c0, t0 = clock.seconds, time.perf_counter()
        params[label], losses[label] = train_loop(
            cfg, steps=TRAIN_STEPS, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
            mesh=mesh, seed=seed, log=log)
        wall = time.perf_counter() - t0
        print(f"[{label}] smoke timing, not a benchmark: {wall:.1f} s in "
              f"all, {clock.seconds - c0:.1f} s of it lowering and compiling")
        print(f"[{label}] losses {losses[label]}")
        # Sharded, the parameters and both moments put a quarter of
        # themselves on each chip (norm scales are replicated).  A
        # replicated moment alone puts two thirds of the state on one.
        spread = max(state["held"]) <= 0.3 * state["whole"]
        ok &= spread
        print(f"[{label}] training state after step 0 (parameters and "
              f"Adam's moments): bytes per device {state['held']} of "
              f"{state['whole']}{'' if spread else '  <-- FAIL: not sharded'}")
        print(f"[{label}] peak_bytes_in_use per device (process peak so "
              f"far) {[peak_bytes(d) for d in devices]}")
    a, b = losses.values()
    rel = float(np.max(np.abs(np.subtract(a, b)) / np.abs(a)))
    agree = len(a) == len(b) == TRAIN_STEPS and rel <= LOSS_RTOL
    print(f"losses (data=2,model=2) vs (data=1,model=4): relative max "
          f"difference {rel:.3e} (tolerance {LOSS_RTOL:.1e})"
          f"{'' if agree else '  <-- FAIL'}")
    gap = update_gap(cfg, seed, mesh, params.pop("data=2,model=2"),
                     params.pop("data=1,model=4"))
    moved = bool(np.isfinite(gap)) and gap <= UPDATE_RTOL
    print(f"parameters after step {TRAIN_STEPS - 1}, (data=2,model=2) vs "
          f"(data=1,model=4): ||difference|| / ||update|| {gap:.3e} "
          f"(tolerance {UPDATE_RTOL:.1e}){'' if moved else '  <-- FAIL'}")
    return ok and agree and moved


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded training check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package ({e}); run "
              f"from the root of a checkout", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX runs on {dev.platform}); "
              f"nothing was run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind} x {len(devices)} ({dev.platform}); "
          f"compile cache {cache_dir}")
    clock = CompileClock()
    if args.four_chips:
        ok = four_chip_train(args.seed, clock, devices[:4])
    else:
        ok = serve_smoke(args.seed, clock, dev)
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
