"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``, ``bench/`` and
the program under ``src/``, on a machine with the TPU chips the cell asks
for (it exits non-zero, printing no result, without them). The cell's
configuration, traffic mix, sizing and metric readers are found by name
(see ``bench/spec.py``). Set-up makes the weights from the seed, builds the
engine and warms up every program the window runs; the window serves the
mix for ``--seconds``; then the served tokens are compared with the plain
float32 reference. ``--trace 1`` records part of the window with the
profiler and reports the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its limit
(also the last lines of standard error). The line before it,
``bench-info``, has the generator's lateness, the compilations inside the
window and the counters; the same goes to ``.bench_runs/``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]
# The TPU runtime's own logs would go to a fixed directory under /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import spec  # noqa: E402

RUNS = REPO / ".bench_runs"
# JAX's persistent compilation cache: a fixed directory inside the
# checkout, whatever the environment says, so that only a cell's first run
# in a checkout compiles and two checkouts share nothing.
CACHE = REPO / ".jax_compile_cache"
# A traced run's profiler starts 30% of the way into the window and
# records a tenth of it.
TRACE_AT, TRACE_SHARE = 0.3, 0.1


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(cell: spec.Cell, seed: int, seconds: float, traced: bool,
            peaks, devices, *, control: bool = False,
            t_start: float = T_START) -> dict:
    """Everything of a run after the look for a chip: set-up, window,
    comparison, metrics. Returns the result line as a dict, with the run's
    other records under ``info``."""
    from bench import check, generator, harness, stats, trace, work
    if seed < 0:
        raise ValueError("--seed must be a non-negative whole number")
    ref = cell.module("reference", cell.config["reference"])
    shape = ref.shape(cell.config)
    cfg = spec.arch_config(cell)
    e = cell.sizing["engine"]
    reqs = generator.make_requests(cell.traffic, seed, seconds, cfg.vocab)
    longest = generator.longest_prompt(cell.traffic)
    most_out = cell.traffic["output"]["max"]
    if longest > e["prefill_len"] or e["prefill_len"] + most_out > e["cache_len"]:
        raise spec.SpecError(
            f"{cell.name}: prompts up to {longest} and outputs up to "
            f"{most_out} do not fit prefill_len {e['prefill_len']} and "
            f"cache_len {e['cache_len']}")

    counter = harness.CompileCounter()
    params = harness.make_params(cfg, ref, shape, seed)
    engine = harness.build_engine(cell, cfg, params, seed)
    harness.warm_up(engine, cell, seed, cfg.vocab)
    trace_dir = None
    if traced:
        trace_dir = RUNS / "trace" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = harness.run_window(
        engine, reqs, seconds, counter=counter,
        preroll_steps=int(cell.sizing.get("preroll_steps", 0)),
        trace_at=seconds * TRACE_AT,
        trace_seconds=seconds * TRACE_SHARE,
        trace_dir=None if trace_dir is None else str(trace_dir))
    setup_s = win.open - t_start
    stats_ = devices[0].memory_stats() or {}
    memory_peak = int(stats_.get("peak_bytes_in_use", 0))
    # The program's state goes before the reference runs on the chip.
    del engine, params
    gc.collect()
    freed_to = int((devices[0].memory_stats() or {}).get("bytes_in_use", 0))

    # Every request due in the window, and every queued one it served.
    attempted = [t for t in win.tracks
                 if t.submitted >= win.open or any(
                     win.open < s <= win.close for s in t.stamps)]
    failed = sum(1 for t in attempted
                 if win.statuses.get(t.gen.rid) not in ("ok", "incomplete"))
    chk = cell.sizing["check"]
    sample = check.draw(win, e["prefill_len"], seed, chk["requests"])
    dims = (e["prefill_len"] + most_out - 1, chk["requests"], most_out)
    readings = check.widest_gap(ref, shape, seed, sample, dims,
                                control=control)
    correct, checks = check.verdict(readings, failed, chk["limits"])
    control_correct = None
    if control:
        control_correct, _ = check.verdict(check.control_readings(readings),
                                           failed, chk["limits"])

    summary = None
    if traced:
        ops, spans, tw = trace.load(trace.find_xplane(trace_dir),
                                    device=_plane(devices[0]))
        if tw is None:
            raise RuntimeError("the trace holds no bench.traced span")
        summary = trace.summarize(ops, spans, tw.start, tw.end)
    run = stats.Run(window=win, shape=shape, peaks=peaks, sizing=cell.sizing,
                    setup_s=setup_s, trace=summary)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = m.reader(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    out = {"correct": correct, "attempted": len(attempted),
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["checks"] = checks
    c0, c1 = win.counters_open, win.counters_close
    out["info"] = {
        "cell": cell.name, "seed": seed, "seconds": win.seconds,
        "requests": len(reqs), "steps": win.steps,
        "compiles_in_window": win.compiles,
        "generator_late_max_s": win.late_max_s,
        "generator_late_mean_s": win.late_mean_s,
        "readings": readings,
        "control_correct": control_correct,
        "counters": {k: c1[k] - c0[k] for k in (
            "decode_steps", "active_step_sum", "decode_tokens",
            "prefill_tokens", "cached_prefill_tokens", "preemptions",
            "completed_requests", "cancelled_requests") + work.COUNTERS
            if k in c1},
        "setup_s": setup_s,
        "bytes_in_use_before_reference": freed_to,
    }
    if win.traced is not None:
        t = win.traced
        w = stats.traced_work(run)
        out["info"]["traced"] = {
            "seconds": t["close"] - t["open"], "chunks": t["chunks"],
            "decode_steps": t["counters_close"]["decode_steps"]
            - t["counters_open"]["decode_steps"],
            "least_s": w.least_s, "flops": w.flops, "bytes": w.bytes,
            "matmul_s": summary.matmul_s, "ops": summary.n_ops}
    return out


def _plane(device) -> str:
    return f"/device:{device.platform.upper()}:{device.id}"


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices, with the compilation cache on; None
    (and a note on standard error) where JAX finds fewer."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: no accelerator: {e}", file=sys.stderr)
        return None
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"bench: {chips} TPU chip(s) needed; JAX finds "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None
    return devices[:chips]


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    devices = tpu_devices(cell.chips)
    if devices is None:
        return 3
    from bench import peaks as peaks_lib
    peaks = peaks_lib.lookup(devices[0].device_kind)
    out = execute(cell, args.seed, args.seconds, bool(args.trace), peaks,
                  devices)
    info = out.pop("info")
    RUNS.mkdir(exist_ok=True)
    record = dict(out, info=info)
    with open(RUNS / f"{cell.name}.{args.seed}.{args.trace}.json", "w") as f:
        json.dump(record, f, indent=1)
    short = dict(info, readings={k: v for k, v in info["readings"].items()
                                 if not isinstance(v, list)})
    print("bench-info " + json.dumps(short), flush=True)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
