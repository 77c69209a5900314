"""Find the highest rate an open-loop cell sustains: a sweep on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 4,6,8

One process sets the cell up once, then serves the cell's mix at each
offered rate for ``--seconds`` and prints, per rate, the output rate, the
time-to-first-token percentiles and how many requests were left
unfinished at the close (a queue that grows across the window marks a
rate above what the system sustains). Between rates the engine drains.
The benchmark's cells offer load at a rate fixed in their mix file, set
from this sweep once; the benchmark's runs never search for a rate.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from bench import generator, harness, run, spec, stats
    from bench.peaks import lookup
    cell = spec.load_cell(args.workload)
    dev = run.tpu_devices(cell.chips)
    if dev is None:
        return 3
    ref = cell.module("reference", cell.config["reference"])
    cfg = spec.arch_config(cell)
    shape = ref.shape(cell.config)
    params = harness.make_params(cfg, shape, args.seed)
    engine = harness.build_engine(cell, cfg, params, args.seed)
    harness.warm_up(engine, cell, args.seed, cfg.vocab)
    counter = harness.CompileCounter()
    first = 0
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        reqs = generator.make_requests(cell.traffic, args.seed + k,
                                       args.seconds, cfg.vocab, rate=rate,
                                       first_rid=first)
        first += len(reqs)
        win = harness.run_window(engine, reqs, args.seconds, counter=counter)
        rec = stats.Run(window=win, shape=shape,
                        peaks=lookup(dev[0].device_kind), sizing=cell.sizing,
                        setup_s=0.0)
        ttft = stats.ttfts(rec)
        tokens = sum(1 for _ in stats.stamps_in(rec, win.open, win.close))
        unfinished = sum(1 for t in win.tracks
                         if t.done_at is None or t.done_at > win.close)
        print(json.dumps({
            "rate_rps": rate, "requests": len(reqs),
            "output_tok_s": tokens / win.seconds,
            "ttft_p50_s": stats.percentile(ttft, 50),
            "ttft_p95_s": stats.percentile(ttft, 95),
            "itl_p95_ms": 1e3 * (stats.percentile(stats.token_gaps(rec), 95)
                                 or 0.0),
            "unfinished_at_close": unfinished,
            "compiles_in_window": win.compiles}), flush=True)
        engine.run(max_steps=100000)
    return 0


if __name__ == "__main__":
    sys.exit(main())
