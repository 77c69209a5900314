"""Readings of the comparison that decides ``correct``: the program's and
the lower-precision control's, on several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

Each seed is a whole run of the cell (weights, engine, warm-up, a window
of ``--seconds`` at the cell's own load), then the reference reads the
gaps of the served tokens and of the tokens that the reference in the
precision below the served one (int8 for bfloat16) puts first at the same
positions. One JSON line per seed, with every gap and the reference's own
margins, so that any number compared can be worked out again. The limit
in the cell's file lies between the two, as ``PERF.md`` records; the
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1,
                    help="0: the program's readings alone")
    args = ap.parse_args(argv)

    from bench import run, spec
    from bench.peaks import lookup
    cell = spec.load_cell(args.workload)
    dev = run.tpu_devices(cell.chips)
    if dev is None:
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.execute(cell, seed, args.seconds, False,
                          lookup(dev[0].device_kind), dev,
                          control=bool(args.control),
                          t_start=time.perf_counter())
        info = out["info"]
        print(json.dumps({"seed": seed, "readings": info["readings"],
                          "correct": out["correct"],
                          "control_correct": info["control_correct"],
                          "memory_peak_bytes":
                              out["device"]["memory_peak_bytes"],
                          "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
