"""Record the small chip trace that ``test_chip_trace.py`` reads.

    python3 bench/tests/record_trace.py --out <dir>

On a machine with a TPU: runs the tiny chat cell of ``data/`` through the
whole harness with ``--trace 1`` (a 3 s window, 0.3 s of it traced), then
writes ``chip_trace.xplane.pb.gz`` (the recorded trace) and
``chip_trace.json`` (what the harness read from it) into ``<dir>``. Copy
both into ``bench/tests/data/`` to renew the test's data.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)

    from bench import run, spec, trace
    from bench.peaks import lookup
    from bench.tests.test_run import make_root
    root = run.RUNS / "tiny_root"
    shutil.rmtree(root, ignore_errors=True)
    (root / "bench").mkdir(parents=True)
    make_root(root)
    cell = spec.load_cell("tiny_qwen.chat", root)
    dev = run.tpu_devices(cell.chips)
    if dev is None:
        return 3
    out = run.execute(cell, args.seed, 3.0, True, lookup(dev[0].device_kind),
                      dev, t_start=time.perf_counter())
    xplane = trace.find_xplane(run.RUNS / "trace" / cell.name)
    dest = Path(args.out)
    dest.mkdir(parents=True, exist_ok=True)
    with open(xplane, "rb") as f, \
            gzip.open(dest / "chip_trace.xplane.pb.gz", "wb") as g:
        shutil.copyfileobj(f, g)
    info = {"device": out["device"], "breakdown": out["breakdown"],
            "matmul_s": out["info"]["traced"]["matmul_s"],
            "ops": out["info"]["traced"]["ops"]}
    with open(dest / "chip_trace.json", "w") as f:
        json.dump(info, f, indent=1)
    print(json.dumps(info))
    return 0


if __name__ == "__main__":
    sys.exit(main())
