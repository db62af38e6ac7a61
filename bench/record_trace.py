#!/usr/bin/env python3
"""Record the small test trace ``bench/testdata/<config>.s<scale>.xplane.pb``:
one cell's configuration cut to a small scale and a few iterations, traced
as a ``--trace 1`` run traces.  It needs the chip, like run.py:

    python bench/record_trace.py --workload g500-s20-hybrid.pagerank --scale 14 --iters 3
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys

sys.path.pop(0)  # bench/ itself: keep bench/trace.py off the import path
sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run  # noqa: E402
from bench import trace as tr  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--scale", type=int, default=14)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    devices = run.require_chips(cell["workload"]["chips"])
    run.use_compile_cache()
    cell["config"]["scale"] = args.scale
    cell["traffic"]["max_iters"] = args.iters
    trace_dir = os.path.join(run.ROOT, "bench_out", "trace-small")
    result = run.run_cell(cell, args.seed, 0.0, True, devices, trace_dir=trace_dir)
    dst = os.path.join(run.BENCH, "testdata",
                       f"{cell['config']['name']}.s{args.scale}.xplane.pb")
    shutil.copyfile(tr.find_xplane(trace_dir), dst)
    print(f"{dst}: {os.path.getsize(dst)} bytes; correct={result['correct']}")
    print(tr.summary(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
