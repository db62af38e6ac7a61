#!/usr/bin/env python3
"""Record the small test trace ``<dest>/<config>.s<scale>.xplane.pb`` and
the op kinds of its step, ``<dest>/<config>.s<scale>.hlo_kinds.json``
(``hlo.op_kinds`` of the compiled step's HLO text): one cell's configuration
cut to a small scale and a few iterations, traced as a ``--trace 1`` run
traces.  It needs the chip, like run.py:

    python bench/record_trace.py --workload g500-s20-hybrid.pagerank --scale 14 --iters 3
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

# run as a script, bench/ is sys.path[0]: drop it so bench/trace.py never
# shadows the standard library's ``trace`` (imported as a module, keep sys.path)
if sys.path and os.path.abspath(sys.path[0] or ".") == os.path.dirname(
        os.path.abspath(__file__)):
    sys.path.pop(0)
sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import hlo, run  # noqa: E402
from bench import trace as tr  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--scale", type=int, default=14)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--dest", default=os.path.join(run.BENCH, "testdata"))
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    devices = run.require_chips(cell["workload"]["chips"])
    run.use_compile_cache()
    cell["config"]["scale"] = args.scale
    cell["traffic"]["max_iters"] = args.iters
    trace_dir = os.path.join(run.ROOT, "bench_out", "trace-small")
    result = run.run_cell(cell, args.seed, 0.0, True, devices, trace_dir=trace_dir)
    os.makedirs(args.dest, exist_ok=True)
    dst = os.path.join(args.dest, f"{cell['config']['name']}.s{args.scale}.xplane.pb")
    shutil.copyfile(tr.find_xplane(trace_dir), dst)
    with open(os.path.join(trace_dir, run.STEP_HLO)) as f:
        kinds = hlo.op_kinds(f.read())
    with open(dst.replace(".xplane.pb", ".hlo_kinds.json"), "w") as f:
        json.dump({k: sorted(v) for k, v in sorted(kinds.items())}, f)
    print(f"{dst}: {os.path.getsize(dst)} bytes; correct={result['correct']}")
    print(tr.summary(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main())
