#!/usr/bin/env python3
"""Readings of the comparison that decides ``correct``, over many seeds in
one process on the chip: the program as the configuration states it, and the
lower-precision control, the program's own narrower wire type for exchanged
values one step below the configuration's (float32 PageRank -> bfloat16
payloads; int32 WCC labels -> int16).  The limits in ``bench/traffic/`` are
set from these readings (PERF.md).  The benchmark's own runs never run this.

    python bench/control.py --workload <cell> --seeds 11,12 --control-seeds 11,12

Each run is one whole solve through run.py's ``run_cell``, at the cell's own
size; one JSON line per run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# run as a script, bench/ is sys.path[0]: drop it so bench/trace.py never
# shadows the standard library's ``trace`` (imported as a module, keep sys.path)
if sys.path and os.path.abspath(sys.path[0] or ".") == os.path.dirname(
        os.path.abspath(__file__)):
    sys.path.pop(0)
sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run  # noqa: E402

NARROWER = {"pagerank": "bfloat16", "wcc": "int16"}


def control_kw(traffic: dict) -> dict:
    """Engine options of the control for this traffic's algorithm."""
    return {"payload_dtype": NARROWER[traffic["algorithm"]]}


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="", help="seeds of the program's runs")
    ap.add_argument("--control-seeds", default="", help="seeds of the control's runs")
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    devices = run.require_chips(cell["workload"]["chips"])
    run.use_compile_cache()
    name = cell["traffic"]["check"]["name"]
    for kind, seeds in (("program", _seeds(args.seeds)),
                        ("control", _seeds(args.control_seeds))):
        kw = control_kw(cell["traffic"]) if kind == "control" else None
        for seed in seeds:
            try:
                r = run.run_cell(cell, seed, 0.0, False, devices, engine_kw=kw)
                line = {"kind": kind, "seed": seed, name: r["checks"][name]["value"],
                        "correct": r["correct"], "checks": r["checks"]}
            except Exception as e:  # a control that crashes has failed
                line = {"kind": kind, "seed": seed, "error": repr(e)[:500]}
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
