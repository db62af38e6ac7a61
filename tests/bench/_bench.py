"""Shared helpers of the benchmark's tests: import ``bench`` from the
checkout root, and cut a cell to a size the CPU runs in seconds."""
from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

CELLS = [w["name"] for w in run.load_benchmark()["workloads"]]
ONE_CHIP_CELLS = [w["name"] for w in run.load_benchmark()["workloads"] if w["chips"] == 1]
# a made-up device for roofline arithmetic on the CPU; no number read with it
# is a device number
CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1 << 30}


def small_cell(name: str, scale: int = 10, cell: dict | None = None) -> dict:
    """The cell as the benchmark loads it (or ``cell``), at ``scale`` with 4
    blocks and a dense region of 8 hubs where the configuration has one."""
    cell = copy.deepcopy(cell or run.load_cell(name))
    cell["config"]["scale"] = scale
    lay = cell["config"]["layout"]
    lay["blocks"] = lay["workers"] = 4
    if lay["theta"] is not None:
        lay["theta"]["hubs"] = 8
    return cell


def run_small(cell: dict, seed: int = 2**31 + 11, **kw) -> dict:
    import jax

    kw.setdefault("require_compiled", False)
    kw.setdefault("peaks_table", CPU_PEAKS)
    return run.run_cell(cell, seed, 0.2, False,
                        jax.devices()[: cell["workload"]["chips"]], **kw)
