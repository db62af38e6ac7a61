"""Shared helpers of the benchmark's tests: import ``bench`` from the
checkout root, cut a cell to a size the CPU runs in seconds, and run a check
on as many devices as a cell asks for."""
from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TESTS = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

CELLS = [w["name"] for w in run.load_benchmark()["workloads"]]
ONE_CHIP_CELLS = [w["name"] for w in run.load_benchmark()["workloads"] if w["chips"] == 1]
CHIPS = {w["name"]: w["chips"] for w in run.load_benchmark()["workloads"]}
# a made-up device for roofline arithmetic on the CPU; no number read with it
# is a device number
CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1 << 30}

_RESULT = "WITH_DEVICES_RESULT "
_CHILD = """\
import importlib, json, sys
sys.path[:0] = {paths!r}
fn = getattr(importlib.import_module({module!r}), {name!r})
print({mark!r} + json.dumps(fn(*json.loads(sys.argv[1]))), flush=True)
"""


def with_devices(chips: int, fn, *args, timeout: int = 900):
    """``fn(*args)`` where JAX sees at least ``chips`` devices: in this
    process where it does, else in a child interpreter on ``chips`` forced CPU
    devices (``JAX_PLATFORMS=cpu``,
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<chips>``), the way
    tests/test_spmd_residency.py runs its meshes; the test process itself
    sees one device and sets no ``XLA_FLAGS``.  ``fn`` is a module-level
    function of a module under tests/bench; its arguments and its result
    pass as JSON."""
    import jax

    if len(jax.devices()) >= chips:
        return fn(*args)
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count={chips}".strip())
    script = _CHILD.format(paths=[TESTS, ROOT, os.path.join(ROOT, "src")],
                           module=fn.__module__, name=fn.__name__, mark=_RESULT)
    proc = subprocess.run([sys.executable, "-c", script, json.dumps(args)],
                          capture_output=True, text=True, env=env, cwd=ROOT,
                          timeout=timeout)
    out = [line for line in proc.stdout.splitlines() if line.startswith(_RESULT)]
    assert proc.returncode == 0 and out, (
        f"child on {chips} devices failed (rc {proc.returncode})\n"
        f"stdout:\n{proc.stdout[-4000:]}\nstderr:\n{proc.stderr[-8000:]}")
    return json.loads(out[-1][len(_RESULT):])


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
    """One run of ``cell`` on this process's devices, with the look for a
    chip skipped; a cell that asks for more devices than the process has
    goes through ``with_devices``."""
    import jax

    chips, devices = cell["workload"]["chips"], jax.devices()
    if len(devices) < chips:
        raise RuntimeError(f"{cell['workload']['name']} needs {chips} devices, this"
                           f" process has {len(devices)}: run it through with_devices")
    kw.setdefault("require_compiled", False)
    kw.setdefault("peaks_table", CPU_PEAKS)
    return run.run_cell(cell, seed, 0.2, False, devices[:chips], **kw)
