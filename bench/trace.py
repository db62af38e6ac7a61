"""Reduction of a JAX profiler trace (``.xplane.pb``) to device busy time,
idle gaps and per-op device time.

Device planes are named ``/device:TPU:<k>``; their ``XLA Ops`` line holds one
event per executed HLO op (fusions, custom calls such as Pallas kernels,
collectives, and a ``while`` op spanning its body's ops), named by the
instruction's HLO text, with start and end in nanoseconds on the same clock
as the host plane ``/host:CPU``.  Their ``XLA Modules`` line holds one event
per executed program (``jit_step(<id>)``), spanning that program's ops:
instruction names repeat from program to program, so an op is told apart by
the program it runs in.  The benchmark marks its window on the host with
``jax.profiler.TraceAnnotation(WINDOW)``.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

WINDOW = "bench.solve"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


_INSTR_NAME = re.compile(r"%?([\w.\-]+)")


def instr_name(name: str) -> str:
    """The HLO instruction name of a device op event.  The trace names an op
    by its whole instruction text (``%fusion.190 = f32[...] fusion(...),
    calls=...``); this is the part before `` = ``."""
    m = _INSTR_NAME.match(name)
    return m.group(1) if m else name


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float    # ns
    device: int = -1  # -1 on the host


def find_xplane(log_dir: str) -> str:
    """The newest ``*.xplane.pb`` under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(path)


def device_ops(profile) -> list[Event]:
    """Every op event on every TPU plane's ``XLA Ops`` line."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        dev = int(plane.name[len(DEVICE_PREFIX):])
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            out.extend(Event(instr_name(e.name), e.start_ns, e.end_ns, dev)
                       for e in line.events)
    return out


def module_events(profile) -> list[Event]:
    """Every program run on every TPU plane's ``XLA Modules`` line, named by
    its module name without the ``(<id>)`` that follows it."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        dev = int(plane.name[len(DEVICE_PREFIX):])
        for line in plane.lines:
            if line.name == MODULES_LINE:
                out.extend(Event(e.name.split("(", 1)[0], e.start_ns, e.end_ns, dev)
                           for e in line.events)
    return out


def inside(events: list[Event], spans: list[Event]) -> list[Event]:
    """The events that start inside one of ``spans`` on their own device."""
    by_dev: dict[int, list[tuple[float, float]]] = {}
    for s in spans:
        by_dev.setdefault(s.device, []).append((s.start, s.end))
    starts = {}
    for d, ss in by_dev.items():
        ss.sort()
        starts[d] = [s for s, _ in ss]
    out = []
    for e in events:
        ss = by_dev.get(e.device)
        if not ss:
            continue
        k = bisect.bisect_right(starts[e.device], e.start) - 1
        if k >= 0 and e.start <= ss[k][1]:
            out.append(e)
    return out


def host_events(profile) -> list[Event]:
    """Every event on the host plane (annotations, dispatches, Python calls)."""
    plane = profile.find_plane_with_name(HOST_PLANE)
    if plane is None:
        return []
    return [Event(e.name, e.start_ns, e.end_ns) for line in plane.lines
            for e in line.events]


def window(host: list[Event], name: str = WINDOW) -> tuple[float, float] | None:
    """(start, end) of the first host annotation called ``name``."""
    spans = [e for e in host if e.name == name]
    if not spans:
        return None
    first = min(spans, key=lambda e: e.start)
    return first.start, first.end


def merged(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(e - s for s, e in merged(intervals, lo, hi))


def idle_share(intervals, lo: float, hi: float) -> float:
    """1 - busy / window, in [0, 1]."""
    return 1.0 - busy_ns(intervals, lo, hi) / (hi - lo)


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle stretches of [lo, hi] between busy intervals."""
    out, cur = [], lo
    for s, e in merged(intervals, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = e
    if hi > cur:
        out.append((cur, hi))
    return out


def host_label(host: list[Event], t0: float, t1: float) -> str:
    """The innermost host event open over the whole of [t0, t1], or "" where
    none is."""
    best = None
    for e in host:
        if e.start <= t0 and e.end >= t1 and (best is None or
                                              e.end - e.start < best.end - best.start):
            best = e
    return best.name if best is not None else ""


def op_seconds(events: list[Event], lo: float, hi: float) -> dict[str, float]:
    """Device seconds per op name inside [lo, hi], summed over devices."""
    out: dict[str, float] = {}
    for e in events:
        d = min(e.end, hi) - max(e.start, lo)
        if d > 0:
            out[e.name] = out.get(e.name, 0.0) + d * 1e-9
    return out


SHOWN_OPCODES = ("gather", "scatter", "custom-call", "dot", "while", "sort",
                 "all-to-all", "all-gather", "all-reduce", "collective-permute")


def breakdown(events: list[Event], host: list[Event], lo: float, hi: float,
              top: int = 10, kinds: dict | None = None) -> dict:
    """The ``top`` device ops by time (each instruction's name, with the
    telling opcodes it runs from ``kinds``, hlo.op_kinds), and the ``top``
    longest idle gaps of device 0 labelled with the host event open over
    each."""
    ops = sorted(op_seconds(events, lo, hi).items(), key=lambda kv: -kv[1])[:top]
    kinds = kinds or {}

    def label(name: str) -> str:
        shown = sorted(set(SHOWN_OPCODES) & kinds.get(name, frozenset()))
        return f"{name} [{' '.join(shown)}]" if shown else name
    ops = [(label(name), s) for name, s in ops]
    devs = sorted({e.device for e in events})
    dev0 = [(e.start, e.end) for e in events if e.device == (devs[0] if devs else -1)]
    idle = sorted(gaps(dev0, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[name, s] for name, s in ops],
            "idle_gaps": [[host_label(host, s, e) or "(no host event)", (e - s) * 1e-9]
                          for s, e in idle]}


def summary(path: str, top: int = 40) -> str:
    """A look at one trace by hand: planes, lines with their event counts, and
    the device ops that took most time."""
    profile = load(path)
    out = []
    for plane in profile.planes:
        lines = [(line.name, sum(1 for _ in line.events)) for line in plane.lines]
        out.append(f"plane {plane.name!r}: lines {lines[:12]}")
    ops = device_ops(profile)
    if ops:
        lo, hi = min(e.start for e in ops), max(e.end for e in ops)
        for name, secs in sorted(op_seconds(ops, lo, hi).items(),
                                 key=lambda kv: -kv[1])[:top]:
            out.append(f"op {secs:.6f} s  {name}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys

    print(summary(sys.argv[1]))
