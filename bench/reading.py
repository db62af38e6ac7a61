"""What a per-layer metric reads: the traced solve, reduced by ``trace.py``,
and the host's own numbers of the run.  Each metric in ``metrics/`` is a
module with a ``read(reading)`` that returns a number, or None where the run
holds nothing for it to read."""
from __future__ import annotations

import dataclasses
import re

from bench import trace as tr


@dataclasses.dataclass
class Reading:
    ops: list            # trace.Event of every device op
    host: list           # trace.Event of every host event
    lo: float            # traced window, ns
    hi: float
    iterations: int      # iterations of the traced solve
    chips: int
    n: int
    edge_slots: int
    weighted: bool
    peaks: dict
    prepare_s: float
    matrix_bytes: int
    hlo: dict = dataclasses.field(default_factory=dict)  # hlo.op_kinds of the step
    # the step program's runs (trace.module_events named as its module), or
    # None where the trace says nothing of programs
    step: list | None = None

    def devices(self) -> list[int]:
        return sorted({e.device for e in self.ops})

    def busy_s(self) -> float:
        """Device busy seconds in the window, averaged over the chips."""
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(tr.busy_ns([(e.start, e.end) for e in self.ops if e.device == d],
                              self.lo, self.hi) for d in devs) * 1e-9 / len(devs)

    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def ms_per_iter(self, patterns=(), opcodes=(), device: int | None = None
                    ) -> float | None:
        """Device milliseconds per iteration of the step's ops whose name
        matches one of ``patterns`` or whose HLO instruction runs one of
        ``opcodes``: on ``device``, or averaged over the chips.  Only ops that
        run inside the step program count, since ``hlo`` describes its
        instructions alone.  None where no op matches."""
        rx = re.compile("|".join(patterns)) if patterns else None
        ops = frozenset(opcodes)

        def hit_op(name: str) -> bool:
            return bool((rx is not None and rx.search(name))
                        or ops & self.hlo.get(name, frozenset()))
        pool = self.ops if self.step is None else tr.inside(self.ops, self.step)
        hit = [e for e in pool if hit_op(e.name)
               and (device is None or e.device == device)]
        if not hit or not self.iterations:
            return None
        secs = sum(tr.op_seconds(hit, self.lo, self.hi).values())
        per_dev = secs if device is not None else secs / max(len(self.devices()), 1)
        return per_dev * 1e3 / self.iterations
