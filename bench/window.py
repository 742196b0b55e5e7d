"""The measured window: a closed loop over an entry's units of work, and
the arithmetic of rates and percentiles over it."""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Sequence


@dataclasses.dataclass
class Call:
    """One completed piece of work: when it started and ended on the host
    clock, and what it did, counted from the traffic (`work`, by unit)."""
    t_start: float
    t_end: float
    work: Dict[str, float]
    info: Dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Window:
    t0: float
    calls: List[Call]

    @property
    def t_end(self) -> float:
        """The window ends at the last completed call."""
        return max(c.t_end for c in self.calls) if self.calls else self.t0

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0

    def total(self, unit: str) -> float:
        return sum(c.work.get(unit, 0.0) for c in self.calls)

    def rate(self, unit: str) -> float:
        """All the work of the window over all its time."""
        return self.total(unit) / self.seconds if self.seconds > 0 else 0.0

    def values(self, key: str) -> List[float]:
        """Every sample of `key` that the calls carry in `info`."""
        out: List[float] = []
        for c in self.calls:
            out.extend(c.info.get(key, ()))
        return out


def run_window(unit, seconds: float, clock=time.perf_counter) -> Window:
    """Call `unit()` back to back, a closed loop, until `seconds` have
    passed; `unit` blocks until its work is complete and returns the
    `Call`s it made.  The last unit always completes."""
    t0 = clock()
    calls: List[Call] = []
    while True:
        calls.extend(unit())
        if clock() - t0 >= seconds:
            return Window(t0=t0, calls=calls)


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between the
    closest ranks, as numpy's default; NaN for no values."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
