"""From the profiler's trace (an XSpace) to the few arrays the reducers read.

Device planes are named ``/device:TPU:<n>``; their line ``XLA Ops`` holds one
event per operation that ran on the chip (millions in a few seconds of these
kernels) and ``XLA Modules`` one per program execution.  Host spans
(``jax.profiler.TraceAnnotation`` from the harness's own files, all named
``bench:*``) sit on the host plane's thread lines.  All times are seconds from
the start of the trace, on one clock.  Only what lies in the ``bench:window``
span is kept.

The chip's trace buffer is finite: on a v5e it held 1.80 s of the 256-lane
per-lane kernel (6.27 M events) and 2.96 s of the 4,096-lane RLC kernel, and
then the device's record stops while the host's goes on.  So the window the
reducers see ends at the last call that returned while the device was still
being recorded (``clip_to_record``): what follows would read as an idle chip.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
RETURN_SLACK_S = 0.02       # a call returns this long after its last device op


class Intervals:
    """A merged set of intervals with the seconds it covers of any [lo, hi]."""

    def __init__(self, starts, ends):
        s, e = np.asarray(starts, float), np.asarray(ends, float)
        order = np.argsort(s, kind="stable")
        s, e = s[order], np.maximum.accumulate(e[order])
        first = np.ones(len(s), bool)
        first[1:] = s[1:] > e[:-1]
        self.starts = s[first]
        self.ends = e[np.append(first[1:], True)] if len(s) else e
        self._cum = np.concatenate([[0.0], np.cumsum(self.ends - self.starts)])

    def covered(self, lo: float, hi: float) -> float:
        if hi <= lo or not len(self.starts):
            return 0.0
        i = int(np.searchsorted(self.ends, lo, "right"))
        j = int(np.searchsorted(self.starts, hi, "left"))
        if j <= i:
            return 0.0
        whole = self._cum[j] - self._cum[i]
        return float(whole - max(0.0, lo - self.starts[i])
                     - max(0.0, self.ends[j - 1] - hi))

    def total(self) -> float:
        return float(self._cum[-1])

    def gaps(self, lo: float, hi: float) -> "Intervals":
        """What of [lo, hi] these intervals leave uncovered."""
        keep = (self.ends > lo) & (self.starts < hi)
        s = np.clip(self.starts[keep], lo, hi)
        e = np.clip(self.ends[keep], lo, hi)
        return Intervals(np.concatenate([[lo], e]), np.concatenate([s, [hi]]))


@dataclass
class Trace:
    window: tuple = (0.0, 0.0)                    # the bench:window span
    spans: list = field(default_factory=list)     # [(thread, name, start, dur)]
    modules: list = field(default_factory=list)   # [(chip, name, start, dur)]
    busy: dict = field(default_factory=dict)      # chip -> Intervals of its ops
    op_seconds: dict = field(default_factory=dict)  # short op name -> seconds

    @property
    def chips(self) -> list:
        return sorted(self.busy)

    def spans_named(self, name: str) -> list:
        return [(s, s + d) for _, n, s, d in self.spans if n == name]


def clip_to_record(tr: Trace) -> Trace:
    """End the window at the last ``bench:entry`` return that the device's
    record still covers, and drop the spans and programs past it.  A return
    is a moment with no device work of a later call begun (dispatches queue
    on one device-owner thread), so what is kept is whole calls."""
    if not tr.busy:
        return tr
    lo, hi = tr.window
    recorded = max(iv.ends[-1] for iv in tr.busy.values()) + RETURN_SLACK_S
    returns = [e for _, e in tr.spans_named("bench:entry") if e <= recorded]
    hi = max(returns) if returns else min(hi, recorded)
    tr.window = (lo, hi)
    tr.spans = [sp for sp in tr.spans if sp[2] + sp[3] <= hi]
    tr.modules = [m for m in tr.modules if m[2] + m[3] <= hi]
    return tr


def short_op(name: str) -> str:
    """``%fusion.12 = (s32[...]) fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def read(xspace: bytes) -> Trace:
    """``xspace``: the serialized trace a profiler session's ``stop()`` gives."""
    import jax

    planes = list(jax.profiler.ProfileData.from_serialized_xspace(xspace).planes)
    tr = Trace()
    for plane in planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                tr.spans.extend((line.name, e.name, e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9) for e in line.events
                                if e.name.startswith("bench:"))
    windows = tr.spans_named("bench:window")
    if len(windows) != 1:
        raise ValueError(f"expected one bench:window span, found {len(windows)}")
    lo, hi = tr.window = windows[0]
    tr.spans = [sp for sp in tr.spans
                if lo <= sp[2] <= hi and sp[1] != "bench:window"]
    for plane in planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if MODULES_LINE in lines:
            tr.modules.extend(
                (plane.name, e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                for e in lines[MODULES_LINE].events
                if lo <= e.start_ns * 1e-9 <= hi)
        starts, ends = [], []
        for e in (lines[OPS_LINE].events if OPS_LINE in lines else ()):
            s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
            if lo <= s <= hi:
                starts.append(s)
                ends.append(min(s + d, hi))
                key = short_op(e.name)
                tr.op_seconds[key] = tr.op_seconds.get(key, 0.0) + d
        if not starts:          # no per-op line: the programs' own intervals
            starts = [s for c, _, s, _ in tr.modules if c == plane.name]
            ends = [min(s + d, hi) for c, _, s, d in tr.modules if c == plane.name]
        if starts:
            tr.busy[plane.name] = Intervals(starts, ends)
    return clip_to_record(tr)
