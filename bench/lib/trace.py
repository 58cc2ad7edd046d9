"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
kernel time by family and the breakdown of where the time went.

Device operations are the events of the "XLA Ops" line of each device
plane (``/device:TPU:<n>``), leaves only (a while loop's event encloses
the operations of its body). The traced window is the span of the
benchmark's own ``TraceAnnotation`` named ``window`` on the host plane;
busy time is the union of the device operation intervals inside it.
Idle gaps are the stretches between busy intervals, each named after the
innermost benchmark span (``generate``, ``train_step``, ``make_batch``,
...) that was open on the host at its midpoint.

To look at a trace by hand: ``python -m bench.lib.trace <file.xplane.pb>``
prints a survey of its planes, lines and busiest events.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation. ``name`` is its HLO text as the trace gives
    it ("%tt_linear.63 = bf16[...] custom-call(...)"); ``instr`` is the
    instruction's own name ("tt_linear.63"), which kernels take from the
    program's entry point that calls them."""
    name: str
    start: int          # ns
    end: int            # ns

    @property
    def instr(self) -> str:
        return self.name.split(" = ", 1)[0].lstrip("%")

    @property
    def is_kernel(self) -> bool:
        return "custom-call(" in self.name


def leaves(ops: list) -> list:
    """The operations that enclose no other: drops control flow (while,
    call, conditional) whose interval holds the operations it runs."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start >= o.end]


def find_xplane(logdir: str) -> str:
    got = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                           recursive=True))
    if not got:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return got[-1]


def _merge(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class Trace:
    """Device operations and host spans of one traced window."""

    def __init__(self, devices: dict, spans: list, window: tuple,
                 everything: dict):
        self.devices = devices          # plane name -> [Op] in the window
        self.spans = spans              # [(name, start, end)] host spans
        self.window = window            # (start, end) ns
        self.everything = everything    # plane name -> every [Op] traced

    @classmethod
    def from_file(cls, path: str, span_names=()) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        devices, spans = {}, []
        names = set(span_names) | {WINDOW_SPAN}
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                ops = []
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for ev in line.events:
                        s = int(ev.start_ns)
                        ops.append(Op(ev.name, s, s + int(ev.duration_ns)))
                devices[plane.name] = leaves(ops)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in names:
                            s = int(ev.start_ns)
                            spans.append((ev.name, s,
                                          s + int(ev.duration_ns)))
        win = [s for s in spans if s[0] == WINDOW_SPAN]
        if not win:
            raise ValueError(f"{path}: no '{WINDOW_SPAN}' span on the host")
        window = (min(s[1] for s in win), max(s[2] for s in win))
        if not devices:
            raise ValueError(f"{path}: no device plane")
        inside = {k: [o for o in ops
                      if o.end > window[0] and o.start < window[1]]
                  for k, ops in devices.items()}
        return cls(inside, [s for s in spans if s[0] != WINDOW_SPAN],
                   window, devices)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clip(self, o):
        return max(o.start, self.window[0]), min(o.end, self.window[1])

    def _busy(self, ops):
        return _merge([self._clip(o) for o in ops])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        tot = [sum(e - s for s, e in self._busy(ops))
               for ops in self.devices.values()]
        return sum(tot) / len(tot) * 1e-9

    def time_of(self, pred, whole: bool = False) -> float:
        """Device seconds of the operations ``pred`` accepts, averaged over
        the devices: inside the window, or with ``whole`` over everything
        traced (for work that is known only for the whole traced call)."""
        if whole:
            tot = [sum(o.end - o.start for o in ops if pred(o))
                   for ops in self.everything.values()]
        else:
            tot = [sum(e - s for s, e in (self._clip(o) for o in ops
                                          if pred(o)))
                   for ops in self.devices.values()]
        return sum(tot) / len(tot) * 1e-9

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` operations that took most device time in the window,
        summed over their calls (instruction name without its ".N")."""
        acc = collections.Counter()
        for ops in self.devices.values():
            for o in ops:
                s, e = self._clip(o)
                acc[re.sub(r"\.\d+$", "", o.instr)] += \
                    (e - s) * 1e-9 / len(self.devices)
        return [[k, v] for k, v in acc.most_common(n)]

    def _span_at(self, t: int) -> str:
        best = None
        for name, s, e in self.spans:
            if s <= t <= e and (best is None or e - s < best[2] - best[1]):
                best = (name, s, e)
        return best[0] if best else "none"

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest idle stretches of the first device, each named
        after what the host was doing."""
        ops = next(iter(self.devices.values()))
        busy = self._busy(ops)
        edges = [self.window[0]] + [x for iv in busy for x in iv] \
            + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._span_at((s + e) // 2), (e - s) * 1e-9]
                for s, e in gaps[:n]]


def describe(path: str, n: int = 40) -> str:
    """Plain-text survey of a trace's planes, lines and busiest event
    names (for looking at a trace by hand)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            acc = collections.Counter()
            cnt = collections.Counter()
            sample = {}
            for ev in line.events:
                acc[ev.name] += ev.duration_ns
                cnt[ev.name] += 1
                sample.setdefault(ev.name, (int(ev.start_ns),
                                            tuple(ev.stats)))
            out.append(f"  LINE {line.name} events={sum(cnt.values())}")
            for k, v in acc.most_common(n):
                st = ", ".join(f"{a}={str(b)[:160]}"
                               for a, b in sample[k][1])
                out.append(f"    {v * 1e-6:10.3f} ms x{cnt[k]:<6} {k[:120]}"
                           f" @{sample[k][0]} [{st[:600]}]")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(describe(sys.argv[1]))
