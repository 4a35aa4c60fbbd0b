"""Reduction of a JAX profiler trace (``.xplane.pb``) to device busy time,
idle gaps and device time per annotated host call.

The harness wraps what it traces in ``jax.profiler.TraceAnnotation`` spans
(``window`` round the whole traced part; ``request``, ``plan.prepare``,
``plan.search``, ``planned_call`` round its calls into the program).  Device
operations are the events of each TPU plane's ``XLA Ops`` line; host and
device events share the profiler's clock.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

#: host annotations the harness writes; the innermost one names an idle gap
ANNOTATIONS = ("window", "request", "plan.prepare", "plan.search",
               "planned_call")
_OPS_LINE = "XLA Ops"


@dataclass
class Trace:
    window: tuple                      # (start_ns, end_ns) of "window"
    devices: dict                      # plane name -> merged [(s, e)] ns
    annotations: list                  # [(name, s, e)] ns, by start
    op_time: dict = field(default_factory=dict)   # op name -> ns, summed

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, start: float = None, end: float = None) -> float:
        """Seconds some operation ran on a device inside [start, end] ns
        (default: the window), averaged over devices."""
        s0 = self.window[0] if start is None else start
        e0 = self.window[1] if end is None else end
        tot = [sum(max(0, min(e, e0) - max(s, s0)) for s, e in iv)
               for iv in self.devices.values()]
        return sum(tot) / max(len(tot), 1) * 1e-9

    def gaps(self, start: float, end: float) -> list:
        """Idle gaps (ns) between consecutive device operations that lie
        inside [start, end], on every device."""
        out = []
        for iv in self.devices.values():
            inside = [(s, e) for s, e in iv if s >= start and e <= end]
            out += [b[0] - a[1] for a, b in zip(inside, inside[1:])
                    if b[0] > a[1]]
        return out

    def spans(self, name: str) -> list:
        return [(s, e) for n, s, e in self.annotations if n == name]

    def named_gaps(self, top: int = 10) -> list:
        """The ``top`` longest idle gaps inside the window, each named by
        the innermost harness annotation that holds it."""
        found = []
        for iv in self.devices.values():
            edges = [self.window[0]] + [x for s, e in iv for x in (s, e)] \
                + [self.window[1]]
            for a, b in zip(edges[0::2], edges[1::2]):
                a, b = max(a, self.window[0]), min(b, self.window[1])
                if b > a:
                    found.append((b - a, self._holder((a + b) / 2)))
        found.sort(reverse=True)
        return [[name, ns * 1e-9] for ns, name in found[:top]]

    def _holder(self, t: float) -> str:
        best = None
        for n, s, e in self.annotations:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (n, e - s)
        return best[0] if best else "none"

    def top_ops(self, top: int = 10) -> list:
        n = max(len(self.devices), 1)
        ops = sorted(self.op_time.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns * 1e-9 / n] for name, ns in ops]


def _merge(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    devices: dict = {}
    op_time: dict = {}
    annotations = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            iv = []
            for line in plane.lines:
                if line.name != _OPS_LINE:
                    continue
                for ev in line.events:
                    s, d = ev.start_ns, ev.duration_ns
                    iv.append((s, s + d))
                    op_time[ev.name] = op_time.get(ev.name, 0.0) + d
            devices[plane.name] = _merge(iv)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ANNOTATIONS:
                        annotations.append(
                            (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    annotations.sort(key=lambda a: a[1])
    windows = [(s, e) for n, s, e in annotations if n == "window"]
    if not devices:
        raise ValueError(f"no TPU device plane with an '{_OPS_LINE}' line in "
                         f"{path}")
    if len(windows) != 1:
        raise ValueError(f"expected one 'window' annotation, found "
                         f"{len(windows)} in {path}")
    return Trace(window=windows[0], devices=devices, annotations=annotations,
                 op_time=op_time)
