"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

What it reads:

- device planes (``/device:TPU:<n>``): the ``XLA Ops`` line (one event per
  operation executed) and the ``XLA Modules`` line (one event per program
  executed, named ``jit_<function>(<id>)``);
- the host plane (``/host:CPU``): the harness's own
  ``jax.profiler.TraceAnnotation`` spans (``window``, ``fit``, ``tile``,
  ``publish``, ``wait``), which lie on the same clock as the device events.

Everything is clipped to the ``window`` span: a trace holds only what ran
inside the measured window. Where the device's trace buffer filled up
(a ``Trace Buffers Dropped`` event), nothing after the dropping began was
recorded: the traced window then ends with the last ``fit`` or ``tile``
span that ended before it, so that it holds whole units of work only
(or where the dropping began, if no unit ended before it).
"""
from __future__ import annotations

import collections
import glob
import os
import re

HARNESS_SPANS = ("window", "fit", "tile", "publish", "wait")
UNITS = ("fit", "tile")  # whole units of work a traced window may end with
# the distributed engine's jitted round (core/distributed.py), as the
# device's XLA Modules line names it
ROUND_PROGRAM = "jit_round_body"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
)
MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")
DROPPED = "Trace Buffers Dropped"


def find_xplane(trace_dir: str) -> str:
    """The newest profile written under ``trace_dir``."""
    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(intervals):
    """Sorted, merged copy of [(start, end), ...]."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def covered(intervals) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def gaps(intervals, lo, hi):
    """The parts of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def module_base(name: str) -> str:
    """``jit_round_body(12)`` -> ``jit_round_body``."""
    return MODULE_NAME.match(name).group(1)


class Device:
    """One device plane, clipped to the window."""

    def __init__(self, name, ops, modules):
        self.name = name
        self.ops = ops  # [(name, start_ns, end_ns)]
        self.modules = modules  # [(name, start_ns, end_ns)]

    def busy_ns(self) -> float:
        events = self.ops or self.modules
        return covered([(s, e) for _, s, e in events])

    def op_ns(self) -> dict:
        out = collections.Counter()
        for n, s, e in self.ops:
            out[n] += e - s
        return out

    def module_ns(self) -> dict:
        out = collections.Counter()
        for n, s, e in self.modules:
            out[module_base(n)] += e - s
        return out

    def module_intervals(self, prefix: str):
        return [(s, e) for n, s, e in self.modules if n.startswith(prefix)]

    def collective_ns(self, within=None) -> float:
        """Device time of collective operations, optionally only those
        lying inside the given [(start, end)] intervals."""
        ivs = [(s, e) for n, s, e in self.ops if COLLECTIVE.search(n)]
        if within is not None:
            inside = []
            for s, e in ivs:
                if any(ws <= s and e <= we for ws, we in within):
                    inside.append((s, e))
            ivs = inside
        return float(sum(e - s for s, e in ivs))


class Trace:
    """A reduced trace: host spans and device planes, clipped to the
    harness's ``window`` span."""

    def __init__(self, spans, devices, window, dropped_ns=None):
        self.spans = spans  # [(name, start_ns, end_ns)]
        self.devices = devices  # [Device]
        self.window = window  # (start_ns, end_ns)
        self.dropped_ns = dropped_ns  # where the device trace began dropping

    def units(self) -> int:
        """Whole fits or tiles inside the traced window."""
        lo, hi = self.window
        return sum(1 for n, s, e in self.spans if n in UNITS and lo <= s and e <= hi)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns() for d in self.devices) * 1e-9 / len(self.devices)

    def idle_gaps(self, limit: int = 10):
        """The longest idle stretches of the first device, each named by the
        innermost harness span the host was in at its midpoint."""
        if not self.devices:
            return []
        dev = self.devices[0]
        events = dev.ops or dev.modules
        out = []
        for s, e in gaps([(a, b) for _, a, b in events], *self.window):
            mid = 0.5 * (s + e)
            inner = [
                (b - a, n) for n, a, b in self.spans if a <= mid <= b
            ]
            name = min(inner)[1] if inner else "outside"
            out.append((name, (e - s) * 1e-9))
        out.sort(key=lambda g: -g[1])
        return out[:limit]

    def top_ops(self, limit: int = 10):
        total = collections.Counter()
        for d in self.devices:
            total.update(d.op_ns() if d.ops else d.module_ns())
        n = max(len(self.devices), 1)
        return [(k, v * 1e-9 / n) for k, v in total.most_common(limit)]


def idle_share(trace):
    """Percent of the window in which no operation ran on the device,
    averaged over the devices; None without a device plane."""
    if trace is None or not trace.devices:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)


def traced_window(spans, window, dropped_ns=None):
    """The part of ``window`` the trace holds: all of it, or, where the
    device trace began dropping at ``dropped_ns``, up to the end of the
    last whole unit (fit or tile) that ended before then."""
    lo, hi = window
    if dropped_ns is None or dropped_ns >= hi:
        return lo, hi
    ends = [e for n, s, e in spans if n in UNITS and lo < e <= dropped_ns]
    return lo, max(ends) if ends else max(lo, dropped_ns)


def load(trace_dir: str) -> Trace:
    """The newest trace written under ``trace_dir``, reduced."""
    return load_file(find_xplane(trace_dir))


def load_file(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, raw, dropped = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HARNESS_SPANS:
                        s = ev.start_ns
                        spans.append((ev.name, s, s + ev.duration_ns))
        elif plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {line.name: line for line in plane.lines}
            ops = _events(lines.get(OPS_LINE))
            modules = _events(lines.get(MODULES_LINE))
            raw.append((plane.name, ops, modules))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    dropped += [s for n, s, _ in _events(line) if n == DROPPED]
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one 'window' span in the trace, got {len(windows)}")
    cut = min(dropped) if dropped else None
    lo, hi = traced_window(spans, windows[0], cut)
    devices = []
    for name, ops, modules in sorted(raw):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi]
        modules = [
            (n, max(s, lo), min(e, hi)) for n, s, e in modules if e > lo and s < hi
        ]
        devices.append(Device(name, ops, modules))
    return Trace(spans, devices, (lo, hi), cut)


def _events(line):
    if line is None:
        return []
    out = []
    for ev in line.events:
        s = ev.start_ns
        out.append((ev.name, s, s + ev.duration_ns))
    return out
