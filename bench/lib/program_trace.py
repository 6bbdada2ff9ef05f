"""The program's own spans and JAX's compile events, read from the host
plane of a profiler trace, beside the reduction of ``bench/lib/trace.py``
(which keeps the harness's spans and the device planes).

What it reads, on the host plane (``/host:CPU``), on the device's clock:

- program spans: ``repro.obs`` spans, which enter
  ``jax.profiler.TraceAnnotation(f"{cat}.{name}")`` while a session
  records; those of the training driver (``driver.*``), the scorer
  (``serve.*``) and the transports (``transport.*``) are kept;
- compile spans: JAX's own trace, lower and compile events
  (``COMPILE_EVENTS``). With the persistent compile cache warm, a TPU v5
  lite trace holds the first two; JAX does not annotate the cache's
  retrieval, which lies in the ``PjitFunction`` call around them.

A program without these spans reads as empty lists: every reader built on
this module then returns None.
"""
from __future__ import annotations

import sys

from bench.lib.trace import clip, covered, find_xplane, gaps, union

PROGRAM_PREFIXES = ("driver.", "serve.", "transport.")
COMPILE_EVENTS = (
    "trace_to_jaxpr_dynamic",
    "lower_sharding_computation",
    "backend_compile_and_load",
)
# a round trip of the driver: the round's dispatch and the objectives'
# readbacks
ROUND_TRIP = ("driver.round", "driver.objectives")
OMEGA = "driver.omega_step"
# the readbacks that wait for an Omega-step's device work
OMEGA_READBACKS = ("driver.rho", "driver.result")


class ProgramTrace:
    """Host-plane spans of one trace: ``program_spans`` and
    ``compile_spans``, each [(name, start_ns, end_ns)] sorted by start."""

    def __init__(self, program_spans, compile_spans):
        self.program_spans = sorted(program_spans, key=lambda s: s[1])
        self.compile_spans = sorted(compile_spans, key=lambda s: s[1])

    def named(self, *names):
        return [s for s in self.program_spans if s[0] in names]

    def holds_compile(self, start, end) -> bool:
        return any(start <= s and e <= end for _, s, e in self.compile_spans)


def read_file(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    program, compiles = [], []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name.startswith(PROGRAM_PREFIXES):
                    out = program
                elif name in COMPILE_EVENTS:
                    out = compiles
                else:
                    continue
                s = ev.start_ns
                out.append((name, s, s + ev.duration_ns))
    return ProgramTrace(program, compiles)


def load_file(path: str):
    """(``bench.lib.trace.load_file(path)``, its ProgramTrace)."""
    from bench.lib import trace

    tr = trace.load_file(path)
    tr.program_trace = read_file(path)
    return tr, tr.program_trace


def of(tr):
    """The ProgramTrace of the reduced trace ``tr``; None where ``tr`` is.

    A metric's reader gets the reduced trace alone. Its file is the one
    that ``load_file`` read, or else the profile under the ``trace_dir``
    of the caller that holds ``tr``: the benchmark's ``run_cell``. What is
    read is kept on ``tr``, so that every reader of a run reads the file
    once. A trace whose file neither names raises: a reader that cannot
    find its profile must not fall silent.
    """
    if tr is None:
        return None
    if getattr(tr, "program_trace", None) is None:
        frame = sys._getframe(1)
        while frame is not None:
            scope = frame.f_locals
            if any(v is tr for v in scope.values()) and isinstance(scope.get("trace_dir"), str):
                tr.program_trace = read_file(find_xplane(scope["trace_dir"]))
                break
            frame = frame.f_back
        else:
            raise LookupError(
                "no profile for this trace: read it with program_trace.load_file, "
                "or from a caller that holds it beside a str trace_dir"
            )
    return tr.program_trace


def intersect(a, b):
    """The parts of the union of ``a`` that the union of ``b`` covers."""
    a, b = union(a), union(b)
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            out.append((max(s, b[k][0]), min(e, b[k][1])))
            k += 1
    return out


def subtract(a, b):
    """The parts of the union of ``a`` that the union of ``b`` leaves."""
    a, b = union(a), union(b)
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        t, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if t < e:
            out.append((t, e))
    return out


def omega_spans(prog):
    """Each Omega-step, from the start of its ``driver.omega_step`` span to
    the end of the first ``driver.rho`` or ``driver.result`` span after it:
    the readback that waits for the step's device work."""
    readbacks = prog.named(*OMEGA_READBACKS)
    out = []
    for _, s, _ in prog.named(OMEGA):
        after = [e for _, rs, e in readbacks if rs >= s]
        if after:
            out.append((s, min(after)))
    return out


def omega_intervals(prog):
    """``omega_spans`` less those that hold a JAX compile event (a retrace
    of ``w_from_alpha``, or of the Omega-step's programs): their time is
    compile_share's, as for ``round_trip_intervals``."""
    return [(s, e) for s, e in omega_spans(prog) if not prog.holds_compile(s, e)]


def omega_share(tr, prog):
    """Percent of the traced window inside ``omega_intervals``; None
    without an Omega-step span."""
    if tr is None or prog is None:
        return None
    ivs = omega_intervals(prog)
    if not ivs:
        return None
    return 100.0 * covered(clip(ivs, *tr.window)) * 1e-9 / tr.window_s


def round_trip_intervals(prog):
    """``driver.round`` and ``driver.objectives`` spans, less those that
    hold a JAX compile event (their time is compile_share's)."""
    return [
        (s, e) for _, s, e in prog.named(*ROUND_TRIP)
        if not prog.holds_compile(s, e)
    ]


def compile_intervals(prog):
    """JAX's compile events, and the round trips and Omega-steps that hold
    one: these also fetch the program from the persistent cache and load
    it, which JAX does not annotate."""
    held = [(s, e) for _, s, e in prog.named(*ROUND_TRIP)] + omega_spans(prog)
    return [(s, e) for _, s, e in prog.compile_spans] + [
        (s, e) for s, e in held if prog.holds_compile(s, e)
    ]


def round_idle_share(tr, prog):
    """Percent of the traced window in which the device is idle inside a
    round trip (``round_trip_intervals``), averaged over the devices, with
    the busy time of ``idle_share``; None without a round span or a
    device plane."""
    if tr is None or prog is None or not tr.devices:
        return None
    spans = union(clip(round_trip_intervals(prog), *tr.window))
    if not spans:
        return None
    inside = covered(spans)
    idle = 0.0
    for d in tr.devices:
        events = d.ops or d.modules
        busy = intersect([(s, e) for _, s, e in events], spans)
        idle += inside - covered(busy)
    return 100.0 * idle / len(tr.devices) * 1e-9 / tr.window_s


def idle_gaps(tr, prog, limit: int = 10):
    """The longest idle stretches of the first device, each named by the
    innermost span of any kind (harness, program, compile) the host was in
    at its midpoint: ``Trace.idle_gaps`` with the program's spans."""
    if not tr.devices:
        return []
    spans = tr.spans + prog.program_spans + prog.compile_spans
    dev = tr.devices[0]
    events = dev.ops or dev.modules
    longest = sorted(
        gaps([(a, b) for _, a, b in events], *tr.window), key=lambda g: g[0] - g[1]
    )[:limit]
    out = []
    for s, e in longest:
        mid = 0.5 * (s + e)
        inner = [(b - a, n) for n, a, b in spans if a <= mid <= b]
        out.append((min(inner)[1] if inner else "outside", (e - s) * 1e-9))
    return out


# the parts an idle second is charged to, first claim first
IDLE_PARTS = ("compile", "round trip", "Omega-step", "shard", "other")


def idle_split(tr, prog) -> dict:
    """Seconds of the first device's idle time in the traced window,
    split by what the host was doing: compiling (``compile_intervals``), a
    round trip (``round_trip_intervals``), an Omega-step
    (``omega_intervals``), the driver's ``driver.shard``, or anything
    else."""
    dev = tr.devices[0]
    events = dev.ops or dev.modules
    idle = gaps([(a, b) for _, a, b in events], *tr.window)
    claims = [
        compile_intervals(prog),
        round_trip_intervals(prog),
        omega_intervals(prog),
        [(s, e) for _, s, e in prog.named("driver.shard")],
    ]
    out = {}
    for part, ivs in zip(IDLE_PARTS, claims):
        out[part] = covered(intersect(idle, ivs)) * 1e-9
        idle = subtract(idle, ivs)
    out["other"] = covered(idle) * 1e-9
    return out
