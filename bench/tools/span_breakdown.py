#!/usr/bin/env python3
"""Print, as one JSON line, what a profiler trace of the benchmark's window
says about the program's spans (bench/lib/program_trace.py):

- ``spans``: the count of each program span, and ``rounds_per_fit``, the
  ``driver.round`` spans inside each whole ``fit``;
- ``omega_share`` and ``round_idle_share`` (percent of the traced window);
- ``idle_s``: the first device's idle seconds split by what the host was
  doing (compile, round trip, Omega-step, shard, other), with
  ``window_s`` and ``busy_s``;
- ``idle_gaps``: the longest idle stretches, named by the innermost span;
- ``stalls``: the longest stretches between two harness spans and the
  longest harness spans, each with the host events (any thread) that
  overlap it by at least ``--min-ms``;
- ``host_events`` (with ``--names``): every host event name with its count
  and seconds, to read names off a trace by hand.

    python3 bench/tools/span_breakdown.py <trace dir> [--names] [--min-ms 1]
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.lib import program_trace  # noqa: E402
from bench.lib.trace import UNITS, find_xplane  # noqa: E402

HARNESS = ("tile", "wait", "publish", "fit")


def host_events(path):
    """Every host-plane event [(line, name, start_ns, end_ns)]."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns
                    out.append((line.name, ev.name, s, s + ev.duration_ns))
    return out


def stalls(tr, events, min_ns, limit=3):
    """The longest stretches between consecutive harness spans, and the
    longest harness spans, with the host events overlapping each."""
    lo, hi = tr.window
    spans = sorted((s, e, n) for n, s, e in tr.spans if n in HARNESS and lo <= s and e <= hi)
    between = [(b[0] - a[1], a[1], b[0], f"after {a[2]}") for a, b in zip(spans, spans[1:])]
    inside = [(e - s, s, e, n) for s, e, n in spans]
    out = []
    for length, s, e, what in sorted(between, reverse=True)[:limit] + sorted(inside, reverse=True)[:limit]:
        overlap = sorted(
            (min(b, e) - max(a, s), line, name, a - s, b - a)
            for line, name, a, b in events
            if a < e and b > s and min(b, e) - max(a, s) >= min_ns
        )[::-1][:12]
        out.append({
            "what": what, "ms": length * 1e-6, "at_s": (s - lo) * 1e-9,
            "host_events": [
                {"line": line[:40], "name": name[:80], "overlap_ms": ov * 1e-6,
                 "starts_ms": st * 1e-6, "ms": d * 1e-6}
                for ov, line, name, st, d in overlap
            ],
        })
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--names", action="store_true")
    ap.add_argument("--min-ms", type=float, default=1.0)
    args = ap.parse_args(argv)

    path = find_xplane(args.trace_dir)
    tr, prog = program_trace.load_file(path)
    lo, hi = tr.window
    out = {"window_s": tr.window_s, "busy_s": tr.busy_s(), "units": tr.units()}
    out["spans"] = dict(collections.Counter(n for n, _, _ in prog.program_spans))
    out["compile_events"] = dict(collections.Counter(n for n, _, _ in prog.compile_spans))
    fits = [(s, e) for n, s, e in tr.spans if n in UNITS and lo <= s and e <= hi]
    out["rounds_per_fit"] = [
        sum(1 for _, a, b in prog.named("driver.round") if s <= a and b <= e)
        for s, e in fits
    ]
    out["omega_share"] = program_trace.omega_share(tr, prog)
    out["round_idle_share"] = program_trace.round_idle_share(tr, prog)
    if tr.devices:
        out["idle_s"] = program_trace.idle_split(tr, prog)
        out["idle_gaps"] = program_trace.idle_gaps(tr, prog)
        out["harness_idle_gaps"] = tr.idle_gaps()
    events = host_events(path)
    out["stalls"] = stalls(tr, events, args.min_ms * 1e6)
    if args.names:
        count, ns = collections.Counter(), collections.Counter()
        for _, name, s, e in events:
            if e > lo and s < hi:
                count[name] += 1
                ns[name] += e - s
        out["host_events"] = [[n, count[n], t * 1e-9] for n, t in ns.most_common(120)]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
