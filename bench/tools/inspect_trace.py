#!/usr/bin/env python3
"""Print what a profiler trace holds: each plane's lines with their event
counts and the most frequent and the longest event names, so that the
names the reduction reads (bench/lib/trace.py) can be checked by hand.

    python3 bench/tools/inspect_trace.py <trace dir> [--top 15]
"""
from __future__ import annotations

import argparse
import collections
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.lib.trace import find_xplane  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    path = find_xplane(args.trace_dir)
    print(path, Path(path).stat().st_size, "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            count, dur = collections.Counter(), collections.Counter()
            first = last = None
            for ev in line.events:
                count[ev.name] += 1
                dur[ev.name] += ev.duration_ns
                first = ev.start_ns if first is None else min(first, ev.start_ns)
                last = ev.start_ns + ev.duration_ns if last is None else max(last, ev.start_ns + ev.duration_ns)
            print(f"  line {line.name!r}: {sum(count.values())} events, {first} .. {last} ns")
            for name, ns in dur.most_common(args.top):
                print(f"    {ns * 1e-9:12.6f} s  x{count[name]:<7d} {name[:110]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
