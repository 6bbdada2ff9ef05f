#!/usr/bin/env python3
"""Sweep a scoring cell's offered rate to find the knee: the highest rate
at which the backlog right after the last request was sent stays within
one tile and completions keep up with arrivals (the window, drain
included, completes at least 97% of the offered rate).

    python3 bench/tools/knee.py --workload school.score --seed 1 \\
        --seconds 5 --rates 5000 10000 20000 40000

One set-up, then one window (with the cell's mid-window hot-swap) per
rate, in the order given, up to the first rate that does not keep up;
one JSON line per rate. Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent))

from bench import run as brun  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="school.score")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    cell = brun.load_json(BENCH / "workloads" / f"{args.workload}.json")
    config = brun.load_json(BENCH / "configs" / f"{cell['config']}.json")
    devices = brun.prepare(cell["chips"])
    if devices is None:
        return 1
    generator = brun.load_module(BENCH / "generators" / f"{config['generator']}.py", "generator")
    kind = brun.load_module(BENCH / "kinds" / f"{cell['kind']}.py", "kind")
    ns = argparse.Namespace(workload=args.workload, seed=args.seed, seconds=args.seconds)
    ctx = brun.Context(ns, cell, config, generator, devices)
    st = kind.setup(ctx)
    for rate in args.rates:
        traffic = dict(cell["traffic"], rate=rate)
        reqs, due, tasks, rows, t0, backlog = kind.window(ctx, st, traffic)
        _, info = kind.summary(ctx, reqs, due, t0, backlog)
        keeps_up = (
            backlog <= cell["batch"]
            and info["completed_per_s"] >= 0.97 * info["offered_per_s"]
        )
        print(json.dumps({"rate": rate, "keeps_up": keeps_up, **info}), flush=True)
        if not keeps_up:  # above the knee the backlog, and the window, grow without end
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
