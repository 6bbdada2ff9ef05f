#!/usr/bin/env python3
"""Readings that set a packed training cell's limits for ``correct``, on
many seeds in one process (no measured window), as
``bench/tools/readings.py`` reads the padded cells:

    python3 bench/tools/readings_packed.py --workload mds.train --seeds 1 2 3 \\
        [--control] [--faults unchanged half] [--out readings.jsonl]

For each seed, the cell's own estimator fits the cell's packed data as the
window's fits do (the program's reading); with ``--control`` the plain
reference in bfloat16 stands in its place; ``--faults`` runs the program
again with each fault of ``bench/lib/faults_packed.py`` planted in it.
Every reading compares with the float32 reference at the highest
precision, by the cell's own numbers. One JSON line per seed; needs the
chip, as a run does.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent))

from bench import run as brun  # noqa: E402
from bench.lib.faults_packed import planted  # noqa: E402


def packed_readings(ctx, kind, control, faults) -> dict:
    import jax.numpy as jnp
    from repro.core.mtl_data import PackedMTLData

    raw = ctx.generator.make(ctx.config, ctx.key, ctx.seed, ("train",))["train"]
    data = PackedMTLData(*raw)
    est = kind.build(ctx, data)
    t0 = time.perf_counter()
    est.fit(data)
    out = {"fit_s_cold": time.perf_counter() - t0}
    program = [kind.fit_result(est)]
    del est
    t0 = time.perf_counter()
    ref = kind.reference(ctx.cell, ctx.config, raw, ctx.seed)
    out["reference_s"] = time.perf_counter() - t0
    rounds = ctx.cell["rounds"]
    out["program"] = kind.readings(program, ref, rounds)
    if control:
        ctl = kind.reference(ctx.cell, ctx.config, raw, ctx.seed, jnp.bfloat16)
        out["control"] = kind.readings([(ctl["W"], ctl["primal"] - ctl["dual"])], ref, rounds)
    for fault in faults:
        with planted(fault):
            est = kind.build(ctx, data)
            est.fit(data)
            out[fault] = kind.readings([kind.fit_result(est)], ref, rounds)
            del est
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cell = brun.load_json(BENCH / "workloads" / f"{args.workload}.json")
    config = brun.load_json(BENCH / "configs" / f"{cell['config']}.json")
    devices = brun.prepare(cell["chips"])
    if devices is None:
        return 1
    generator = brun.load_module(BENCH / "generators" / f"{config['generator']}.py", "generator")
    kind = brun.load_module(BENCH / "kinds" / f"{cell['kind']}.py", "kind")
    for seed in args.seeds:
        ns = argparse.Namespace(workload=args.workload, seed=seed, seconds=0.0)
        ctx = brun.Context(ns, cell, config, generator, devices)
        out = packed_readings(ctx, kind, args.control, args.faults)
        line = json.dumps({"workload": args.workload, "seed": seed, **out})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
