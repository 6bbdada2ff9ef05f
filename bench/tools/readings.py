#!/usr/bin/env python3
"""Readings that set a cell's limits for ``correct``, on many seeds in one
process (no measured window):

    python3 bench/tools/readings.py --workload mnist.train --seeds 1 2 3 \\
        [--control] [--faults half no_exchange] [--out readings.jsonl]

Training cells: for each seed, the cell's own estimator fits the cell's
data as the window's fits do (the program's reading); with ``--control``
the plain reference in bfloat16 stands in its place; ``--faults`` runs the
program again with each fault of ``bench/lib/faults.py`` planted in it.
Every reading compares with the float32 reference at the highest
precision, by the cell's own numbers.

Scoring cells: set-up and one window at the cell's rate (``--seconds``);
the program's reading, and with ``--control`` the same requests scored by
the reference in bfloat16.

One JSON line per seed; needs the chip, as a run does.
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
from bench.lib.faults import planted  # noqa: E402


def train_readings(ctx, kind, control, faults) -> dict:
    import jax.numpy as jnp
    from repro.core.mtl_data import MTLData

    raw = ctx.generator.make(ctx.config, ctx.key, ctx.seed, ("train",))["train"]
    data = MTLData(*raw)
    est = kind.build(ctx, data)
    t0 = time.perf_counter()
    est.fit(data)
    out = {"fit_s_cold": time.perf_counter() - t0}
    program = [kind.fit_result(est)]
    del est
    t0 = time.perf_counter()
    ref = kind.reference(ctx.cell, ctx.config, raw, ctx.seed)
    out["reference_s"] = time.perf_counter() - t0
    out["program"] = kind.readings(program, ref, ctx.cell["rounds"])
    runs = {}
    if control:
        runs["control"] = kind.reference(ctx.cell, ctx.config, raw, ctx.seed, jnp.bfloat16)
    for name, r in runs.items():
        out[name] = kind.readings([(r["W"], r["primal"] - r["dual"])], ref, ctx.cell["rounds"])
    for fault in faults:
        with planted(fault):
            est = kind.build(ctx, data)
            est.fit(data)
            out[fault] = kind.readings([kind.fit_result(est)], ref, ctx.cell["rounds"])
            del est
    return out


def score_readings(ctx, kind, control) -> dict:
    import jax.numpy as jnp
    import numpy as np

    st = kind.setup(ctx)
    reqs, due, tasks, rows, t0, backlog = kind.window(ctx, st, ctx.cell["traffic"])
    _, info = kind.summary(ctx, reqs, due, t0, backlog)
    out = {"info": info, "program": kind.readings(reqs, st["x_test"], tasks, rows, st["weights"])}
    if control:
        # the reference's dot in bfloat16, on the device, in the program's place
        X = jnp.asarray(st["x_test"][tasks, rows], jnp.bfloat16)
        versions = np.asarray([r.snapshot_version for r in reqs])
        z = np.empty(len(reqs))
        for v, W in st["weights"].items():
            sel = versions == v
            w = jnp.asarray(W[tasks[sel]], jnp.bfloat16)
            z[sel] = np.asarray(
                jnp.einsum("nd,nd->n", w, X[sel], preferred_element_type=jnp.bfloat16),
                np.float64,
            )
        for r, zi in zip(reqs, z):
            r.score = float(zi)
        out["control"] = kind.readings(reqs, st["x_test"], tasks, rows, st["weights"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
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
        ns = argparse.Namespace(workload=args.workload, seed=seed, seconds=args.seconds)
        ctx = brun.Context(ns, cell, config, generator, devices)
        if cell["kind"] == "train":
            out = train_readings(ctx, kind, args.control, args.faults)
        else:
            out = score_readings(ctx, kind, args.control)
        line = json.dumps({"workload": args.workload, "seed": seed, **out})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
