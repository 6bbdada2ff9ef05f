#!/usr/bin/env python3
"""The on-chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``bench/workloads/<cell>.json``,
its configuration in ``bench/configs/<config>.json``, the data generator
in ``bench/generators/<generator>.py``, the runner of the cell's kind in
``bench/kinds/<kind>.py`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``; ``BENCHMARK.json`` at the root says which
metrics a cell reports.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and the result
carries its per-layer metrics, read from the trace. Earlier lines of
standard output say what the run did; the numbers that decide
``correct`` are the last lines of standard error and the result's last
key; the last line of standard output is the result. Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


class Window:
    """Host-clock bounds of the measured window."""

    def __init__(self):
        self.t0 = self.t1 = None

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Context:
    """What a kind's runner gets: the cell, its configuration and
    generator, the devices, and the hooks that mark set-up, the window and
    the harness's trace spans."""

    def __init__(self, args, cell, config, generator, devices, trace_dir=None):
        import jax

        from bench.lib.seeds import data_key

        self.jax = jax
        self.seed = args.seed
        self.seconds = args.seconds
        self.cell = cell
        self.config = config
        self.generator = generator
        self.devices = devices
        self.chips = cell["chips"]
        self.key = data_key(args.seed)
        self.trace_dir = trace_dir
        self.setup_end = None
        self.win = None
        self.memory_peak = None

    def info(self, **fields) -> None:
        print(json.dumps({"info": fields}), flush=True)

    def setup_done(self) -> None:
        """Marks the end of set-up. What set-up built (data, requests) is
        moved out of the garbage collector's reach, so that a full
        collection of the harness's own objects cannot stall the window."""
        self.setup_end = time.perf_counter()
        gc.collect()
        gc.freeze()

    def span(self, name: str):
        if self.trace_dir is None:
            return contextlib.nullcontext()
        return self.jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        if self.setup_end is None:
            self.setup_done()
        win = Window()
        if self.trace_dir is not None:
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # no per-call Python events
            opts.enable_hlo_proto = False
            self.jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with self.span("window"):
                win.t0 = time.perf_counter()
                yield win
                win.t1 = time.perf_counter()
        finally:
            if self.trace_dir is not None:
                self.jax.profiler.stop_trace()
        self.win = win

    def read_memory(self) -> None:
        peaks = []
        for d in self.devices[: self.chips]:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        self.memory_peak = max(peaks) if peaks else None


class Run:
    """What a per-layer metric's reader gets."""

    def __init__(self, chips, peak, window_s, counters, compile_window, trace):
        self.chips = chips
        self.peak = peak
        self.window_s = window_s  # host clock
        self.counters = counters
        self.compile_window = compile_window
        self.trace = trace  # bench.lib.trace.Trace, or None


def per_layer(bench, name, run) -> dict:
    out = {}
    for entry in bench["per_layer"]:
        if not applies(entry, name):
            continue
        reader = load_module(BENCH / "metrics" / f"{entry['name']}.py", f"metric_{len(out)}")
        value = reader.read(run)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def finite(obj):
    """``obj`` with every non-finite float replaced by None, so that the
    result stays strict JSON."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [finite(v) for v in obj]
    return obj


def run_cell(args, devices, trace_dir=None, cell=None, config=None) -> dict:
    """Set up, measure and check one cell on ``devices``; returns the
    result object (without printing it). ``cell`` and ``config`` default
    to the files named by ``args.workload``."""
    import jax

    from bench.lib import trace as trace_mod
    from bench.lib.compile_clock import CompileClock
    from bench.lib.peaks import peak

    bench = load_json(ROOT / "BENCHMARK.json")
    if cell is None:
        cell = load_json(BENCH / "workloads" / f"{args.workload}.json")
    if config is None:
        config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    generator = load_module(BENCH / "generators" / f"{config['generator']}.py", "generator")
    kind = load_module(BENCH / "kinds" / f"{cell['kind']}.py", "kind")
    chip_peak = peak(devices[0].device_kind)

    clock = CompileClock()
    clock.install(jax.monitoring)
    ctx = Context(args, cell, config, generator, devices, trace_dir)
    res = kind.run(ctx)
    win = ctx.win
    compile_window = clock.between(win.t0, win.t1)
    # set-up with every program in the persistent cache: the compiles that
    # missed it (all of them in a checkout's first run; in later runs those
    # whose program depends on the seed's data) are reported apart
    setup_wall = ctx.setup_end - T_START
    cold = clock.cold_seconds(T_START, ctx.setup_end)
    setup_s = setup_wall - cold
    ctx.info(
        setup_s=setup_s, setup_wall_s=setup_wall, setup_cold_compile_s=cold,
        setup_compile=clock.between(T_START, ctx.setup_end), window_s=win.seconds,
        compile_in_window=compile_window, memory_peak_bytes=ctx.memory_peak,
    )

    checks = res["checks"]
    correct = all(v <= limit for v, limit in checks.values())  # NaN fails
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": ctx.memory_peak,
    }
    result = {"correct": correct, "attempted": res["attempted"], "failed": res["failed"]}
    if trace_dir is None:
        e2e = {"setup_s": (setup_s, "s"), **res["e2e"]}
        metrics = {}
        for entry in bench["end_to_end"]:
            if applies(entry, args.workload):
                value, unit = e2e[entry["name"]]
                metrics[entry["name"]] = {"value": value, "unit": unit}
        result["metrics"] = metrics
    else:
        tr = trace_mod.load(trace_dir)
        run = Run(cell["chips"], chip_peak, win.seconds, res["counters"], compile_window, tr)
        result["metrics"] = per_layer(bench, args.workload, run)
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        ctx.info(
            traced_window_s=tr.window_s, traced_units=tr.units(),
            trace_dropped_at_s=None if tr.dropped_ns is None
            else (tr.dropped_ns - tr.window[0]) * 1e-9,
        )
        result["breakdown"] = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
    result["device"] = device
    result["checks"] = {k: {"value": v, "limit": limit} for k, (v, limit) in checks.items()}
    return result


def prepare(chips: int):
    """The TPU devices, with the compile cache and the import paths set;
    None (after saying why on standard error) when there is no TPU, too
    few chips or no program beside the benchmark."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r})", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"bench: the cell needs {chips} chips, JAX found {len(devices)}", file=sys.stderr)
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    # every program, however fast it compiled or small it is, is cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(1, str(ROOT / "src"))
    try:
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"bench: the program is not beside the benchmark: {e}", file=sys.stderr)
        return None
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--trace-dir", default=None,
        help="keep the profiler trace here (default: a temporary directory)",
    )
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    workload = BENCH / "workloads" / f"{args.workload}.json"
    if not workload.is_file():
        print(f"bench: no cell named {args.workload!r}", file=sys.stderr)
        return 1
    chips = load_json(workload)["chips"]

    devices = prepare(chips)
    if devices is None:
        return 1

    if args.trace:
        if args.trace_dir:
            result = run_cell(args, devices, args.trace_dir)
        else:
            with tempfile.TemporaryDirectory() as d:
                result = run_cell(args, devices, d)
    else:
        result = run_cell(args, devices)

    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}", file=sys.stderr)
    print(json.dumps(finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
