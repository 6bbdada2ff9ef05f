"""Observability layer: span tracer, metrics registry, exporters, and the
unified wire_stats schema shared by every transport.

Tracing and the global registry are process-wide state, so every test that
touches them goes through the ``clean_obs`` fixture (tracer disabled and
cleared on exit, global registry untouched — tests build their own).
"""
import json
import threading
import urllib.request

import pytest

from repro import obs
from repro.obs.export import JsonlExporter, MetricsHTTPServer, to_prometheus
from repro.obs.metrics import (
    MetricsRegistry,
    publish_serving_metrics,
    publish_wire_stats,
)


@pytest.fixture
def clean_obs():
    obs.disable()
    obs.get_tracer().clear()
    yield
    obs.disable()
    obs.get_tracer().clear()


class FakeClock:
    """Deterministic monotone clock: each tick() advances by ``step``."""

    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step

    def __call__(self):
        return self.t

    def tick(self, dt=None):
        self.t += self.step if dt is None else dt
        return self.t


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------
def test_span_disabled_is_noop(clean_obs):
    assert not obs.enabled()
    with obs.span("gate", cat="transport", worker=0):
        pass
    assert obs.get_tracer().events() == []
    # the disabled path hands back one shared object — no per-call alloc
    assert obs.span("a") is obs.span("b", cat="x", k=1)


def test_span_records_chrome_complete_events(clean_obs):
    clk = FakeClock()
    tracer = obs.enable(clear=True, clock=clk)
    with obs.span("commit", cat="transport", worker=3, round=7):
        clk.tick(0.25)
    obs.disable()
    (e,) = tracer.events()
    assert e["name"] == "commit" and e["cat"] == "transport"
    assert e["ph"] == "X"
    assert e["dur"] == pytest.approx(0.25e6)  # microseconds
    assert e["args"] == {"worker": 3, "round": 7}


def test_span_nesting_and_breakdown(clean_obs):
    clk = FakeClock()
    obs.enable(clear=True, clock=clk)
    with obs.span("round", cat="transport"):
        with obs.span("solve", cat="transport"):
            clk.tick(1.0)
        with obs.span("solve", cat="transport"):
            clk.tick(2.0)
    obs.disable()
    bd = obs.phase_breakdown()
    assert bd["solve"]["count"] == 2
    assert bd["solve"]["total_s"] == pytest.approx(3.0)
    assert bd["solve"]["max_s"] == pytest.approx(2.0)
    assert bd["round"]["total_s"] == pytest.approx(3.0)
    # the inner spans lie inside the outer one on the same thread
    evs = sorted(obs.get_tracer().events(), key=lambda e: e["dur"])
    outer = evs[-1]
    for inner in evs[:-1]:
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_ring_buffer_caps_and_counts_drops(clean_obs):
    tracer = obs.enable(capacity=4, clear=True)
    for i in range(10):
        with obs.span(f"s{i}"):
            pass
    obs.disable()
    evs = tracer.events()
    assert len(evs) == 4
    assert tracer.dropped == 6
    # ring keeps the NEWEST spans
    assert [e["name"] for e in evs] == ["s6", "s7", "s8", "s9"]


def test_export_chrome_trace(tmp_path, clean_obs):
    obs.enable(clear=True)
    with obs.span("fit_async", cat="driver"):
        with obs.span("w_step", cat="driver", outer=0):
            pass
    obs.disable()
    path = tmp_path / "trace.json"
    n = obs.export_chrome(str(path))
    assert n == 2
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"fit_async", "w_step"}
    # thread-name metadata rows so chrome://tracing labels the lanes
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evs)


def test_concurrent_spans_stay_well_formed(clean_obs):
    """4 threads emit nested spans concurrently; every thread's events
    must form a proper per-thread nesting with no cross-thread bleed."""
    n_threads, n_outer = 4, 25
    tracer = obs.enable(capacity=4096, clear=True)
    barrier = threading.Barrier(n_threads)

    def worker(w):
        barrier.wait()
        for r in range(n_outer):
            with obs.span("round", cat="t", worker=w, round=r):
                for _ in range(3):
                    with obs.span("inner", cat="t", worker=w):
                        pass

    threads = [
        threading.Thread(target=worker, args=(w,)) for w in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    obs.disable()

    evs = tracer.events()
    assert tracer.dropped == 0
    assert len(evs) == n_threads * n_outer * 4
    by_tid = {}
    for e in evs:
        by_tid.setdefault(e["tid"], []).append(e)
    assert len(by_tid) == n_threads
    for tid, tevs in by_tid.items():
        # one worker id per thread: no event landed on the wrong lane
        assert len({e["args"]["worker"] for e in tevs}) == 1
        assert sum(e["name"] == "round" for e in tevs) == n_outer
        # proper nesting: sorted by start (ties: longest first), each span
        # must close before every still-open ancestor does
        tevs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in tevs:
            t0, t1 = e["ts"], e["ts"] + e["dur"]
            while stack and stack[-1] <= t0:
                stack.pop()
            if stack:
                assert t1 <= stack[-1] + 1e-6
            stack.append(t1)


def _profiled(tmp_path, body):
    """Run ``body()`` under a JAX profiler session (no Python tracer) and
    return the host plane's events as [(name, stats)]."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    return [
        (ev.name, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    ]


def test_span_reaches_the_profiler_while_a_session_records(tmp_path, clean_obs):
    """obs disabled: under a profiler session ``span`` is the profiler's
    annotation, named ``{cat}.{name}`` with its labels as stats; outside
    the session it is the shared no-op again."""
    import jax

    def body():
        assert obs.span("x") is not obs.span("y")
        with obs.span("commit", cat="transport", worker=3, round=7):
            with obs.span("solve", cat="transport"):
                pass

    events = _profiled(tmp_path, body)
    assert ("transport.commit", {"worker": 3, "round": 7}) in events
    assert [n for n, _ in events].count("transport.solve") == 1
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert obs.span("a") is obs.span("b", cat="x", k=1)
    assert obs.get_tracer().events() == []


def test_enabled_span_records_in_both_sinks(tmp_path, clean_obs):
    tracer = obs.enable(clear=True)

    def body():
        with obs.span("omega_step", cat="driver", outer=2):
            pass

    events = _profiled(tmp_path, body)
    obs.disable()
    assert ("driver.omega_step", {"outer": 2}) in events
    (e,) = tracer.events()
    assert (e["name"], e["cat"], e["args"]) == ("omega_step", "driver", {"outer": 2})


def test_obs_imports_and_spans_without_jax():
    """JAX is looked up lazily: with it unimportable, obs still imports
    and a span is the shared no-op (disabled) or a ring-buffer span."""
    import os
    import subprocess
    import sys

    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from repro import obs\n"
        "assert obs.span('a') is obs.span('b')\n"
        "t = obs.enable(clear=True)\n"
        "with obs.span('c', cat='driver'): pass\n"
        "assert [e['name'] for e in t.events()] == ['c']\n"
        "print('ok')\n"
    )
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": src}, timeout=60,
    )
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_enable_capacity_change_rebuilds_ring(clean_obs):
    t1 = obs.enable(capacity=8, clear=True)
    t2 = obs.enable(capacity=8)  # same capacity: same tracer
    assert t1 is t2
    t3 = obs.enable(capacity=16)
    assert t3 is not t1
    obs.disable()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("repro_test_commits", "commits", labels=("worker",))
    c.inc(worker=0)
    c.inc(2.0, worker=0)
    c.inc(worker=1)
    series = {d["worker"]: v for d, v in c.series()}
    assert series == {"0": 3.0, "1": 1.0}  # label values stringify
    with pytest.raises(ValueError):
        c.inc(-1.0, worker=0)  # counters only go up

    g = reg.gauge("repro_test_depth", "queue depth")
    g.set(5.0)
    g.add(-2.0)
    assert g.value() == 3.0

    h = reg.histogram(
        "repro_test_latency", "s", buckets=(0.1, 1.0, 10.0)
    )
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    ((_, state),) = h.series()
    assert state.count == 4
    assert state.sum == pytest.approx(55.55)
    assert state.counts == [1, 1, 1, 1]  # per-bucket + overflow


def test_metric_label_and_name_validation():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("bad name!", "x")
    c = reg.counter("repro_test_c", "x", labels=("worker",))
    with pytest.raises(ValueError):
        c.inc(replica=0)  # undeclared label
    c.inc()  # omitted declared label defaults to "" (one catch-all series)
    ((labels, v),) = c.series()
    assert labels == {"worker": ""} and v == 1.0


def test_registry_get_or_create_conflicts():
    reg = MetricsRegistry()
    c1 = reg.counter("repro_test_x", "x", labels=("a",))
    assert reg.counter("repro_test_x", "x", labels=("a",)) is c1
    with pytest.raises(TypeError):
        reg.gauge("repro_test_x", "x")  # kind conflict
    with pytest.raises(ValueError):
        reg.counter("repro_test_x", "x", labels=("b",))  # label conflict


def test_registry_as_dict_is_json_ready():
    reg = MetricsRegistry()
    reg.counter("repro_test_n", "n").inc()
    reg.histogram("repro_test_h", "h", buckets=(1.0,)).observe(0.5)
    json.dumps(reg.as_dict())  # must not raise


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter(
        "repro_transport_n_commits", "commits", labels=("transport",)
    ).inc(3, transport="threaded")
    reg.histogram("repro_serve_lat", "s", buckets=(0.5, 1.0)).observe(0.7)
    text = to_prometheus(reg)
    assert "# TYPE repro_transport_n_commits counter" in text
    assert 'repro_transport_n_commits{transport="threaded"} 3' in text
    # histograms expose CUMULATIVE buckets plus _sum/_count
    assert 'repro_serve_lat_bucket{le="0.5"} 0' in text
    assert 'repro_serve_lat_bucket{le="1"} 1' in text  # integral le: no .0
    assert 'repro_serve_lat_bucket{le="+Inf"} 1' in text
    assert "repro_serve_lat_count 1" in text


def test_jsonl_exporter(tmp_path):
    reg = MetricsRegistry()
    g = reg.gauge("repro_test_g", "g")
    path = tmp_path / "metrics.jsonl"
    clk = FakeClock()
    exp = JsonlExporter(str(path), registry=reg, clock=clk)
    g.set(1.0)
    exp.snapshot()
    clk.tick()
    g.set(2.0)
    exp.snapshot()
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == 2
    assert lines[0]["t"] == 0.0 and lines[1]["t"] == 1.0
    assert "metrics" in lines[0]


def test_metrics_http_server_serves_prometheus():
    reg = MetricsRegistry()
    reg.counter("repro_test_hits", "hits").inc(7)
    with MetricsHTTPServer(port=0, registry=reg) as srv:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5
        ).read().decode()
    assert "repro_test_hits 7" in body


# ---------------------------------------------------------------------------
# wire_stats: one schema across every transport
# ---------------------------------------------------------------------------
def test_new_wire_stats_rejects_unknown_keys():
    from repro.core.transport import WIRE_STATS_SCHEMA, new_wire_stats

    ws = new_wire_stats(codec="int8")
    assert set(ws) == set(WIRE_STATS_SCHEMA)
    assert ws["codec"] == "int8"
    with pytest.raises(ValueError):
        new_wire_stats(snapshot_byts=1)  # typo'd counter name


@pytest.mark.parametrize("name", ["simulated", "threaded", "gossip"])
def test_transports_share_wire_stats_schema(
    name, small_problem, small_cfg, one_device_mesh
):
    """Every transport's ``wire_stats`` carries the documented key union —
    gossip-only keys (spectral_gap, mix traffic) included, zeroed where a
    transport has nothing to report."""
    from repro.core import AsyncOptions, MeshAxes
    from repro.core.omega_regularizers import resolve_regularizer
    from repro.core.transport import WIRE_STATS_SCHEMA, get_transport

    opts = AsyncOptions(
        transport=name,
        # simulated derives its worker count from the mesh data axis
        n_workers=None if name == "simulated" else 4,
        **({"topology": "ring"} if name == "gossip" else {}),
    )
    cfg = small_cfg
    reg = resolve_regularizer(cfg, None, m=small_problem.train.m)
    t = get_transport(name).factory()
    kw = (
        dict(mesh=one_device_mesh, axes=MeshAxes(data="data"))
        if name == "simulated"
        else dict(mesh=None, axes=MeshAxes())
    )
    t.setup(
        cfg, small_problem.train, reg=reg, init=None, track=False,
        options=opts, **kw,
    )
    try:
        assert set(t.wire_stats) == set(WIRE_STATS_SCHEMA), name
        assert isinstance(t.wire_stats["codec"], str)
        assert isinstance(t.wire_stats["topology"], str)
        if name == "gossip":
            assert t.wire_stats["spectral_gap"] > 0
        else:
            assert t.wire_stats["spectral_gap"] == 0.0
    finally:
        t.close()


def test_publish_wire_stats_gauges():
    from repro.core.transport import new_wire_stats

    reg = MetricsRegistry()
    ws = new_wire_stats(codec="bf16", n_commits=12, commit_bytes=3456)
    publish_wire_stats(ws, transport="threaded", registry=reg)
    text = to_prometheus(reg)
    assert (
        'repro_transport_n_commits{transport="threaded",codec="bf16",'
        'topology="star"} 12' in text
    )
    assert "repro_transport_commit_bytes" in text
    # str-valued schema fields are labels, not gauges
    assert "repro_transport_codec " not in text


# ---------------------------------------------------------------------------
# ServingMetrics: merge idempotence + summary schema
# ---------------------------------------------------------------------------
_SUMMARY_KEYS = {
    "submitted", "completed", "rejected", "expired", "slo_s",
    "slo_violations", "swaps", "last_version", "elapsed_s",
    "throughput_rps", "queue_depth_max", "tiles", "tile_fill",
    "decode_steps", "slot_occupancy", "ttft", "latency",
    "latency_buckets", "per_task",
}


def _loaded_metrics(clock):
    from repro.serve.metrics import ServingMetrics

    m = ServingMetrics(slo_s=1.0, clock=clock)
    m.on_submit(task=0)
    m.on_submit(task=1)
    m.on_tile(filled=2, slots=4)
    m.on_complete(0, latency_s=0.2, violated=False)
    m.on_complete(1, latency_s=2.0, violated=True)
    m.on_swap(version=3)
    m.observe_queue_depth(5)
    return m


def test_serving_metrics_merge_empty_windows_is_identity():
    """Merging any number of EMPTY windows into a loaded one changes no
    counter — rollups of idle replicas are a no-op, applied repeatedly."""
    from repro.serve.metrics import ServingMetrics

    clk = FakeClock(step=0.0)
    m = _loaded_metrics(clk)
    empties = [ServingMetrics(slo_s=1.0, clock=clk) for _ in range(3)]
    once = m.merge(*empties)
    twice = once.merge(*empties)
    base, s1, s2 = m.summary(), once.summary(), twice.summary()
    assert s1 == base
    assert s2 == s1
    # and empty + empty stays empty
    e = empties[0].merge(empties[1]).summary()
    assert e["submitted"] == 0 and e["completed"] == 0
    assert e["throughput_rps"] == 0.0


def test_serving_metrics_summary_schema_pinned():
    """``summary()`` is the BENCH_serving row shape AND what the obs
    bridge flattens into gauges — additions/renames must be deliberate."""
    clk = FakeClock(step=0.0)
    s = _loaded_metrics(clk).summary()
    assert set(s) == _SUMMARY_KEYS
    json.dumps(s)  # JSON-ready end to end
    assert s["submitted"] == 2 and s["completed"] == 2
    assert s["slo_violations"] == 1
    assert s["tile_fill"] == pytest.approx(0.5)
    assert set(s["per_task"]) == {"0", "1"}


def test_publish_serving_metrics_gauges():
    clk = FakeClock(step=0.0)
    reg = MetricsRegistry()
    publish_serving_metrics(_loaded_metrics(clk), replica="2", registry=reg)
    text = to_prometheus(reg)
    assert 'repro_serve_submitted{replica="2"} 2' in text
    assert 'repro_serve_slo_violations{replica="2"} 1' in text
    # latency quantile sub-dict flattens to its own gauge family
    assert "repro_serve_latency_p50" in text
