"""Continuous-batching scheduler: packing, deadlines, hot-swap, metrics.

Everything here runs on a VIRTUAL clock (the scheduler's injectable
``clock=``), so queueing behavior is deterministic; the wall-clock load
run lives in the ``load``-marked test at the bottom (CI slow job).
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dual import task_scores
from repro.serve import (
    ContinuousBatchingScheduler,
    LatencyHistogram,
    ModelSnapshot,
    MTLScoringEngine,
    QueueFull,
    ScoreRequest,
    ServingMetrics,
    VirtualClock as ManualClock,
)


class PacedEngine:
    """Adapter wrapper: each tile advances the virtual clock by a scripted
    service time (straggler tiles included) before scoring; everything but
    ``run_tile`` delegates to the wrapped engine."""

    def __init__(self, inner, clock, service_s):
        self.inner, self.clock = inner, clock
        self.service_s = list(service_s)
        self.tiles = 0

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def run_tile(self, reqs, snapshot):
        dt = self.service_s[min(self.tiles, len(self.service_s) - 1)]
        self.tiles += 1
        self.clock.advance(dt)
        self.inner.run_tile(reqs, snapshot)


@pytest.fixture()
def W():
    return np.random.RandomState(0).randn(5, 12).astype(np.float32)


def _requests(n, m=5, d=12, seed=1):
    rng = np.random.RandomState(seed)
    return [
        ScoreRequest(task=int(rng.randint(m)), x=rng.randn(d).astype(np.float32))
        for _ in range(n)
    ]


def test_partial_tiles_pack_immediately(W):
    """Arrivals smaller than a batch still get served (padded tile) —
    continuous batching, not blocking-until-full."""
    clk = ManualClock()
    sched = ContinuousBatchingScheduler(
        MTLScoringEngine(W, batch=4), clock=clk
    )
    reqs = _requests(3)
    sched.submit_many(reqs)
    done = sched.step()
    assert [r is d for r, d in zip(reqs, done)] == [True] * 3
    assert all(r.status == "done" and r.score is not None for r in reqs)
    assert sched.metrics.tiles == 1 and sched.metrics.tile_fill() == 0.75
    assert sched.step() == []  # idle


def test_fifo_vs_edf_packing(W):
    clk = ManualClock()
    eng = MTLScoringEngine(W, batch=2)
    sched = ContinuousBatchingScheduler(eng, policy="edf", clock=clk)
    a, b, c = _requests(3)
    sched.submit(a, deadline_s=10.0)
    sched.submit(b)  # no deadline -> packs last under EDF
    sched.submit(c, deadline_s=1.0)
    tile = sched.step()
    assert tile == [c, a]  # earliest deadline first
    assert sched.step() == [b]

    sched2 = ContinuousBatchingScheduler(
        MTLScoringEngine(W, batch=2), policy="fifo", clock=ManualClock()
    )
    a2, b2, c2 = _requests(3)
    sched2.submit(a2, deadline_s=10.0)
    sched2.submit(b2)
    sched2.submit(c2, deadline_s=1.0)
    assert sched2.step() == [a2, b2]  # arrival order


def test_deadline_aware_admission_and_expiry(W):
    clk = ManualClock()
    sched = ContinuousBatchingScheduler(
        MTLScoringEngine(W, batch=4), clock=clk
    )
    # expired at the door: absolute deadline already in the past
    dead = _requests(1)[0]
    dead.deadline_s = -1.0
    sched.submit(dead)
    assert dead.status == "expired" and sched.pending == 0
    # expired at packing: deadline passes while queued
    late, ok = _requests(2)
    sched.submit(late, deadline_s=0.5)
    sched.submit(ok, deadline_s=100.0)
    clk.advance(1.0)
    tile = sched.step()
    assert late.status == "expired" and late.score is None
    assert tile == [ok] and ok.status == "done"
    m = sched.metrics
    assert m.expired == 2 and m.slo_violations == 2 and m.completed == 1


def test_slo_violation_accounting(W):
    clk = ManualClock()
    eng = PacedEngine(MTLScoringEngine(W, batch=4), clk, [0.2])
    sched = ContinuousBatchingScheduler(eng, slo_s=0.1, clock=clk)
    sched.submit_many(_requests(2))
    sched.step()  # service 0.2s > slo 0.1s
    assert sched.metrics.slo_violations == 2
    assert sched.metrics.latency.percentile(50) == pytest.approx(0.2)


def test_bounded_queue_rejects(W):
    sched = ContinuousBatchingScheduler(
        MTLScoringEngine(W, batch=2), max_queue=2, clock=ManualClock()
    )
    r1, r2, r3 = _requests(3)
    sched.submit(r1)
    sched.submit(r2)
    with pytest.raises(QueueFull):
        sched.submit(r3)
    assert sched.metrics.rejected == 1 and sched.pending == 2


def test_admission_validates_once(W):
    sched = ContinuousBatchingScheduler(
        MTLScoringEngine(W, batch=2), clock=ManualClock()
    )
    with pytest.raises(ValueError, match="task id"):
        sched.submit(ScoreRequest(task=9, x=np.zeros(12, np.float32)))
    with pytest.raises(ValueError, match="feature shape"):
        sched.submit(ScoreRequest(task=0, x=np.zeros(3, np.float32)))
    assert sched.pending == 0


# ---------------------------------------------------------------------------
# hot-swap
# ---------------------------------------------------------------------------
def test_hot_swap_bit_equal_no_drops(W):
    """Scores before/after a snapshot switch are BIT-equal to direct
    task_scores against the respective W version; every request is scored
    exactly once."""
    rng = np.random.RandomState(3)
    W2 = rng.randn(*W.shape).astype(np.float32)
    clk = ManualClock()
    eng = MTLScoringEngine(W, batch=4, version=1)
    sched = ContinuousBatchingScheduler(eng, clock=clk)
    reqs = _requests(10, seed=4)
    sched.submit_many(reqs)
    done = list(sched.step())  # one tile on v1
    sched.publish(ModelSnapshot(version=2, W=W2))
    while sched.pending:
        done += sched.step()
    # no dropped or double-scored requests
    assert len(done) == len(reqs) and len({id(r) for r in done}) == len(reqs)
    assert sorted({r.snapshot_version for r in done}) == [1, 2]
    step = jax.jit(task_scores)
    for version, Wv in ((1, W), (2, W2)):
        group = [r for r in done if r.snapshot_version == version]
        assert group, f"no requests served on version {version}"
        X = np.stack([r.x for r in group])
        t = np.asarray([r.task for r in group], np.int32)
        # pad to the tile shape so the comparison runs the exact executable
        pad = (-len(group)) % eng.batch
        Xp = np.concatenate([X, np.zeros((pad, X.shape[1]), np.float32)])
        tp = np.concatenate([t, np.zeros((pad,), np.int32)])
        ref = np.asarray(step(jnp.asarray(Wv), jnp.asarray(Xp), jnp.asarray(tp)))
        got = np.asarray([r.score for r in group], np.float32)
        np.testing.assert_array_equal(got, ref[: len(group)])


def test_in_flight_tile_completes_on_packed_snapshot(W):
    """A publish landing mid-tile must NOT leak into that tile."""
    W2 = np.random.RandomState(5).randn(*W.shape).astype(np.float32)
    clk = ManualClock()
    eng = MTLScoringEngine(W, batch=4, version=1)
    sched = ContinuousBatchingScheduler(eng, clock=clk)

    inner_run_tile = eng.run_tile

    def swapping_run_tile(reqs, snapshot):
        # simulate a training thread publishing while the tile executes
        sched.publish(ModelSnapshot(version=2, W=W2))
        inner_run_tile(reqs, snapshot)

    eng.run_tile = swapping_run_tile
    reqs = _requests(2, seed=6)
    sched.submit_many(reqs)
    (r0, r1) = sched.step()
    assert r0.snapshot_version == 1 and r1.snapshot_version == 1
    assert r0.score == pytest.approx(float(r0.x @ W[r0.task]), abs=1e-5)
    eng.run_tile = inner_run_tile
    more = _requests(1, seed=7)
    sched.submit_many(more)
    assert sched.step()[0].snapshot_version == 2


def test_publish_version_must_increase(W):
    eng = MTLScoringEngine(W, batch=2, version=3)
    sched = ContinuousBatchingScheduler(eng, clock=ManualClock())
    # equal version = duplicate delivery: idempotent no-op, not a swap
    assert sched.publish(ModelSnapshot(version=3, W=W)) == 3
    assert sched.metrics.swaps == 0
    with pytest.raises(ValueError, match="not newer"):
        sched.publish(ModelSnapshot(version=2, W=W))
    with pytest.raises(TypeError):
        sched.publish(W)
    with pytest.raises(ValueError, match="shape"):
        eng.publish(ModelSnapshot(version=9, W=np.zeros((2, 2), np.float32)))
    assert sched.publish_weights(W) == 4  # auto-increment
    # an external version counter BEHIND the scheduler's is re-stamped
    # into its monotone version space, never dropped (transport counters
    # and estimator versions are independent sequences)
    assert sched.publish_weights(W, version=1) == 5
    # the scheduler shape-checks published snapshots against the engine
    with pytest.raises(ValueError, match="shape"):
        sched.publish(ModelSnapshot(version=9, W=np.zeros((2, 2), np.float32)))
    with pytest.raises(ValueError, match="shape"):
        sched.publish_weights(np.zeros((2, 2), np.float32))
    assert sched.version == 5  # nothing installed by the rejected pushes


def test_scheduler_picks_up_engine_pushed_snapshot(W):
    """A scheduler composed directly over an engine must notice snapshots
    pushed INTO the engine (e.g. by an estimator) at pack time."""
    W2 = np.random.RandomState(11).randn(*W.shape).astype(np.float32)
    eng = MTLScoringEngine(W, batch=4, version=1)
    sched = ContinuousBatchingScheduler(eng, clock=ManualClock())
    eng.swap(W2)  # push lands on the engine, not the scheduler
    (out,) = sched.submit_many(_requests(1, seed=12))
    r = out.request
    assert out.admitted
    sched.step()
    assert r.snapshot_version == 2 and sched.version == 2
    assert r.score == pytest.approx(float(r.x @ W2[r.task]), abs=1e-5)
    assert sched.metrics.swaps == 1


def test_engine_push_survives_scheduler_counter_running_ahead(W):
    """Pickup is by snapshot IDENTITY: an engine-side push whose version
    number is BEHIND a scheduler counter that other producers restamped
    ahead must still install (restamped), not be silently ignored."""
    W2 = np.random.RandomState(14).randn(*W.shape).astype(np.float32)
    W3 = np.random.RandomState(15).randn(*W.shape).astype(np.float32)
    eng = MTLScoringEngine(W, batch=4, version=1)
    sched = ContinuousBatchingScheduler(eng, clock=ManualClock())
    # e.g. a transport subscription pushes the scheduler counter to 6
    for _ in range(5):
        sched.publish_weights(W2)
    assert sched.version == 6 and eng.version == 1
    eng.swap(W3)  # engine-side push: version 2, numerically behind 6
    (out,) = sched.submit_many(_requests(1, seed=16))
    r = out.request
    sched.step()
    assert r.snapshot_version == 7  # restamped into the scheduler space
    assert r.score == pytest.approx(float(r.x @ W3[r.task]), abs=1e-5)


def test_failed_tile_requeues_requests(W):
    eng = MTLScoringEngine(W, batch=4)
    sched = ContinuousBatchingScheduler(eng, clock=ManualClock())
    reqs = [o.request for o in sched.submit_many(_requests(3, seed=13))]

    def boom(tile, snapshot):
        raise RuntimeError("device fell over")

    eng.run_tile = boom
    with pytest.raises(RuntimeError, match="fell over"):
        sched.step()
    # nothing lost: the tile went back to the head of the queue
    assert sched.pending == 3
    assert all(r.status == "queued" and r.score is None for r in reqs)
    eng.run_tile = MTLScoringEngine.run_tile.__get__(eng)
    assert sched.run_until_idle() == 3
    assert all(r.status == "done" for r in reqs)


def test_concurrent_submit_and_publish_thread_safety(W):
    """Training thread publishes while a serving thread steps: every
    request completes exactly once on SOME published version."""
    versions = [ModelSnapshot(version=v, W=W * v) for v in range(2, 12)]
    eng = MTLScoringEngine(W, batch=8, version=1)
    sched = ContinuousBatchingScheduler(eng)
    reqs = _requests(64, seed=8)

    def trainer():
        for snap in versions:
            sched.publish(snap)

    t = threading.Thread(target=trainer)
    for r in reqs[:32]:
        sched.submit(r)
    t.start()
    done = []
    backlog = list(reqs[32:])
    while len(done) < len(reqs):
        done += sched.step()
        while backlog and sched.pending < 8:  # feed the rest mid-flight
            sched.submit(backlog.pop(0))
    t.join()
    assert len(done) == 64 and all(r.status == "done" for r in reqs)
    assert all(1 <= r.snapshot_version <= 11 for r in reqs)
    assert sched.version == 11


# ---------------------------------------------------------------------------
# metrics unit behavior
# ---------------------------------------------------------------------------
def test_latency_histogram_percentiles_and_decimation():
    h = LatencyHistogram(max_samples=64)
    for v in np.linspace(0.001, 0.1, 1000):
        h.observe(float(v))
    assert h.count == 1000
    assert h.percentile(50) == pytest.approx(0.0505, rel=0.1)
    assert h.percentile(99) <= 0.1 and h.summary()["max_s"] == pytest.approx(0.1)
    assert sum(b["count"] for b in h.buckets()) == 1000
    assert len(h._samples) <= 64


def test_metrics_summary_shape():
    clk = ManualClock()
    m = ServingMetrics(slo_s=0.5, clock=clk)
    m.on_submit(3)
    clk.advance(2.0)
    m.on_complete(3, 0.7, violated=True)
    m.on_tile(3, 4)
    s = m.summary()
    assert s["throughput_rps"] == pytest.approx(0.5)
    assert s["slo_violations"] == 1 and s["per_task"]["3"]["slo_violations"] == 1
    assert s["tile_fill"] == 0.75
    assert s["latency"]["p50_s"] == pytest.approx(0.7)


# ---------------------------------------------------------------------------
# transport subscription -> live serving hot-swap
# ---------------------------------------------------------------------------
def test_transport_subscription_feeds_scheduler(small_problem, small_cfg):
    """core/transport.py hook: a Sigma install notifies subscribers with
    raw-size (W, sigma, version) — wired straight into a scheduler, every
    install hot-swaps the served weights."""
    from repro.core import AsyncOptions
    from repro.core.omega_regularizers import resolve_regularizer
    from repro.core.transport import get_transport

    transport = get_transport("threaded").factory()
    reg = resolve_regularizer(small_cfg, None)
    transport.setup(
        small_cfg, small_problem.train, mesh=None, axes=None, reg=reg,
        init=None, track=False,
        options=AsyncOptions(n_workers=1, transport="threaded"),
    )
    try:
        m, d = small_problem.train.m, small_problem.train.d
        eng = MTLScoringEngine(np.zeros((m, d), np.float32), batch=4, version=0)
        sched = ContinuousBatchingScheduler(eng, clock=ManualClock())
        seen = []
        transport.subscribe(lambda W, sigma, v: seen.append((W.shape, sigma.shape, v)))
        transport.subscribe(sched.publish_weights)

        rng = np.random.RandomState(0)
        sig = np.eye(m, dtype=np.float32) / m
        for _ in range(2):
            transport.install_sigma(
                jnp.asarray(sig), jnp.asarray(np.eye(m, dtype=np.float32) * m),
                defer=False,
            )
        assert [v for _, _, v in seen] == [1, 2]
        assert all(ws == (m, d) and ss == (m, m) for ws, ss, _ in seen)
        assert sched.version == 2
        r = ScoreRequest(task=0, x=rng.randn(d).astype(np.float32))
        sched.submit(r)
        sched.step()
        assert r.snapshot_version == 2 and r.score is not None
    finally:
        transport.close()


# ---------------------------------------------------------------------------
# load test (CI slow job: -m "slow or load")
# ---------------------------------------------------------------------------
@pytest.mark.load
def test_load_generator_records_bench(tmp_path):
    """Queued arrivals, mixed tasks, straggler tiles — through the real
    benchmark harness, recording p50/p95/p99 latency, throughput and
    SLO-violation counts to a BENCH_serving.json."""
    import importlib.util
    import json
    import os

    bench = os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "bench_serving.py"
    )
    spec = importlib.util.spec_from_file_location("bench_serving", bench)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    out = tmp_path / "BENCH_serving.json"
    mod.main([
        "--requests", "400", "--batch", "16", "--tasks", "8", "--d", "24",
        "--rate", "2000", "--slo-ms", "50", "--straggler-every", "7",
        "--out", str(out),
    ])
    rows = json.loads(out.read_text())
    load_rows = [r for r in rows if r["kind"] == "load"]
    assert load_rows, "bench wrote no load rows"
    for row in load_rows:
        s = row["metrics"]
        assert s["completed"] + s["expired"] == row["requests"] == s["submitted"]
        lat = s["latency"]
        assert 0 < lat["p50_s"] <= lat["p95_s"] <= lat["p99_s"]
        assert s["throughput_rps"] > 0 and s["slo_violations"] >= 0
        assert s["swaps"] >= 1  # the bench hot-swaps mid-load
    # head-of-line fix row: per-slot decode batching must decouple short-
    # request p99 from the longest in-flight generation
    (inter,) = [r for r in rows if r["kind"] == "lm_interleave"]
    assert inter["streaming"]["short_p99_s"] < inter["blocking"]["short_p99_s"]
    assert inter["streaming"]["slot_occupancy"] > 0
    # AOT warmup row: warm-start worst case beat the cold trace+compile
    (wc,) = [r for r in rows if r["kind"] == "warm_vs_cold"]
    for eng in ("lm", "mtl"):
        assert wc[eng]["warm_max_s"] < wc[eng]["cold_first_s"]
