"""The yardstick's arithmetic on hand counts: interval algebra, the trace
reduction on hand-made planes, the FLOP and byte counts, the peak table,
the traffic generator and BENCHMARK.json's own consistency."""
import json
import math

import numpy as np
import pytest

from bench.lib import counts, trace, traffic
from bench.lib.peaks import peak
from bench.run import BENCH, ROOT, applies


def test_union_gaps_and_clip():
    ivs = [(5, 7), (0, 2), (1, 3), (6, 9)]
    assert trace.union(ivs) == [(0, 3), (5, 9)]
    assert trace.covered(ivs) == 7.0
    assert trace.gaps(ivs, 0, 12) == [(3, 5), (9, 12)]
    assert trace.gaps(ivs, 1, 6) == [(3, 5)]
    assert trace.clip(ivs, 2, 6) == [(5, 6), (2, 3)]


def _device(ops, modules=()):
    return trace.Device("/device:TPU:0", list(ops), list(modules))


def test_busy_idle_and_gap_attribution():
    # window 0..100 ns; ops cover 10..30 and 50..60; host was in "fit"
    # during 0..40 and in "publish" during 40..70
    dev = _device([("fusion.1", 10, 30), ("fusion.2", 20, 25), ("all-gather.3", 50, 60)])
    spans = [("window", 0, 100), ("fit", 0, 40), ("publish", 40, 70)]
    tr = trace.Trace(spans, [dev], (0, 100))
    assert tr.busy_s() == pytest.approx(30e-9)
    assert trace.idle_share(tr) == pytest.approx(70.0)
    gaps = tr.idle_gaps()
    assert gaps[0] == ("window", pytest.approx(40e-9))  # 60..100, no inner span
    assert ("publish", pytest.approx(20e-9)) in gaps  # 30..50, midpoint 40
    assert ("fit", pytest.approx(10e-9)) in gaps  # 0..10
    assert dev.collective_ns() == 10.0
    assert dict(tr.top_ops())["fusion.1"] == pytest.approx(20e-9)


def test_module_names_and_collectives_inside_rounds():
    dev = _device(
        [("all-reduce.1", 12, 14), ("all-gather.2", 30, 33), ("fusion", 10, 20)],
        [("jit_round_body(7)", 10, 20), ("jit_objectives(3)", 25, 40),
         ("jit_round_body(7)", 50, 55)],
    )
    assert trace.module_base("jit_round_body(7)") == "jit_round_body"
    assert dev.module_ns()[trace.ROUND_PROGRAM] == 15
    rounds = dev.module_intervals(trace.ROUND_PROGRAM)
    assert dev.collective_ns(within=rounds) == 2.0


def test_a_dropped_trace_ends_with_the_last_whole_fit():
    spans = [("window", 0, 100), ("fit", 0, 30), ("fit", 31, 62), ("fit", 63, 95)]
    assert trace.traced_window(spans, (0, 100)) == (0, 100)
    assert trace.traced_window(spans, (0, 100), 150) == (0, 100)
    assert trace.traced_window(spans, (0, 100), 70) == (0, 62)
    assert trace.traced_window(spans, (0, 100), 62) == (0, 62)
    # no whole fit before the drop: up to the drop
    assert trace.traced_window(spans, (0, 100), 20) == (0, 20)
    tr = trace.Trace(spans, [], (0, 62), 70)
    assert tr.units() == 2


def test_idle_share_without_devices_is_none():
    assert trace.idle_share(trace.Trace([("window", 0, 10)], [], (0, 10))) is None
    assert trace.idle_share(None) is None


def test_round_counts_by_hand():
    # MNIST: 10 tasks, d=784, H=12032, 120,000 rows
    assert counts.round_flops(784, 12032, 10) == 4 * 784 * 12032 * 10
    assert counts.round_bytes(784, 120000) == 120000 * 784 * 4
    p = peak("TPU v5 lite")
    t, bound = counts.least_time(
        counts.round_flops(784, 12032, 10), counts.round_bytes(784, 120000), p
    )
    assert bound == "bytes"
    assert t == pytest.approx(376.32e6 / 819e9)
    t, bound = counts.least_time(1e12, 1.0, p)
    assert bound == "flops" and t == pytest.approx(1e12 / 197e12)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peak"):
        peak("TPU v9 imaginary")


def test_traffic_same_work_for_every_seed():
    spec = {"rate": 1000, "zipf_a": 1.2, "gap_seed": 0}
    rows = np.array([5, 7, 9, 11])
    a = traffic.open_loop(spec, 1, 2.0, rows)
    b = traffic.open_loop(spec, 2**33 + 5, 2.0, rows)
    for due, tasks, r in (a, b):
        assert len(due) == 2000 and due[-1] == pytest.approx(2.0)
        assert np.all(np.diff(due) >= 0)
        assert np.all(r < rows[tasks]) and np.all(r >= 0)
    # the same set of gaps, in another order
    assert np.allclose(np.sort(np.diff(a[0], prepend=0)), np.sort(np.diff(b[0], prepend=0)))
    assert not np.allclose(np.diff(a[0]), np.diff(b[0]))
    again = traffic.open_loop(spec, 1, 2.0, rows)
    assert all(np.array_equal(x, y) for x, y in zip(a, again))


def test_zipf_draw_is_skewed_to_the_first_tasks():
    rng = np.random.default_rng(0)
    t = traffic.zipf_tasks(rng, 20000, 139, 1.2)
    share = np.bincount(t, minlength=139) / t.size
    p = 1.0 / np.arange(1, 140) ** 1.2
    assert share[0] == pytest.approx(p[0] / p.sum(), rel=0.05)
    assert share[0] > share[10] > share[100]


def test_benchmark_json_names_files_that_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        cell = json.loads((BENCH / "workloads" / f"{w['name']}.json").read_text())
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert (BENCH / "kinds" / f"{cell['kind']}.py").is_file()
        e2e = [m["name"] for m in bench["end_to_end"] if applies(m, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(applies(m, w["name"]) for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and not math.isnan(m["bound"])
