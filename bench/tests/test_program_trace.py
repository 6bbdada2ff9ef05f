"""The program-span readers (bench/lib/program_trace.py) on hand counts,
and on ``data/small_train_spans.xplane.pb``: ``school.train`` traced on a
TPU v5 lite with ``--seconds 0.1`` (one whole fit: 5 outer iterations x 5
rounds) by a program that has the driver's spans."""
import importlib.util
from pathlib import Path

import pytest

from bench.lib import program_trace as pt
from bench.lib import trace
from bench.lib.peaks import peak
from bench.run import BENCH, Run

DATA = Path(__file__).resolve().parent / "data"
RECORDED = ("small_train.xplane.pb", "small_score.xplane.pb")


def reader(name):
    spec = importlib.util.spec_from_file_location(name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(tr, counters=None):
    return Run(1, peak("TPU v5 lite"), tr.window_s, counters or {}, None, tr)


def _hand():
    """Window 0..1000 ns; the device busy 100..150, 300..320, 600..700.

    Rounds 0..200 (holds a compile event at 10..90), 250..330 and
    400..450; objectives 330..360; an Omega-step 450..550 read back by
    rho 560..720, another 750..800 (holds a compile event at 760..790)
    read back by result 800..850; shard 900..950.
    """
    dev = trace.Device("/device:TPU:0", [("op", 100, 150), ("op", 300, 320), ("op", 600, 700)], [])
    tr = trace.Trace([("window", 0, 1000), ("fit", 0, 1000)], [dev], (0, 1000))
    prog = pt.ProgramTrace(
        [
            ("driver.round", 0, 200), ("driver.round", 250, 330),
            ("driver.objectives", 330, 360), ("driver.round", 400, 450),
            ("driver.omega_step", 450, 550), ("driver.rho", 560, 720),
            ("driver.omega_step", 750, 800), ("driver.result", 800, 850),
            ("driver.shard", 900, 950),
        ],
        [("backend_compile_and_load", 10, 90), ("trace_to_jaxpr_dynamic", 760, 790)],
    )
    return tr, prog


def test_interval_intersect_and_subtract():
    a = [(0, 10), (20, 30), (5, 12)]
    b = [(8, 22), (25, 26), (40, 50)]
    assert pt.intersect(a, b) == [(8, 12), (20, 22), (25, 26)]
    assert pt.subtract(a, b) == [(0, 8), (22, 25), (26, 30)]
    assert pt.intersect(a, []) == [] and pt.subtract(a, []) == [(0, 12), (20, 30)]


def test_hand_counts():
    tr, prog = _hand()
    # the round and the Omega-step holding a compile event are left out,
    # and count as compile
    assert pt.round_trip_intervals(prog) == [(250, 330), (330, 360), (400, 450)]
    assert pt.compile_intervals(prog) == [(10, 90), (760, 790), (0, 200), (750, 850)]
    # 250..360 holds 20 ns busy, 400..450 none: 90 + 50 idle of 1000
    assert pt.round_idle_share(tr, prog) == pytest.approx(14.0)
    # Omega-step 450 to the end of the rho after it, 720; the one at 750
    # to the end of the result, 850, holds a compile event
    assert pt.omega_spans(prog) == [(450, 720), (750, 850)]
    assert pt.omega_intervals(prog) == [(450, 720)]
    assert pt.omega_share(tr, prog) == pytest.approx(27.0)
    split = pt.idle_split(tr, prog)
    assert split == pytest.approx(
        {"compile": 250e-9, "round trip": 140e-9, "Omega-step": 170e-9,
         "shard": 50e-9, "other": 220e-9}
    )
    assert sum(split.values()) == pytest.approx(tr.window_s - tr.busy_s())
    # gaps 700..1000, 320..600, 150..300, 0..100, named at their midpoints
    assert pt.idle_gaps(tr, prog) == [
        ("driver.result", pytest.approx(300e-9)), ("driver.omega_step", pytest.approx(280e-9)),
        ("fit", pytest.approx(150e-9)), ("backend_compile_and_load", pytest.approx(100e-9)),
    ]
    assert [n for n, _ in tr.idle_gaps()] == ["fit"] * 4


def test_readers_without_program_spans_return_none():
    tr, _ = _hand()
    empty = pt.ProgramTrace([], [])
    assert pt.omega_share(tr, empty) is None
    assert pt.round_idle_share(tr, empty) is None
    assert pt.omega_share(None, None) is None
    # a trace read from a program without the driver's spans
    tr.program_trace = empty
    run = _run(tr)
    assert reader("omega_share.train")(run) is None
    assert reader("round_idle_share.train")(run) is None
    # an untraced run
    untraced = Run(1, peak("TPU v5 lite"), 1.0, {}, None, None)
    assert reader("omega_share.train")(untraced) is None
    assert reader("round_idle_share.train")(untraced) is None


def test_reader_without_its_profile_raises():
    """A traced run whose profile neither load_file nor a caller's
    trace_dir names: the reader raises rather than fall silent."""
    tr, _ = _hand()
    with pytest.raises(LookupError):
        reader("omega_share.train")(_run(tr))
    with pytest.raises(LookupError):
        reader("round_idle_share.train")(_run(tr))


# What the benchmark's own reduction and readers gave on the traces
# recorded before the program had driver spans, before program_trace.py
# existed: window_s, busy_s, units, the longest idle gap, and the readers.
BEFORE = {
    "small_train.xplane.pb": {
        "window_s": 2.19163305, "busy_s": 0.075247745, "units": 1,
        "gap": ("fit", 0.3813474),
        "readers": {
            "idle_share.train": 96.56659015066413,
            "idle_share.score": 96.56659015066413,
            "collective_share.mesh4": None,
            "round_roofline": 0.05480936059154186,
        },
    },
    "small_score.xplane.pb": {
        "window_s": 0.303869804, "busy_s": 0.000223991, "units": 128,
        "gap": ("window", 0.003410658),
        "readers": {
            "idle_share.train": 99.92628718054526,
            "idle_share.score": 99.92628718054526,
            "collective_share.mesh4": None,
            "round_roofline": None,
        },
    },
}


@pytest.mark.parametrize("name", RECORDED)
def test_old_traces_read_as_before(name):
    """Traces recorded before the program had driver spans: the harness's
    reduction and every existing reader give the values they gave before
    (``BEFORE``), and the new readers find no span to read."""
    before = BEFORE[name]
    tr, prog = pt.load_file(str(DATA / name))
    assert tr.window_s == pytest.approx(before["window_s"], rel=1e-12)
    assert tr.busy_s() == pytest.approx(before["busy_s"], rel=1e-12)
    assert tr.units() == before["units"]
    gap_name, gap_s = tr.idle_gaps()[0]
    assert (gap_name, gap_s) == (before["gap"][0], pytest.approx(before["gap"][1], rel=1e-12))
    assert not [n for n, _, _ in prog.program_spans if n.startswith("driver.")]
    run = _run(tr, {"d": 28, "H": 128, "tasks": 139, "samples": 11592})
    for metric, value in before["readers"].items():
        got = reader(metric)(run)
        assert got == (None if value is None else pytest.approx(value, rel=1e-12)), metric
    assert reader("omega_share.train")(run) is None
    assert reader("round_idle_share.train")(run) is None


def test_reader_finds_the_file_through_its_callers_trace_dir(tmp_path):
    """No load_file: the reader walks up to the caller holding the reduced
    trace and a ``trace_dir``, as the benchmark's run_cell does."""
    prof = tmp_path / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes((DATA / "small_train_spans.xplane.pb").read_bytes())

    def run_cell(trace_dir):
        tr = trace.load(trace_dir)
        return reader("omega_share.train")(_run(tr)), tr

    value, tr = run_cell(str(tmp_path))
    _, prog = pt.load_file(str(DATA / "small_train_spans.xplane.pb"))
    assert value is not None and value == pt.omega_share(tr, prog)


@pytest.fixture(scope="module")
def spans():
    return pt.load_file(str(DATA / "small_train_spans.xplane.pb"))


def test_recorded_fit_holds_every_driver_span(spans):
    tr, prog = spans
    names = [n for n, _, _ in prog.program_spans]
    ((_, lo, hi),) = [s for s in tr.spans if s[0] == "fit"]
    inside = [n for n, s, e in prog.program_spans if lo <= s and e <= hi]
    assert inside.count("driver.engine_run") == 1
    assert inside.count("driver.round") == 25
    assert inside.count("driver.objectives") == 25
    assert inside.count("driver.omega_step") == 5
    assert inside.count("driver.rho") == 5
    assert inside.count("driver.shard") == 1 and inside.count("driver.result") == 1
    assert set(names) >= {"driver.engine_run", "driver.round", "driver.omega_step"}
    assert prog.compile_spans  # the round program is retraced every outer iteration


def test_recorded_readers_and_named_gaps(spans):
    tr, prog = spans
    omega = reader("omega_share.train")(_run(tr))
    rounds = reader("round_idle_share.train")(_run(tr))
    assert omega == pt.omega_share(tr, prog) and 0 < omega < 100
    assert rounds == pt.round_idle_share(tr, prog) and 0 < rounds < 100
    assert rounds <= trace.idle_share(tr)
    split = pt.idle_split(tr, prog)
    assert sum(split.values()) == pytest.approx(tr.window_s - tr.busy_s(), rel=1e-9)
    named = [n for n, _ in pt.idle_gaps(tr, prog)]
    # the harness alone names every long gap "fit"; the program's spans
    # and JAX's compile events name them here
    assert all(n == "fit" for n, _ in tr.idle_gaps())
    assert "fit" not in named[:3]
    assert all(n.startswith("driver.") or n in pt.COMPILE_EVENTS for n in named[:3])
