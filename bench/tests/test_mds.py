"""The mds.train cell on the CPU at a small size (6 tasks of 6 to 210 rows,
d = 64): a sound run is correct; the bfloat16 control and each fault of
bench/lib/faults_packed.py are not; and pass_roofline.train reads a trace
by hand."""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as brun
from bench.lib import faults_packed, peaks, trace
from bench.lib.seeds import data_key
from bench.tests.small import load

CELLS = {"mds.train": {"outer_iters": 2, "rounds": 3}}
CONFIGS = {"mds": {"tasks": 6, "d": 64, "n_min": 8, "n_max_task": 300, "active": 8}}
SEED = 2**31 + 11


@pytest.fixture(autouse=True)
def cpu_peak(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


def run_small(seed=SEED):
    cell, config = load("mds.train", cells=CELLS, configs=CONFIGS)
    args = argparse.Namespace(workload="mds.train", seed=seed, seconds=0.5, trace=0)
    return brun.run_cell(args, jax.devices(), None, cell, config)


def test_generator_packs_the_published_skew():
    gen = brun.load_module(brun.BENCH / "generators" / "mds_like.py", "generator")
    _, config = load("mds.train", cells=CELLS, configs={"mds": {}})
    n = gen.sizes(config)
    assert (n.shape[0], n.min(), n.max(), n.sum()) == (22, 220, 14526, 98312)
    _, small = load("mds.train", cells=CELLS, configs=CONFIGS)
    x, y, mask, n = gen.make(small, data_key(SEED), SEED)["train"]
    assert x.shape == (int(np.sum(n)), 64) and y.shape == mask.shape == x.shape[:1]
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=1), 1.0, rtol=1e-5)
    assert set(np.unique(np.asarray(y))) == {-1.0, 1.0}
    assert np.all((np.asarray(x) > 0).sum(axis=1) == 8)
    again = gen.make(small, data_key(SEED), SEED)["train"]
    assert all(np.array_equal(a, b) for a, b in zip((x, y), again))


def test_sound_run_is_correct():
    res = run_small()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] >= 1


@pytest.mark.parametrize("fault", sorted(faults_packed.FAULTS))
def test_broken_run_is_not_correct(fault, monkeypatch):
    faults_packed.plant(fault, monkeypatch.setattr)
    res = run_small()
    assert not res["correct"], res["checks"]


def test_control_is_not_correct():
    """The plain reference in bfloat16, one precision below the
    configuration's float32, reads outside at least one limit."""
    cell, config = load("mds.train", cells=CELLS, configs=CONFIGS)
    gen = brun.load_module(brun.BENCH / "generators" / "mds_like.py", "generator")
    kind = brun.load_module(brun.BENCH / "kinds" / "train_packed.py", "kind")
    raw = gen.make(config, data_key(SEED), SEED)["train"]
    ref = kind.reference(cell, config, raw, SEED)
    ctl = kind.reference(cell, config, raw, SEED, jnp.bfloat16)
    out = kind.readings([(ctl["W"], ctl["primal"] - ctl["dual"])], ref, cell["rounds"])
    assert any(out[k] > v for k, v in cell["limits"].items()), out


def test_pass_roofline_reads_the_packed_programs():
    """Two objective calls (two passes each) and one W(alpha) call over
    1,000 rows of d = 100: 5 passes of 400 kB against 5 ms of device time."""
    reader = brun.load_module(brun.BENCH / "metrics" / "pass_roofline.train.py", "m")
    ms = 1_000_000
    dev = trace.Device("/device:TPU:0", [], [
        ("jit_packed_objectives(4)", 0, 2 * ms),
        ("jit_round_body(2)", 2 * ms, 9 * ms),
        ("jit_packed_objectives(4)", 9 * ms, 11 * ms),
        ("jit_packed_w_from_alpha(5)", 11 * ms, 12 * ms),
    ])
    tr = trace.Trace([("window", 0, 12 * ms)], [dev], (0, 12 * ms))
    p = peaks.PEAKS["TPU v5 lite"]
    run = brun.Run(1, p, 0.012, {"d": 100, "samples": 1000}, {}, tr)
    least = 5 * 1000 * 100 * 4 / p["hbm_bytes_per_s"]
    assert reader.read(run) == pytest.approx(100.0 * least / 5e-3)
    # the padded layout's programs hold no packed pass
    dev.modules = [("jit_objectives(1)", 0, ms), ("jit_round_body(2)", ms, 2 * ms)]
    assert reader.read(run) is None
