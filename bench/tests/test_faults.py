"""A run of each cell, on the CPU at a small size, with the look for a chip
skipped: sound, it is correct; with the timed path broken underneath, in
each way the cell can be broken (bench/lib/faults.py), it is not."""
import argparse

import jax
import pytest

from bench import run as brun
from bench.lib import faults, peaks
from bench.tests.small import load

TRAIN = ("mnist.train", "school.train", "mnist.mesh4")


@pytest.fixture(autouse=True)
def cpu_peak(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


def run_small(name, seed=2**31 + 3):
    cell, config = load(name)
    args = argparse.Namespace(workload=name, seed=seed, seconds=0.5, trace=0)
    return brun.run_cell(args, jax.devices(), None, cell, config)


CASES = [(c, f) for c in TRAIN for f in ("unchanged", "half")] + [
    ("mnist.mesh4", "no_exchange"),
    ("school.score", "altered"),
]


@pytest.mark.parametrize("name", TRAIN + ("school.score",))
def test_sound_run_is_correct(name):
    res = run_small(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


@pytest.mark.parametrize("name,fault", CASES)
def test_broken_run_is_not_correct(name, fault, monkeypatch):
    faults.plant(fault, monkeypatch.setattr)
    res = run_small(name)
    assert not res["correct"], res["checks"]


def test_planted_fault_is_taken_out_after_the_block():
    from repro.core import distributed

    orig = distributed.make_local_solve
    with faults.planted("half"):
        assert distributed.make_local_solve is not orig
    assert distributed.make_local_solve is orig
