"""The trace reduction on small traces recorded on a TPU v5 lite:

- ``data/small_train.xplane.pb``: ``school.train`` traced with
  ``--seconds 0.1`` (one whole fit: 5 outer iterations x 5 rounds of the
  ``pallas_round`` kernel);
- ``data/small_score.xplane.pb``: ``school.score`` traced for 0.3 s.
"""
from pathlib import Path

import pytest

from bench.lib import trace

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def train():
    return trace.load_file(str(DATA / "small_train.xplane.pb"))


@pytest.fixture(scope="module")
def score():
    return trace.load_file(str(DATA / "small_score.xplane.pb"))


def test_train_trace_holds_one_fit_of_25_rounds(train):
    assert len(train.devices) == 1
    dev = train.devices[0]
    assert [n for n, _, _ in train.spans].count("fit") == 1
    assert len(dev.module_intervals(trace.ROUND_PROGRAM)) == 25
    # the Mosaic kernel runs inside every round program
    kernels = [n for n, _, _ in dev.ops if "tpu_custom_call" in n]
    assert len(kernels) == 25


def test_train_busy_and_idle_are_consistent(train):
    busy = train.busy_s()
    assert 0 < busy < train.window_s
    assert trace.idle_share(train) == pytest.approx(100 * (1 - busy / train.window_s))
    rounds = train.devices[0].module_intervals(trace.ROUND_PROGRAM)
    assert 0 < sum(e - s for s, e in rounds) * 1e-9 <= train.window_s
    gaps = train.idle_gaps()
    assert gaps and gaps[0][0] == "fit"  # the longest gap lies inside the fit
    assert sum(g for _, g in gaps) <= train.window_s - busy + 1e-9


def test_score_trace_tiles_match_score_programs(score):
    dev = score.devices[0]
    tiles = [n for n, _, _ in score.spans].count("tile")
    programs = dev.module_intervals("jit_score_step")
    assert tiles > 10 and len(programs) == tiles
    assert 90 < trace.idle_share(score) < 100
    top = dict(score.top_ops())
    assert 0 < sum(top.values()) <= sum(e - s for _, s, e in dev.ops) * 1e-9 + 1e-12
