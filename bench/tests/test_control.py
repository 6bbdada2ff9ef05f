"""The control: the plain reference computed in bfloat16, one precision
below the configuration's float32, put in the program's place. Here on
the CPU at the sizes of ``small.CONTROL_*``, as at the cells' own size on
the chip (``bench/tools/readings.py --control``), it reads outside at
least one of the cell's limits. (That sound runs read inside every limit
is ``test_faults.test_sound_run_is_correct``.)
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as brun
from bench.lib.seeds import data_key
from bench.tests.small import load, load_control
from bench.tools import readings


@pytest.mark.parametrize("name", ["mnist.train", "school.train"])
def test_training_control_is_not_correct(name):
    cell, config = load_control(name)
    gen = brun.load_module(brun.BENCH / "generators" / f"{config['generator']}.py", "generator")
    kind = brun.load_module(brun.BENCH / "kinds" / "train.py", "kind")
    seed = 2**31 + 5
    raw = gen.make(config, data_key(seed), seed, ("train",))["train"]
    ref = kind.reference(cell, config, raw, seed)
    ctl = kind.reference(cell, config, raw, seed, jnp.bfloat16)
    out = kind.readings([(ctl["W"], ctl["primal"] - ctl["dual"])], ref, cell["rounds"])
    limits = cell["limits"]
    assert any(out[k] > limits[k] for k in limits), out


def test_scoring_control_is_not_correct():
    import argparse

    cell, config = load("school.score")
    gen = brun.load_module(brun.BENCH / "generators" / f"{config['generator']}.py", "generator")
    kind = brun.load_module(brun.BENCH / "kinds" / "score.py", "kind")
    ns = argparse.Namespace(workload="school.score", seed=2**31 + 5, seconds=0.5)
    out = readings.score_readings(brun.Context(ns, cell, config, gen, jax.devices()), kind, True)
    limits = cell["limits"]
    assert all(out["program"][k] <= limits[k] for k in limits), out["program"]
    assert any(out["control"][k] > limits[k] for k in limits), out["control"]
    assert np.isfinite(out["control"]["score_err"])
