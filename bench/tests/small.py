"""Cells and configurations cut to sizes the CPU tests can hold; every
other key is the committed file's."""
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

SMALL_CONFIG = {
    "mnist": {"tasks": 4, "d": 100, "n_per_task_train": 96},
    "school": {"tasks": 12, "n_avg": 24},
}
SMALL_CELL = {
    "mnist.train": {"outer_iters": 2, "rounds": 2},
    "mnist.mesh4": {"outer_iters": 2, "rounds": 2},
    "school.train": {"outer_iters": 2, "rounds": 2},
    "school.score": {"fit": {"outer_iters": 1, "rounds": 2},
                     "traffic": {"rate": 400, "zipf_a": 1.2, "gap_seed": 0}},
}


def load(name, cells=SMALL_CELL, configs=SMALL_CONFIG):
    """(cell, config) of ``name`` with the given sizes put in."""
    with open(BENCH / "workloads" / f"{name}.json") as f:
        cell = json.load(f)
    with open(BENCH / "configs" / f"{cell['config']}.json") as f:
        config = json.load(f)
    cell.update(cells[name])
    config.update(configs[cell["config"]])
    return cell, config


# the control test's sizes: large enough that bfloat16's drift shows as it
# does at the cells' own size (the full School set, a tenth of MNIST's rows)
CONTROL_CONFIG = {
    "mnist": {"n_per_task_train": 1200},
    "school": {},
}
CONTROL_CELL = {
    "mnist.train": {"outer_iters": 2, "rounds": 5},
    "school.train": {"outer_iters": 1, "rounds": 5},
}


def load_control(name):
    return load(name, CONTROL_CELL, CONTROL_CONFIG)
