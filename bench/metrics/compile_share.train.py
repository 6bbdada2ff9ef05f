"""Share of the training window spent in JAX's trace, lowering and backend
compile (or persistent-cache retrieval) events, as jax.monitoring reports
them: what rebuilding the round program for every outer iteration costs
inside a fit."""


def read(run):
    return 100.0 * run.compile_window["seconds"] / run.window_s
