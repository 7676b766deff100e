"""Seconds JAX spent tracing, lowering and compiling (or loading from the
persistent cache) during set-up, summed over threads (jax.monitoring)."""


def read(run):
    return sum(d for _, t, d in run.compiles.rows if t < run.window[0])
