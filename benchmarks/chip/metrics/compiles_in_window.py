"""Executables compiled or loaded inside the window (jax.monitoring
backend-compile events); a warm scan has none."""

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def read(run):
    lo, hi = run.window
    return float(sum(1 for e, t, _ in run.compiles.rows if e == BACKEND_COMPILE and lo <= t <= hi))
