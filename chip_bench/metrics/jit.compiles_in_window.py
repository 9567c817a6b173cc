"""Backend compiles during the window (JAX runtime and the entry), counted
from ``jax.monitoring``'s ``backend_compile_duration`` events."""


def read(run):
    return float(run.window.compiles)
