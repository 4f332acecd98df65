"""Executables built inside the measured window (compiled, or loaded from
the persistent cache), counted from ``jax.monitoring``; 0 when warm-up
covered every program the window uses."""


def read(run):
    return run["compiles_in_window"]
