"""Share of the window spent tracing, lowering, compiling and loading
programs from the persistent cache (summed ``jax.monitoring`` durations),
in %.  Layer: the solver API (``core/solver_api.py``, ``core/askotch.py``
``solve``, which re-jits its step on every call)."""

from metrics.work import share


def read(rec):
    return share(sum(rec["compile_s"].values()), rec["window_s"])
