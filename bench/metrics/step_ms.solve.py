"""Device milliseconds of the ASkotch step program per iteration: the busy
time of the jitted step (``core/askotch.py`` ``_step``) over the iterations
the window's solves ran.  What the preconditioner's precision costs, and
what a cheaper step would save.  Layer: solvers (``core/askotch.py``)."""

#: the step's program as a trace names it: ``jit__step`` for the module-level
#: ``_step``; ``jit_step`` for a step jitted under the plain name
STEP_PROGRAMS = ("jit__step", "jit_step")


def read(rec):
    s = rec["summary"]
    iters = sum(rec.get("iters") or [])
    if s is None or not iters:
        return None
    secs = sum(s.program_s.get(p, 0.0) for p in STEP_PROGRAMS)
    return 1000.0 * secs / iters if secs > 0 else None
