"""Orders of magnitude by which the solves cut the relative residual, per
100 iterations: the mean over the window's solves of -100 log10(r) / iters,
where r is the residual (worst head) the solve stopped on; from w = 0 the
residual is 1.  The stopping iteration moves only when a solve meets
``tol`` a whole check period sooner or later; this number moves with the
solver's progress inside a period.  Layer: solvers (``core/askotch.py``)."""

import math


def read(rec):
    solves = [(r, k) for r, k in zip(rec.get("resid_at_stop") or [], rec.get("iters") or [])
              if r > 0 and k > 0]
    if not solves:
        return None
    return sum(-100.0 * math.log10(r) / k for r, k in solves) / len(solves)
