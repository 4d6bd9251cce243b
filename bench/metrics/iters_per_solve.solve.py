"""Mean ``SolveResult.iters`` over the solves the window completed: a
count.  Layer: solvers (``core/askotch.py``)."""


def read(rec):
    iters = rec.get("iters") or []
    return sum(iters) / len(iters) if iters else None
