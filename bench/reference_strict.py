"""The plain reference of ``reference.py`` with one rule more: a head whose
residual is not finite (an answer holding nan or inf) reads an infinite
residual, which is over any limit.

``load.SolveLoop.check`` folds the solves in with Python's ``max``, and
``max(0.0, nan)`` is 0.0, so under ``reference.py`` alone a nan answer
passes.  A configuration whose solve can diverge names this module as its
``reference``.  It imports nothing of the program.
"""

from __future__ import annotations

import jax.numpy as jnp

import named

_plain = named.load("", "reference")
matmul = _plain.matmul
kernel_block = _plain.kernel_block
kernel_matvec = _plain.kernel_matvec


def relative_residuals(x, y, w, **kwargs):
    """Per-head ||(K + lam I) w - y|| / ||y||, as ``reference.py`` computes
    it, with inf for each head that is not finite."""
    r = _plain.relative_residuals(x, y, w, **kwargs)
    return jnp.where(jnp.isfinite(r), r, jnp.inf)
