"""The kernel tile-compute precision policy — shared vocabulary and validation.

Free of ``repro`` imports on purpose: both the kernel dispatch (``kernels/ops.py``) and
the solver API (``core/solver_api.py``) validate precision strings, and the
import chains between ``repro.kernels`` and ``repro.core`` run in both
directions, so the policy's single source of truth lives below both.

``"f32"`` — tiles, distances, kernel maps and accumulators all f32 (the
bit-identical default).  ``"bf16"`` — A/B/V tile/chunk traffic and the
kernel-times-value matmul run in bf16 with f32 accumulation; distances,
kernel maps, outputs and every solver-internal quantity stay f32 (the
f32-islands rule, docs/architecture.md "Precision policy").

:data:`MATMUL_PRECISION` also sets the precision of the dense f32 algebra
of the callers that enter :func:`solver_matmuls`: the Skotch/ASkotch step,
local (``core/askotch.py``) and sharded (``distributed/krr_dist.py``), with
its Nystrom sketch and factorization, powering, Woodbury and Cholesky
applies and gradient algebra; and the PCG preconditioner's factors and apply
(``core/pcg.py``, ``solve_pcg_dist``).  Under ``"f32"`` each such product
computes at full f32; a TPU's default is one bf16 pass, whose error on an
ill-conditioned block swamps lam and the small eigenvalues a preconditioner
divides by.  Other users of ``core/nystrom.py`` (the tune engine's Nystrom)
run at the backend's default.
"""

from __future__ import annotations

import contextlib

import jax

PRECISIONS = ("f32", "bf16")

#: the precision of every f32 product of a solver's dense algebra, per
#: policy ("highest" is ``jax.lax.Precision.HIGHEST``; "default" is the
#: backend's own, one bf16 pass on a TPU)
MATMUL_PRECISION = {"f32": "highest", "bf16": "default"}


def check_precision(precision: str) -> str:
    """Validate a precision-policy string ("f32" | "bf16") and return it."""
    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; expected one of {PRECISIONS}"
        )
    return precision


def solver_matmuls(precision: str):
    """Context under which a solver traces (or runs) its dense algebra for
    the policy ``precision``: every product that names no precision of its
    own computes at ``MATMUL_PRECISION[precision]``.  Under "bf16" it
    changes nothing."""
    if MATMUL_PRECISION[check_precision(precision)] == "default":
        return contextlib.nullcontext()
    return jax.default_matmul_precision(MATMUL_PRECISION[precision])
