"""Breaks planted under the timed path, for the control and the fault tests.

Each entry of :data:`SWAPS` is a context manager, given the cell's
configuration, that replaces one part of the program while it is active:

* ``control``: the kernel layer (``repro.kernels.ops.kernel_matvec`` and
  ``kernel_block``) replaced by the configuration's plain reference at ``high``
  precision, one step below the f32 at ``highest`` the configurations state.
* ``step_high``, ``step_bf16``: only the ASkotch step's kernel work (its
  K_BB block and its row-block matvec) replaced by the reference at ``high``
  or at one bf16 pass; the full-residual check keeps the program's kernel.
* ``step_unchanged``: the ASkotch step returns its state unchanged.
* ``half_batch``: every kernel matvec sums over half of the training rows and
  doubles the result.
* ``answer_altered``: the first weight a solve returns moved by the norm of
  the targets, where the solve returns it.

The benchmark's own runs never use them: ``bench/control.py`` and the tests
do.
"""

from __future__ import annotations

import contextlib
import functools

import named


@contextlib.contextmanager
def _patched(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def _reference_kernels(config: dict, precision: str, check_too: bool):
    """The kernel layer replaced by the reference at ``precision``: every
    call, or with ``check_too`` false only the step's (a K_BB block, or a
    matvec over fewer rows than the training set)."""
    from repro.kernels import ops

    reference = named.load("", config["reference"])
    real_matvec = ops.kernel_matvec

    def kernel_matvec(a, b, v, *, kernel="rbf", sigma=1.0, **kw):
        if not check_too and a.shape[0] >= b.shape[0]:
            return real_matvec(a, b, v, kernel=kernel, sigma=sigma, **kw)
        v2 = v[:, None] if v.ndim == 1 else v
        out = reference.kernel_matvec(a, b, v2, kernel=kernel,
                                      sigma=float(sigma), precision=precision)
        return out[:, 0] if v.ndim == 1 else out

    def kernel_block(a, b, *, kernel="rbf", sigma=1.0, **_):
        return reference.kernel_block(a, b, kernel=kernel, sigma=float(sigma),
                                      precision=precision)

    with _patched(ops, "kernel_matvec", kernel_matvec), \
            _patched(ops, "kernel_block", kernel_block):
        yield


def control(config: dict):
    return _reference_kernels(config, "high", check_too=True)


@contextlib.contextmanager
def step_unchanged(config: dict):
    from repro.core import askotch

    real = askotch.make_step

    def make_step(problem, cfg, probs=None):
        step = real(problem, cfg, probs)

        def unchanged(state):
            return state, step(state)[1]

        return unchanged

    with _patched(askotch, "make_step", make_step):
        yield


@contextlib.contextmanager
def half_batch(config: dict):
    from repro.kernels import ops

    real = ops.kernel_matvec

    def kernel_matvec(a, b, v, **kw):
        return 2.0 * real(a, b[::2], v[::2], **kw)

    with _patched(ops, "kernel_matvec", kernel_matvec):
        yield


@contextlib.contextmanager
def answer_altered(config: dict):
    import jax.numpy as jnp

    from repro.core import askotch

    real_solve = askotch.solve

    def solve(problem, *args, **kw):
        res = real_solve(problem, *args, **kw)
        # by the targets' norm: no answer within a tol below 1 absorbs that
        res.w = res.w.at[0].add(jnp.linalg.norm(problem.y))
        return res

    with _patched(askotch, "solve", solve):
        yield


SWAPS = {
    "control": control,
    "step_high": functools.partial(_reference_kernels, precision="high", check_too=False),
    "step_bf16": functools.partial(_reference_kernels, precision="bf16", check_too=False),
    "step_unchanged": step_unchanged,
    "half_batch": half_batch,
    "answer_altered": answer_altered,
}
