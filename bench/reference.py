"""The plain reference the benchmark compares the program with.

Kernel ridge regression in straightforward ``jax.numpy``: the kernel matrix
K(a, x) is formed one block of rows at a time from the squared-distance
expansion ``|a|^2 + |x|^2 - 2 a.x``, mapped through the kernel and
multiplied by the weights.  It imports nothing of the program.

``precision`` selects how an f32 matrix product is computed:

* ``"highest"``: full f32 (``jax.lax.Precision.HIGHEST``), the reference.
* ``"high"``: three bf16 passes, written out here (hi/lo splits of both
  operands, the lo x lo product dropped) so that it computes the same on the
  CPU as on the TPU.  It is the control: the precision one step below what a
  configuration states, which the comparison must refuse.
* ``"bf16"``: one bf16 pass with f32 accumulation, the TPU's default for an
  f32 product.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROWS_PER_BLOCK = 1024


def _split(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def matmul(a: jax.Array, b: jax.Array, precision: str) -> jax.Array:
    """a @ b in f32 at ``precision`` ("highest", "high" or "bf16")."""
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    if precision == "highest":
        return dot(a, b)
    if precision == "bf16":
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    # bf16 x bf16 products are exact in f32: each term is one bf16 pass
    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


def kernel_map(kernel: str, d2: jax.Array, sigma: float) -> jax.Array:
    if kernel == "rbf":
        return jnp.exp(-d2 / (2.0 * sigma**2))
    raise ValueError(f"the reference has no kernel {kernel!r}")


def kernel_block(a, x, *, kernel: str, sigma: float, precision: str):
    """K(a, x) for a block of rows ``a``."""
    aa = jnp.sum(a * a, axis=1, keepdims=True)
    xx = jnp.sum(x * x, axis=1)[None, :]
    d2 = jnp.maximum(aa + xx - 2.0 * matmul(a, x.T, precision), 0.0)
    return kernel_map(kernel, d2, sigma)


@functools.partial(jax.jit, static_argnames=("kernel", "sigma", "precision"))
def kernel_matvec(a, x, v, *, kernel: str, sigma: float, precision: str = "highest"):
    """K(a, x) @ v, (m, d) x (n, d) x (n, t) -> (m, t), a block of
    ``ROWS_PER_BLOCK`` rows of ``a`` at a time."""
    m = a.shape[0]
    rows = min(ROWS_PER_BLOCK, m)
    pad = -m % rows
    blocks = jnp.pad(a, ((0, pad), (0, 0))).reshape(-1, rows, a.shape[1])

    def one(block):
        k = kernel_block(block, x, kernel=kernel, sigma=sigma, precision=precision)
        return matmul(k, v, precision)

    out = jax.lax.map(one, blocks)
    return out.reshape(-1, v.shape[1])[:m]


def relative_residuals(x, y, w, *, kernel: str, sigma: float, lam: float,
                       precision: str = "highest") -> jax.Array:
    """Per-head ||(K + lam I) w - y|| / ||y|| of the system a solve answers;
    ``y`` and ``w`` are (n, t)."""
    r = kernel_matvec(x, x, w, kernel=kernel, sigma=sigma, precision=precision)
    r = r + lam * w - y
    return jnp.linalg.norm(r, axis=0) / jnp.linalg.norm(y, axis=0)
