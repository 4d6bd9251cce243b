"""Pallas TPU kernel: fused K(A, B) block materialization.

Skotch/ASkotch materialize the b x b block K_BB once per iteration (Nystrom
sketch input + powering matvecs reuse it).  This kernel builds it tile by
tile — pairwise distance on the MXU (or a VPU column reduction for L1)
fused with the elementwise kernel map, writing each (bm, bn) tile straight
from VMEM.

Same tiling contract as kernel_matvec (see that module's docstring).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.kernel_matvec import _apply_kernel, _cast_tiles, _distance_tile


def _block_body(a_ref, b_ref, o_ref, *, kernel: str, sigma: float):
    # operand tiles at policy width (f32/bf16); distance + map + output f32
    dist = _distance_tile(a_ref[...], b_ref[...], kernel)
    o_ref[...] = _apply_kernel(dist, kernel, sigma)


@functools.partial(
    jax.jit,
    static_argnames=(
        "kernel", "sigma", "bm", "bn", "dchunk", "interpret", "precision",
    ),
)
def kernel_block_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    kernel: str = "rbf",
    sigma: float = 1.0,
    bm: int = 256,
    bn: int = 256,
    dchunk: int = 32,
    interpret: bool = False,
    precision: str = "f32",
) -> jax.Array:
    """Materialize K(a, b): (m, d), (n, d) -> (m, n) f32.

    ``precision="bf16"`` loads the A/B tiles in bf16; the distance
    accumulation and the materialized block stay f32.
    """
    m, d = a.shape
    n = b.shape[0]
    bm = min(bm, max(8, m))
    bn = min(bn, max(8, n))
    mp, np_, dp = -(-m // bm) * bm, -(-n // bn) * bn, -(-d // dchunk) * dchunk
    a_p = jnp.pad(a, ((0, mp - m), (0, dp - d)))
    b_p = jnp.pad(b, ((0, np_ - n), (0, dp - d)))
    a_p, b_p = _cast_tiles(precision, a_p, b_p)

    out = pl.pallas_call(
        functools.partial(_block_body, kernel=kernel, sigma=float(sigma)),
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, dp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]
