"""Pallas TPU kernels: fused multi-kernel matvec / block build.

A weighted-sum kernel ``K_w = sum_i w_i K_i`` (q base kernels, weights w on
the simplex) costs the same data movement as a single kernel: per (bm, bn)
tile the pairwise base tile is computed at most once per kernel FAMILY
(``core.kernels.KERNEL_FAMILIES``: squared-L2 on the MXU for rbf/matern52,
a per-column L1 reduction on the VPU for laplacian, raw / normalized a.b^T
for the dot-product and cosine kernels) and the q elementwise kernel maps +
weighted accumulation stay in VMEM.  This is what makes a q-kernel operator
sweep cost ~1 kernel sweep instead of q (docs/tuning.md, "Multi-kernel
sweeps").

Three entry points, all validated against ``ref.kernel_*_multi`` in
interpret mode:

  * ``kernel_matvec_multi_pallas``      — (sum_i w_i K_i) @ V; ``weights``
    may be (q,) or per-column (q, t) (the stacked tuning engine's case) and
    is a traced array input, so weight changes never recompile.
  * ``kernel_matvec_components_pallas`` — stacked per-kernel K_i @ V
    (q, m, t): the per-kernel Nystrom sketches in one data sweep.
  * ``kernel_block_multi_pallas``       — materialize sum_i w_i K_i(A, B).

Tiling is identical to ``kernel_matvec``/``kernel_block`` (same bm/bn/dchunk
defaults, same padding rules); the only extra VMEM is one (q, kv) weight
tile and, for the components variant, a (q, bm, kv) accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.kernels import kernel_family
from repro.kernels.kernel_matvec import (
    _apply_kernel,
    _base_tile,
    _cast_tiles,
    _mxu_dot,
)


def _tiles(a, b, kernels):
    """Base tiles shared by every kernel map, one per family present
    ("l2"/"l1"/"dot"/"cos" -> (bm, bn) f32 tile)."""
    return {
        fam: _base_tile(a, b, fam)
        for fam in dict.fromkeys(kernel_family(k) for k in kernels)
    }


def _tile_for(kernel, tiles, sigma):
    return _apply_kernel(tiles[kernel_family(kernel)], kernel, sigma)


def _multi_matvec_body(
    a_ref, b_ref, v_ref, w_ref, o_ref, *, kernels, sigmas
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # tiles at policy width (f32/bf16); base tiles, weight row products
    # and the accumulator stay f32, the per-kernel matmul runs at policy
    # width with f32 accumulation
    v = v_ref[...]
    tiles = _tiles(a_ref[...], b_ref[...], kernels)
    acc = jnp.zeros_like(o_ref)
    for i, (kn, sg) in enumerate(zip(kernels, sigmas)):
        ktile = _tile_for(kn, tiles, sg)
        # w_ic (K_i v)[:, c] == (K_i (v * w_i))[:, c]: pre-scaling v per
        # kernel lets one accumulator serve every kernel and column
        acc += _mxu_dot(
            ktile.astype(v.dtype),
            (v * w_ref[i, :][None, :]).astype(v.dtype),
            (1, 0),
        )
    o_ref[...] += acc


def _components_body(a_ref, b_ref, v_ref, o_ref, *, kernels, sigmas):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    v = v_ref[...]
    tiles = _tiles(a_ref[...], b_ref[...], kernels)
    for i, (kn, sg) in enumerate(zip(kernels, sigmas)):
        ktile = _tile_for(kn, tiles, sg)
        o_ref[i, ...] += _mxu_dot(ktile.astype(v.dtype), v, (1, 0))


def _block_multi_body(a_ref, b_ref, o_ref, *, kernels, sigmas, weights):
    tiles = _tiles(a_ref[...], b_ref[...], kernels)
    acc = jnp.zeros_like(o_ref)
    for kn, sg, w in zip(kernels, sigmas, weights):
        acc += w * _tile_for(kn, tiles, sg)
    o_ref[...] = acc


def _pad_multi(a, b, v, bm, bn, dchunk, interpret, precision="f32"):
    m, d = a.shape
    n = b.shape[0]
    kv = v.shape[1]
    bm = min(bm, max(8, m))
    bn = min(bn, max(8, n))
    mp, np_, dp = -(-m // bm) * bm, -(-n // bn) * bn, -(-d // dchunk) * dchunk
    kvp = -(-kv // 128) * 128 if not interpret else kv
    a_p = jnp.pad(a, ((0, mp - m), (0, dp - d)))
    b_p = jnp.pad(b, ((0, np_ - n), (0, dp - d)))
    v_p = jnp.pad(v, ((0, np_ - n), (0, kvp - kv)))
    a_p, b_p, v_p = _cast_tiles(precision, a_p, b_p, v_p)
    return a_p, b_p, v_p, (m, n, kv, bm, bn, mp, np_, dp, kvp)


@functools.partial(
    jax.jit,
    static_argnames=(
        "kernels", "sigmas", "bm", "bn", "dchunk", "interpret", "precision",
    ),
)
def kernel_matvec_multi_pallas(
    a: jax.Array,
    b: jax.Array,
    v: jax.Array,
    weights: jax.Array,
    *,
    kernels: tuple[str, ...],
    sigmas: tuple[float, ...],
    bm: int = 256,
    bn: int = 256,
    dchunk: int = 32,
    interpret: bool = False,
    precision: str = "f32",
) -> jax.Array:
    """out = (sum_i w_i K_i(a, b)) @ v; weights (q,) or per-column (q, kv).

    ``precision="bf16"`` loads the A/B/V tiles in bf16; the weight tile,
    distance tiles and accumulator stay f32 (output is f32 either way).
    """
    squeeze = v.ndim == 1
    if squeeze:
        v = v[:, None]
    a_p, b_p, v_p, (m, n, kv, bm, bn, mp, np_, dp, kvp) = _pad_multi(
        a, b, v, bm, bn, dchunk, interpret, precision
    )
    q = len(kernels)
    w2 = jnp.broadcast_to(
        weights[:, None] if weights.ndim == 1 else weights, (q, kv)
    ).astype(jnp.float32)
    # pad the sublane (q) dim to a multiple of the f32 tile minimum; only
    # rows [0, q) are ever read (static python loop), the lane dim pads with
    # the v columns
    qp = -(-q // 8) * 8
    w_p = jnp.pad(w2, ((0, qp - q), (0, kvp - kv)))

    out = pl.pallas_call(
        functools.partial(
            _multi_matvec_body, kernels=kernels, sigmas=sigmas
        ),
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, dp), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, kvp), lambda i, j: (j, 0)),
            pl.BlockSpec((qp, kvp), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, kvp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, kvp), jnp.float32),
        interpret=interpret,
    )(a_p, b_p, v_p, w_p)
    out = out[:m, :kv]
    return out[:, 0] if squeeze else out


@functools.partial(
    jax.jit,
    static_argnames=(
        "kernels", "sigmas", "bm", "bn", "dchunk", "interpret", "precision",
    ),
)
def kernel_matvec_components_pallas(
    a: jax.Array,
    b: jax.Array,
    v: jax.Array,
    *,
    kernels: tuple[str, ...],
    sigmas: tuple[float, ...],
    bm: int = 256,
    bn: int = 256,
    dchunk: int = 32,
    interpret: bool = False,
    precision: str = "f32",
) -> jax.Array:
    """Stacked per-kernel products: out[i] = K_i(a, b) @ v, shape (q, m[, kv]).

    ``precision="bf16"`` loads the A/B/V tiles in bf16 with f32 accumulation.
    """
    squeeze = v.ndim == 1
    if squeeze:
        v = v[:, None]
    a_p, b_p, v_p, (m, n, kv, bm, bn, mp, np_, dp, kvp) = _pad_multi(
        a, b, v, bm, bn, dchunk, interpret, precision
    )
    q = len(kernels)

    out = pl.pallas_call(
        functools.partial(
            _components_body, kernels=kernels, sigmas=sigmas
        ),
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, dp), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, kvp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((q, bm, kvp), lambda i, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, mp, kvp), jnp.float32),
        interpret=interpret,
    )(a_p, b_p, v_p)
    out = out[:, :m, :kv]
    return out[:, :, 0] if squeeze else out


@functools.partial(
    jax.jit,
    static_argnames=(
        "kernels", "sigmas", "weights", "bm", "bn", "dchunk", "interpret",
        "precision",
    ),
)
def kernel_block_multi_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    kernels: tuple[str, ...],
    sigmas: tuple[float, ...],
    weights: tuple[float, ...],
    bm: int = 256,
    bn: int = 256,
    dchunk: int = 32,
    interpret: bool = False,
    precision: str = "f32",
) -> jax.Array:
    """Materialize sum_i w_i K_i(a, b): (m, d), (n, d) -> (m, n) f32.

    ``precision="bf16"`` loads the A/B tiles in bf16 with f32 accumulation.
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
        functools.partial(
            _block_multi_body, kernels=kernels, sigmas=sigmas,
            weights=weights,
        ),
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
