"""Pallas TPU kernel: fused pairwise-kernel x matvec — ASkotch's O(n*b) hot spot.

Computes ``out = K(A, B) @ V`` without materializing K, where
``K[i, j] = k(A[i], B[j])`` for any kernel in ``core.kernels.KERNEL_NAMES``.

TPU-native tiling (see docs/architecture.md, "Pallas matvec tiling"):

  grid = (m // bm, n // bn); the n axis is the contraction and iterates
  innermost so the (bm, kv) f32 accumulator tile stays resident in VMEM.

  Per grid step, VMEM holds:
    A tile (bm, d), B tile (bn, d), V tile (bn, kv), base tile (bm, bn),
    accumulator (bm, kv).
  The base tile depends on the kernel's FAMILY (``core.kernels.
  KERNEL_FAMILIES``): the squared-L2 tile (rbf/matern52) comes from the MXU
  via the ||a||^2 + ||b||^2 - 2 a.b^T expansion (one (bm,d)x(d,bn) matmul,
  f32 accumulate); the dot-product family (linear/polynomial/sigmoid) and
  cosine skip the norm terms and use the raw (or row-normalized) a.b^T matmul
  directly — same MXU shape, strictly less VPU work.  The L1 distance
  (laplacian) has no matmul form: it is reduced on the VPU one feature
  column at a time, each a (bm, 1) - (1, bn) broadcast into the (bm, bn) f32
  accumulator, so no 3-D slab is ever built (one (256, 256) f32 tile is
  256 KB).

  Default bm=bn=256, d zero-padded to a multiple of ``dchunk`` (32), kv
  zero-padded to 128 lanes: the MXU matmuls are (256,dp)x(dp,256) and
  (256,256)x(256,kv).  Zero feature columns leave every family's base tile
  unchanged.

Validated against ``ref.kernel_matvec`` in interpret mode (tests sweep shapes,
dtypes and kernels); on TPU hardware the same code runs compiled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.kernels import kernel_family

_SQRT5 = 5.0**0.5


def _apply_kernel(base: jax.Array, kernel: str, sigma: float) -> jax.Array:
    """Elementwise kernel map on the VPU given the kernel's base tile
    (squared-L2 / L1 distances, inner products, or cosine similarities —
    whichever ``core.kernels.KERNEL_FAMILIES[kernel]`` names)."""
    if kernel == "rbf":
        return jnp.exp(-base / (2.0 * sigma**2))
    if kernel == "laplacian":
        return jnp.exp(-base / sigma)
    if kernel == "matern52":
        d2 = base
        d = jnp.sqrt(d2 + 1e-20)
        s5 = _SQRT5 * d / sigma
        return (1.0 + s5 + 5.0 * d2 / (3.0 * sigma**2)) * jnp.exp(-s5)
    if kernel == "linear":
        return base / sigma**2
    if kernel == "polynomial":
        return (base / sigma**2 + 1.0) ** 3
    if kernel == "sigmoid":
        return jnp.tanh(base / sigma**2 + 1.0)
    if kernel == "cosine":
        return base
    raise ValueError(f"unknown kernel {kernel!r}")


def _mxu_dot(x: jax.Array, y: jax.Array, contract: tuple[int, int]) -> jax.Array:
    """2-D matmul on the MXU contracting ``x``'s axis ``contract[0]`` with
    ``y``'s axis ``contract[1]``, accumulated in f32.  f32 operands contract
    at full f32 precision: Mosaic's default contracts them in one bf16 pass,
    which puts percent-level error into the squared-L2 expansion."""
    precision = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        x, y, (((contract[0],), (contract[1],)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32,
    )


def _dot_tile(a: jax.Array, b: jax.Array) -> jax.Array:
    """(bm, bn) f32 inner-product tile a.b^T from the MXU — operands at their
    stored width (f32/bf16) with f32 accumulation."""
    return _mxu_dot(a, b, (1, 1))


def _base_tile(a: jax.Array, b: jax.Array, family: str) -> jax.Array:
    """(bm, bn) f32 base tile for a kernel family: squared-L2 ("l2"), L1
    ("l1"), inner product ("dot"), or cosine similarity ("cos").

    Accepts raw operand tiles in f32 OR bf16 — the mixed-precision contract:
    the MXU contraction takes the operands at their stored width with
    ``preferred_element_type=f32`` (f32 accumulation), the norms and the L1
    slab reduction upcast to f32 first (bf16 -> f32 is exact per element).
    The returned tile is always f32.  Zero-padded feature columns leave every
    family's tile unchanged; zero-padded ROWS yield 0 similarities under the
    "cos" family's zero-norm-divides-by-1 convention (sklearn's), so padding
    never pollutes live rows in any family.
    """
    if family == "l1":
        # one feature column at a time: (bm, 1) - (1, bn) broadcasts reduce
        # |a - b| on the VPU without a 3-D slab.  Zero-padded columns add 0.
        af = a.astype(jnp.float32)
        bf = b.astype(jnp.float32)
        acc = jnp.zeros((a.shape[0], b.shape[0]), jnp.float32)
        for c in range(a.shape[1]):
            acc += jnp.abs(af[:, c : c + 1] - bf[:, c : c + 1].T)
        return acc
    if family == "dot":
        return _dot_tile(a, b)
    if family == "cos":
        af = a.astype(jnp.float32)
        bf = b.astype(jnp.float32)
        an = jnp.sqrt(jnp.sum(af * af, axis=-1, keepdims=True))  # (bm, 1)
        bn_ = jnp.sqrt(jnp.sum(bf * bf, axis=-1, keepdims=True)).T  # (1, bn)
        ab = _dot_tile(a, b)
        return ab / (jnp.where(an == 0.0, 1.0, an) * jnp.where(bn_ == 0.0, 1.0, bn_))
    if family != "l2":
        raise ValueError(f"unknown kernel family {family!r}")
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    aa = jnp.sum(af * af, axis=-1, keepdims=True)  # (bm, 1)
    bb = jnp.sum(bf * bf, axis=-1, keepdims=True).T  # (1, bn)
    ab = _dot_tile(a, b)
    return jnp.maximum(aa + bb - 2.0 * ab, 0.0)


def _distance_tile(a: jax.Array, b: jax.Array, kernel: str) -> jax.Array:
    """Base tile for one kernel — :func:`_base_tile` keyed by the kernel's
    family (kept as the per-kernel spelling the single-kernel bodies use)."""
    return _base_tile(a, b, kernel_family(kernel))


def _cast_tiles(precision: str, *arrays: jax.Array) -> tuple[jax.Array, ...]:
    """Host-side tile dtype for the requested precision policy: bf16 halves
    the HBM/VMEM traffic of every A/B/V tile; f32 is the identity."""
    if precision == "bf16":
        return tuple(x.astype(jnp.bfloat16) for x in arrays)
    return arrays


def _matvec_body(a_ref, b_ref, v_ref, o_ref, *, kernel: str, sigma: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # tiles arrive at policy width (f32 or bf16); the distance tile and the
    # kernel map are f32, the second matmul runs at policy width with f32
    # accumulation (preferred_element_type) into the resident o_ref tile
    v = v_ref[...]
    dist = _distance_tile(a_ref[...], b_ref[...], kernel)
    ktile = _apply_kernel(dist, kernel, sigma)
    o_ref[...] += _mxu_dot(ktile.astype(v.dtype), v, (1, 0))


@functools.partial(
    jax.jit,
    static_argnames=(
        "kernel", "sigma", "bm", "bn", "dchunk", "interpret", "precision",
    ),
)
def kernel_matvec_pallas(
    a: jax.Array,
    b: jax.Array,
    v: jax.Array,
    *,
    kernel: str = "rbf",
    sigma: float = 1.0,
    bm: int = 256,
    bn: int = 256,
    dchunk: int = 32,
    interpret: bool = False,
    precision: str = "f32",
) -> jax.Array:
    """out = K(a, b) @ v.  a: (m, d), b: (n, d), v: (n, k)|(n,) -> (m, k)|(m,).

    ``precision="bf16"`` loads the A/B/V tiles in bf16 (half the HBM/VMEM
    traffic, 2x MXU rate on TPU) while the distance accumulation, kernel map
    and output accumulator stay f32; the output is f32 either way.
    """
    squeeze = v.ndim == 1
    if squeeze:
        v = v[:, None]
    m, d = a.shape
    n = b.shape[0]
    kv = v.shape[1]

    bm = min(bm, max(8, m))
    bn = min(bn, max(8, n))
    # Pad everything to tile multiples.  Zero-padded V rows nullify padded-B
    # contributions; padded-A rows are sliced off the output; zero-padded
    # features leave both L2 and L1 distances unchanged.
    mp, np_, dp = -(-m // bm) * bm, -(-n // bn) * bn, -(-d // dchunk) * dchunk
    kvp = -(-kv // 128) * 128 if not interpret else kv
    a_p = jnp.pad(a, ((0, mp - m), (0, dp - d)))
    b_p = jnp.pad(b, ((0, np_ - n), (0, dp - d)))
    v_p = jnp.pad(v, ((0, np_ - n), (0, kvp - kv)))
    a_p, b_p, v_p = _cast_tiles(precision, a_p, b_p, v_p)

    out = pl.pallas_call(
        functools.partial(_matvec_body, kernel=kernel, sigma=float(sigma)),
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, dp), lambda i, j: (j, 0)),
            pl.BlockSpec((bn, kvp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, kvp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, kvp), jnp.float32),
        interpret=interpret,
    )(a_p, b_p, v_p)
    out = out[:m, :kv]
    return out[:, 0] if squeeze else out
