"""Ahead-of-time compiles for a described TPU v5e: the Pallas tiles of the
main path, every kernel family in f32 and bf16, and the sharded ASkotch
step on a 4-chip mesh.

Nothing runs: the TPU compiler, which is installed with jax, compiles for a
topology that is described and not attached.  That catches what the
interpreter accepts and Mosaic refuses (an unsupported primitive, a slice
off the tiling, more VMEM than a kernel may use) without a chip.  The
topology is described inside a module fixture, never at import, so that
only the process that runs this file loads the TPU library; where it cannot
be described (a jax without libtpu) every test here skips.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.distributed.krr_dist import (
    DistKRRConfig,
    abstract_dist_inputs,
    make_dist_askotch_step,
)
from repro.kernels.kernel_block import kernel_block_pallas
from repro.kernels.kernel_matvec import kernel_matvec_pallas
from repro.kernels.multi import kernel_matvec_multi_pallas

N = 1_000_000  # taxi deployment rows on one chip (configs/askotch_krr.py)
D = 9
B = N // 100  # askotch's default block size
FAMILY_KERNELS = ("rbf", "laplacian", "linear", "cosine")  # l2, l1, dot, cos
#: (rows m of the query block, heads t) on the main path: the full residual
#: matvec, the row-block matvec at one and many heads, a serving bucket
MATVEC_SHAPES = {
    "residual": (N, 1),
    "row_block": (B, 1),
    "row_block_t16": (B, 16),
    "serving_bucket": (8, 1),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or it cannot describe a v5e
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without the chip; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("kernel", FAMILY_KERNELS)
@pytest.mark.parametrize("shape", list(MATVEC_SHAPES))
def test_kernel_matvec_compiles_for_v5e(one_chip, kernel, precision, shape):
    m, t = MATVEC_SHAPES[shape]
    text = _compiled_text(
        lambda a, b, v: kernel_matvec_pallas(
            a, b, v, kernel=kernel, precision=precision
        ),
        _f32((m, D), one_chip), _f32((N, D), one_chip), _f32((N, t), one_chip),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("kernel", FAMILY_KERNELS)
def test_kernel_block_compiles_for_v5e(one_chip, kernel, precision):
    text = _compiled_text(
        lambda a: kernel_block_pallas(a, a, kernel=kernel, precision=precision),
        _f32((B, D), one_chip),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_kernel_matvec_multi_with_laplacian_compiles_for_v5e(
    one_chip, precision
):
    kernels, t = ("rbf", "laplacian", "matern52"), 16
    text = _compiled_text(
        lambda a, b, v, w: kernel_matvec_multi_pallas(
            a, b, v, w, kernels=kernels, sigmas=(1.0, 2.0, 1.5),
            precision=precision,
        ),
        _f32((B, D), one_chip), _f32((N, D), one_chip),
        _f32((N, t), one_chip), _f32((len(kernels), t), one_chip),
    )
    assert "tpu_custom_call" in text


def test_dist_askotch_step_pallas_compiles_for_v5e_4x1(topo):
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1), ("data", "model"))
    cfg = DistKRRConfig(n=N, d=D, block_size=500, rank=100, backend="pallas")
    step, sh = make_dist_askotch_step(mesh, cfg)
    state, x, y = abstract_dist_inputs(cfg)

    def place(s, sharding):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)

    state = jax.tree.map(place, state, sh["state"])
    text = _compiled_text(step, state, place(x, sh["x"]), place(y, sh["y"]))
    assert "tpu_custom_call" in text
