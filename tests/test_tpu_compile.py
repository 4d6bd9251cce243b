"""Ahead-of-time compiles for a described TPU v5e: the Pallas tiles of the
main path, every kernel family in f32 and bf16, the sharded ASkotch step on
a 4-chip mesh, and the tiles and the ASkotch step of the 10-class, d = 784
one-vs-all deployment on one chip.

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

from repro.core import askotch
from repro.core.krr import KRRProblem
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

#: the one-vs-all deployment (bench/configs/ova10-d784.json): MNIST's n and
#: d, ten heads, askotch's default block n/100
OVA_N, OVA_D, OVA_T, OVA_B = 60_000, 784, 10, 600
#: (rows m, heads t) of its matvecs: the full-residual check, the row block
OVA_MATVEC_SHAPES = {"residual": (OVA_N, OVA_T), "row_block": (OVA_B, OVA_T)}


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


@pytest.mark.parametrize("shape", list(OVA_MATVEC_SHAPES))
def test_ova10_kernel_matvec_compiles_for_v5e(one_chip, shape):
    m, t = OVA_MATVEC_SHAPES[shape]
    text = _compiled_text(
        lambda a, b, v: kernel_matvec_pallas(a, b, v, kernel="rbf", sigma=63.0),
        _f32((m, OVA_D), one_chip), _f32((OVA_N, OVA_D), one_chip),
        _f32((OVA_N, t), one_chip),
    )
    assert "tpu_custom_call" in text


def test_ova10_kernel_block_compiles_for_v5e(one_chip):
    text = _compiled_text(
        lambda a: kernel_block_pallas(a, a, kernel="rbf", sigma=63.0),
        _f32((OVA_B, OVA_D), one_chip),
    )
    assert "tpu_custom_call" in text


def test_ova10_askotch_step_compiles_for_v5e(one_chip):
    """The single-device step at the deployment's shapes, its dense algebra
    at full f32; the training set is an argument, so a small stand-in builds
    the step and the described shapes replace it."""
    problem = KRRProblem(x=jnp.zeros((8, OVA_D)), y=jnp.zeros((8, OVA_T)),
                         sigma=63.0, lam_unscaled=1e-6, backend="pallas")
    cfg = askotch.ASkotchConfig(block_size=OVA_B, rank=100, backend="pallas")
    step = askotch.make_step(problem, cfg)

    def described(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    state = askotch.init_state(problem)._replace(
        w=jnp.zeros((OVA_N, OVA_T)), v=jnp.zeros((OVA_N, OVA_T)),
        z=jnp.zeros((OVA_N, OVA_T)))
    compiled = step.func.lower(
        _f32((OVA_N, OVA_D), one_chip), _f32((OVA_N, OVA_T), one_chip), None,
        jax.tree.map(described, step.args[3]), jax.tree.map(described, state),
        **step.keywords,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
