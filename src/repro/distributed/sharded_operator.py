"""ShardedKernelOperator — the KernelOperator contract over a row-sharded x.

``core.operator.KernelOperator`` made one object the owner of
``(kernel, sigma, backend, chunking)`` for every single-device solver.  This
layer restates the same four primitives — ``matvec``, ``row_block_matvec``,
``block``/``block_idx``, ``trace_est``, plus ``restrict``/``with_points`` —
over an ``x`` whose rows are sharded across the non-"model" axes of a
``jax.sharding.Mesh``.  Every collective is explicit (``psum`` /
``all_gather`` inside ``shard_map``); all local compute dispatches through a
plain per-shard :class:`KernelOperator`, so the xla/pallas/interpret kernel
backends — multi-RHS ``(n, t)`` included — come for free (docs/
architecture.md, layer 3).

Sharding contract (rows = every mesh axis except "model"):

  * ``x`` (n, d), iterates/RHS (n,) or (n, t)  — row-sharded ``P(rows, ...)``
  * block points ``a``/``b``, indices ``idx``, outputs of
    ``row_block_matvec``/``block``/``gather_rows``  — replicated ``P()``

Per-primitive collective cost (t RHS columns, S row shards, M model shards):

  primitive                 collectives                      wire bytes
  ------------------------  -------------------------------  -----------------
  matvec                    allgather x, v over rows;        n(d + t) + n_loc t
                            psum over model
  row_block_matvec          psum over rows (+ allgather      b t  (+ b t)
                            over model when M | b)
  block                     allgather over model             b_a b_b
  gather_rows / block_idx   ONE packed psum over rows        b (d + extras)
  trace_est                 none (unit-diagonal kernels)     0

The ``shard_*`` methods are the same composites exposed for use INSIDE an
ambient ``shard_map`` over ``mesh`` — ``distributed/krr_dist.py`` fuses a
whole ASkotch iteration into one shard_map body built from them (block
gather, distributed Nystrom, Woodbury applies, powering) without touching
``kernels.ops`` or hand-rolling collectives.

The tuning engine (``core/tune/engine.py``) runs its stacked per-sigma
solves against this operator through the same primitives a local
``KernelOperator`` exposes — ``matvec``/``matvec_cols`` for the fused
column block, ``sketch``/``sketch_components`` for the per-sigma Nystrom
factors — so every search policy (grid / random / successive halving,
with or without sigma-continuation) runs unchanged over a mesh: policies
only ever see host-side score arrays, and the engine's mid-solve rung
scoring is one more distributed ``matvec``.

A mesh of total size 1 degrades gracefully: every collective is a no-op and
all code paths run in a plain single-device pytest process.

Observability: each global-array primitive counts its collectives into
``repro_collective_dispatch_total{primitive=..., collective=...}``
(``repro.obs.metrics``).  Counts are **dispatch-level** — tallied in the
host-side ``call`` wrappers per primitive invocation, so a jitted caller
that traces a wrapper once still counts every dispatch, but collectives
fused inside someone else's shard_map body (the ``shard_*`` composites)
are not tallied here.
"""

from __future__ import annotations

import dataclasses
from functools import cached_property

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.operator import KernelOperator
from repro.distributed.meshes import shard_map
from repro.obs.metrics import counter as _obs_counter

MODEL_AXIS = "model"


def _count_collective(primitive: str, collective: str, count: int = 1) -> None:
    """Tally ``count`` dispatches of a collective inside ``primitive``."""
    if count:
        _obs_counter(
            "repro_collective_dispatch_total",
            labels={"primitive": primitive, "collective": collective},
            help="host-side dispatches of mesh collectives by primitive",
        ).inc(count)


def row_axes(mesh: Mesh) -> tuple[str, ...]:
    """Every mesh axis except "model" shards rows (("pod", "data") on the
    multi-pod mesh, ("data",) on solver meshes)."""
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)


@dataclasses.dataclass(frozen=True)
class ShardedKernelOperator:
    """Mesh-aware linear-operator view of K = K(x, x).

    ``x`` is a global ``(n, d)`` array placed row-sharded on ``mesh`` (use
    :meth:`bind` to place a host array).  ``x`` may also be ``None`` — an
    *unbound* operator is the (mesh, kernel-config) view whose ``shard_*``
    composites serve solver-owned shard_map bodies that receive their x shard
    as an argument (``krr_dist.make_dist_askotch_step``).
    """

    mesh: Mesh
    x: jax.Array | None = None
    kernel: str | tuple[str, ...] = "rbf"
    sigma: float | tuple[float, ...] = 1.0
    backend: str = "auto"
    chunk_a: int = 4096
    chunk_b: int = 8192
    weights: tuple[float, ...] | None = None  # multi-kernel combination
    precision: str = "f32"  # tile-compute policy: "f32" | "bf16"

    def __post_init__(self) -> None:
        if isinstance(self.kernel, list):
            object.__setattr__(self, "kernel", tuple(self.kernel))
        if isinstance(self.sigma, list):
            object.__setattr__(self, "sigma", tuple(self.sigma))
        if self.weights is not None:
            object.__setattr__(
                self, "weights", tuple(float(w) for w in self.weights)
            )

    # -- construction --------------------------------------------------------

    @classmethod
    def bind(cls, mesh: Mesh, x: jax.Array, **cfg) -> "ShardedKernelOperator":
        """Place ``x`` row-sharded on ``mesh`` and return a bound operator."""
        op = cls(mesh=mesh, x=None, **cfg)
        n = x.shape[0]
        if n % op.n_row_shards != 0:
            raise ValueError(
                f"n = {n} rows do not shard evenly over {op.n_row_shards} row "
                f"shard(s) of mesh axes {op.rows}; pad the dataset or pick a "
                f"mesh whose row-axis product divides n"
            )
        x_sh = jax.device_put(x, NamedSharding(mesh, P(op.rows, None)))
        return dataclasses.replace(op, x=x_sh)

    # -- mesh/axis structure -------------------------------------------------

    @property
    def rows(self) -> tuple[str, ...]:
        """The mesh axes sharding rows (every axis except "model")."""
        return row_axes(self.mesh)

    @property
    def model(self) -> str | None:
        """The "model" axis name if the mesh has one, else None."""
        return MODEL_AXIS if MODEL_AXIS in self.mesh.axis_names else None

    @property
    def n_row_shards(self) -> int:
        """Total number of row shards S (product of the non-"model" axes)."""
        s = 1
        for a in self.rows:
            s *= self.mesh.shape[a]
        return s

    @property
    def n_model(self) -> int:
        """Size M of the "model" axis (1 when the mesh has none)."""
        return self.mesh.shape[MODEL_AXIS] if self.model else 1

    @property
    def n(self) -> int:
        """Global row count of the bound (row-sharded) x."""
        self._require_bound()
        return self.x.shape[0]

    @property
    def d(self) -> int:
        """Feature dimension of the row points."""
        self._require_bound()
        return self.x.shape[1]

    @property
    def shape(self) -> tuple[int, int]:
        """(n, n) — the global kernel matrix shape this operator applies."""
        return (self.n, self.n)

    @property
    def n_loc(self) -> int:
        """Rows per shard, n / S (bind() guarantees the division is exact)."""
        return self.n // self.n_row_shards

    def _require_bound(self) -> None:
        if self.x is None:
            raise ValueError(
                "operator is unbound (x=None); global-array primitives need "
                "a bound operator — use ShardedKernelOperator.bind(mesh, x)"
            )

    def vec_spec(self, ndim: int) -> P:
        """PartitionSpec of a row-sharded iterate/RHS: (n,) or (n, t)."""
        return P(self.rows) if ndim == 1 else P(self.rows, *([None] * (ndim - 1)))

    def sharding(self, ndim: int) -> NamedSharding:
        """NamedSharding for placing a (n, ...) row-aligned array."""
        return NamedSharding(self.mesh, self.vec_spec(ndim))

    def replicated(self) -> NamedSharding:
        """NamedSharding for fully-replicated (block-level) arrays."""
        return NamedSharding(self.mesh, P())

    # -- local views ---------------------------------------------------------

    def local_op(self, pts: jax.Array) -> KernelOperator:
        """Per-shard operator over ``pts`` — the ONLY kernel dispatch point
        in the distributed stack (kernels.ops via core.operator /
        core.multikernel).  A kernel TUPLE yields a per-shard
        ``WeightedSumKernelOperator``, which is how multi-kernel solves run
        on a mesh without any collective changes."""
        from repro.core.multikernel import make_operator

        return make_operator(
            pts, kernel=self.kernel, sigma=self.sigma, weights=self.weights,
            backend=self.backend, chunk_a=self.chunk_a, chunk_b=self.chunk_b,
            precision=self.precision,
        )

    # -- derived operators ---------------------------------------------------

    def with_points(self, x_new: jax.Array) -> "ShardedKernelOperator":
        """Same configuration over a different (row-shardable) row set."""
        return ShardedKernelOperator.bind(
            self.mesh, x_new, kernel=self.kernel, sigma=self.sigma,
            backend=self.backend, chunk_a=self.chunk_a, chunk_b=self.chunk_b,
            weights=self.weights, precision=self.precision,
        )

    def restrict(self, idx: jax.Array) -> KernelOperator:
        """Operator over ``x[idx]`` (centers, dictionaries, sampled blocks).

        Sub-row-sets are small by construction, so the restriction is
        gathered (one packed psum) and returned as a *replicated* plain
        KernelOperator — downstream code is mesh-free from here on.
        """
        (xb,), _owned = self.gather_rows(idx)
        return self.local_op(xb)

    # -- shard-level composites (call INSIDE a shard_map over self.mesh) -----

    def shard_row_id(self) -> jax.Array:
        """Linearized row-shard index of the calling device."""
        rid = jnp.int32(0)
        for a in self.rows:
            rid = rid * self.mesh.shape[a] + jax.lax.axis_index(a)
        return rid.astype(jnp.int32)

    def shard_model_id(self) -> jax.Array:
        """"model"-axis index of the calling device (0 without the axis)."""
        return jax.lax.axis_index(self.model) if self.model else jnp.int32(0)

    def model_slice(self, arr: jax.Array, loc: int) -> jax.Array:
        """This model shard's row slice of a replicated (b, ...) array."""
        if self.n_model == 1:
            return arr
        return jax.lax.dynamic_slice_in_dim(arr, self.shard_model_id() * loc, loc)

    def model_all_gather(self, arr: jax.Array) -> jax.Array:
        """all_gather over "model" (no-op when the axis is absent/size 1)."""
        if self.n_model == 1:
            return arr
        return jax.lax.all_gather(arr, self.model, tiled=True)

    def model_psum(self, arr: jax.Array) -> jax.Array:
        """psum over "model" (no-op when the axis is absent/size 1)."""
        if self.n_model == 1:
            return arr
        return jax.lax.psum(arr, self.model)

    def shard_gather_rows(
        self, x_l: jax.Array, idx: jax.Array, extras: tuple[jax.Array, ...] = ()
    ) -> tuple[tuple[jax.Array, ...], jax.Array, jax.Array]:
        """Packed-psum gather of global rows ``idx`` from the row shards.

        ``x_l`` is this shard's (n_loc, d) rows; each extra is a row-aligned
        (n_loc,) or (n_loc, t) shard.  ONE psum moves x and every extra
        together (b * (d + sum t_i) f32): fewer collective launches, and a
        strict dependency chain (independent collectives can deadlock
        thread-starved executors and serialize on real ICI anyway).

        Returns ``((x_B, *extras_B), owned, local_pos)`` — the gathered rows
        replicated across the mesh, plus this shard's ownership mask and
        clipped local positions (the scatter-back coordinates).
        """
        n_loc = x_l.shape[0]
        lo = self.shard_row_id() * n_loc
        local_pos = jnp.clip(idx - lo, 0, n_loc - 1)
        owned = ((idx >= lo) & (idx < lo + n_loc)).astype(x_l.dtype)
        cols = [x_l[local_pos]]
        widths = [x_l.shape[1]]
        for e in extras:
            tile = e[local_pos]
            cols.append(tile[:, None] if tile.ndim == 1 else tile)
            widths.append(cols[-1].shape[1])
        packed = jnp.concatenate(cols, axis=1) * owned[:, None]
        packed = jax.lax.psum(packed, self.rows)
        outs, off = [], 0
        for e, w in zip((x_l, *extras), widths):
            piece = packed[:, off : off + w]
            outs.append(piece[:, 0] if e.ndim == 1 else piece)
            off += w
        return tuple(outs), owned, local_pos

    def shard_row_block_matvec(
        self, x_l: jax.Array, a_l: jax.Array, v_l: jax.Array
    ) -> jax.Array:
        """K(a_l, x) @ v — this shard's partial, psum'd over rows.

        ``a_l``: this model shard's (b_loc, d) block rows (replicated block
        pre-sliced with :meth:`shard_block_slice`); ``v_l``: the (n_loc[, t])
        row shard.  Output: (b_loc[, t]) replicated over rows, still sharded
        over model — ``model_all_gather`` assembles the full block.
        """
        part = self.local_op(x_l).row_block_matvec(a_l, v_l)
        return jax.lax.psum(part, self.rows)

    def shard_block_slice(self, arr: jax.Array) -> jax.Array:
        """This model shard's rows of a replicated block array (b, ...)."""
        if self.n_model == 1:
            return arr
        b = arr.shape[0]
        if b % self.n_model:
            raise ValueError(
                f"block of {b} rows does not shard over {self.n_model} model "
                f"shard(s); round the block size up to a multiple of "
                f"{self.n_model}"
            )
        return self.model_slice(arr, b // self.n_model)

    def shard_block_nystrom(
        self, xb: jax.Array, rank: int, key: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        """Distributed rank-r Nystrom of K_BB, U rows sharded over "model".

        ``xb``: the replicated (b, d) block.  The sketch rows are computed by
        this model shard ((b/M, r) local kernel matvec), the two r x r Grams
        are psum'd over "model", and the eigh of B^T B is replicated (r is
        ~100 — trivial).  Returns ``(u_l, lam)``: this shard's (b/M, r) rows
        of U and the replicated (r,) Nystrom eigenvalues.
        """
        b = xb.shape[0]
        xb_l = self.shard_block_slice(xb)
        omega = jax.random.normal(key, (b, rank), jnp.float32)
        omega, _ = jnp.linalg.qr(omega)  # replicated (b x r)
        omega_l = self.shard_block_slice(omega)
        y_sketch = self.local_op(xb).row_block_matvec(xb_l, omega)  # (b/M, r)
        shift = jnp.float32(1.19e-7) * b  # eps * tr(K_BB); unit-diag kernels
        y_sketch = y_sketch + shift * omega_l
        gram = self.model_psum(omega_l.T @ y_sketch)  # (r, r)
        gram = 0.5 * (gram + gram.T)
        chol = jnp.linalg.cholesky(gram + 1e-6 * jnp.eye(rank))
        b_mat = jax.scipy.linalg.solve_triangular(chol, y_sketch.T, lower=True).T
        btb = self.model_psum(b_mat.T @ b_mat)  # (r, r)
        evals, evecs = jnp.linalg.eigh(btb)
        evals, evecs = evals[::-1], evecs[:, ::-1]
        s_vals = jnp.sqrt(jnp.maximum(evals, 1e-30))
        u_l = b_mat @ (evecs / s_vals[None, :])  # (b/M, r) local rows of U
        lam_ny = jnp.maximum(evals - shift, 0.0)
        return u_l, lam_ny

    def shard_woodbury_apply(
        self, u_l: jax.Array, lam_ny: jax.Array, rho: jax.Array, g_l: jax.Array
    ) -> jax.Array:
        """(U diag(lam) U^T + rho I)^{-1} g with U rows sharded over "model".

        ``g_l``: (b/M,) or (b/M, t).  One r[ x t] psum over "model" serves
        all t columns.
        """
        utg = self.model_psum(u_l.T @ g_l)  # (r[, t])
        scale = lam_ny + rho
        scaled = utg / (scale[:, None] if utg.ndim == 2 else scale)
        return u_l @ scaled + (g_l - u_l @ utg) / rho

    def shard_woodbury_invsqrt(
        self, u_l: jax.Array, lam_ny: jax.Array, rho: jax.Array, g_l: jax.Array
    ) -> jax.Array:
        """(U diag(lam) U^T + rho I)^{-1/2} g — Eq. (16) on model-sharded U."""
        utg = self.model_psum(u_l.T @ g_l)
        scale = jnp.sqrt(lam_ny + rho)
        scaled = utg / (scale[:, None] if utg.ndim == 2 else scale)
        return u_l @ scaled + (g_l - u_l @ utg) / jnp.sqrt(rho)

    def shard_block_powering(
        self,
        xb: jax.Array,
        u_l: jax.Array,
        lam_ny: jax.Array,
        rho: jax.Array,
        lam: jax.Array,
        v0: jax.Array,
        num_iters: int,
    ) -> tuple[jax.Array, jax.Array]:
        """get_L (Algorithm 5) on the preconditioned distributed block:
        top eigenvalue of P^{-1/2} (K_BB + lam I) P^{-1/2}.

        ``v0``: replicated (b,) start vector.  The loop is UNROLLED:
        collectives inside a lax.scan share one HLO channel id, which the
        in-process CPU communicator cannot disambiguate across iterations;
        unrolling gives each collective its own channel (and lets XLA
        pipeline them on real hardware).  Returns (v_last, L_estimate).
        """
        b = xb.shape[0]
        b_loc = b // self.n_model
        xb_l = self.shard_block_slice(xb)
        lop = self.local_op(xb)

        def kbb_lam_mv(v_full):  # (b,) replicated -> (b/M,) local
            part = lop.row_block_matvec(xb_l, v_full)
            return part + lam * self.model_slice(v_full, b_loc)

        v = v0 / jnp.maximum(jnp.linalg.norm(v0), 1e-30)
        lam_est = jnp.float32(1.0)
        for _ in range(num_iters):
            v_l = self.model_slice(v, b_loc)
            u1 = self.shard_woodbury_invsqrt(u_l, lam_ny, rho, v_l)
            u1_full = self.model_all_gather(u1)  # (b,)
            u2 = kbb_lam_mv(u1_full)
            u3 = self.shard_woodbury_invsqrt(u_l, lam_ny, rho, u2)
            stats = self.model_psum(jnp.stack([v_l @ u3, u3 @ u3]))  # packed
            lam_est, nrm = stats[0], jnp.sqrt(stats[1])
            v = self.model_all_gather(u3 / jnp.maximum(nrm, 1e-30))
        return v, lam_est

    # -- the four primitives over global arrays ------------------------------

    @cached_property
    def _matvec_fn(self):
        def local(x_l, v_l):
            x_full = jax.lax.all_gather(x_l, self.rows, tiled=True)
            v_full = jax.lax.all_gather(v_l, self.rows, tiled=True)
            n = x_full.shape[0]
            if self.n_model > 1 and n % self.n_model == 0:
                # split the contraction over "model": each shard applies a
                # column slice of K, psum assembles the full product
                sl = n // self.n_model
                xs = self.model_slice(x_full, sl)
                vs = self.model_slice(v_full, sl)
                part = self.local_op(xs).row_block_matvec(x_l, vs)
                return jax.lax.psum(part, self.model)
            return self.local_op(x_full).row_block_matvec(x_l, v_full)

        jitted: dict[int, object] = {}  # keyed on RHS ndim; jit caches shapes

        def call(v):
            if v.ndim not in jitted:
                spec = self.vec_spec(v.ndim)
                jitted[v.ndim] = jax.jit(shard_map(
                    local, mesh=self.mesh,
                    in_specs=(P(self.rows, None), spec), out_specs=spec,
                ))
            if self.n_row_shards > 1:
                _count_collective("matvec", "all_gather", 2)  # x and v
            if self.n_model > 1 and self.n % self.n_model == 0:
                _count_collective("matvec", "psum")
            return jitted[v.ndim](self.x, v)

        return call

    def matvec(self, v: jax.Array) -> jax.Array:
        """K(x, x) @ v; v row-sharded (n,) or (n, t) -> same sharding out."""
        self._require_bound()
        return self._matvec_fn(v)

    def _require_multikernel(self) -> None:
        if not isinstance(self.kernel, tuple):
            raise ValueError(
                "per-column-weighted primitives need a multi-kernel operator "
                f"(a kernel tuple); got kernel={self.kernel!r}"
            )

    @cached_property
    def _matvec_cols_fn(self):
        def local(x_l, v_l, wc):
            x_full = jax.lax.all_gather(x_l, self.rows, tiled=True)
            v_full = jax.lax.all_gather(v_l, self.rows, tiled=True)
            n = x_full.shape[0]
            if self.n_model > 1 and n % self.n_model == 0:
                sl = n // self.n_model
                xs = self.model_slice(x_full, sl)
                vs = self.model_slice(v_full, sl)
                part = self.local_op(xs).row_block_matvec_cols(x_l, vs, wc)
                return jax.lax.psum(part, self.model)
            return self.local_op(x_full).row_block_matvec_cols(x_l, v_full, wc)

        jitted = jax.jit(shard_map(
            local, mesh=self.mesh,
            in_specs=(P(self.rows, None), self.vec_spec(2), P()),
            out_specs=self.vec_spec(2),
        ))

        def call(v, w_cols):
            if self.n_row_shards > 1:
                _count_collective("matvec_cols", "all_gather", 2)
            if self.n_model > 1 and self.n % self.n_model == 0:
                _count_collective("matvec_cols", "psum")
            return jitted(self.x, v, w_cols)

        return call

    def matvec_cols(self, v: jax.Array, w_cols: jax.Array) -> jax.Array:
        """Per-column-weighted multi-kernel matvec: out[:, c] =
        (sum_i w_cols[i, c] K_i) @ v[:, c]; v row-sharded (n, t), ``w_cols``
        replicated (q, t).  One fused data sweep per shard — the mesh leg of
        the multi-kernel tuning engine."""
        self._require_bound()
        self._require_multikernel()
        return self._matvec_cols_fn(v, jnp.asarray(w_cols, jnp.float32))

    @cached_property
    def _sketch_components_fn(self):
        def local(x_l, v_l):
            x_full = jax.lax.all_gather(x_l, self.rows, tiled=True)
            v_full = jax.lax.all_gather(v_l, self.rows, tiled=True)
            n = x_full.shape[0]
            if self.n_model > 1 and n % self.n_model == 0:
                sl = n // self.n_model
                xs = self.model_slice(x_full, sl)
                vs = self.model_slice(v_full, sl)
                part = self.local_op(xs).row_block_components(x_l, vs)
                return jax.lax.psum(part, self.model)
            return self.local_op(x_full).row_block_components(x_l, v_full)

        return jax.jit(shard_map(
            local, mesh=self.mesh,
            in_specs=(P(self.rows, None), self.vec_spec(2)),
            out_specs=P(None, self.rows, None),
        ))

    def sketch_components(self, omega: jax.Array) -> jax.Array:
        """Stacked per-kernel sketches (q, n, r): out[i] = K_i @ omega, rows
        sharded on axis 1.  ONE data sweep serves all q Nystrom sketches of
        the multi-kernel tuner."""
        self._require_bound()
        self._require_multikernel()
        if self.n_row_shards > 1:
            _count_collective("sketch_components", "all_gather", 2)
        if self.n_model > 1 and self.n % self.n_model == 0:
            _count_collective("sketch_components", "psum")
        return self._sketch_components_fn(self.x, omega)

    @cached_property
    def _row_block_matvec_fn(self):
        def local(a, x_l, v_l):
            if self.n_model > 1 and a.shape[0] % self.n_model == 0:
                a_l = self.shard_block_slice(a)
                part = self.shard_row_block_matvec(x_l, a_l, v_l)
                return self.model_all_gather(part)
            return self.shard_row_block_matvec(x_l, a, v_l)

        jitted: dict[int, object] = {}

        def call(a, v):
            if v.ndim not in jitted:
                jitted[v.ndim] = jax.jit(shard_map(
                    local, mesh=self.mesh,
                    in_specs=(P(), P(self.rows, None), self.vec_spec(v.ndim)),
                    out_specs=P(),
                ))
            if self.n_row_shards > 1:
                _count_collective("row_block_matvec", "psum")
            if self.n_model > 1 and a.shape[0] % self.n_model == 0:
                _count_collective("row_block_matvec", "all_gather")
            return jitted[v.ndim](a, self.x, v)

        return call

    def row_block_matvec(self, a: jax.Array, v: jax.Array) -> jax.Array:
        """K(a, x) @ v for a replicated row block ``a`` (b, d); v row-sharded
        (n,)|(n, t) -> replicated (b,)|(b, t).  ASkotch's hot spot, Falkon's
        K_nm products, prediction/serving."""
        self._require_bound()
        return self._row_block_matvec_fn(jnp.asarray(a), v)

    @cached_property
    def _block_fn(self):
        def local(a, b):
            a_l = self.shard_block_slice(a)
            tile = self.local_op(b).block(a_l, b)
            return self.model_all_gather(tile)

        jitted = jax.jit(shard_map(
            local, mesh=self.mesh, in_specs=(P(), P()), out_specs=P(),
        ))

        def call(a, b):
            if self.n_model == 1 or a.shape[0] % self.n_model:
                return self.local_op(b).block(a, b)  # replicated compute
            _count_collective("block", "all_gather")
            return jitted(a, b)

        return call

    def block(self, a: jax.Array, b: jax.Array | None = None) -> jax.Array:
        """Materialize K(a, b) for replicated point sets (small tiles only);
        rows of ``a`` split over "model" when divisible."""
        b = a if b is None else b
        return self._block_fn(jnp.asarray(a), jnp.asarray(b))

    def block_idx(self, idx: jax.Array) -> jax.Array:
        """K_BB for a replicated global row-index block (ASkotch step)."""
        (xb,), _ = self.gather_rows(idx)
        return self.block(xb, xb)

    @cached_property
    def _gather_rows_fn(self):
        jitted: dict[tuple[int, ...], object] = {}

        def call(idx, extras):
            key = tuple(e.ndim for e in extras)
            if key not in jitted:

                def local(idx, x_l, *e_l):
                    outs, owned, _pos = self.shard_gather_rows(x_l, idx, e_l)
                    return outs, owned

                in_specs = (P(), P(self.rows, None)) + tuple(
                    self.vec_spec(nd) for nd in key
                )
                out_specs = (tuple(P() for _ in range(1 + len(key))),
                             P(self.rows))
                jitted[key] = jax.jit(shard_map(
                    local, mesh=self.mesh, in_specs=in_specs,
                    out_specs=out_specs,
                ))
            if self.n_row_shards > 1:
                _count_collective("gather_rows", "psum")  # one packed psum
            return jitted[key](idx, self.x, *extras)

        return call

    def gather_rows(
        self, idx: jax.Array, *extras: jax.Array
    ) -> tuple[tuple[jax.Array, ...], jax.Array]:
        """Gather ``x[idx]`` (+ row-aligned extras) to every device via ONE
        packed psum.  Returns ``((x_B, *extras_B), owned)`` with the gathered
        arrays replicated and ``owned`` the row-sharded ownership mask."""
        self._require_bound()
        return self._gather_rows_fn(jnp.asarray(idx), tuple(extras))

    def trace_est(self) -> jax.Array:
        """tr K — no collective.  n for the unit-diagonal testbed kernels;
        a weighted combination scales by its weight sum."""
        if isinstance(self.kernel, tuple) and self.weights is not None:
            return jnp.float32(sum(self.weights) * self.n)
        return jnp.float32(self.n)

    # -- composites shared by solvers ----------------------------------------

    def k_lam_matvec(self, v: jax.Array, lam: jax.Array | float) -> jax.Array:
        """(K + lam I) @ v, row-sharded in and out."""
        return self.matvec(v) + lam * v

    def sketch(self, omega: jax.Array) -> jax.Array:
        """K @ omega for a row-sharded (n, r) test matrix — distributed
        Nystrom sketches over the full kernel without materializing it."""
        return self.matvec(omega)
