"""Layer-Hessian utilities for GPTQ/GPTVQ.

The per-layer objective Hessian of ``||W X - Ŵ X||_F^2`` w.r.t. a row of W is
``H = X X^T`` (shape (c, c), c = in_features), shared across rows.

Accumulation comes in three flavours:

  * ``HessianState`` + ``accumulate``: the full (c, c) running sum used by
    the main quantization pass (GPTQ/GPTVQ need the whole matrix for the
    Cholesky error feedback).
  * ``DiagHessianState`` + ``accumulate_diag``: an O(c) running sum of
    ``sum_i x_i^2`` per column. The budget pre-pass only ever reads
    ``diag(H)``, so it uses this state and never materializes (c, c).
  * ``accumulate_sharded``: data-parallel accumulation over a
    ``jax.sharding`` mesh — calibration rows are sharded across the mesh's
    data axis, each device computes a partial ``X_s^T X_s`` (or the diag
    partial), and a single ``psum`` merges the partials. Numerically this
    matches single-device accumulation up to summation order.

Everything downstream of the accumulated H is per-layer-local.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


class HessianState(NamedTuple):
    H: jax.Array  # (c, c) running sum of X X^T
    n: jax.Array  # scalar: number of accumulated tokens


class DiagHessianState(NamedTuple):
    """O(c) accumulator: only the diagonal ``sum_i x_i[q]^2`` per column."""

    diag: jax.Array  # (c,) running sum of x^2 per column
    n: jax.Array     # scalar: number of accumulated tokens


def init_hessian(c: int, dtype=jnp.float32) -> HessianState:
    return HessianState(jnp.zeros((c, c), dtype), jnp.zeros((), jnp.int32))


def init_diag_hessian(c: int, dtype=jnp.float32) -> DiagHessianState:
    return DiagHessianState(jnp.zeros((c,), dtype), jnp.zeros((), jnp.int32))


@jax.jit
def accumulate(state: HessianState, x: jax.Array) -> HessianState:
    """Accumulate inputs ``x`` of shape (..., c) into the Hessian."""
    c = state.H.shape[0]
    xf = x.reshape(-1, c).astype(state.H.dtype)
    return HessianState(state.H + xf.T @ xf, state.n + xf.shape[0])


@jax.jit
def accumulate_diag(state: DiagHessianState, x: jax.Array) -> DiagHessianState:
    """Accumulate ``diag(X^T X)`` without ever forming (c, c)."""
    c = state.diag.shape[0]
    xf = x.reshape(-1, c).astype(state.diag.dtype)
    return DiagHessianState(state.diag + jnp.sum(xf * xf, axis=0),
                            state.n + xf.shape[0])


# -- mesh-parallel accumulation ----------------------------------------------

@functools.lru_cache(maxsize=None)
def _sharded_partial_fns(mesh, axis: str):
    """Build (full, diag) shard_map partial-Hessian fns for a mesh axis.

    Each device receives its row-shard of the flattened activations,
    computes the local ``X_s^T X_s`` (or its diagonal), and a single
    ``psum`` over ``axis`` merges the partials — one collective per
    accumulate call. Cached per (mesh, axis): ``jax.sharding.Mesh`` is
    hashable, so repeated calls reuse the compiled fns.
    """

    def _full(xf):
        part = xf.T @ xf
        return jax.lax.psum(part, axis)

    def _diag(xf):
        part = jnp.sum(xf * xf, axis=0)
        return jax.lax.psum(part, axis)

    full = jax.jit(jax.shard_map(_full, mesh=mesh, in_specs=P(axis, None),
                                 out_specs=P(), check_vma=False))
    diag = jax.jit(jax.shard_map(_diag, mesh=mesh, in_specs=P(axis, None),
                                 out_specs=P(), check_vma=False))
    return full, diag


def data_mesh(n_devices: int, axis: str = "data"):
    """1-D mesh over the first ``n_devices`` devices for
    ``accumulate_sharded``. Its axis is ``Auto``: the sharded partials are
    merged by a psum inside shard_map and leave it replicated, so nothing
    downstream (the GPTVQ sweep) should carry an explicit sharding type —
    ``jax.make_mesh``'s default ``Explicit`` axes would propagate one."""
    return jax.make_mesh((n_devices,), (axis,),
                         axis_types=(jax.sharding.AxisType.Auto,))


def _shard_rows(x: jax.Array, c: int, n_dev: int):
    """Flatten to (rows, c) and zero-pad rows to a multiple of n_dev.

    Zero rows contribute nothing to ``X^T X``; the true row count is
    returned separately so ``n`` stays exact.
    """
    xf = x.reshape(-1, c).astype(jnp.float32)
    rows = xf.shape[0]
    pad = (-rows) % n_dev
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad, c), xf.dtype)], axis=0)
    return xf, rows


def accumulate_sharded(state, x: jax.Array, mesh, axis: str = "data"):
    """Data-parallel ``accumulate``/``accumulate_diag`` over a mesh axis.

    Rows of the flattened calibration activations are sharded across the
    mesh's ``axis`` devices; each computes a partial and one psum merges
    them. Accepts either a ``HessianState`` or a ``DiagHessianState`` and
    returns the same kind. Matches the single-device path numerically
    (floating-point summation order differs, so comparisons should be
    allclose rather than bitwise).
    """
    n_dev = mesh.shape[axis]
    full_fn, diag_fn = _sharded_partial_fns(mesh, axis)
    if isinstance(state, DiagHessianState):
        c = state.diag.shape[0]
        xf, rows = _shard_rows(x, c, n_dev)
        return DiagHessianState(state.diag + diag_fn(xf), state.n + rows)
    c = state.H.shape[0]
    xf, rows = _shard_rows(x, c, n_dev)
    return HessianState(state.H + full_fn(xf), state.n + rows)


def finalize(state: HessianState) -> jax.Array:
    """Mean Hessian (scale-invariant for the argmin, but keeps damping sane)."""
    n = jnp.maximum(state.n, 1).astype(state.H.dtype)
    return state.H / n


def finalize_diag(state: DiagHessianState) -> jax.Array:
    """Mean Hessian diagonal, (c,)."""
    n = jnp.maximum(state.n, 1).astype(state.diag.dtype)
    return state.diag / n


@functools.partial(jax.jit, static_argnames=("percdamp",))
def inv_hessian_cholesky(H: jax.Array, percdamp: float = 0.01) -> jax.Array:
    """Return upper-triangular U with ``H^{-1} = U^T U`` (GPTQ formulation).

    Dead columns (zero diagonal — inputs never active, e.g. unrouted MoE
    expert dims) are given unit diagonal so they quantize round-to-nearest
    with no error feedback, matching the GPTQ reference treatment. The
    damping level is ``percdamp`` times the mean *live* diagonal: dividing
    by the live-column count rather than c keeps layers with many dead
    columns from being systematically under-damped.
    """
    c = H.shape[0]
    diag = jnp.diagonal(H)
    dead = diag == 0
    H = H + jnp.diag(jnp.where(dead, 1.0, 0.0))
    live = jnp.maximum(jnp.sum(~dead), 1).astype(H.dtype)
    damp = percdamp * jnp.sum(jnp.where(dead, 0.0, diag)) / live
    damp = jnp.where(damp <= 0, 1e-8, damp)
    H = H + damp * jnp.eye(c, dtype=H.dtype)
    # H^{-1} via Cholesky solves (stable), then Cholesky of the inverse.
    L = jnp.linalg.cholesky(H)
    eye = jnp.eye(c, dtype=H.dtype)
    Linv = jax.scipy.linalg.solve_triangular(L, eye, lower=True)
    Hinv = Linv.T @ Linv
    # unique lower factor of Hinv, transposed -> upper U with Hinv = U^T U
    U = jnp.linalg.cholesky(Hinv).T
    return U


def cholesky_diag_weights(U: jax.Array) -> jax.Array:
    """Per-column error importance ``1 / U[q,q]^2``.

    ``U[q,q]^2`` is the q-th diagonal of the *conditioned* inverse Hessian
    (the Schur complement given all previous columns are already fixed), so
    ``1/U[q,q]^2`` is exactly the weight GPTQ's Eq. (2) assigns to the
    quantization error of column q. Used as the diagonal H-weights of the
    VQ assignment / EM distance (DESIGN.md §6.1).
    """
    d = jnp.diagonal(U)
    return 1.0 / jnp.maximum(d * d, 1e-20)
