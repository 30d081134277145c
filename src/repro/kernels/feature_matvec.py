"""Pallas TPU kernels for the feature-partitioned ERM hot loop.

Every algorithm in the paper's family F^{lam,L} spends its FLOPs in two
GEMVs per round on each machine:

    z_j = A_j w_j        (n x d_j) @ (d_j)   -> the ReduceAll summand
    g_j = A_j^T r        (d_j x n) @ (n)     -> the partial-gradient term

On TPU these are tall-skinny matmuls; the kernels below tile them into
VMEM blocks (MXU-aligned, or a whole shorter dimension) with an
accumulation grid.
The contraction dimension is the innermost grid axis, so each output
block stays resident in VMEM while partial products accumulate into it
(revisiting semantics), and HBM traffic is one pass over A_j.

A_j is read where it lies, never copied to the block grid: the grid is
``cdiv(n, bn) x cdiv(d_j, bd)``.  A dimension no longer than its block
takes the whole dimension as its block (legal at any size, and it never
overhangs); a longer one that is not a multiple of its block ends in a
block that overhangs the array.  Past the array's end a TPU reads
unspecified values (NaN among them, and NaN x 0 is NaN), so on the last
block along the contraction axis the overhang of the A tile is zeroed in
VMEM; every other block runs the unmasked body, and a shape its blocks
divide traces no mask at all.  Overhang along the other axis only feeds
output rows past n (or d_j), which are sliced off.  Only the small
vectors (w, r, h, masks) are padded to the grid.

XLA may keep A_j with its rows as the minor dimension (it does for
400,000 x 500 on a v5e, the layout with the least padding), while the
kernels read row-major tiles: it then inserts one copy of A_j into that
layout before the kernel.  A jitted loop that holds A_j fixed hoists the
copy out of the loop, so it is paid once a solve, not once a round.

Batched right-hand sides are supported (w: (d_j, B), r: (n, B)) because
DISCO-F's CG and the benchmark harness evaluate multiple vectors at once;
B=1 recovers the GEMV. The batch axis is tiled into BLOCK_B-wide VMEM
blocks of its own (a third grid axis), so a wide RHS panel (B > 128)
never forces the whole panel into one block.

``feature_hvp`` is the fused Hessian-vector-product data term: machine j
needs A_j^T (h ⊙ av) where h = l''(z) and av = Av are shared R^n vectors.
Fusing the Hadamard into the reduction pass keeps the scaled residual
block VMEM-resident instead of materializing h ⊙ av in HBM first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


# Block sizes: MXU-aligned. A-block of 512x512 f32 = 1 MiB in VMEM; with
# double buffering this uses ~2-3 MiB of the ~16 MiB/core budget.
BLOCK_N = 512
BLOCK_D = 512
BLOCK_B = 128

# Padding of the vectors to the block grid (A itself is read in place)
# runs under this ``jax.named_scope``, so its device ops carry it in
# their HLO ``op_name``.  Each kernel's ``pallas_call`` is named for its
# entry point: the name is the kernel's label in compiled programs and
# device traces.
PAD_SCOPE = "repro.pad"


def _matvec_kernel(a_ref, w_ref, o_ref, *, extent):
    """Grid (n_blocks, b_blocks, d_blocks): o[i,b] += A[i,j] @ w[j,b];
    the contraction axis j is innermost so o stays VMEM-resident."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def acc(a):
        o_ref[...] += _dot(a, w_ref[...], o_ref.dtype)

    _with_a_tile(a_ref, acc, extent=extent, dim=1)


def feature_matvec(A_j, w_j, *, block_n: int = BLOCK_N,
                   block_d: int = BLOCK_D, block_b: int = BLOCK_B,
                   interpret: bool | None = None):
    """z_j = A_j @ w_j.  A_j: (n, d_j); w_j: (d_j,) or (d_j, B)."""
    squeeze = w_j.ndim == 1
    if squeeze:
        w_j = w_j[:, None]
    n, dj = A_j.shape
    b = w_j.shape[1]
    bn, bd = min(block_n, n), min(block_d, dj)
    bb = min(block_b, _rup(b))
    w_p = _pad2(w_j, bd, bb)
    grid = (pl.cdiv(n, bn), w_p.shape[1] // bb, pl.cdiv(dj, bd))
    out = pl.pallas_call(
        functools.partial(_matvec_kernel, extent=dj),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, k, j: (i, j)),
            pl.BlockSpec((bd, bb), lambda i, k, j: (j, k)),
        ],
        out_specs=pl.BlockSpec((bn, bb), lambda i, k, j: (i, k)),
        out_shape=jax.ShapeDtypeStruct((_rup(n, bn), w_p.shape[1]),
                                       _acc_dtype(A_j.dtype)),
        interpret=_interp(interpret),
        name="feature_matvec",
    )(A_j, w_p)
    out = out[:n, :b].astype(A_j.dtype)
    return out[:, 0] if squeeze else out


def _rmatvec_kernel(a_ref, r_ref, o_ref, *, extent):
    """Grid (d_blocks, b_blocks, n_blocks): o[j,b] += A[i,j]^T @ r[i,b];
    the contraction axis i is innermost so o stays VMEM-resident."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def acc(a):
        o_ref[...] += _dot(a.T, r_ref[...], o_ref.dtype)

    _with_a_tile(a_ref, acc, extent=extent, dim=0)


def feature_rmatvec(A_j, r, *, block_n: int = BLOCK_N,
                    block_d: int = BLOCK_D, block_b: int = BLOCK_B,
                    interpret: bool | None = None):
    """g_j = A_j^T @ r.  A_j: (n, d_j); r: (n,) or (n, B)."""
    squeeze = r.ndim == 1
    if squeeze:
        r = r[:, None]
    n, dj = A_j.shape
    b = r.shape[1]
    bn, bd = min(block_n, n), min(block_d, dj)
    bb = min(block_b, _rup(b))
    r_p = _pad2(r, bn, bb)
    grid = (pl.cdiv(dj, bd), r_p.shape[1] // bb, pl.cdiv(n, bn))
    out = pl.pallas_call(
        functools.partial(_rmatvec_kernel, extent=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda j, k, i: (i, j)),
            pl.BlockSpec((bn, bb), lambda j, k, i: (i, k)),
        ],
        out_specs=pl.BlockSpec((bd, bb), lambda j, k, i: (j, k)),
        out_shape=jax.ShapeDtypeStruct((_rup(dj, bd), r_p.shape[1]),
                                       _acc_dtype(A_j.dtype)),
        interpret=_interp(interpret),
        name="feature_rmatvec",
    )(A_j, r_p)
    out = out[:dj, :b].astype(A_j.dtype)
    return out[:, 0] if squeeze else out


def _hvp_kernel(a_ref, h_ref, r_ref, o_ref, *, extent):
    """Grid (d_blocks, b_blocks, n_blocks): o[j,b] += A[i,j]^T (h[i] ⊙
    r[i,b]); the Hadamard happens on the VMEM-resident r block, so the
    scaled residual never round-trips through HBM."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def acc(a):
        o_ref[...] += _dot(a.T, h_ref[...] * r_ref[...], o_ref.dtype)

    _with_a_tile(a_ref, acc, extent=extent, dim=0)


def feature_hvp(A_j, h, av, *, block_n: int = BLOCK_N,
                block_d: int = BLOCK_D, block_b: int = BLOCK_B,
                interpret: bool | None = None):
    """u_j = A_j^T (h ⊙ av) — the HVP data term in one fused pass.

    A_j: (n, d_j); h: (n,) per-sample curvature l''(z); av: (n,) or
    (n, B) reduced Av right-hand side(s).
    """
    squeeze = av.ndim == 1
    if squeeze:
        av = av[:, None]
    n, dj = A_j.shape
    b = av.shape[1]
    bn, bd = min(block_n, n), min(block_d, dj)
    bb = min(block_b, _rup(b))
    h_p = _pad2(h[:, None], bn, 1)
    r_p = _pad2(av, bn, bb)
    grid = (pl.cdiv(dj, bd), r_p.shape[1] // bb, pl.cdiv(n, bn))
    out = pl.pallas_call(
        functools.partial(_hvp_kernel, extent=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda j, k, i: (i, j)),
            pl.BlockSpec((bn, 1), lambda j, k, i: (i, 0)),
            pl.BlockSpec((bn, bb), lambda j, k, i: (i, k)),
        ],
        out_specs=pl.BlockSpec((bd, bb), lambda j, k, i: (j, k)),
        out_shape=jax.ShapeDtypeStruct((_rup(dj, bd), r_p.shape[1]),
                                       _acc_dtype(A_j.dtype)),
        interpret=_interp(interpret),
        name="feature_hvp",
    )(A_j, h_p.astype(A_j.dtype), r_p)
    out = out[:dj, :b].astype(A_j.dtype)
    return out[:, 0] if squeeze else out


# ---- helpers ---------------------------------------------------------------

def _dot(a, b, out_dtype):
    """An MXU product exact to the operands' precision.  Mosaic's default
    takes f32 operands through one bf16 pass (relative error ~1e-3),
    enough to keep a certified solve from ever reaching eps = 1e-6; bf16
    operands are exact by default (and refuse HIGHEST)."""
    f32 = a.dtype == jnp.float32 and b.dtype == jnp.float32
    return jnp.dot(a, b, preferred_element_type=out_dtype,
                   precision=lax.Precision.HIGHEST if f32 else None)


def _with_a_tile(a_ref, use, *, extent: int, dim: int):
    """Call ``use(a)`` on this grid step's A tile.

    ``dim`` is the tile's contraction dimension, which is grid axis 2 in
    every composed kernel, and ``extent`` the array's size along it.
    Where the block does not divide the extent, the last block overhangs
    the array; on that block alone the tile is zeroed past the extent, so
    the overhang adds exact zeros.  Every other block, and every block of
    a shape its blocks divide, runs ``use(a_ref[...])`` unmasked."""
    block = a_ref.shape[dim]
    if extent % block == 0:
        use(a_ref[...])
        return
    blk = pl.program_id(2)
    last = blk == pl.num_programs(2) - 1

    @pl.when(last)
    def _edge():
        a = a_ref[...]
        idx = blk * block + lax.broadcasted_iota(jnp.int32, a.shape, dim)
        use(jnp.where(idx < extent, a, jnp.zeros_like(a)))

    @pl.when(jnp.logical_not(last))
    def _interior():
        use(a_ref[...])


def _rup(x: int, to: int = 128) -> int:
    return max(to, (x + to - 1) // to * to)


def _pad2(x, r0: int, r1: int):
    p0 = (-x.shape[0]) % r0
    p1 = (-x.shape[1]) % r1
    if p0 or p1:
        with jax.named_scope(PAD_SCOPE):
            x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def _acc_dtype(dt):
    return jnp.float32 if dt in (jnp.bfloat16, jnp.float16,
                                 jnp.dtype("bfloat16"),
                                 jnp.dtype("float16")) else dt


def _interp(flag):
    if flag is not None:
        return flag
    return jax.default_backend() != "tpu"
