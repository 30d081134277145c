"""Pallas TPU kernels for the feature-partitioned ERM hot loop.

Every algorithm in the paper's family F^{lam,L} spends its FLOPs in two
GEMVs per round on each machine:

    z_j = A_j w_j        (n x d_j) @ (d_j)   -> the ReduceAll summand
    g_j = A_j^T r        (d_j x n) @ (n)     -> the partial-gradient term

Each kernel has two bodies on one accumulation grid, chosen by the width
B of the right-hand side:

* B > 1 (DISCO-F's batched CG, the benchmark's panels): tall-skinny
  matmuls on the MXU.  A_j is tiled into row-major VMEM blocks
  (MXU-aligned, or a whole shorter dimension) and the right-hand side
  into BLOCK_B-wide panels of its own (a third grid axis), so a wide
  panel (B > 128) never forces the whole panel into one block.
* B = 1 (every round of every first-order solve): a GEMV on the VPU in
  f32.  An MXU product would pad the vector to a 128-lane panel and
  multiply it in six bf16 passes, which makes the kernel MXU-bound on
  127 lanes of zeros; the VPU needs two f32 operations per element of
  A_j and is bound by the one pass over A_j in HBM.  The body reads
  A_j^T, a (d_j, n) array with n along the lanes, in tiles of
  ``GEMV_TILE_BYTES``: z = A_j w sums ``At * w[:, None]`` over the
  sublanes into a lane-dense row of z, and A_j^T r sums
  ``At * r[None, :]`` over 128-lane groups and then across the lanes
  into a column of g.  Every R^n vector crosses HBM lane-dense, with no
  pad.  The call runs under the ``repro.gemv`` scope.  Its work grows
  with B (about 128 x the MXU body's time at B = 128), so each body wins
  at its own width.

The contraction dimension is the innermost grid axis, so each output
block stays resident in VMEM while partial products accumulate into it
(revisiting semantics), and HBM traffic is one pass over A_j.

A_j is read where it lies, never copied to the block grid.  A dimension
no longer than its block takes the whole dimension as its block (the
B = 1 body rounds it up to whole native tiles instead: 128 lanes, 8 f32
or 16 bf16 rows); a longer one that is not a multiple of its block ends
in a block that overhangs the array.  Past the array's end a TPU reads
unspecified values (NaN among them, and NaN x 0 is NaN), so on the last
block along the contraction axis the overhang is kept out of the sums:
the MXU body zeroes the A tile past the extent in VMEM, and the B = 1
body skips the whole row chunks or lane groups past it and masks the
one it cuts.  Every other block runs the unmasked body, and a shape its
blocks divide traces no mask at all.  Overhang along the other axis
only feeds outputs past n (or d_j), which are sliced off.  Only the MXU
body's vectors (w, r, h, masks) are padded to its grid.

XLA may keep A_j with its rows as the minor dimension (it does for
400,000 x 500 on a v5e, the layout with the least padding).  That is
A_j^T in row-major tiles, what the B = 1 body reads; the MXU body reads
row-major tiles of A_j, so XLA inserts one copy of A_j into that layout
before it.  A jitted loop that holds A_j fixed hoists such a copy out of
the loop, so it is paid once a solve, not once a round.

``feature_hvp`` is the fused Hessian-vector-product data term: machine j
needs A_j^T (h ⊙ av) where h = l''(z) and av = Av are shared R^n vectors.
Fusing the Hadamard into the reduction pass keeps the scaled residual
block VMEM-resident instead of materializing h ⊙ av in HBM first.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Block sizes: MXU-aligned. A-block of 512x512 f32 = 1 MiB in VMEM; with
# double buffering this uses ~2-3 MiB of the ~16 MiB/core budget.
BLOCK_N = 512
BLOCK_D = 512
BLOCK_B = 128

# The B = 1 body's A_j^T tile: BLOCK_D rows (or all of d_j) by as many
# 128-lane columns of n as fit this many bytes, so that a grid step's
# fixed cost stays a few percent of its tile's HBM read.  Two tiles are
# in flight, within the kernel's VMEM limit.
GEMV_TILE_BYTES = 8 * 1024 * 1024
GEMV_VMEM_BYTES = 4 * GEMV_TILE_BYTES

# Padding of the vectors to the block grid (A itself is read in place)
# runs under this ``jax.named_scope``, and so does the B = 1 body's
# kernel, so their device ops carry it in their HLO ``op_name``.  Each
# kernel's ``pallas_call`` is named for its entry point, whichever body
# it runs: the name is the kernel's label in compiled programs and
# device traces.
PAD_SCOPE = "repro.pad"
GEMV_SCOPE = "repro.gemv"


def _matvec_kernel(a_ref, w_ref, o_ref, *, extent, gemv):
    """Grid (n_blocks, b_blocks, d_blocks): o[i,b] += A[i,j] @ w[j,b];
    the contraction axis j is innermost so o stays VMEM-resident."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if gemv:
        _gemv_rows(a_ref, w_ref, o_ref, extent=extent)
        return

    def acc(a):
        o_ref[...] += _dot(a, w_ref[...], o_ref.dtype)

    _with_a_tile(a_ref, acc, extent=extent, dim=1)


def feature_matvec(A_j, w_j, *, block_n: int | None = None,
                   block_d: int = BLOCK_D, block_b: int = BLOCK_B,
                   interpret: bool | None = None):
    """z_j = A_j @ w_j.  A_j: (n, d_j); w_j: (d_j,) or (d_j, B)."""
    squeeze = w_j.ndim == 1
    if squeeze:
        w_j = w_j[:, None]
    n, dj = A_j.shape
    b = w_j.shape[1]
    t = _Tiles(A_j, b, "d", block_n, block_d, block_b)
    out = t.call(
        functools.partial(_matvec_kernel, extent=dj, gemv=t.gemv),
        [t.a(A_j), t.dvec(w_j)], t.nvec_spec(),
        t.nvec_shape(_acc_dtype(A_j.dtype)), "feature_matvec",
        interpret)
    out = (out[0, :n, None] if t.gemv else out[:n, :b]).astype(A_j.dtype)
    return out[:, 0] if squeeze else out


def _rmatvec_kernel(a_ref, r_ref, o_ref, *, extent, gemv):
    """Grid (d_blocks, b_blocks, n_blocks): o[j,b] += A[i,j]^T @ r[i,b];
    the contraction axis i is innermost so o stays VMEM-resident."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if gemv:
        _gemv_lanes(a_ref, lambda s: _f32(r_ref[:, s]), o_ref,
                    extent=extent)
        return

    def acc(a):
        o_ref[...] += _dot(a.T, r_ref[...], o_ref.dtype)

    _with_a_tile(a_ref, acc, extent=extent, dim=0)


def feature_rmatvec(A_j, r, *, block_n: int | None = None,
                    block_d: int = BLOCK_D, block_b: int = BLOCK_B,
                    interpret: bool | None = None):
    """g_j = A_j^T @ r.  A_j: (n, d_j); r: (n,) or (n, B)."""
    squeeze = r.ndim == 1
    if squeeze:
        r = r[:, None]
    n, dj = A_j.shape
    b = r.shape[1]
    t = _Tiles(A_j, b, "n", block_n, block_d, block_b)
    out = t.call(
        functools.partial(_rmatvec_kernel, extent=n, gemv=t.gemv),
        [t.a(A_j), t.nvec(r)], t.dvec_spec(),
        t.dvec_shape(_acc_dtype(A_j.dtype)), "feature_rmatvec",
        interpret)
    out = out[:dj, :b].astype(A_j.dtype)
    return out[:, 0] if squeeze else out


def _hvp_kernel(a_ref, h_ref, r_ref, o_ref, *, extent, gemv):
    """Grid (d_blocks, b_blocks, n_blocks): o[j,b] += A[i,j]^T (h[i] ⊙
    r[i,b]); the Hadamard happens on the VMEM-resident r block, so the
    scaled residual never round-trips through HBM."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if gemv:
        _gemv_lanes(a_ref, lambda s: _f32(h_ref[:, s]) * _f32(r_ref[:, s]),
                    o_ref, extent=extent)
        return

    def acc(a):
        o_ref[...] += _dot(a.T, h_ref[...] * r_ref[...], o_ref.dtype)

    _with_a_tile(a_ref, acc, extent=extent, dim=0)


def feature_hvp(A_j, h, av, *, block_n: int | None = None,
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
    t = _Tiles(A_j, b, "n", block_n, block_d, block_b)
    out = t.call(
        functools.partial(_hvp_kernel, extent=n, gemv=t.gemv),
        [t.a(A_j), t.nvec(h[:, None].astype(A_j.dtype), cols=1),
         t.nvec(av)], t.dvec_spec(),
        t.dvec_shape(_acc_dtype(A_j.dtype)), "feature_hvp", interpret)
    out = out[:dj, :b].astype(A_j.dtype)
    return out[:, 0] if squeeze else out


# ---- the two bodies' tiles -------------------------------------------------

class _Tiles:
    """The blocks, operands and grid of one composed kernel call.

    ``contract`` names A_j's contraction axis, ``"d"`` for z = A_j w and
    ``"n"`` for the A_j^T products; the grid is (other axis, right-hand
    side blocks, contraction axis).  At B = 1 (``gemv``) the operands
    take the VPU body's layout: A_j^T, R^n vectors as (1, n) rows and
    R^d vectors as (d_j, 1) columns, none of them padded; otherwise the
    MXU body's: A_j, and vectors padded to the block grid.  A
    ``block_n`` of None takes the body's own: BLOCK_N rows of A_j, or as
    many lanes of A_j^T as fit ``GEMV_TILE_BYTES``."""

    def __init__(self, A_j, b, contract, block_n, block_d, block_b):
        n, dj = A_j.shape
        self.gemv = b == 1
        self.contract = contract
        if self.gemv:
            self.bd = min(block_d, _rup(dj, _sublanes(A_j.dtype)))
            fit = GEMV_TILE_BYTES // (self.bd * A_j.dtype.itemsize)
            self.bn = min(block_n or max(128, fit // 128 * 128), _rup(n))
            self.bb = 1
        else:
            self.bd = min(block_d, dj)
            self.bn = min(block_n or BLOCK_N, n)
            self.bb = min(block_b, _rup(b))
        self.n_blocks = pl.cdiv(n, self.bn)
        self.d_blocks = pl.cdiv(dj, self.bd)
        self.b_blocks = pl.cdiv(b, self.bb)

    def _spec(self, block, index):
        """A BlockSpec whose ``index(n_blk, d_blk, b_blk)`` is read off
        this call's grid."""
        if self.contract == "n":
            return pl.BlockSpec(block, lambda j, k, i: index(i, j, k))
        return pl.BlockSpec(block, lambda i, k, j: index(i, j, k))

    def a(self, A_j):
        if self.gemv:
            return A_j.T, self._spec((self.bd, self.bn),
                                     lambda i, j, k: (j, i))
        return A_j, self._spec((self.bn, self.bd), lambda i, j, k: (i, j))

    def nvec(self, x, cols=None):
        """An R^n operand, (n, B) or, with ``cols=1``, a single (n, 1)
        vector shared by every right-hand side block."""
        if self.gemv:
            return x.T, self.nvec_spec()
        if cols:
            return _pad2(x, self.bn, 1), self._spec(
                (self.bn, 1), lambda i, j, k: (i, 0))
        return _pad2(x, self.bn, self.bb), self.nvec_spec()

    def dvec(self, x, cols=None):
        """An R^d_j operand, (d_j, B) or (d_j, 1) as ``nvec``."""
        if self.gemv:
            return x, self.dvec_spec()
        if cols:
            return _pad2(x, self.bd, 1), self._spec(
                (self.bd, 1), lambda i, j, k: (j, 0))
        return _pad2(x, self.bd, self.bb), self.dvec_spec()

    def nvec_spec(self):
        if self.gemv:
            return self._spec((1, self.bn), lambda i, j, k: (0, i))
        return self._spec((self.bn, self.bb), lambda i, j, k: (i, k))

    def dvec_spec(self):
        return self._spec((self.bd, self.bb), lambda i, j, k: (j, k))

    def nvec_shape(self, dtype):
        n_out, b_out = self.n_blocks * self.bn, self.b_blocks * self.bb
        shape = (1, n_out) if self.gemv else (n_out, b_out)
        return jax.ShapeDtypeStruct(shape, dtype)

    def dvec_shape(self, dtype):
        return jax.ShapeDtypeStruct(
            (self.d_blocks * self.bd, self.b_blocks * self.bb), dtype)

    def call(self, kernel, operands, out_spec, out_shape, name, interpret):
        other = self.n_blocks if self.contract == "d" else self.d_blocks
        inner = self.d_blocks if self.contract == "d" else self.n_blocks
        args, specs = zip(*operands)
        call = pl.pallas_call(
            kernel, grid=(other, self.b_blocks, inner),
            in_specs=list(specs), out_specs=out_spec, out_shape=out_shape,
            interpret=_interp(interpret), name=name,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=GEMV_VMEM_BYTES) if self.gemv else None)
        with (jax.named_scope(GEMV_SCOPE) if self.gemv
              else contextlib.nullcontext()):
            return call(*args)


# ---- the B = 1 body --------------------------------------------------------

def _gemv_rows(a_ref, w_ref, o_ref, *, extent: int):
    """o (1, bn) += sum over the tile's rows k of At[k, :] * w[k] in f32:
    the z = A_j w side.  ``extent`` is d_j; on an overhanging last block
    only its rows inside d_j count (the last partial chunk is masked,
    since A and w may both be NaN past the end).  Chunks of one native
    tile of rows accumulate elementwise, and the sublanes are summed once,
    at the end."""
    sub = _sublanes(a_ref.dtype)

    def rows_dot(rows):
        def part(start):
            return (_f32(a_ref[pl.ds(start, sub), :])
                    * _f32(w_ref[pl.ds(start, sub), :]))

        full, tail = divmod(rows, sub)
        acc = lax.fori_loop(
            0, full, lambda c, acc: acc + part(pl.multiple_of(c * sub, sub)),
            jnp.zeros((sub, a_ref.shape[1]), jnp.float32))
        if tail:
            t = part(full * sub)
            row = lax.broadcasted_iota(jnp.int32, t.shape, 0)
            acc = acc + jnp.where(row < tail, t, 0.0)
        o_ref[...] += jnp.sum(acc, axis=0, keepdims=True)

    _with_extent(a_ref.shape[0], extent, rows_dot)


def _gemv_lanes(a_ref, vec, o_ref, *, extent: int):
    """o (bd, 1) += At @ v over the tile's lanes in f32: the A_j^T side.
    ``vec(s)`` loads v's lanes ``s`` as an f32 (1, 128) row; ``extent`` is
    n, and on an overhanging last block only the lanes inside n count
    (the partial 128-lane group is masked, since A and v may both be NaN
    past the end).  Each row chunk sums its 128-lane groups elementwise
    in a pairwise tree, then across the lanes."""
    sub = _sublanes(a_ref.dtype)

    def lanes_dot(lanes):
        def rows_sum(start):
            terms = []
            for lo in range(0, lanes, 128):
                s = pl.ds(lo, 128)
                t = _f32(a_ref[pl.ds(start, sub), s]) * vec(s)
                if lanes - lo < 128:
                    lane = lax.broadcasted_iota(jnp.int32, t.shape, 1)
                    t = jnp.where(lane < lanes - lo, t, 0.0)
                terms.append(t)
            while len(terms) > 1:
                terms = [terms[k] + terms[k + 1] if k + 1 < len(terms)
                         else terms[k] for k in range(0, len(terms), 2)]
            return jnp.sum(terms[0], axis=1, keepdims=True)

        def chunk(c, carry):
            start = pl.multiple_of(c * sub, sub)
            o_ref[pl.ds(start, sub), :] += rows_sum(start)
            return carry

        lax.fori_loop(0, a_ref.shape[0] // sub, chunk, 0)

    _with_extent(a_ref.shape[1], extent, lanes_dot)


def _with_extent(block: int, extent: int, use):
    """Call ``use(valid)`` with how much of this grid step's contraction
    block lies inside the array: all of it, except on an overhanging
    last block (grid axis 2), where the count is static."""
    if extent % block == 0:
        use(block)
        return
    last = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(last)
    def _edge():
        use(extent % block)

    @pl.when(jnp.logical_not(last))
    def _interior():
        use(block)


def _sublanes(dtype) -> int:
    """Rows of one native (sublane x 128) tile: 8 for f32, 16 for bf16."""
    return max(8, 32 // jnp.dtype(dtype).itemsize)


def _f32(x):
    return x.astype(jnp.float32)


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
    def tile(valid):
        a = a_ref[...]
        if valid < a.shape[dim]:
            idx = lax.broadcasted_iota(jnp.int32, a.shape, dim)
            a = jnp.where(idx < valid, a, jnp.zeros_like(a))
        use(a)

    _with_extent(a_ref.shape[dim], extent, tile)


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
