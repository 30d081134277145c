"""Fused whole-round Pallas kernels with in-kernel wire channels.

``feature_matvec``/``feature_rmatvec``/``feature_hvp`` already fuse one
GEMV each; every algorithm in the paper's family F^{lam,L} still
composes its round from two of them plus jnp epilogues, so machine j's
A_j block crosses HBM twice per round — and a lossy wire channel
(``core.channel``) costs a third pass over the upload vector.  The
kernels here collapse all of that:

* ``make_round_step`` builds ONE kernel per round-step, grid over the
  machine axis, with machine j's whole padded A_j block VMEM-resident:

      lg   = l'(z, y)                       (in-kernel curvature term)
      g    = (A_j^T lg) / n + lam y_j       (masked partial gradient)
      x,y  = update(x_j, y_j, g, coeff)     (the algorithm's block-local
                                             update, traced into the body)
      zloc = A_j y_new                      (next round's response summand)
      out  = channel_stage(rnd + 1)(zloc)   (the UPLOAD, already on-wire)

  so A_j is read from HBM exactly once per round-step and the channel
  transform (fp16/bf16/int8 stochastic rounding with the hash-derived
  offsets of ``core.channel``) happens in the same pass that emits the
  upload vector.  The communicator reduces it with
  ``reduce_all(..., pretransformed=True)`` — record metadata, wire
  pricing and fault injection are byte-identical to the composed path.

* ``fused_pgrad``/``fused_phvp`` are the composed-oracle fallbacks for
  round shapes the whole-round kernel cannot rotate (DISCO-F's CG
  interleaves scalar reduces between the HVP and the next matvec, so a
  one-A-read round is impossible there): the same accumulation grid as
  ``feature_rmatvec``/``feature_hvp`` with the gradient epilogue
  (``/n + lam v``, block mask) folded into the last contraction block —
  one A-read per oracle instead of an extra d-vector HBM round-trip.
  Like those, they read A_j in place, and take the VPU body at one
  right-hand side and the MXU body at several (``feature_matvec``'s
  module docstring).

Conformance contract: wherever ``round_step_fits`` and
``channel_stages`` admit a cell, the fused step's ledger stream and
round marks are identical to the composed ``kernel`` backend's, and its
iterates and uploads agree with them to f32 rounding.  The ledger holds
by construction (metadata-only records, identical tags, shapes and
pricing).  The iterates are not bit-identical because the whole-block
dots and the composed kernels' tilings are different programs whose
sums may round differently in the last ulp; the epilogue and update
arithmetic runs in the same f32 op order as the composed jnp epilogues,
and ``Channel.apply`` runs verbatim inside the kernel body — elementwise
transforms do not care that the payload is the padded (n_pad, 1) column
(int8's per-message max is unchanged by |0| padding; pad lanes are
sliced off before the wire).  ``tests/test_ledger_invariance.py`` and
``tests/test_kernel_properties.py`` pin all of this, and
``tests/test_tpu_compile.py`` compiles the kernel for a TPU v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .feature_matvec import (BLOCK_B, BLOCK_D, BLOCK_N, _acc_dtype,
                             _dot, _f32, _gemv_lanes, _interp, _pad2, _rup,
                             _Tiles, _with_a_tile)
from ..core.channel import Channel, ScheduledChannel

# The whole-round kernel keeps machine j's entire padded A_j block in
# one VMEM tile, so it only engages when that tile is a single
# MXU-aligned block.
ROUND_STEP_MAX_N = BLOCK_N
ROUND_STEP_MAX_D = BLOCK_D

# The whole-round kernel's name in compiled programs and device traces.
KERNEL_NAME = "fused_round_step"

# VMEM budget for one grid step (A block + vectors, double-buffered).
# ~16 MiB/core on current TPUs; stay at half to leave room for the
# scratch the compiler adds.
ROUND_STEP_VMEM_BYTES = 8 * 1024 * 1024

# Channel stages the kernel can reproduce bit-identically in-body:
# everything elementwise (plus int8's per-message max).  fp16 qualifies
# because ``Channel.apply`` rounds to the half grid with int32 ops
# (Mosaic has no f16 vector cast).  topk needs lax.top_k over the full
# message — not a Mosaic-friendly shape — so topk cells fall back to the
# composed path.
IN_KERNEL_STAGES = ("identity", "fp16", "bf16", "int8")


def channel_stages(channel):
    """The fixed stages an in-kernel wire must reproduce, or ``None``
    when any stage needs ops outside the kernel's reach."""
    if isinstance(channel, ScheduledChannel):
        stages = tuple(channel.stages)
    elif isinstance(channel, Channel):
        stages = ((0, channel),)
    else:
        return None     # unresolved gap spec, or not a channel at all
    if all(st.kind in IN_KERNEL_STAGES for _, st in stages):
        return stages
    return None


def round_step_fits(n: int, d_max: int, itemsize: int = 4) -> bool:
    """Whole-A_j-resident is only sound when the padded block is a
    single MXU tile inside the VMEM budget."""
    n_pad, d_pad = _rup(n), _rup(d_max)
    if n_pad > ROUND_STEP_MAX_N or d_pad > ROUND_STEP_MAX_D:
        return False
    vecs = 4 * d_pad + 4 * n_pad           # x/y/mask/g + z/y_data/zloc/nmask
    return 2 * (n_pad * d_pad + vecs) * itemsize <= ROUND_STEP_VMEM_BYTES


def _apply_stage(stages, x, rnd):
    """The channel transform at round ``rnd`` inside a kernel body.

    Single stage: static dispatch.  Multi-stage schedule: a where-select
    over the (static) stage table — every stage's transform is computed
    on the VMEM-resident block and the active one selected lane-wise,
    which is bit-identical to ``ScheduledChannel.apply``'s ``lax.switch``
    without asking Mosaic for multi-branch control flow."""
    if len(stages) == 1:
        return stages[0][1].apply(x)
    rnd = jnp.asarray(rnd, jnp.int32)
    starts = jnp.asarray([s for s, _ in stages[1:]], dtype=jnp.int32)
    idx = jnp.sum(rnd >= starts)
    out = stages[0][1].apply(x)
    for i, (_, stage) in enumerate(stages[1:], start=1):
        out = jnp.where(idx == i, stage.apply(x), out)
    return out


def make_round_step(A_stk, mask, y_data, loss, *, n: int, lam: float,
                    update, channel, interpret: bool | None = None):
    """Build the fused whole-round step for one ``LocalDistERM`` cell.

    A_stk: (m, n, d_max) stacked feature blocks; mask: (m, d_max) valid-
    coordinate mask; y_data: (n,) labels; ``update(x, y, g, coeff) ->
    (x_new, y_new)`` is the algorithm's block-local update (elementwise,
    traced into the kernel body); ``channel`` the communicator's wire
    channel (must pass ``channel_stages``).

    Returns ``step(z, x_stk, y_stk, coeff, rnd) -> (x_new, y_new,
    zloc_next)`` where ``z`` is this round's reduced response, carries
    are (m, d_max), ``rnd`` is the current round index (concrete or
    traced) and ``zloc_next`` (m, n) is next round's per-machine upload
    with the round-``rnd+1`` channel stage already applied.
    """
    stages = channel_stages(channel)
    if stages is None:
        raise ValueError(f"channel {getattr(channel, 'name', channel)!r} "
                         f"has no in-kernel stage set")
    m, n_rows, d_max = A_stk.shape
    assert n_rows == n
    n_pad, d_pad = _rup(n), _rup(d_max)
    A_p = jnp.pad(jnp.asarray(A_stk, jnp.float32),
                  ((0, 0), (0, n_pad - n), (0, d_pad - d_max)))
    mask_p = jnp.pad(jnp.asarray(mask, jnp.float32),
                     ((0, 0), (0, d_pad - d_max)))[:, None, :]
    yd_p = jnp.pad(jnp.asarray(y_data, jnp.float32)[:, None],
                   ((0, n_pad - n), (0, 0)))
    # pad rows contribute nothing to the dots (A pad rows are zero), but
    # a custom loss could emit non-finite l'(0, 0); mask them to keep
    # 0 * lg finite.
    nmask = jnp.pad(jnp.ones((n, 1), jnp.float32),
                    ((0, n_pad - n), (0, 0)))

    def _round_math(a, z, yd, nm, x, y, mk, coeff, rnd):
        lg = loss.grad(z, yd) * nm
        g = _dot(a.T, lg, jnp.float32).T / n
        g = (g + lam * y) * mk
        x_new, y_new = update(x, y, g, coeff)
        zloc = _dot(a, y_new.T, jnp.float32)
        zloc = _apply_stage(stages, zloc, rnd + 1)
        return x_new, y_new, zloc.T

    # Algorithm updates close over jnp scalars (step sizes, momentum
    # coefficients — f32-wrapped exactly so execute_batch can hoist
    # them), and the stage table materializes small index arrays.  A
    # Pallas body cannot capture such constants, so trace the round
    # math once, hoist the jaxpr's consts, and feed each back in as an
    # extra kernel operand (reshaped to a (1, size) VMEM row).  The
    # body replays the jaxpr verbatim — same ops, same order.
    z = jnp.zeros
    closed = jax.make_jaxpr(_round_math)(
        z((n_pad, d_pad), jnp.float32),
        z((n_pad, 1), jnp.float32), z((n_pad, 1), jnp.float32),
        z((n_pad, 1), jnp.float32), z((1, d_pad), jnp.float32),
        z((1, d_pad), jnp.float32), z((1, d_pad), jnp.float32),
        jnp.float32(0.0), jnp.int32(0))
    consts = [jnp.asarray(c) for c in closed.consts]
    const_rows = [c.reshape(1, -1) for c in consts]
    n_fixed = 9

    n_args = len(closed.jaxpr.invars)

    def math_fn(*args):            # (*round_args, *consts) -> 3 arrays
        return jax.core.eval_jaxpr(closed.jaxpr, args[n_args:],
                                   *args[:n_args])

    def body(*refs):
        (a_ref, z_ref, yd_ref, nm_ref, x_ref, y_ref, mk_ref,
         cf_ref, rn_ref) = refs[:n_fixed]
        c_refs = refs[n_fixed:n_fixed + len(consts)]
        xo_ref, yo_ref, zo_ref = refs[n_fixed + len(consts):]
        cvals = [cr[0, 0] if c.ndim == 0 else cr[...].reshape(c.shape)
                 for cr, c in zip(c_refs, consts)]
        x_new, y_new, zloc_t = math_fn(
            a_ref[...], z_ref[...], yd_ref[...], nm_ref[...],
            x_ref[...], y_ref[...], mk_ref[...],
            cf_ref[0, 0], rn_ref[0, 0], *cvals)
        xo_ref[...] = x_new
        yo_ref[...] = y_new
        zo_ref[...] = zloc_t

    # Per-machine rows travel as (m, 1, width) with the machine axis
    # squeezed out of the block, so each block's last two dims equal the
    # array's — the only way Mosaic accepts a one-row block.
    def row(width):
        return pl.BlockSpec((pl.Squeezed(), 1, width), lambda j: (j, 0, 0))

    def shared(shape):
        return pl.BlockSpec(shape, lambda j: (0,) * len(shape))

    call = pl.pallas_call(
        body,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((pl.Squeezed(), n_pad, d_pad), lambda j: (j, 0, 0)),
            shared((n_pad, 1)), shared((n_pad, 1)), shared((n_pad, 1)),
            row(d_pad), row(d_pad), row(d_pad),
            shared((1, 1)), shared((1, 1)),
        ] + [shared(c.shape) for c in const_rows],
        out_specs=[row(d_pad), row(d_pad), row(n_pad)],
        out_shape=[
            jax.ShapeDtypeStruct((m, 1, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((m, 1, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((m, 1, n_pad), jnp.float32),
        ],
        interpret=_interp(interpret),
        name=KERNEL_NAME,
    )

    # The cell's data (A_p, labels, masks, hoisted algorithm consts)
    # enters the jitted step as ARGUMENTS, not closure captures: under
    # an outer trace (``api.batch``'s ``make_jaxpr`` split) argument
    # values surface as outer-jaxpr consts that execute_batch stacks
    # per cell, while captures would be baked inside the pjit equation
    # and every grouped cell would silently replay the first cell's
    # data.
    @jax.jit
    def _step(A_p, yd_p, nmask, mask_p, crows, z, x_stk, y_stk, coeff,
              rnd):
        z_col = jnp.asarray(z, jnp.float32)[:, None]
        z_p = jnp.pad(z_col, ((0, n_pad - n), (0, 0)))
        x_p = _pad2(jnp.asarray(x_stk, jnp.float32), 1, d_pad)[:, None, :]
        y_p = _pad2(jnp.asarray(y_stk, jnp.float32), 1, d_pad)[:, None, :]
        cf = jnp.asarray(coeff, jnp.float32).reshape(1, 1)
        rn = jnp.asarray(rnd, jnp.int32).reshape(1, 1)
        x_new, y_new, zloc = call(A_p, z_p, yd_p, nmask, x_p, y_p,
                                  mask_p, cf, rn, *crows)
        return (x_new[:, 0, :d_max], y_new[:, 0, :d_max], zloc[:, 0, :n])

    def step(z, x_stk, y_stk, coeff, rnd):
        return _step(A_p, yd_p, nmask, mask_p, tuple(const_rows),
                     z, x_stk, y_stk, coeff, rnd)

    return step


# --------------------------------------------------------------------------
# Epilogue-fused composed oracles (the fallback / DISCO-F CG variant)
# --------------------------------------------------------------------------

def _pgrad_kernel(a_ref, r_ref, w_ref, mk_ref, o_ref, *, n, lam, extent,
                  gemv):
    """Grid (d_blocks, b_blocks, n_blocks): o[j,b] += A[i,j]^T @ r[i,b]
    with the gradient epilogue (o/n + lam w) * mask folded into the last
    contraction block, so the partial gradient never round-trips HBM
    between the reduction and its scaling."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if gemv:
        _gemv_lanes(a_ref, lambda s: _f32(r_ref[:, s]), o_ref,
                    extent=extent)
    else:
        def acc(a):
            o_ref[...] += _dot(a.T, r_ref[...], o_ref.dtype)

        _with_a_tile(a_ref, acc, extent=extent, dim=0)

    @pl.when(i == pl.num_programs(2) - 1)
    def _epilogue():
        o_ref[...] = (o_ref[...] / n + lam * w_ref[...]) * mk_ref[...]


def fused_pgrad(A_j, r, w_j, mask_j, *, n: int, lam: float,
                block_n: int | None = None, block_d: int = BLOCK_D,
                block_b: int = BLOCK_B, interpret: bool | None = None):
    """g_j = (A_j^T r / n + lam w_j) * mask_j in one accumulation pass.

    A_j: (n_rows, d_j); r: (n_rows,) or (n_rows, B); w_j like the
    output; mask_j: (d_j,).  ``n`` is the divisor (the global sample
    count — it need not equal ``n_rows``).
    """
    squeeze = r.ndim == 1
    if squeeze:
        r = r[:, None]
        w_j = w_j[:, None]
    n_rows, dj = A_j.shape
    b = r.shape[1]
    t = _Tiles(A_j, b, "n", block_n, block_d, block_b)
    out = t.call(
        functools.partial(_pgrad_kernel, n=n, lam=lam, extent=n_rows,
                          gemv=t.gemv),
        [t.a(A_j), t.nvec(r), t.dvec(w_j.astype(A_j.dtype)),
         t.dvec(mask_j[:, None].astype(A_j.dtype), cols=1)],
        t.dvec_spec(), t.dvec_shape(_acc_dtype(A_j.dtype)), "fused_pgrad",
        interpret)
    out = out[:dj, :b].astype(A_j.dtype)
    return out[:, 0] if squeeze else out


def _phvp_kernel(a_ref, h_ref, r_ref, v_ref, mk_ref, o_ref, *, n, lam,
                 extent, gemv):
    """Grid (d_blocks, b_blocks, n_blocks): o[j,b] += A[i,j]^T (h[i] ⊙
    r[i,b]) with the HVP epilogue (o/n + lam v) * mask folded into the
    last contraction block — DISCO-F's CG applies this every inner
    iteration, so the saved d-vector round-trip compounds."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if gemv:
        _gemv_lanes(a_ref, lambda s: _f32(h_ref[:, s]) * _f32(r_ref[:, s]),
                    o_ref, extent=extent)
    else:
        def acc(a):
            o_ref[...] += _dot(a.T, h_ref[...] * r_ref[...], o_ref.dtype)

        _with_a_tile(a_ref, acc, extent=extent, dim=0)

    @pl.when(i == pl.num_programs(2) - 1)
    def _epilogue():
        o_ref[...] = (o_ref[...] / n + lam * v_ref[...]) * mk_ref[...]


def fused_phvp(A_j, h, av, v_j, mask_j, *, n: int, lam: float,
               block_n: int | None = None, block_d: int = BLOCK_D,
               block_b: int = BLOCK_B, interpret: bool | None = None):
    """u_j = (A_j^T (h ⊙ av) / n + lam v_j) * mask_j in one fused pass.

    A_j: (n_rows, d_j); h: (n_rows,); av: (n_rows,) or (n_rows, B);
    v_j like the output; mask_j: (d_j,).
    """
    squeeze = av.ndim == 1
    if squeeze:
        av = av[:, None]
        v_j = v_j[:, None]
    n_rows, dj = A_j.shape
    b = av.shape[1]
    t = _Tiles(A_j, b, "n", block_n, block_d, block_b)
    out = t.call(
        functools.partial(_phvp_kernel, n=n, lam=lam, extent=n_rows,
                          gemv=t.gemv),
        [t.a(A_j), t.nvec(h[:, None].astype(A_j.dtype), cols=1),
         t.nvec(av), t.dvec(v_j.astype(A_j.dtype)),
         t.dvec(mask_j[:, None].astype(A_j.dtype), cols=1)],
        t.dvec_spec(), t.dvec_shape(_acc_dtype(A_j.dtype)), "fused_phvp",
        interpret)
    out = out[:dj, :b].astype(A_j.dtype)
    return out[:, 0] if squeeze else out
