"""Lossy channel transforms for worker->center messages.

The paper's lower bounds are stated in communication *rounds*; the
bit-complexity refinements (Arjevani & Shamir 2015; Ghadiri et al. 2024)
ask what each round *costs on the wire*.  This module models that axis:
a ``Channel`` is a transform applied to every vector payload a machine
uploads (the per-machine ``ReduceAll`` contribution, the per-machine
block of an all-to-all broadcast) plus the arithmetic for the bits that
payload occupies after the transform.  The communicators in
``core.comm`` apply the transform before reducing and record the wire
bits in the ``CommLedger``, so the certification harness can meter
bit budgets next to round counts.

Fixed channels:

  * ``identity``   — the exact f32 wire; 32 bits/element.  The default,
                     and the one every existing certification runs under:
                     with it the computation graph and the ledger's
                     legacy ``(kind, elems, bytes, tag)`` stream are
                     bit-identical to a channel-free build.  ``fp32`` is
                     an accepted alias (schedules read better with it).
  * ``fp16``/``bf16`` — deterministic nearest-even cast to half /
                     bfloat16 and back; 16 bits/element.
  * ``int8``       — per-message symmetric quantization to the int8 grid
                     with *stochastic rounding* (unbiased given uniform
                     rounding offsets); 8 bits/element + one f32 scale
                     per message.  The rounding offsets are derived from
                     an integer hash of the payload's own float bits, so
                     the transform is a pure traceable function (scan-
                     and ``vmap``-safe, no RNG key threading through the
                     round engine) while still varying per round as the
                     iterate moves.
  * ``topk``/``topk:<rho>`` — magnitude top-k sparsification keeping a
                     ``rho`` fraction of entries (default 0.1); each
                     survivor costs its f32 value plus a 32-bit index.

Adaptive channels (the bits-to-eps frontier axis):

  * ``sched:<ch>@<round>,...`` — precision as a pure function of the
    round index: ``sched:fp32@0,int8@5,topk:0.25@20`` sends exact f32
    for rounds 0-4, int8 for rounds 5-19, top-k from round 20 on.  The
    first stage must start at round 0 and starts must strictly increase.
    Because the stage is a function of the round index alone, the scan
    engines thread the index as scanned ``xs`` and the trace-once ledger
    replay re-prices each record from its ``round_marks`` offset — per
    round wire bits stay exact without re-tracing.  A one-entry schedule
    (``sched:int8@0``) is bit-identical to the fixed channel on every
    path (transform, pricing, graph).
  * ``gap:<ch0>,<ch>@<thr>,...`` — gap-adaptive *specification*:
    ``gap:int8,fp16@1e-3,identity@1e-5`` starts at int8 and refines to
    the next stage the round after the measured suboptimality gap
    crosses each (strictly decreasing) threshold.  A ``GapChannel`` is
    resolved — against an identity probe run's gap series — into a
    concrete ``ScheduledChannel`` before execution (``repro.api`` does
    this at plan time); communicators reject the unresolved spec.

Scalar reductions (``reduce_scalar``) bypass every channel: they carry
the model's control quantities (step sizes, CG inner products) whose
corruption would change *which algorithm runs*, not how much it pays —
exactly as bit-complexity treatments keep O(log) control bits exact.
That bypass is what makes the incremental family a bits hard instance:
its rounds are scalar-dominated, so no precision schedule can lower the
certified floor (see ``benchmarks/bits_frontier.py``).  Likewise the
center->worker return of a ReduceAll is exact; the metered payload is
the per-machine upload, matching the ledger's per-machine ``elems``
convention.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Optional, Sequence, Tuple, Union

import numpy as np

import jax.numpy as jnp
from jax import lax


# Canonical channel kinds; mirrored in repro.api._resolve (the single
# capability resolver) — tests/test_channel.py pins equality.
CHANNELS = ("identity", "fp16", "bf16", "int8", "topk", "sched", "gap")

DEFAULT_TOPK_RHO = 0.1
INDEX_BITS = 32     # per-survivor coordinate index on a top-k wire
SCALE_BITS = 32     # per-message f32 scale on the int8 wire


def _hash_uniform(x: jnp.ndarray) -> jnp.ndarray:
    """Per-element uniforms in [0, 1) from an integer hash of the f32
    payload bits (xorshift-multiply avalanche).  Deterministic and
    traceable — the stochastic-rounding offsets need no RNG key, so the
    transform composes with ``vmap``/``scan``/``eval_shape`` unchanged —
    yet decorrelated from the value's magnitude and fresh every round
    (the hash input is the moving iterate's own bits)."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    h = bits ^ jnp.uint32(0x9E3779B9)
    h = (h ^ (h >> 16)) * jnp.uint32(0x45D9F3B)
    h = (h ^ (h >> 16)) * jnp.uint32(0x45D9F3B)
    h = h ^ (h >> 16)
    # keep 24 bits so the uniform is exact in f32; below 2^24 the bits
    # read the same as int32, and Mosaic converts only signed ints
    top = lax.bitcast_convert_type(h >> 8, jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _fp16_round_trip(x: jnp.ndarray) -> jnp.ndarray:
    """``x.astype(float16).astype(float32)`` for f32 ``x`` in int32 ops.

    Mosaic cannot lower an f16 vector cast, so the whole-round kernel
    rounds to the half grid on the f32 bit pattern instead: nearest-even
    to 10 mantissa bits in the normal range (overflow to inf), nearest-
    even to multiples of 2^-24 below 2^-14 (half subnormals), sign kept
    (so -0 stays -0).  NaNs pass through as NaN.  Equal bit for bit to
    the cast everywhere else (``tests/test_channel.py`` pins it)."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    sign = bits & jnp.int32(-0x80000000)
    a = bits & jnp.int32(0x7FFFFFFF)
    exp = a >> 23
    # normal half: drop 13 mantissa bits, ties to even
    r = (a + jnp.int32(0x0FFF) + ((a >> 13) & 1)) & jnp.int32(0x7FFFE000)
    r = jnp.where(r >= jnp.int32(0x47800000), jnp.int32(0x7F800000), r)
    # subnormal half: q = round(|x| * 2^24), ties to even
    mant = (a & jnp.int32(0x7FFFFF)) | jnp.int32(0x800000)
    s = jnp.minimum(jnp.int32(126) - exp, jnp.int32(25))
    q = (mant + (jnp.int32(1) << (s - 1)) - 1 + ((mant >> s) & 1)) >> s
    sub = lax.bitcast_convert_type(
        q.astype(jnp.float32) * jnp.float32(2.0 ** -24), jnp.int32)
    out = jnp.where(exp >= 113, r, sub)
    out = jnp.where(a > jnp.int32(0x7F800000), a, out)       # NaN
    return lax.bitcast_convert_type(out | sign, jnp.float32).astype(x.dtype)


def stochastic_round(y: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """``floor(y + u)`` — unbiased for ``u ~ U[0, 1)``:
    ``E_u[floor(y + u)] = y`` exactly.  Split out so the unbiasedness
    property is testable with explicit uniforms (the channel feeds it
    hash-derived ones)."""
    return jnp.floor(y + u)


@dataclasses.dataclass(frozen=True)
class Channel:
    """One fixed wire model: a payload transform + its bit arithmetic.

    ``apply`` maps ONE message (a single machine's payload, any shape)
    to what the receiver decodes; callers ``vmap`` it over a stacked
    machine axis.  ``wire_bits`` prices one message of ``elems``
    elements at source ``itemsize`` bytes/element (``rnd`` is accepted
    and ignored so fixed and scheduled channels share one call shape).
    """

    name: str                   # canonical, e.g. "int8", "topk:0.25"
    kind: str                   # member of CHANNELS
    rho: float = 1.0            # topk keep fraction

    @property
    def lossless(self) -> bool:
        return self.kind == "identity"

    @property
    def scheduled(self) -> bool:
        return False

    def stage_at(self, rnd: int) -> "Channel":
        return self

    # ---- payload transform ----------------------------------------------
    def apply(self, x: jnp.ndarray, rnd=None) -> jnp.ndarray:
        if self.kind == "identity":
            return x
        if self.kind == "fp16":
            return _fp16_round_trip(x)
        if self.kind == "bf16":
            return x.astype(jnp.bfloat16).astype(x.dtype)
        if self.kind == "int8":
            return self._int8(x)
        return self._topk(x)

    def _int8(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = jnp.max(jnp.abs(x)) / jnp.asarray(127.0, x.dtype)
        safe = jnp.where(scale > 0, scale, jnp.ones_like(scale))
        q = stochastic_round(x / safe, _hash_uniform(x))
        q = jnp.clip(q, -127.0, 127.0)
        return jnp.where(scale > 0, q * safe, jnp.zeros_like(x))

    def _topk(self, x: jnp.ndarray) -> jnp.ndarray:
        flat = x.reshape(-1)
        k = self.topk_k(flat.shape[0])
        _, idx = lax.top_k(jnp.abs(flat), k)
        out = jnp.zeros_like(flat).at[idx].set(flat[idx])
        return out.reshape(x.shape)

    # ---- wire arithmetic -------------------------------------------------
    def topk_k(self, elems: int) -> int:
        return max(1, min(int(elems), math.ceil(self.rho * int(elems))))

    def wire_bits(self, elems: int, itemsize: int = 4, rnd=None) -> int:
        """Bits one message of ``elems`` source elements occupies on the
        wire under this channel."""
        elems = int(elems)
        if self.kind == "identity":
            return elems * itemsize * 8
        if self.kind in ("fp16", "bf16"):
            return elems * 16
        if self.kind == "int8":
            return elems * 8 + SCALE_BITS
        return self.topk_k(elems) * (itemsize * 8 + INDEX_BITS)


@dataclasses.dataclass(frozen=True)
class ScheduledChannel:
    """Round-indexed precision schedule: stage ``i`` (a fixed
    ``Channel``) is active for rounds ``starts[i] <= k < starts[i+1]``.

    The stage is a pure function of the round index, so the transform is
    traceable two ways: concrete round -> static dispatch to the active
    stage (python engine, capture-time); traced round -> one
    ``lax.switch`` over the stage table (scan engines thread the round
    index as scanned ``xs``).  Pricing is never traced: communicators
    stamp each record with its payload geometry and the ledger replay
    re-prices from the record's round offset, so per-round wire bits
    stay exact under trace-once scheduling.
    """

    name: str                                   # canonical "sched:..."
    stages: Tuple[Tuple[int, Channel], ...]     # ((start_round, stage), ...)
    kind: str = "sched"
    rho: float = 1.0

    @property
    def lossless(self) -> bool:
        # A schedule is invisible to the graph only if EVERY stage is.
        return all(st.lossless for _, st in self.stages)

    @property
    def scheduled(self) -> bool:
        # One-entry schedules take every fixed-channel fast path: no
        # round threading, no re-pricing — bit-identical to the constant
        # channel by construction (only the canonical name differs).
        return len(self.stages) > 1

    def stage_at(self, rnd: int) -> Channel:
        active = self.stages[0][1]
        for start, stage in self.stages:
            if int(rnd) >= start:
                active = stage
            else:
                break
        return active

    # ---- payload transform ----------------------------------------------
    def apply(self, x: jnp.ndarray, rnd=None) -> jnp.ndarray:
        if not self.scheduled:
            return self.stages[0][1].apply(x)
        if rnd is None:
            raise ValueError(f"channel {self.name!r} needs the round "
                             f"index to pick a stage; pass apply(x, rnd)")
        if isinstance(rnd, (int, np.integer)):
            return self.stage_at(int(rnd)).apply(x)
        # traced round index: one switch over the (static) stage table
        starts = jnp.asarray([s for s, _ in self.stages[1:]],
                             dtype=jnp.int32)
        idx = jnp.sum(jnp.asarray(rnd, jnp.int32) >= starts)
        branches = [lambda v, _st=stage: _st.apply(v)
                    for _, stage in self.stages]
        return lax.switch(idx, branches, x)

    # ---- wire arithmetic -------------------------------------------------
    def wire_bits(self, elems: int, itemsize: int = 4, rnd=None) -> int:
        """Bits one message of ``elems`` elements occupies at round
        ``rnd`` (round 0's stage when ``rnd`` is None — callers that
        price provisionally during tracing are re-priced at replay)."""
        return self.stage_at(0 if rnd is None else int(rnd)).wire_bits(
            elems, itemsize)


@dataclasses.dataclass(frozen=True)
class GapChannel:
    """Gap-adaptive channel *specification* — not yet a wire model.

    Stage 0 is threshold-free; stage ``i > 0`` activates the round after
    the measured suboptimality gap first drops to ``thresholds[i]``
    (strictly decreasing).  ``resolve(gaps)`` turns the spec into a
    concrete ``ScheduledChannel`` against a measured gap series (the
    plan layer runs an identity probe to get one); executing the
    unresolved spec is an error, which keeps the communicators and
    engines free of any data-dependent control flow.
    """

    name: str                                             # canonical "gap:..."
    stages: Tuple[Tuple[Optional[float], Channel], ...]   # ((thr, stage), ...)
    kind: str = "gap"
    rho: float = 1.0

    @property
    def lossless(self) -> bool:
        return all(st.lossless for _, st in self.stages)

    @property
    def scheduled(self) -> bool:
        return True

    def _unresolved(self):
        return ValueError(
            f"channel {self.name!r} is a gap-adaptive specification; "
            f"resolve it against a measured gap series first "
            f"(repro.api.plan does this via an identity probe run)")

    def apply(self, x, rnd=None):
        raise self._unresolved()

    def wire_bits(self, elems, itemsize=4, rnd=None):
        raise self._unresolved()

    def resolve(self, gaps: Sequence[float]) -> ScheduledChannel:
        """Pin stage switch rounds against a gap trajectory: stage ``i``
        starts the round AFTER the first round whose gap <= threshold
        (the controller reacts to what it has measured).  Unreached
        thresholds drop their stage; if two thresholds are crossed at
        the same round the finer (later) stage wins."""
        g = np.asarray(list(gaps), dtype=float)
        starts = [(0, self.stages[0][1])]
        for thr, stage in self.stages[1:]:
            hit = np.nonzero(g <= thr)[0]
            if hit.size == 0:
                continue
            start = int(hit[0]) + 1
            if start <= starts[-1][0]:
                starts[-1] = (starts[-1][0], stage)
            else:
                starts.append((start, stage))
        return make_schedule(starts)


def make_schedule(stages: Sequence[Tuple[int, Channel]]) -> ScheduledChannel:
    """Build a ``ScheduledChannel`` with its canonical name from
    ``(start_round, stage)`` pairs (starts strictly increasing from 0)."""
    stages = tuple((int(s), st) for s, st in stages)
    name = "sched:" + ",".join(f"{st.name}@{s}" for s, st in stages)
    return ScheduledChannel(name=name, stages=stages)


_IDENTITY = Channel(name="identity", kind="identity")

_TOPK_RE = re.compile(r"topk(?::([^,@]*))?\Z")

AnyChannel = Union[Channel, ScheduledChannel, GapChannel]


def _parse_fixed(name: str) -> Channel:
    """Parse one fixed (non-composite) channel name.  Errors name the
    offending token; composite parsers add the segment context."""
    if name in ("", "identity", "fp32"):
        # fp32 is an alias: schedules like "sched:fp32@0,int8@5" read as
        # the paper's "full precision early" — canonicalized to identity.
        return _IDENTITY
    if name == "fp16":
        return Channel(name="fp16", kind="fp16")
    if name == "bf16":
        return Channel(name="bf16", kind="bf16")
    if name == "int8":
        return Channel(name="int8", kind="int8")
    m = _TOPK_RE.match(name)
    if m:
        if m.group(1) is None:
            rho = DEFAULT_TOPK_RHO
        elif not m.group(1).strip():
            # "topk:" used to fall through to the generic unknown-channel
            # error, which named the whole token instead of the real
            # problem (an empty keep fraction after the colon).
            raise ValueError(
                f"empty topk keep fraction in {name!r}: write "
                f"'topk' for the default ({DEFAULT_TOPK_RHO:g}) or "
                f"'topk:<rho>' with 0 < rho <= 1")
        else:
            try:
                rho = float(m.group(1))
            except ValueError:
                raise ValueError(
                    f"bad topk keep fraction {m.group(1)!r} in "
                    f"{name!r}: not a number") from None
        if not 0.0 < rho <= 1.0:
            raise ValueError(f"topk keep fraction must be in (0, 1]; "
                             f"got {rho:g} in {name!r}")
        return Channel(name=f"topk:{rho:g}", kind="topk", rho=rho)
    hint = ""
    if "@" in name:
        # a bare "int8@5" is almost always a schedule stage that lost
        # its "sched:" prefix; the generic message sent users to the
        # fixed-channel list, which cannot explain the '@'.
        hint = (f"; a '<channel>@<round>' stage only makes sense inside "
                f"a schedule — did you mean 'sched:{name}'?")
    raise ValueError(f"unknown channel {name!r}; expected one of "
                     f"{CHANNELS} (topk also takes 'topk:<rho>'){hint}")


def _parse_sched(name: str) -> ScheduledChannel:
    body = name[len("sched:"):]
    if not body.strip():
        raise ValueError(f"channel {name!r}: empty schedule; expected "
                         f"'sched:<channel>@<start round>,...'")
    stages = []
    for seg in body.split(","):
        seg = seg.strip()
        if not seg:
            raise ValueError(f"channel {name!r}: empty segment "
                             f"(doubled or trailing comma)")
        ch_name, sep, start_s = seg.rpartition("@")
        if not sep:
            raise ValueError(
                f"channel {name!r}: bad segment {seg!r}: missing "
                f"'@<start round>' (every schedule stage needs one)")
        try:
            start = int(start_s)
        except ValueError:
            raise ValueError(
                f"channel {name!r}: bad segment {seg!r}: start round "
                f"{start_s!r} is not an integer") from None
        if start < 0:
            raise ValueError(f"channel {name!r}: bad segment {seg!r}: "
                             f"start round must be >= 0")
        if not ch_name.strip():
            raise ValueError(f"channel {name!r}: bad segment {seg!r}: "
                             f"missing channel name before '@'")
        try:
            stage = _parse_fixed(ch_name.strip())
        except ValueError as e:
            raise ValueError(
                f"channel {name!r}: bad segment {seg!r}: {e}") from None
        stages.append((start, stage))
    if stages[0][0] != 0:
        raise ValueError(f"channel {name!r}: first stage must start at "
                         f"round 0 (got @{stages[0][0]})")
    for (a, _), (b, _) in zip(stages, stages[1:]):
        if b <= a:
            raise ValueError(f"channel {name!r}: stage starts must be "
                             f"strictly increasing (got @{a} then @{b})")
    return make_schedule(stages)


def _parse_gap(name: str) -> GapChannel:
    body = name[len("gap:"):]
    segs = [s.strip() for s in body.split(",")] if body.strip() else []
    if len(segs) < 2:
        raise ValueError(
            f"channel {name!r}: a gap channel needs a starting stage "
            f"plus at least one '<channel>@<gap threshold>' refinement, "
            f"e.g. 'gap:int8,fp16@1e-3,identity@1e-5'")
    for seg in segs:
        if not seg:
            raise ValueError(f"channel {name!r}: empty segment "
                             f"(doubled or trailing comma)")
    if "@" in segs[0]:
        raise ValueError(
            f"channel {name!r}: bad segment {segs[0]!r}: the first "
            f"(coarsest) stage takes no threshold — it is active from "
            f"round 0")
    try:
        stages = [(None, _parse_fixed(segs[0]))]
    except ValueError as e:
        raise ValueError(
            f"channel {name!r}: bad segment {segs[0]!r}: {e}") from None
    for seg in segs[1:]:
        ch_name, sep, thr_s = seg.rpartition("@")
        if not sep:
            raise ValueError(
                f"channel {name!r}: bad segment {seg!r}: missing "
                f"'@<gap threshold>'")
        try:
            thr = float(thr_s)
        except ValueError:
            raise ValueError(
                f"channel {name!r}: bad segment {seg!r}: threshold "
                f"{thr_s!r} is not a number") from None
        if not (thr > 0 and math.isfinite(thr)):
            raise ValueError(f"channel {name!r}: bad segment {seg!r}: "
                             f"threshold must be finite and > 0")
        if not ch_name.strip():
            raise ValueError(f"channel {name!r}: bad segment {seg!r}: "
                             f"missing channel name before '@'")
        prev = stages[-1][0]
        if prev is not None and thr >= prev:
            raise ValueError(
                f"channel {name!r}: bad segment {seg!r}: thresholds "
                f"must strictly decrease (got {prev:g} then {thr:g})")
        try:
            stage = _parse_fixed(ch_name.strip())
        except ValueError as e:
            raise ValueError(
                f"channel {name!r}: bad segment {seg!r}: {e}") from None
        stages.append((thr, stage))
    canonical = "gap:" + stages[0][1].name + "".join(
        f",{st.name}@{thr:g}" for thr, st in stages[1:])
    return GapChannel(name=canonical, stages=tuple(stages))


def parse_channel(channel: Union[None, str, AnyChannel]) -> AnyChannel:
    """Resolve a channel *name* to a channel object.

    Accepts ``None`` (identity), a channel instance (passed through),
    the canonical fixed kinds (plus the ``fp32`` alias for identity and
    ``topk:<rho>`` with ``0 < rho <= 1``), round schedules
    (``sched:<ch>@<round>,...``) and gap-adaptive specs
    (``gap:<ch0>,<ch>@<thr>,...``).  Raises ``ValueError`` naming the
    offending segment on anything malformed — callers in ``repro.api``
    surface that as a plan-time error, and the ``REPRO_CHANNEL`` env
    path hits the same messages.
    """
    if channel is None:
        return _IDENTITY
    if isinstance(channel, (Channel, ScheduledChannel, GapChannel)):
        return channel
    # exact names only: "identity " is not a channel (the env var path
    # strips at its own boundary, api/_axes.py)
    name = str(channel)
    if name.startswith("sched:"):
        return _parse_sched(name)
    if name.startswith("gap:"):
        return _parse_gap(name)
    if name in ("sched", "gap"):
        raise ValueError(
            f"channel {name!r} needs stages: 'sched:<ch>@<round>,...' "
            f"or 'gap:<ch0>,<ch>@<thr>,...'")
    return _parse_fixed(name)
