"""Jaxpr walking and static message extraction.

The communicators wrap every wire message's graph ops in a
``jax.named_scope`` token encoding the ledger record they priced
(``core.comm.comm_scope_name``).  The token rides each traced equation's
``source_info.name_stack`` — through ``scan``, ``shard_map``, ``cond``
and friends — without perturbing the jaxpr text or the compiled
computation.  This module recovers the *static* message schedule from a
traced program: walk every equation (recursing into sub-jaxprs), group
the equations claimed by each comm token, and parse the token back into
a ``StaticMessage``.  ``repro.analysis.schedule`` then proves this
static schedule equal to the trace-once ``CommLedger`` capture and its
replay/expansion.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np
import jax.extend.core as jex
import jax.numpy as jnp

from ..core.comm import CommLedger, parse_comm_scope

# --------------------------------------------------------------------------
# Generic jaxpr traversal
# --------------------------------------------------------------------------


def _sub_jaxprs(eqn) -> Iterator[Tuple[str, Any]]:
    """(param path fragment, jaxpr) for every sub-jaxpr of an equation —
    ``scan``/``while``/``cond`` bodies, ``pjit``/``shard_map`` callees,
    custom-derivative wrappers."""
    for key, val in eqn.params.items():
        items = val if isinstance(val, (list, tuple)) else (val,)
        many = isinstance(val, (list, tuple))
        for j, item in enumerate(items):
            sub = None
            if isinstance(item, jex.ClosedJaxpr):
                sub = item.jaxpr
            elif isinstance(item, jex.Jaxpr):
                sub = item
            if sub is not None:
                yield (f"{key}[{j}]" if many else key), sub


def iter_eqns(jaxpr, path: str = "") -> Iterator[Tuple[Any, str]]:
    """Depth-first (eqn, path) over a jaxpr and all its sub-jaxprs."""
    for i, eqn in enumerate(jaxpr.eqns):
        p = f"{path}eqns[{i}]"
        yield eqn, p
        for frag, sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, f"{p}.{frag}.")


def comm_token(eqn) -> Optional[str]:
    """The comm scope token on an equation's name stack, or None.
    Messages never nest, so at most one token appears; the innermost
    wins if an exotic caller ever nests them."""
    stack = str(eqn.source_info.name_stack)
    tok = None
    for seg in stack.split("/"):
        if seg.startswith("comm["):
            tok = seg
    return tok


def format_eqn(eqn, width: int = 160) -> str:
    """A finding-sized rendering of one equation."""
    text = " ".join(str(eqn).split())
    return text if len(text) <= width else text[:width - 1] + "…"


# --------------------------------------------------------------------------
# Static messages
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StaticMessage:
    """One wire message recovered from the jaxpr alone."""

    idx: int                     # ledger record index at trace time
    rnd: int                     # round (step offset or absolute trace
                                 # round — see comm_scope_name)
    kind: str
    direction: str
    shape: Tuple[int, ...]
    dtype: str
    bits: int
    wire: Optional[Tuple[int, int]]
    tag: str
    path: str                    # first anchoring equation's path
    prims: Tuple[str, ...]       # primitive names inside the scope

    @property
    def itemsize(self) -> int:
        return int(np.dtype(self.dtype).itemsize)


def extract_messages(jaxpr) -> Tuple[List[StaticMessage], List[str]]:
    """All wire messages in a traced program, in record order, plus a
    list of problems (malformed tokens, duplicated record indices) the
    schedule verifier reports as ``sched-scope``/``sched-index``."""
    by_token: Dict[str, Dict[str, Any]] = {}
    problems: List[str] = []
    for eqn, path in iter_eqns(jaxpr):
        tok = comm_token(eqn)
        if tok is None:
            continue
        slot = by_token.get(tok)
        if slot is None:
            meta = parse_comm_scope(tok)
            if meta is None:
                problems.append(f"malformed comm scope token {tok!r} "
                                f"at {path}")
                by_token[tok] = {"meta": None}
                continue
            by_token[tok] = slot = {"meta": meta, "path": path,
                                    "prims": []}
        if slot["meta"] is None:
            continue
        slot["prims"].append(eqn.primitive.name)
    msgs: List[StaticMessage] = []
    seen_idx: Dict[int, str] = {}
    for tok, slot in by_token.items():
        meta = slot["meta"]
        if meta is None:
            continue
        idx = int(meta["idx"])
        if idx in seen_idx:
            problems.append(
                f"two comm scopes claim record index {idx}: "
                f"{seen_idx[idx]!r} and {tok!r} — mixed traces?")
            continue
        seen_idx[idx] = tok
        msgs.append(StaticMessage(
            idx=idx, rnd=int(meta["rnd"]), kind=str(meta["kind"]),
            direction=str(meta["direction"]),
            shape=tuple(meta["shape"]), dtype=str(meta["dtype"]),
            bits=int(meta["bits"]), wire=meta["wire"],
            tag=str(meta["tag"]), path=str(slot["path"]),
            prims=tuple(slot["prims"])))
    msgs.sort(key=lambda msg: msg.idx)
    return msgs, problems


# --------------------------------------------------------------------------
# Step tracing (shared by plan audits and mutation fixtures)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class TracedStep:
    """One traced segment step: the jaxpr, its hoisted consts, and the
    schedule the trace captured into the scratch ledger."""

    closed: Any                              # ClosedJaxpr
    consts: List[Any]
    structure: str
    records: List[Any]                       # captured CommRecords
    rounds_per_step: int
    marks: List[int]                         # record-stream round marks
    segments: List[int]                      # program segment indices
    counts: List[int]                        # scan length per segment


def trace_steps(dist, program) -> List[TracedStep]:
    """Trace every distinct segment step of a local ``RoundProgram``
    into (jaxpr, consts, captured schedule) — the same ``make_jaxpr``
    split ``repro.api.batch.prepare_cell`` performs, shared here so
    mutation fixtures (raw ``dist`` + program, no ``ExecutionPlan``)
    go through the identical trace path the batch engine uses."""
    from ..api.batch import _convert, _segment_xs

    scheduled = getattr(getattr(dist.comm, "channel", None),
                        "scheduled", False)
    real = dist.comm.ledger
    dist.comm.ledger = scratch = CommLedger()
    dist.comm._tracing = True
    out: List[TracedStep] = []
    try:
        carry = program.init
        by_step: Dict[tuple, TracedStep] = {}
        for s, seg in enumerate(program.segments):
            xs = _segment_xs(seg)
            key = (id(seg.step), xs.dtype.str, xs.shape[1:])
            if key not in by_step:
                n0, r0 = len(scratch.records), scratch.rounds
                m0 = len(scratch.round_marks)
                if scheduled:
                    def traced(c, rx, _step=seg.step):
                        rk, x = rx
                        dist.comm.begin_round(rk)
                        try:
                            return _step(dist, c, x)
                        finally:
                            dist.comm.reset_round()
                    conv = _convert(traced, carry,
                                    (jnp.int32(0), jnp.asarray(xs[0])))
                else:
                    conv = _convert(lambda c, x: seg.step(dist, c, x),
                                    carry, jnp.asarray(xs[0]))
                ts = TracedStep(
                    closed=conv.closed, consts=list(conv.consts),
                    structure=conv.structure,
                    records=list(scratch.records[n0:]),
                    rounds_per_step=scratch.rounds - r0,
                    marks=[m - n0 for m in scratch.round_marks[m0:]],
                    segments=[], counts=[])
                by_step[key] = ts
                out.append(ts)
            by_step[key].segments.append(s)
            by_step[key].counts.append(int(seg.count))
    finally:
        dist.comm.ledger = real
        dist.comm._tracing = False
    return out


__all__ = [
    "StaticMessage", "TracedStep", "comm_token", "extract_messages",
    "format_eqn", "iter_eqns", "trace_steps",
]
