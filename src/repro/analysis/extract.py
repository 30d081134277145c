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

from ..core.comm import parse_comm_scope
from ..core.engine import Capture, trace_segments

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
# Step tracing (plan audits and mutation fixtures)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class TracedStep(Capture):
    """One traced segment step (jaxpr, consts, captured schedule) and
    the program segments it drives."""

    segments: List[int] = dataclasses.field(default_factory=list)
    counts: List[int] = dataclasses.field(default_factory=list)


def trace_steps(dist, program) -> List[TracedStep]:
    """Trace every distinct segment step of a local ``RoundProgram``
    into (jaxpr, consts, captured schedule) through
    ``core.engine.trace_segments``, the trace the batch engine groups
    cells by, so mutation fixtures (raw ``dist`` + program, no
    ``ExecutionPlan``) audit exactly what the engines run."""
    by_cap: Dict[int, TracedStep] = {}
    for s, (seg, cap) in enumerate(zip(program.segments,
                                       trace_segments(dist, program))):
        ts = by_cap.get(id(cap))
        if ts is None:
            ts = by_cap[id(cap)] = TracedStep(cap.closed, cap.pure,
                                              *cap.schedule)
        ts.segments.append(s)
        ts.counts.append(int(seg.count))
    return list(by_cap.values())


__all__ = [
    "StaticMessage", "TracedStep", "comm_token", "extract_messages",
    "format_eqn", "iter_eqns", "trace_steps",
]
