"""Schedule conformance: the jaxpr's messages == the ledger's messages.

Three layers, each proved exactly (no tolerances — a wire schedule is
discrete data):

1. **Step conformance** — for every traced segment step, the messages
   recovered from the jaxpr (``extract.extract_messages``) must match
   the records the trace captured into the scratch ledger one-for-one:
   count, order, kind, tag, shape, dtype, wire arithmetic, provisional
   bits, and round offset within the step.  Each message must also
   anchor to a real reduction/collective equation — a ledger record with
   no graph ops behind it is phantom traffic.
2. **Replay conformance** — the static schedule, expanded over the
   program's segments (repeating each step ``count`` times, advancing
   round indices, re-pricing scheduled channels per round), must equal
   the trace-once ``CommLedger.replay_schedule`` stream record-for-
   record, round-mark-for-round-mark.  This is the replay every scan
   engine and ``execute_batch`` group uses — so proving it against the
   jaxpr proves the meter for every compiled run.
3. **Dynamic conformance** (optional, ``execute=True``) — the same
   static expansion must equal the ledger of an actually executed run
   (the eager python engine for local plans — a fully independent
   meter — and the expanded ``shard_map`` driver ledger for sharded
   plans).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..core.comm import CommLedger, sanitize_scope_tag
from ..core.runtime import ScanSpan
from .extract import StaticMessage, TracedStep, extract_messages
from .findings import Finding

# comparison key: one tuple per message/record; `rnd` appended by the
# expansion layers
_FIELDS = ("kind", "tag", "shape", "dtype", "bits", "wire", "direction")


def _rec_key(rec) -> tuple:
    return (rec.kind, sanitize_scope_tag(rec.tag), tuple(rec.shape),
            rec.dtype, int(rec.bits),
            tuple(rec.wire) if rec.wire is not None else None,
            rec.direction)


def _msg_key(msg: StaticMessage) -> tuple:
    return (msg.kind, msg.tag, msg.shape, msg.dtype, int(msg.bits),
            msg.wire, msg.direction)


def round_offsets(n_records: int, marks: Sequence[int]) -> List[int]:
    """Round index of each record position, from the round-boundary
    marks (marks[k] == record position right after round k+1 ended, so
    record i belongs to round ``#{m : m <= i}``)."""
    out = []
    j = 0
    ms = sorted(marks)
    for i in range(n_records):
        while j < len(ms) and ms[j] <= i:
            j += 1
        out.append(j)
    return out


_LOCAL_ANCHORS = {"reduce_all": ("reduce_sum", "add_any"),
                  "all_to_all_broadcast": ()}
_SHARDED_ANCHORS = {"reduce_all": ("psum",),
                    "all_to_all_broadcast": ("all_gather",)}


def _check_messages(msgs: List[StaticMessage], problems: List[str],
                    records: Sequence[Any], marks: Sequence[int],
                    *, placement: str, where: str,
                    rel_round_base: Optional[int] = None,
                    span_starts: Optional[Dict[int, int]] = None,
                    ) -> List[Finding]:
    """Static messages vs captured records, one-for-one.

    ``rel_round_base``: when set, message round fields are step-relative
    offsets rebased so the first record sits at round
    ``rel_round_base`` (local step traces).  ``span_starts`` (sharded
    scheduled traces): maps a record index to the index of the first
    record of its scan span — messages inside a span carry rounds
    relative to the span's start round.
    """
    fs: List[Finding] = []
    for p in problems:
        fs.append(Finding("sched-scope", "error", f"{where}: {p}"))
    if len(msgs) != len(records):
        fs.append(Finding(
            "sched-count", "error",
            f"{where}: the jaxpr carries {len(msgs)} wire message(s) "
            f"but the captured schedule has {len(records)} record(s)"))
        return fs
    if not msgs:
        return fs
    base_idx = msgs[0].idx
    offs = round_offsets(len(records), marks)
    anchors = _SHARDED_ANCHORS if placement == "sharded" \
        else _LOCAL_ANCHORS
    for i, (msg, rec) in enumerate(zip(msgs, records)):
        if msg.idx != base_idx + i:
            fs.append(Finding(
                "sched-index", "error",
                f"{where}: message record indices are not contiguous "
                f"(expected {base_idx + i}, found {msg.idx}) — a record "
                f"was captured without a traced message or vice versa",
                path=msg.path))
            return fs
        mk, rk = _msg_key(msg), _rec_key(rec)
        if mk != rk:
            diffs = [f"{name}: jaxpr={mv!r} ledger={rv!r}"
                     for name, mv, rv in zip(_FIELDS, mk, rk) if mv != rv]
            fs.append(Finding(
                "sched-field", "error",
                f"{where}: message {i} ({rec.tag!r}) disagrees with its "
                f"ledger record on " + "; ".join(diffs),
                path=msg.path))
        # round position
        if rel_round_base is not None:
            want = offs[i]
            got = msg.rnd - msgs[0].rnd + offs[0]
        elif span_starts is not None and msg.idx in span_starts:
            start = span_starts[msg.idx]
            want = offs[i]
            got = msg.rnd + offs[start]
        else:
            want = offs[i]
            got = msg.rnd
        if got != want:
            fs.append(Finding(
                "sched-round", "error",
                f"{where}: message {i} ({rec.tag!r}) sits in round "
                f"{got} of the jaxpr but round {want} of the captured "
                f"schedule", path=msg.path))
        need = anchors.get(msg.kind, ())
        if need and not any(p in msg.prims for p in need):
            fs.append(Finding(
                "sched-anchor", "error",
                f"{where}: message {i} ({rec.tag!r}, kind {msg.kind}) "
                f"anchors to no {' / '.join(need)} equation — the scope "
                f"contains only {sorted(set(msg.prims))}",
                path=msg.path))
        elif not msg.prims:
            fs.append(Finding(
                "sched-anchor", "error",
                f"{where}: message {i} ({rec.tag!r}) owns no equations "
                f"at all", path=msg.path))
    return fs


# --------------------------------------------------------------------------
# Local plans: step traces -> expansion -> replay / executed run
# --------------------------------------------------------------------------

def _step_for_segment(steps: List[TracedStep],
                      s: int) -> TracedStep:
    for ts in steps:
        if s in ts.segments:
            return ts
    raise ValueError(f"no traced step covers segment {s}")


def ledger_stream(led: CommLedger) -> List[tuple]:
    """(fields…, round) per record — the exact comparison stream."""
    offs = round_offsets(len(led.records), led.round_marks)
    return [_rec_key(r) + (offs[i],)
            for i, r in enumerate(led.records)]


def static_expand_local(steps: List[TracedStep], program,
                        chan) -> Tuple[List[tuple], int]:
    """Expand the per-step static schedule over the program's segments,
    re-pricing scheduled channels from each repeat's global round —
    implemented from the jaxpr-extracted messages alone, independently
    of ``CommLedger.replay_schedule``."""
    scheduled = getattr(chan, "scheduled", False)
    stream: List[tuple] = []
    base = 0
    for s, seg in enumerate(program.segments):
        ts = _step_for_segment(steps, s)
        msgs, _ = extract_messages(ts.closed.jaxpr)
        offs = round_offsets(len(ts.records), ts.marks)
        rels = [msg.rnd - msgs[0].rnd + offs[0] for msg in msgs] \
            if msgs else []
        rps = ts.rounds
        for k in range(int(seg.count)):
            for msg, rel in zip(msgs, rels):
                rnd = base + k * rps + rel
                bits = msg.bits
                if scheduled and msg.wire is not None:
                    per, nmsg = msg.wire
                    bits = nmsg * chan.wire_bits(per, msg.itemsize,
                                                 rnd=rnd)
                stream.append((msg.kind, msg.tag, msg.shape, msg.dtype,
                               int(bits), msg.wire, msg.direction, rnd))
        base += rps * int(seg.count)
    return stream, base


def replay_expand_local(steps: List[TracedStep], program,
                        chan) -> CommLedger:
    """The trace-once replay every scan engine / batch group performs."""
    sched_chan = chan if getattr(chan, "scheduled", False) else None
    led = CommLedger()
    for s, seg in enumerate(program.segments):
        ts = _step_for_segment(steps, s)
        led.replay_schedule(ts.records, ts.rounds, ts.marks,
                            int(seg.count), channel=sched_chan,
                            faults=None)
    return led


def _compare_streams(static: List[tuple], dynamic: List[tuple],
                     code: str, where: str,
                     total_rounds: Tuple[int, int]) -> List[Finding]:
    fs: List[Finding] = []
    if len(static) != len(dynamic):
        fs.append(Finding(
            code, "error",
            f"{where}: static expansion has {len(static)} record(s), "
            f"the replayed/executed ledger {len(dynamic)}"))
        return fs
    names = _FIELDS + ("round",)
    for i, (a, b) in enumerate(zip(static, dynamic)):
        if a != b:
            diffs = [f"{n}: static={x!r} dynamic={y!r}"
                     for n, x, y in zip(names, a, b) if x != y]
            fs.append(Finding(
                code, "error",
                f"{where}: record {i} ({b[1]!r}) diverges — "
                + "; ".join(diffs)))
            if len(fs) >= 5:
                fs.append(Finding(code, "error",
                                  f"{where}: … further diffs suppressed"))
                return fs
    if total_rounds[0] != total_rounds[1]:
        fs.append(Finding(
            code, "error",
            f"{where}: static expansion spans {total_rounds[0]} "
            f"round(s), the ledger {total_rounds[1]}"))
    return fs


def verify_local_schedule(steps: List[TracedStep], program, chan,
                          executed_ledger: Optional[CommLedger] = None,
                          ) -> Tuple[List[Finding], Dict[str, int]]:
    """Layers 1+2 (and 3 when ``executed_ledger`` is given) for a local
    plan's traced steps.  Returns (findings, schedule stats)."""
    findings: List[Finding] = []
    for ts in steps:
        msgs, problems = extract_messages(ts.closed.jaxpr)
        where = f"segment(s) {ts.segments}"
        findings += _check_messages(
            msgs, problems, ts.records, ts.marks, placement="local",
            where=where, rel_round_base=0)
    if any(f.severity == "error" for f in findings):
        return findings, {}
    static, rounds_s = static_expand_local(steps, program, chan)
    replay = replay_expand_local(steps, program, chan)
    findings += _compare_streams(
        static, ledger_stream(replay), "sched-replay",
        "trace-once replay", (rounds_s, replay.rounds))
    if executed_ledger is not None:
        findings += _compare_streams(
            static, ledger_stream(executed_ledger), "sched-dynamic",
            "executed run", (rounds_s, executed_ledger.algo_rounds))
    stats = {"messages": len(static), "rounds": rounds_s,
             "total_bits": int(sum(rec[4] for rec in static))}
    return findings, stats


# --------------------------------------------------------------------------
# Sharded plans: one traced shard_map program + scan spans
# --------------------------------------------------------------------------

def static_expand_sharded(msgs: List[StaticMessage],
                          trace_marks: Sequence[int],
                          spans: Sequence[ScanSpan],
                          chan) -> Tuple[List[tuple], int]:
    """Expand the trace-time static schedule the way the sharded driver
    expands its ledger: records outside scan spans copy once; each
    span's records repeat ``count`` times with advancing rounds and
    per-round scheduled re-pricing."""
    scheduled = getattr(chan, "scheduled", False)
    offs = round_offsets(len(msgs), trace_marks)

    def emit(stream, msg, rnd):
        bits = msg.bits
        if scheduled and msg.wire is not None:
            per, nmsg = msg.wire
            bits = nmsg * chan.wire_bits(per, msg.itemsize, rnd=rnd)
        stream.append((msg.kind, msg.tag, msg.shape, msg.dtype,
                       int(bits), msg.wire, msg.direction, rnd))

    stream: List[tuple] = []
    rounds_total = 0
    prev_end = 0
    for start, end, r_traced, count, _ in spans:
        for i in range(prev_end, start):
            emit(stream, msgs[i],
                 rounds_total + offs[i] - offs[prev_end])
        if start > prev_end:
            rounds_total += offs[start] - offs[prev_end]
        span = msgs[start:end]
        for k in range(count):
            for i, msg in enumerate(span):
                rel = offs[start + i] - (offs[start]
                                         if start < len(offs) else 0)
                emit(stream, msg, rounds_total + rel)
            rounds_total += r_traced
        prev_end = end
    for i in range(prev_end, len(msgs)):
        emit(stream, msgs[i], rounds_total + offs[i] - offs[prev_end])
    return stream, rounds_total


def verify_sharded_schedule(closed, led: CommLedger,
                            spans: Sequence[ScanSpan],
                            chan,
                            executed_ledger: Optional[CommLedger] = None,
                            ) -> Tuple[List[Finding], Dict[str, int]]:
    """Static messages of the traced ``shard_map`` program vs its
    trace-time ledger, then the span expansion vs the executed run."""
    msgs, problems = extract_messages(closed.jaxpr)
    scheduled = getattr(chan, "scheduled", False)
    span_starts: Optional[Dict[int, int]] = None
    if scheduled:
        span_starts = {}
        for sp in spans:
            for i in range(sp.start, sp.end):
                span_starts[i] = sp.start
    findings = _check_messages(
        msgs, problems, led.records, led.round_marks,
        placement="sharded", where="sharded trace",
        span_starts=span_starts)
    if any(f.severity == "error" for f in findings):
        return findings, {}
    static, rounds_s = static_expand_sharded(
        msgs, led.round_marks, spans, chan)
    stats = {"messages": len(static), "rounds": rounds_s,
             "total_bits": int(sum(rec[4] for rec in static))}
    if executed_ledger is not None:
        findings += _compare_streams(
            static, ledger_stream(executed_ledger), "sched-dynamic",
            "executed sharded run", (rounds_s, executed_ledger.rounds))
    return findings, stats


__all__ = [
    "ledger_stream", "replay_expand_local", "round_offsets",
    "static_expand_local", "static_expand_sharded",
    "verify_local_schedule", "verify_sharded_schedule",
]
