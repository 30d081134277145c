"""Communication model + metering.

The paper restricts each round to:
  * computation phase: a constant number of Reduce/ReduceAll ops of an R^n
    vector (or scalars),
  * communication phase: each machine j broadcasts O(1) vectors in R^{d_j}
    (an all-to-all broadcast == one ReduceAll of an R^d vector).

Two communicator backends implement this model:

  * ``LocalCommunicator`` — m simulated machines on the host; per-machine
    state is stacked on a leading axis. Used by the reference algorithms,
    the feasible-set certifier, and the CPU benchmarks.
  * ``ShardMapCommunicator`` — the same interface bound to ``jax.lax``
    collectives over a named mesh axis, for use inside ``shard_map``.
    "Machine j" is mesh slice j of the `model` axis.

Every call is recorded in a ``CommLedger`` as a typed message —
direction, shape, dtype, payload bytes, and *wire bits* — so benchmarks
can report rounds, op counts, bytes, and bit totals, and assert the
paper's per-round budget (O(n + d) bits/round) is respected by each
algorithm.  ``end_round()`` additionally marks the record-stream
position of every round boundary (``round_marks``), so per-round and
rounds-prefix bit totals are exact even for algorithms with non-uniform
round structure.

Both communicators accept a ``channel`` (``core.channel``): a lossy
transform (fp16/bf16 cast, int8 stochastic-rounding quantization, top-k
sparsification) applied to every per-machine vector upload before the
reduction, with the transformed payload's wire bits recorded in the
ledger.  The default identity channel leaves both the computation graph
and the legacy ``(kind, elems, bytes, tag)`` record stream bit-identical
to a channel-free build; scalar reductions always bypass the channel.

Both communicators also accept a ``faults`` spec (``core.faults``): a
seeded schedule of injected wire faults (message drops, bit flips,
straggler rounds, one crash-restart).  Faults are *value-transparent* —
every faulted message is detected (checksum / timeout), NACKed, and
retransmitted until a clean copy arrives, so delivered payloads and all
computed results stay bit-identical to the fault-free run.  What changes
is the ledger: each failed attempt appends a 32-bit NACK plus a resend
copy of the record, both ``retransmit=True``, and straggler / crash
recovery appends extra rounds counted in ``recovery_rounds``.  Fault
granularity is the ledger record (a record's ``wire`` message bundle
fails and resends atomically), so ``total_bits == clean bits + exactly
the injected retransmission bits`` holds by construction.

Also here: ``collective_bytes_from_hlo`` — the dry-run HLO auditor that sums
payload bytes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops in a lowered/compiled module (used by the roofline).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .channel import AnyChannel, parse_channel
from .faults import FaultSpec, checksum as _fault_checksum, corrupt as _fault_corrupt, parse_faults


# --------------------------------------------------------------------------
# Ledger
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CommRecord:
    """One metered message.  The first four fields are the legacy stream
    the conformance suites pin bit-identical across backends / engines /
    batching; the typed tail (direction, shape, dtype, bits) is the
    message-level accounting added for bit budgets.  ``bytes`` is always
    the *source* payload (elems x itemsize); ``bits`` is what the payload
    occupies on the wire after the channel transform (== bytes x 8 under
    the identity channel)."""

    kind: str          # reduce_all | reduce | broadcast | all_to_all_broadcast
    elems: int         # payload element count (per machine contribution)
    bytes: int
    tag: str = ""
    direction: str = "worker->center"   # | "worker->all"
    shape: Optional[Tuple[int, ...]] = None   # () is a scalar; None derives
    dtype: str = "float32"
    bits: int = 0
    # wire geometry (per-message source elems, message count) for records
    # the channel prices — lets replay re-price a scheduled channel from
    # the record's round offset.  None == channel-exempt (scalars).
    # Deliberately NOT part of typed_stream(): it is pricing provenance,
    # not a wire observable.
    wire: Optional[Tuple[int, int]] = None
    # recovery traffic: True for NACKs, resends of faulted messages, and
    # crash-replay records.  Part of typed_stream() (it is a wire
    # observable: the receiver sees the duplicate), so total_bits splits
    # exactly into clean_bits() + retransmit_bits().
    retransmit: bool = False

    def __post_init__(self):
        if self.shape is None:
            self.shape = (self.elems,)
        if not self.bits:
            self.bits = self.bytes * 8


@dataclasses.dataclass
class CommLedger:
    records: List[CommRecord] = dataclasses.field(default_factory=list)
    rounds: int = 0
    # record-stream position of each round boundary: round_marks[k] ==
    # len(records) right after round k+1 ended.  Lets per-round / first-K
    # bit totals stay exact for non-uniform round structures.
    round_marks: List[int] = dataclasses.field(default_factory=list)
    _round_open: bool = False
    # wire rounds spent on recovery (straggler idles + crash replay);
    # algo_rounds == rounds - recovery_rounds is the algorithm's own
    # round count, which keys scheduled-channel stages and fault draws.
    recovery_rounds: int = 0
    # index of the next non-retransmit wire message — the per-message key
    # of the fault schedule.  Advanced identically by eager metering and
    # by replay, so both engines draw the same faults for the same
    # message.
    wire_msgs: int = 0
    # while True, every record is flagged retransmit (crash-replay
    # re-execution) and no fresh faults are drawn.
    mark_retransmit: bool = False

    @property
    def algo_rounds(self) -> int:
        return self.rounds - self.recovery_rounds

    def record(self, kind: str, elems: int, itemsize: int = 4, tag: str = "",
               *, shape: Optional[Tuple[int, ...]] = None,
               dtype: str = "float32", direction: str = "worker->center",
               bits: Optional[int] = None,
               wire: Optional[Tuple[int, int]] = None,
               retransmit: bool = False):
        nbytes = int(elems) * itemsize
        retransmit = bool(retransmit or self.mark_retransmit)
        self.records.append(CommRecord(
            kind, int(elems), nbytes, tag,
            direction=direction,
            shape=tuple(shape) if shape is not None else (int(elems),),
            dtype=dtype,
            bits=int(bits) if bits is not None else nbytes * 8,
            wire=tuple(wire) if wire is not None else None,
            retransmit=retransmit))
        if wire is not None and not retransmit:
            self.wire_msgs += 1
        self._round_open = True

    def end_round(self, recovery: bool = False):
        self.rounds += 1
        if recovery:
            self.recovery_rounds += 1
        self.round_marks.append(len(self.records))
        self._round_open = False

    def idle_round(self):
        """An empty recovery round (straggler delay): the wire stays open
        but carries nothing — wire rounds advance, the algorithm's don't."""
        self.end_round(recovery=True)

    def append_recovery(self, rec: CommRecord):
        """Price one failed delivery of ``rec``: a 32-bit NACK
        (center->worker resend request) plus a resend copy of the full
        record.  The checksum itself rides in the unpriced message header
        (like shape/dtype metadata), so this pair is *exactly* the
        injected retransmission traffic."""
        self.records.append(CommRecord(
            "nack", 1, 4, rec.tag, direction="center->worker", shape=(),
            retransmit=True))
        self.records.append(dataclasses.replace(rec, retransmit=True))
        self._round_open = True

    def end_round_faulted(self, faults: FaultSpec):
        """End an algorithm round, then inject the fault schedule's
        straggler delay for it (deterministic in the 0-based algo round)."""
        r = self.algo_rounds
        self.end_round()
        for _ in range(faults.straggle_delay(r)):
            self.idle_round()

    def replay_schedule(self, records: Sequence[CommRecord], rounds: int,
                        marks: Sequence[int], count: int,
                        channel: Optional[AnyChannel] = None,
                        faults: Optional[FaultSpec] = None):
        """Append a captured per-step schedule ``count`` times: the
        record objects are shared (replay is metering, not mutation), the
        round counter advances by ``rounds`` per repeat, and the step's
        round-boundary marks are rebased onto this ledger's stream.  The
        scan engine and ``execute_batch`` route their trace-once
        schedules through here so the replayed stream — marks included —
        is bit-identical to the per-call python-engine stream.

        Under a *scheduled* ``channel`` the captured records carry
        provisional prices (tracing sees a symbolic round index), so each
        repeat re-prices its channel-metered records from the record's
        round offset within the step — wire bits per round stay exact
        without re-tracing.  Fixed channels keep the shared-object fast
        path (prices are round-invariant by construction).

        With an active ``faults`` spec the replay walks the schedule
        record by record, drawing the same per-message fault decisions
        the eager python engine draws live, and appending the identical
        NACK/resend records and straggler idle rounds — so the faulted
        trace-once stream is bit-identical to the faulted per-call
        stream."""
        if faults is not None and faults.active:
            self._replay_faulted(records, rounds, marks, count, channel,
                                 faults)
            return
        if channel is not None and getattr(channel, "scheduled", False):
            for _ in range(count):
                base = len(self.records)
                self.records.extend(
                    repriced_records(records, marks, self.rounds, channel))
                self.round_marks.extend(base + m for m in marks)
                self.rounds += rounds
            return
        for _ in range(count):
            base = len(self.records)
            self.records.extend(records)
            self.round_marks.extend(base + m for m in marks)
        self.rounds += rounds * count

    def _replay_faulted(self, records: Sequence[CommRecord], rounds: int,
                        marks: Sequence[int], count: int,
                        channel: Optional[AnyChannel],
                        faults: FaultSpec):
        scheduled = channel is not None and getattr(channel, "scheduled",
                                                    False)
        for _ in range(count):
            recs = (repriced_records(records, marks, self.algo_rounds,
                                     channel) if scheduled else records)
            mi = 0
            for j, rec in enumerate(recs):
                while mi < len(marks) and marks[mi] <= j:
                    self.end_round_faulted(faults)
                    mi += 1
                self.records.append(rec)
                if rec.wire is not None and not rec.retransmit:
                    msg = self.wire_msgs
                    self.wire_msgs += 1
                    for _kind in faults.attempts(msg):
                        self.append_recovery(rec)
            while mi < len(marks):
                self.end_round_faulted(faults)
                mi += 1
            for _ in range(rounds - len(marks)):
                self.end_round_faulted(faults)

    # ---- summaries -----------------------------------------------------
    def typed_stream(self) -> List[Tuple]:
        """The full typed record stream — legacy tuple plus the
        bit-accounting tail — as hashable tuples.  The conformance
        surfaces (tests, ``benchmarks/comm_bits``) compare THIS, so a
        future field lands in every one of them at once."""
        return [(r.kind, r.elems, r.bytes, r.bits, r.tag, tuple(r.shape),
                 r.dtype, r.direction, r.retransmit) for r in self.records]

    def total_bytes(self) -> int:
        return sum(r.bytes for r in self.records)

    def total_bits(self) -> int:
        return sum(r.bits for r in self.records)

    def retransmit_bits(self) -> int:
        """Wire bits of recovery traffic (NACKs + resends + crash replay)."""
        return sum(r.bits for r in self.records if r.retransmit)

    def clean_bits(self) -> int:
        """Wire bits net of recovery — bit-identical to a fault-free run."""
        return sum(r.bits for r in self.records if not r.retransmit)

    def retransmissions(self) -> int:
        """Number of resent payload messages (NACKs not counted)."""
        return sum(1 for r in self.records
                   if r.retransmit and r.kind != "nack")

    def op_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for r in self.records:
            out[r.kind] = out.get(r.kind, 0) + 1
        return out

    def bytes_per_round(self) -> float:
        return self.total_bytes() / max(1, self.rounds)

    def bits_per_round(self) -> float:
        return self.total_bits() / max(1, self.rounds)

    def bits_through_round(self, k: int) -> int:
        """Wire bits of the first ``k`` rounds, exact via ``round_marks``
        (proportional fallback if a caller bypassed the marked paths)."""
        if k >= self.rounds:
            return self.total_bits()
        if k <= 0:
            return 0
        if len(self.round_marks) == self.rounds:
            return sum(r.bits for r in self.records[:self.round_marks[k - 1]])
        return int(round(self.total_bits() * k / max(1, self.rounds)))

    def assert_budget(self, n: int, d: int, const: int = 8,
                      itemsize: int = 4):
        """Assert the paper's per-round budget: <= const ReduceAll of R^n
        plus const broadcast of R^{d} total, i.e. O(n+d) elements/round."""
        budget = const * (n + d) * itemsize
        per_round = self.bytes_per_round()
        if per_round > budget:
            raise AssertionError(
                f"communication budget violated: {per_round:.0f} B/round "
                f"> {budget} B/round (n={n}, d={d}, const={const})")


def repriced_records(records: Sequence[CommRecord], marks: Sequence[int],
                     base_round: int, channel: AnyChannel
                     ) -> List[CommRecord]:
    """Copies of a captured step's ``records`` with every channel-priced
    payload (``wire`` set) re-priced for a repeat whose first round is
    global round ``base_round``.  Record ``j``'s round offset within the
    step is the number of marks at or before it — the same invariant
    ``round_marks`` encodes (``marks[k] == #records once round k+1
    ended``).  Channel-exempt records (scalars) are shared unchanged."""
    out: List[CommRecord] = []
    mi, offset = 0, 0
    for j, rec in enumerate(records):
        while mi < len(marks) and marks[mi] <= j:
            offset += 1
            mi += 1
        if rec.wire is None:
            out.append(rec)
            continue
        per_elems, nmsg = rec.wire
        itemsize = rec.bytes // max(1, rec.elems)
        bits = nmsg * channel.wire_bits(per_elems, itemsize,
                                        rnd=base_round + offset)
        out.append(dataclasses.replace(rec, bits=int(bits))
                   if int(bits) != rec.bits else rec)
    return out


def inject_crash_recovery(ledger: CommLedger, faults: FaultSpec) -> int:
    """Post-pass for the trace-once engines: splice the crash-replay
    records into a replayed ledger exactly where the live python engine
    records them.

    The fault model crashes the center after it completes algorithm round
    ``k``, losing everything since its last snapshot (round ``s``); rounds
    ``s+1..k`` are re-executed from the restored snapshot.  The python
    engine does this live (``engine._run_python`` restores the carry via
    ``repro.checkpoint`` and re-runs the steps under
    ``ledger.mark_retransmit``); the scan/batch engines replay a captured
    schedule, so the same traffic is spliced in here: copies of rounds
    ``s+1..k``'s non-retransmit records, flagged ``retransmit=True``,
    inserted right after round ``k`` (and after its straggler idles, which
    the live path emits inside the round's ``end_round``).  Original
    per-record bits are kept — the live path pins the original round index
    for scheduled-channel pricing, so both streams price the replay at the
    round it re-executes.  Returns the number of replayed rounds."""
    s, k = faults.crash_span(ledger.algo_rounds)
    if k == 0:
        return 0

    def end_mark_index(r: int) -> int:
        """round_marks index of 1-based algo round ``r``'s end (straggle
        idles own their own marks, recomputed from the seeded schedule)."""
        w = 0
        for j in range(r - 1):
            w += 1 + faults.straggle_delay(j)
        return w

    def end_pos(r: int) -> int:
        return 0 if r == 0 else ledger.round_marks[end_mark_index(r)]

    insert_at = end_pos(k)
    copied: List[CommRecord] = []
    copy_marks: List[int] = []
    for r in range(s + 1, k + 1):
        copied.extend(dataclasses.replace(rc, retransmit=True)
                      for rc in ledger.records[end_pos(r - 1):end_pos(r)]
                      if not rc.retransmit)
        copy_marks.append(insert_at + len(copied))
    # marks splice point: after round k's own mark and its straggle idles
    splice = end_mark_index(k) + 1 + faults.straggle_delay(k - 1)
    n = len(copied)
    ledger.records[insert_at:insert_at] = copied
    ledger.round_marks = (ledger.round_marks[:splice] + copy_marks +
                          [m + n for m in ledger.round_marks[splice:]])
    ledger.rounds += k - s
    ledger.recovery_rounds += k - s
    return k - s


# --------------------------------------------------------------------------
# Communicators
# --------------------------------------------------------------------------

# --------------------------------------------------------------------------
# Wire scopes — the static-analysis anchor
# --------------------------------------------------------------------------
#
# Every wire message a communicator emits is wrapped in a
# ``jax.named_scope`` whose name encodes the ledger record it just
# priced.  ``jax.named_scope`` rides the tracer name stack: it lands in
# each traced equation's ``source_info.name_stack`` (surviving into
# ``scan``/``shard_map`` sub-jaxprs) WITHOUT touching the jaxpr's
# pretty-printed text, the compiled computation, or any numeric value —
# so ``execute_batch`` structure grouping and every bit-identity gate
# are unaffected.  ``repro.analysis`` walks the jaxpr and parses these
# tokens back into the *static* message schedule, which it then proves
# equal to the trace-once ledger replay.

_SCOPE_SAFE_RE = re.compile(r"[^A-Za-z0-9_.+-]")

_DIRECTION_CODES = {
    "worker->center": "w2c",
    "worker->all": "w2a",
    "center->worker": "c2w",
}
_DIRECTION_NAMES = {v: k for k, v in _DIRECTION_CODES.items()}

COMM_SCOPE_RE = re.compile(
    r"comm\[i=(?P<idx>\d+);r=(?P<rnd>\d+);k=(?P<kind>[a-z_]+);"
    r"d=(?P<direction>[A-Za-z0-9_.+-]*);s=(?P<shape>[0-9x]*);"
    r"t=(?P<dtype>[A-Za-z0-9_]*);b=(?P<bits>\d+);"
    r"w=(?P<wire>(?:\d+\.\d+)|-);g=(?P<tag>[A-Za-z0-9_.+-]*)\]")


def sanitize_scope_tag(tag: str) -> str:
    """Ledger tags (``"z=Aw"``, ``"|w|^2"``) may use characters a scope
    name cannot carry; both the emitter and the verifier canonicalize
    through this before comparing."""
    return _SCOPE_SAFE_RE.sub("-", tag)


def comm_scope_name(rec: CommRecord, idx: int, rnd: int) -> str:
    """Scope token for ledger record ``rec`` at position ``idx``,
    emitted in round ``rnd`` (an offset within the traced step when the
    engine pinned a round base, else the ledger's absolute counter)."""
    shape = "x".join(str(int(s)) for s in rec.shape)
    wire = "-" if rec.wire is None else f"{rec.wire[0]}.{rec.wire[1]}"
    d = _DIRECTION_CODES.get(rec.direction, sanitize_scope_tag(rec.direction))
    return (f"comm[i={idx};r={rnd};k={rec.kind};d={d};s={shape};"
            f"t={rec.dtype};b={rec.bits};w={wire};g={sanitize_scope_tag(rec.tag)}]")


def parse_comm_scope(token: str) -> Optional[Dict[str, object]]:
    """Inverse of ``comm_scope_name``; ``None`` if ``token`` is not a
    comm scope.  ``shape`` comes back as a tuple, ``wire`` as
    ``(per_elems, nmsg)`` or ``None``, ``direction`` decoded."""
    m = COMM_SCOPE_RE.fullmatch(token)
    if m is None:
        return None
    shape_s = m.group("shape")
    wire_s = m.group("wire")
    d = m.group("direction")
    return {
        "idx": int(m.group("idx")),
        "rnd": int(m.group("rnd")),
        "kind": m.group("kind"),
        "direction": _DIRECTION_NAMES.get(d, d),
        "shape": tuple(int(s) for s in shape_s.split("x")) if shape_s else (),
        "dtype": m.group("dtype"),
        "bits": int(m.group("bits")),
        "wire": (None if wire_s == "-"
                 else tuple(int(p) for p in wire_s.split("."))),
        "tag": m.group("tag"),
    }


class _ChannelWireMixin:
    """Channel plumbing shared by both communicators: parsing/rejection,
    round-index tracking for scheduled channels, and wire pricing.

    Round identity: under the python engine nobody calls
    ``begin_round`` — the ledger's concrete round counter IS the round
    index (it advances at every ``end_round``, so it is exact even
    intra-step).  The scan engines thread the round index as scanned
    ``xs`` and pin it with ``begin_round`` before each step;
    ``end_round`` then advances a local offset for multi-round steps.  A
    *traced* index prices provisionally (stage 0); the ledger replay
    re-prices from round offsets, so the trace-once stream still carries
    exact per-round wire bits.
    """

    def _init_channel(self, channel):
        self.channel: AnyChannel = parse_channel(channel)
        if getattr(self.channel, "kind", "") == "gap":
            raise ValueError(
                f"channel {self.channel.name!r} is a gap-adaptive "
                f"specification; resolve it to a schedule before "
                f"constructing a communicator (repro.api.plan resolves "
                f"gap channels via an identity probe run)")
        self._round_base = None
        self._round_offset = 0

    def _init_faults(self, faults):
        self.faults: FaultSpec = parse_faults(faults)
        # True while an engine captures a schedule (jax.eval_shape /
        # make_jaxpr): fault injection must not pollute the captured
        # stream — the ledger replay injects it instead.
        self._tracing = False

    def begin_round(self, rnd):
        """Pin the round index of subsequent messages (scan engines pass
        the scanned — possibly traced — index here)."""
        self._round_base = rnd
        self._round_offset = 0

    def reset_round(self):
        """Drop a pinned round index (call after a traced run so a stale
        tracer never leaks into eager metering)."""
        self._round_base = None
        self._round_offset = 0

    def _round_index(self):
        """The round the next message belongs to: concrete under the
        python engine (ledger counter), possibly traced under scan.
        Channel schedules are indexed by *algorithm* round, so recovery
        rounds (straggler idles, crash replay) never shift the stage."""
        if self._round_base is None:
            return self.ledger.algo_rounds
        return self._round_base + self._round_offset

    def _wire_scope(self, payload=None):
        """``jax.named_scope`` for the graph ops realizing the wire
        message the ledger just recorded (call right after
        ``ledger.record``).  The name encodes the record so the static
        verifier can recover the message schedule from the jaxpr alone;
        the round field is the concrete offset within the traced step
        when ``begin_round`` pinned a (possibly traced) base, else the
        ledger's concrete round counter."""
        led = self.ledger
        rec = led.records[-1]
        idx = len(led.records) - 1
        rnd = (self._round_offset if self._round_base is not None
               else led.algo_rounds)
        return jax.named_scope(comm_scope_name(rec, idx, rnd))

    def _price(self, per_elems: int, itemsize: int, nmsg: int = 1) -> int:
        """Wire bits for ``nmsg`` channel-transformed messages of
        ``per_elems`` elements at the current round (stage 0 provisional
        when the round index is traced — replay re-prices)."""
        ch = self.channel
        if getattr(ch, "scheduled", False):
            rnd = self._round_index()
            if not isinstance(rnd, (int, np.integer)):
                rnd = None
            return nmsg * ch.wire_bits(per_elems, itemsize, rnd=rnd)
        return nmsg * ch.wire_bits(per_elems, itemsize)

    def _apply_channel(self, x):
        """The per-message transform at the current round."""
        if getattr(self.channel, "scheduled", False):
            rnd = self._round_index()
            return self.channel.apply(x, rnd)
        return self.channel.apply(x)

    def end_round(self):
        if self._round_base is not None:
            self._round_offset += 1
        led = self.ledger
        if led.mark_retransmit:
            # crash-replay re-execution: a recovery round, no fresh faults
            led.end_round(recovery=True)
            return
        f = getattr(self, "faults", None)
        if f is not None and f.active and not self._tracing:
            led.end_round_faulted(f)
            return
        led.end_round()

    def _inject_faults(self, payload):
        """The eager detect-and-retransmit dance for the wire message the
        ledger just recorded.  Draws the seeded fault schedule for this
        message index; for each failed attempt, genuinely corrupts the
        concrete payload in transit (bit flip), verifies the XOR-fold
        checksum catches it, and prices the NACK + resend.  The delivered
        payload is always the clean copy, so computed values stay
        bit-identical to the fault-free run.  No-op while tracing (the
        ledger replay injects the identical records instead) or during
        crash-replay re-execution (a replayed message is recovery
        traffic, not a fresh draw)."""
        led = self.ledger
        f = getattr(self, "faults", None)
        if (f is None or not f.active or led.mark_retransmit
                or self._tracing or isinstance(payload, jax.core.Tracer)):
            return
        rec = led.records[-1]
        if rec.wire is None or rec.retransmit:
            return
        msg = led.wire_msgs - 1   # record() just advanced it
        events = f.attempts(msg)
        if not events:
            return
        clean = np.asarray(payload)
        for a, kind in enumerate(events):
            if kind == "flip":
                sent = _fault_corrupt(clean, f.seed, msg, a)
                if _fault_checksum(sent) == _fault_checksum(clean):
                    raise AssertionError(
                        "checksum failed to detect an injected bit flip")
            led.append_recovery(rec)


class LocalCommunicator(_ChannelWireMixin):
    """Simulates m machines on host. Per-machine values are stacked on a
    leading axis of size m. Used by reference algorithms and tests.

    ``channel`` (name or ``core.channel`` object) is applied per machine
    to every vector upload before the reduction; the identity default
    skips the transform entirely, so channel-free semantics — compute
    graph and ledger stream alike — are untouched."""

    def __init__(self, m: int, ledger: Optional[CommLedger] = None,
                 channel=None, faults=None):
        self.m = m
        self.ledger = ledger if ledger is not None else CommLedger()
        self._init_channel(channel)
        self._init_faults(faults)

    def _transmit(self, x_stacked):
        """The lossy worker->center wire, per machine (leading axis)."""
        if self.channel.lossless:
            return x_stacked
        return jax.vmap(self._apply_channel)(x_stacked)

    def reduce_all(self, x_stacked, tag: str = "",
                   pretransformed: bool = False) -> jnp.ndarray:
        """ReduceAll: each machine holds x_j (stacked (m, ...)); returns the
        sum, conceptually available on every machine.

        ``pretransformed`` declares that the caller already applied this
        round's channel transform to every per-machine payload (the fused
        round-step kernel emits the upload vector through the in-kernel
        channel stage) — the record, its wire pricing, and fault
        injection are byte-identical to the untransformed path; only the
        redundant second transform is skipped."""
        x_stacked = jnp.asarray(x_stacked)
        # per-machine payload metadata from the aval, NOT from slicing
        # x_stacked[0]: a traced slice would plant a dead machine-axis
        # gather in every step jaxpr, which the static class certifier
        # (repro.analysis) must treat as reading another machine's block
        per_shape = tuple(x_stacked.shape[1:])
        per_size = int(np.prod(per_shape, dtype=np.int64)) if per_shape else 1
        itemsize = x_stacked.dtype.itemsize
        self.ledger.record("reduce_all", per_size, itemsize, tag,
                           shape=per_shape,
                           dtype=str(x_stacked.dtype),
                           direction="worker->center",
                           bits=self._price(per_size, itemsize),
                           wire=(per_size, 1))
        self._inject_faults(x_stacked)
        with self._wire_scope():
            xfer = x_stacked if pretransformed else self._transmit(x_stacked)
            return jnp.sum(xfer, axis=0)

    def reduce_scalar(self, x_stacked, tag: str = "") -> jnp.ndarray:
        # scalars carry control quantities: never channel-transformed
        self.ledger.record("reduce_all", 1, 4, tag, shape=(),
                           direction="worker->center")
        with self._wire_scope():
            return jnp.sum(x_stacked, axis=0)

    def all_to_all_broadcast(self, blocks_stacked, tag: str = ""):
        """Each machine broadcasts its R^{d_j} block; every machine ends up
        with all blocks. Locally this is the identity on the stacked array;
        the ledger charges sum_j d_j = d elements (wire bits: m per-machine
        messages through the channel)."""
        blocks_stacked = jnp.asarray(blocks_stacked)
        itemsize = blocks_stacked.dtype.itemsize
        per_elems = int(np.prod(blocks_stacked.shape[1:], dtype=np.int64)) \
            if blocks_stacked.ndim > 1 else 1
        m = blocks_stacked.shape[0]
        self.ledger.record("all_to_all_broadcast", blocks_stacked.size,
                           itemsize, tag,
                           shape=tuple(blocks_stacked.shape),
                           dtype=str(blocks_stacked.dtype),
                           direction="worker->all",
                           bits=self._price(per_elems, itemsize, m),
                           wire=(per_elems, m))
        self._inject_faults(blocks_stacked)
        with self._wire_scope():
            out = self._transmit(blocks_stacked)
            if self.channel.lossless:
                # a lossless local broadcast is the identity — it traces
                # to zero equations, leaving the scope (and the message)
                # invisible to the static verifier.  An optimization
                # barrier is a semantic no-op that still owns an
                # equation, anchoring the scope in the jaxpr.
                out = lax.optimization_barrier(out)
            return out


class ShardMapCommunicator(_ChannelWireMixin):
    """The same interface bound to lax collectives over mesh axis ``axis``.

    Use inside ``shard_map``: per-machine arrays are the *local* shards (no
    stacking axis). Ledger recording happens at trace time — callers run one
    traced step per round (or multiply a one-round ledger by round count).
    The channel is applied to the local shard (one message) before the
    collective, mirroring the Local path's per-machine transform.
    """

    def __init__(self, axis: str, ledger: Optional[CommLedger] = None,
                 channel=None, faults=None):
        self.axis = axis
        self.ledger = ledger if ledger is not None else CommLedger()
        self._init_channel(channel)
        if parse_faults(faults).active:
            raise ValueError(
                "fault injection requires the local placement (the "
                "detect/retransmit dance runs on concrete host arrays); "
                "run faulted specs with placement='local'")
        self._init_faults(None)

    def _transmit(self, x_local):
        if self.channel.lossless:
            return x_local
        return self._apply_channel(x_local)

    def reduce_all(self, x_local, tag: str = "") -> jnp.ndarray:
        itemsize = x_local.dtype.itemsize
        self.ledger.record("reduce_all", x_local.size, itemsize, tag,
                           shape=tuple(x_local.shape),
                           dtype=str(x_local.dtype),
                           direction="worker->center",
                           bits=self._price(x_local.size, itemsize),
                           wire=(x_local.size, 1))
        with self._wire_scope():
            return lax.psum(self._transmit(x_local), self.axis)

    def reduce_scalar(self, x_local, tag: str = "") -> jnp.ndarray:
        self.ledger.record("reduce_all", 1, 4, tag, shape=(),
                           direction="worker->center")
        with self._wire_scope():
            return lax.psum(x_local, self.axis)

    def all_to_all_broadcast(self, block_local, tag: str = "") -> jnp.ndarray:
        """all_gather of the local R^{d_j} block -> (m, d_j) on every shard."""
        itemsize = block_local.dtype.itemsize
        self.ledger.record("all_to_all_broadcast", block_local.size,
                           itemsize, tag,
                           shape=tuple(block_local.shape),
                           dtype=str(block_local.dtype),
                           direction="worker->all",
                           bits=self._price(block_local.size, itemsize),
                           wire=(block_local.size, 1))
        with self._wire_scope():
            return lax.all_gather(self._transmit(block_local), self.axis)


# --------------------------------------------------------------------------
# HLO collective audit (used by the dry-run roofline)
# --------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")

_COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
# e.g. replica_groups=[16,16]<=[256]T(1,0) (iota format)
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    nb = _DTYPE_BYTES[dtype]
    if not dims:
        return nb
    n = 1
    for tok in dims.split(","):
        tok = tok.strip()
        if tok:
            n *= int(tok)
    return n * nb


def _group_size(line: str) -> int:
    m = _REPLICA_GROUPS_RE.search(line)
    if m:
        toks = [t for t in m.group(1).split(",") if t.strip()]
        return max(1, len(toks))
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        # replica_groups=[num_groups, group_size]<=[total]
        return max(1, int(m.group(2)))
    return 1


@dataclasses.dataclass
class CollectiveAudit:
    bytes_by_op: Dict[str, int]
    count_by_op: Dict[str, int]
    # the part of bytes_by_op whose ops carry the measure's scope in
    # their op_name: measurement, not communication
    measure_bytes_by_op: Dict[str, int] = dataclasses.field(
        default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())

    @property
    def wire_bytes(self) -> int:
        """Collective bytes outside the measure's scope."""
        return self.total_bytes - sum(self.measure_bytes_by_op.values())


_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def _in_scope(hlo_line: str, scope: str) -> bool:
    """Whether an HLO op's ``op_name`` metadata holds ``scope``."""
    m = _OP_NAME_RE.search(hlo_line)
    return m is not None and scope in m.group(1).split("/")


def collective_bytes_from_hlo(hlo_text: str,
                              measure_scope: Optional[str] = None
                              ) -> CollectiveAudit:
    """Sum payload bytes of collective ops in an HLO module text; those
    whose ``op_name`` holds the scope ``measure_scope`` are also summed
    apart, as measurement.

    Methodology (documented for the roofline):
      * all-reduce / all-to-all / collective-permute: result bytes
        (operand and result payloads coincide).
      * all-gather: result bytes (the fully-gathered tensor ~= bytes that
        cross links per participating device, ring all-gather moves
        (k-1)/k of it — we charge the full tensor, slightly conservative).
      * reduce-scatter: result bytes x group size (operand payload).
      * async pairs: the ``-start`` op is counted, the ``-done`` is skipped.
    """
    bytes_by_op: Dict[str, int] = {}
    count_by_op: Dict[str, int] = {}
    measure: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "=" not in stripped:
            continue
        rhs = stripped.split("=", 1)[1]
        opname = None
        for op in _COLLECTIVE_OPS:
            # match `op(`, `op-start(` but not `op-done(`
            if re.search(rf"\b{op}(-start)?\(", rhs):
                opname = op
                break
        if opname is None:
            continue
        # result shapes: everything before the op call on the rhs
        head = rhs.split(opname)[0]
        shapes = _SHAPE_RE.findall(head)
        if not shapes:
            continue
        nbytes = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        if opname == "reduce-scatter":
            nbytes *= _group_size(stripped)
        bytes_by_op[opname] = bytes_by_op.get(opname, 0) + nbytes
        count_by_op[opname] = count_by_op.get(opname, 0) + 1
        if measure_scope is not None and _in_scope(stripped,
                                                   measure_scope):
            measure[opname] = measure.get(opname, 0) + nbytes
    return CollectiveAudit(bytes_by_op, count_by_op, measure)


def collective_bytes_from_lowered(lowered,
                                  measure_scope: Optional[str] = None
                                  ) -> CollectiveAudit:
    """Audit a ``jax.stages.Lowered`` computation (e.g.
    ``ShardedProgram.lower()``'s product): compile it and sum the
    collective payloads of the optimized HLO module.  Compilation beats
    auditing the pre-optimization text — it is what actually runs, after
    fusion, async splitting, and collective combining."""
    try:
        text = lowered.compile().as_text()
    except Exception:
        # some backends cannot render compiled HLO; the pre-optimization
        # lowering still names every collective
        text = lowered.as_text(dialect="hlo")
    return collective_bytes_from_hlo(text, measure_scope)
