"""Certification service: unit tests + deterministic load/soak.

Everything runs on the injected clock — the service never reads wall
time — so the soak trace produces the identical batch sequence, cache
counters, and envelope stream on every run (CI replays it three times
back-to-back to enforce exactly that).
"""
import json

import numpy as np
import pytest

from repro import api
from repro.serve import (
    Arrival, CertificationService, CoalescingScheduler, ProgramCache,
    QuarantinedError, QueueFullError, SpecError, SubmissionQueue,
    replay_trace, spec_pool, synthetic_trace,
)
from repro.serve.queue import PendingRun


SMALL = dict(instance="thm2_chain",
             instance_params=dict(d=6, kappa=8.0, lam=0.5, m=2),
             algorithm="dagd", rounds=5, eps=[1e-1])


def _fake_run(key, t=0.0, seq=0, client="c"):
    class _Cell:
        def group_key(self):
            return key
    return PendingRun(ticket=f"f{seq}", client_id=client, seq=seq,
                      spec=None, plan=None,
                      cell=None if key is None else _Cell(), arrival=t)


# --------------------------------------------------------------------------
# Scheduler
# --------------------------------------------------------------------------

def test_scheduler_count_flush_releases_full_batches():
    sched = CoalescingScheduler(max_batch=8, max_wait=10.0)
    for i in range(17):
        sched.add(_fake_run(("k",), t=0.0, seq=i))
    batches = sched.due(0.0)
    assert [b.width for b in batches] == [8, 8]
    # members in arrival order
    assert [r.seq for r in batches[0].runs] == list(range(8))
    assert [r.seq for r in batches[1].runs] == list(range(8, 16))
    assert sched.pending == 1
    # the straggler waits for its deadline...
    assert sched.due(5.0) == []
    # ...and is released once its wait exceeds max_wait
    (tail,) = sched.due(10.0)
    assert tail.width == 1 and tail.runs[0].seq == 16
    assert sched.pending == 0


def test_scheduler_deadline_and_flush():
    sched = CoalescingScheduler(max_batch=8, max_wait=0.25)
    for i in range(3):
        sched.add(_fake_run(("k",), t=0.0, seq=i))
    assert sched.due(0.2) == []
    (b,) = sched.due(0.25)
    assert b.width == 3 and b.grouped
    # flush releases partial groups regardless of age
    sched.add(_fake_run(("k",), t=1.0, seq=9))
    (b,) = sched.due(1.0, flush=True)
    assert b.width == 1


def test_scheduler_sequential_runs_bypass_the_pool():
    sched = CoalescingScheduler(max_batch=8, max_wait=10.0)
    sched.add(_fake_run(None, t=0.0, seq=0))
    sched.add(_fake_run(("k",), t=0.0, seq=1))
    batches = sched.due(0.0)          # no flush, nothing due but the
    assert len(batches) == 1          # unbatchable singleton
    assert not batches[0].grouped and batches[0].width == 1


def test_scheduler_release_order_is_pool_insertion_order():
    sched = CoalescingScheduler(max_batch=8, max_wait=0.1)
    sched.add(_fake_run(("b",), t=0.0, seq=0))
    sched.add(_fake_run(("a",), t=0.0, seq=1))
    sched.add(_fake_run(("b",), t=0.0, seq=2))
    keys = [b.key for b in sched.due(1.0)]
    assert keys == [("b",), ("a",)]


# --------------------------------------------------------------------------
# Program cache
# --------------------------------------------------------------------------

def test_cache_hit_requires_key_and_width():
    cache = ProgramCache(capacity=4)
    e1, hit = cache.lookup(("k",), 8)
    assert not hit                    # new key
    _, hit = cache.lookup(("k",), 1)
    assert not hit                    # known key, new width: jit respecializes
    e2, hit = cache.lookup(("k",), 8)
    assert hit and e2 is e1           # same runners dict survives
    st = cache.stats()
    assert (st.hits, st.misses, st.executions) == (1, 2, 3)


def test_cache_lru_eviction():
    cache = ProgramCache(capacity=2)
    cache.lookup(("a",), 1)
    cache.lookup(("b",), 1)
    cache.lookup(("a",), 1)           # touch a: b is now LRU
    cache.lookup(("c",), 1)           # evicts b
    assert cache.stats().evictions == 1 and len(cache) == 2
    _, hit = cache.lookup(("a",), 1)
    assert hit
    _, hit = cache.lookup(("b",), 1)  # evicted: pays the compile again
    assert not hit


# --------------------------------------------------------------------------
# Admission queue
# --------------------------------------------------------------------------

def test_queue_rejects_before_any_compute():
    q = SubmissionQueue(max_depth=4)
    with pytest.raises(SpecError):
        q.admit("{not json")
    with pytest.raises(SpecError):
        q.admit(dict(SMALL, bogus=1))
    with pytest.raises(api.PlanError):
        q.admit(dict(SMALL, algorithm="bogus"))
    with pytest.raises(SpecError, match="resolution-only"):
        q.admit(dict(instance_params=dict(d=6, kappa=8.0, m=2),
                     rounds=5))
    assert (q.admitted, q.rejected, q.outstanding) == (0, 4, 0)


def test_queue_admission_control_and_client_seq():
    q = SubmissionQueue(max_depth=2)
    r0 = q.admit(SMALL, client_id="a", now=1.0)
    with pytest.raises(SpecError):
        q.admit("{", client_id="a")   # rejection must not burn a seq
    r1 = q.admit(SMALL, client_id="a", now=2.0)
    assert (r0.seq, r1.seq) == (0, 1)
    assert (r0.ticket, r1.ticket) == ("t000001", "t000002")
    assert r0.arrival == 1.0 and r0.cell is not None
    with pytest.raises(QueueFullError):
        q.admit(SMALL, client_id="b")
    q.complete()
    r2 = q.admit(SMALL, client_id="b")
    assert r2.seq == 0                # seq is per-client


# --------------------------------------------------------------------------
# Service: sequential fallback + rejection accounting
# --------------------------------------------------------------------------

def test_service_sequential_fallback_matches_direct_execution():
    svc = CertificationService(max_batch=8, max_wait=10.0)
    spec = api.RunSpec(**SMALL, engine="python")   # unbatchable
    svc.submit(spec, client_id="c", now=0.0)
    (env,) = svc.step(0.0)            # immediately due, no coalescing
    assert not env.batched and not env.cache_hit and env.width == 1
    assert svc.stats()["fallbacks"] == 1 and svc.stats()["batches"] == 0
    pl = api.plan(spec)
    ref = pl.execute()
    assert env.result.ledger.typed_stream() == ref.ledger.typed_stream()
    assert env.verdicts == [dict(
        eps=e, measured_rounds=ref.measured_rounds(pl.eps_abs(e)),
        bound_rounds=pl.bound(pl.eps_abs(e)).rounds,
        certified=pl.certify(ref, e)) for e in spec.eps]


# --------------------------------------------------------------------------
# The deterministic soak
# --------------------------------------------------------------------------

def _soak_trace():
    """192 dense arrivals (3 structures x 64, shuffled, 5 clients,
    1ms apart) + 9 stragglers spaced 1s apart.  With max_batch=8 and
    max_wait=0.25 the dense phase (0.191s span) can only count-flush:
    8 full width-8 batches per structure; every straggler deadline-
    flushes alone at width 1.  Expected cache ledger, exactly:

        dense:      per structure 1 miss + 7 hits   -> 3 miss, 21 hit
        stragglers: per structure 1 miss + 2 hits   -> 3 miss,  6 hit
        total:      33 executions, 6 misses, hit rate 27/33 ~ 0.818
    """
    pools = spec_pool()
    dense = synthetic_trace(n_per_structure=64, seed=7, dt=1e-3,
                            clients=5, pools=pools)
    stragglers = [Arrival(t=5.0 + k, client_id="lone",
                          spec=pools[k % 3][k % 4]) for k in range(9)]
    return pools, dense + stragglers


def test_soak_deterministic_trace():
    pools, trace = _soak_trace()
    svc = CertificationService(max_batch=8, max_wait=0.25,
                               cache_capacity=32)
    envs = replay_trace(svc, trace)

    # -- no spec lost, duplicated, or reordered within a client --------
    assert len(envs) == len(trace) == 201
    assert len({e.ticket for e in envs}) == 201
    submitted, served = {}, {}
    for a in trace:
        submitted.setdefault(a.client_id, []).append(a.spec)
    for e in envs:
        served.setdefault(e.client_id, []).append(e)
    for cid, stream in served.items():
        assert [e.seq for e in stream] == list(range(len(stream)))
        assert [e.spec for e in stream] == submitted[cid]

    # -- cache counters: exact, and above the published floor ----------
    st = svc.cache.stats()
    assert (st.executions, st.misses, st.hits) == (33, 6, 27)
    assert st.hit_rate >= 0.80
    assert st.evictions == 0 and st.size == 3
    stats = svc.stats()
    assert stats["fallbacks"] == 0 and stats["rejected"] == 0
    assert stats["completed"] == 201 and stats["pending"] == 0
    assert stats["batches"] == 33

    # -- every served result identical to direct execution -------------
    refs = {}
    for pool in pools:
        for spec in pool:
            pl = api.plan(spec)
            res = pl.execute()
            refs[spec.to_json()] = (pl, res)
    for e in envs:
        pl, ref = refs[e.spec.to_json()]
        assert e.result.ledger.typed_stream() == ref.ledger.typed_stream()
        assert e.result.ledger.total_bits() == ref.ledger.total_bits()
        assert e.result.ledger.rounds == ref.ledger.rounds
        assert e.verdicts == [dict(
            eps=eps, measured_rounds=ref.measured_rounds(pl.eps_abs(eps)),
            bound_rounds=pl.bound(pl.eps_abs(eps)).rounds,
            certified=pl.certify(ref, eps)) for eps in e.spec.eps]
        np.testing.assert_allclose(e.result.w, ref.w,
                                   rtol=1e-5, atol=1e-5)

    # -- replaying the same trace on a fresh service is bit-identical --
    svc2 = CertificationService(max_batch=8, max_wait=0.25,
                                cache_capacity=32)
    envs2 = replay_trace(svc2, trace)
    assert svc2.stats() == stats
    assert [(e.ticket, e.client_id, e.seq, e.width, e.cache_hit,
             e.batched) for e in envs2] == \
           [(e.ticket, e.client_id, e.seq, e.width, e.cache_hit,
             e.batched) for e in envs]
    for a, b in zip(envs, envs2):
        assert a.result.ledger.typed_stream() == \
            b.result.ledger.typed_stream()
        assert a.verdicts == b.verdicts


# --------------------------------------------------------------------------
# scheduled channels through the service
# --------------------------------------------------------------------------

SCHED_STRUCTURES = (
    ("dagd", "identity"),
    ("dagd", "sched:int8@0,fp16@10"),
    ("dagd", "sched:int8@0,fp16@20"),
    ("dgd", "sched:int8@0,fp16@10"),
)


def test_soak_mixed_scheduled_channels():
    """Mixed fixed/scheduled structures under load: the group key
    separates schedules (same algorithm, different switch round never
    pools), the cache ledger stays exact, and every envelope — the
    re-priced scheduled records included — is bit-identical to direct
    execution of its spec.

    64 dense arrivals (4 structures x 16, shuffled, 3 clients, 1ms
    apart): with max_batch=8 the dense phase can only count-flush, two
    width-8 batches per structure -> per structure 1 miss + 1 hit."""
    pools = spec_pool(structures=SCHED_STRUCTURES)
    trace = synthetic_trace(n_per_structure=16, seed=11, dt=1e-3,
                            clients=3, pools=pools)
    svc = CertificationService(max_batch=8, max_wait=0.25,
                               cache_capacity=16)
    envs = replay_trace(svc, trace)

    assert len(envs) == len(trace) == 64
    st = svc.cache.stats()
    assert (st.executions, st.misses, st.hits) == (8, 4, 4)
    assert st.evictions == 0 and st.size == 4
    stats = svc.stats()
    assert stats["fallbacks"] == 0 and stats["rejected"] == 0
    assert stats["completed"] == 64 and stats["batches"] == 8

    # four distinct group keys; the wire channel is the separating axis
    keys = {}
    for pool, (algo, channel) in zip(pools, SCHED_STRUCTURES):
        cell = api.prepare_cell(api.plan(pool[0]))
        assert cell is not None, (algo, channel)
        keys[(algo, channel)] = cell.group_key()
    assert len(set(keys.values())) == len(SCHED_STRUCTURES)
    assert keys[("dagd", "sched:int8@0,fp16@10")][2] == \
        "sched:int8@0,fp16@10"
    assert keys[("dagd", "sched:int8@0,fp16@20")][2] == \
        "sched:int8@0,fp16@20"

    # every envelope bit-identical to direct execution of its spec
    refs = {}
    for pool in pools:
        for spec in pool:
            pl = api.plan(spec)
            refs[spec.to_json()] = (pl, pl.execute())
    for e in envs:
        pl, ref = refs[e.spec.to_json()]
        assert e.result.ledger.typed_stream() == ref.ledger.typed_stream()
        assert e.result.ledger.round_marks == ref.ledger.round_marks
        assert e.result.ledger.total_bits() == ref.ledger.total_bits()
        assert e.verdicts == [dict(
            eps=eps, measured_rounds=ref.measured_rounds(pl.eps_abs(eps)),
            bound_rounds=pl.bound(pl.eps_abs(eps)).rounds,
            certified=pl.certify(ref, eps)) for eps in e.spec.eps]
        np.testing.assert_allclose(e.result.w, ref.w,
                                   rtol=1e-5, atol=1e-5)

# --------------------------------------------------------------------------
# Resilience: degradation ladder, retries, dead letters, quarantine
# --------------------------------------------------------------------------

def test_queue_full_error_carries_backpressure_hints():
    q = SubmissionQueue(max_depth=1, retry_after=0.25)
    q.admit(SMALL, client_id="a")
    with pytest.raises(QueueFullError) as ei:
        q.admit(SMALL, client_id="b")
    assert ei.value.depth == 1 and ei.value.retry_after == 0.25
    assert q.rejected_full == 1 and q.rejected == 1


def test_cache_circuit_breaker_trips_and_resets():
    cache = ProgramCache(capacity=4, breaker_threshold=2)
    key = ("k",)
    cache.lookup(key, 8)
    cache.record_failure(key)
    assert not cache.tripped(key) and cache.breaker_open == 0
    assert len(cache) == 0            # failed entry dropped
    cache.record_failure(key)
    assert cache.tripped(key) and cache.breaker_open == 1
    assert cache.stats().breaker_open == 1
    cache.record_success(key)
    assert not cache.tripped(key) and cache.breaker_open == 0


def test_group_failure_degrades_sequentially_without_loss(monkeypatch):
    """A grouped batch that raises mid-execution must produce one ok
    envelope per run via the sequential ladder — no ticket lost, no
    duplicates, ordering preserved."""
    orig = api.execute_group
    calls = dict(n=0)

    def chaotic(cells, runner_cache=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("chaos: injected mid-batch failure")
        return orig(cells, runner_cache=runner_cache)

    monkeypatch.setattr(api, "execute_group", chaotic)
    svc = CertificationService(max_batch=4, max_wait=10.0)
    for i in range(4):
        svc.submit(SMALL, client_id="c", now=0.0)
    envs = svc.step(0.0)              # count-flush at width 4
    assert len(envs) == 4
    assert [e.seq for e in envs] == [0, 1, 2, 3]
    assert all(e.status == "ok" for e in envs)
    assert len({e.ticket for e in envs}) == 4
    stats = svc.stats()
    assert stats["group_failures"] == 1 and stats["dead_letters"] == 0
    assert stats["completed"] == 4 and stats["pending"] == 0
    # the sequential re-runs are still bit-identical to direct execution
    ref = api.plan(api.RunSpec(**SMALL)).execute()
    for e in envs:
        assert e.result.ledger.typed_stream() == ref.ledger.typed_stream()


def test_breaker_routes_batches_around_the_grouped_path(monkeypatch):
    def always_fail(cells, runner_cache=None):
        raise RuntimeError("chaos: grouped path down")

    monkeypatch.setattr(api, "execute_group", always_fail)
    svc = CertificationService(max_batch=2, max_wait=10.0,
                               breaker_threshold=1)
    svc.submit(SMALL, now=0.0)
    svc.submit(SMALL, now=0.0)
    envs = svc.step(0.0)
    assert len(envs) == 2 and all(e.status == "ok" for e in envs)
    assert svc.stats()["group_failures"] == 1
    # breaker now open: the next batch skips execute_group entirely
    svc.submit(SMALL, now=1.0)
    svc.submit(SMALL, now=1.0)
    envs = svc.step(1.0)
    assert len(envs) == 2 and all(e.status == "ok" for e in envs)
    stats = svc.stats()
    assert stats["group_failures"] == 1       # not called again
    assert stats["breaker_skips"] == 2
    assert stats["cache"]["breaker_open"] == 1


def test_retry_backoff_then_dead_letter_then_quarantine(monkeypatch):
    """A run whose execution always fails walks the whole ladder: retry
    with backoff, engine fallback, dead-letter envelope (still in the
    client stream), and quarantine of later submissions of that spec."""
    monkeypatch.setattr(api.ExecutionPlan, "execute",
                        lambda self: (_ for _ in ()).throw(
                            FloatingPointError("chaos: poisoned spec")))
    svc = CertificationService(max_batch=8, max_wait=10.0,
                               max_retries=1, retry_backoff=0.1)
    spec = api.RunSpec(**SMALL, engine="python")   # unbatchable
    svc.submit(spec, client_id="c", now=0.0)
    assert svc.step(0.0) == []        # first failure: retry scheduled
    assert svc.stats()["retries"] == 1 and svc.pending == 1
    assert svc.step(0.05) == []       # backoff not yet expired
    (env,) = svc.step(0.1)            # retry fails -> dead letter
    assert env.status == "error" and env.result is None
    assert "FloatingPointError" in env.error
    assert env.ticket == "t000001" and env.seq == 0
    d = env.to_dict()
    assert d["status"] == "error" and "chaos" in d["error"]
    stats = svc.stats()
    assert stats["dead_letters"] == 1 and stats["completed"] == 1
    assert stats["quarantined"] == 1 and stats["pending"] == 0
    # the poisoned spec is now rejected at the door
    with pytest.raises(QuarantinedError):
        svc.submit(spec, client_id="c", now=0.2)
    assert svc.stats()["rejected_quarantined"] == 1
    # a different spec is unaffected
    other = api.RunSpec(**dict(SMALL, rounds=4), engine="python")
    assert svc.submit(other, client_id="c", now=0.2) == "t000002"


def test_cli_exits_nonzero_on_a_dead_letter(monkeypatch, tmp_path, capsys):
    """An admitted spec that dead-letters fails the CLI run, exactly as a
    rejected payload does: a smoke run must not exit 0 over lost work."""
    from repro.serve.__main__ import main as serve_main
    monkeypatch.setattr(api.ExecutionPlan, "execute",
                        lambda self: (_ for _ in ()).throw(
                            FloatingPointError("chaos: poisoned spec")))
    path = tmp_path / "specs.jsonl"
    spec = api.RunSpec(**SMALL, engine="python")
    path.write_text(json.dumps(dict(client_id="c", spec=spec.to_dict())))
    assert serve_main(["--input", str(path)]) == 1
    (line,) = capsys.readouterr().out.splitlines()
    assert json.loads(line)["status"] == "error"


def test_python_engine_fallback_rescues_scan_failures(monkeypatch):
    """When only the compiled path fails, the ladder lands on the python
    round engine and the envelope is still ok (engine invariance makes
    the verdicts identical)."""
    orig = api.ExecutionPlan.execute

    def scan_poison(self):
        if self.engine == "scan":
            raise RuntimeError("chaos: compiled path down")
        return orig(self)

    monkeypatch.setattr(api.ExecutionPlan, "execute", scan_poison)
    monkeypatch.setattr(api, "execute_group",
                        lambda cells, runner_cache=None: (_ for _ in ())
                        .throw(RuntimeError("chaos: grouped path down")))
    svc = CertificationService(max_batch=1, max_wait=10.0, max_retries=0)
    svc.submit(SMALL, client_id="c", now=0.0)
    (env,) = svc.step(0.0)
    assert env.status == "ok"
    stats = svc.stats()
    assert stats["engine_fallbacks"] == 1 and stats["dead_letters"] == 0
    ref = api.plan(api.RunSpec(**SMALL, engine="python")).execute()
    assert env.result.ledger.typed_stream() == ref.ledger.typed_stream()


def test_chaos_soak_no_loss_dup_reorder(monkeypatch):
    """The deterministic soak under executor chaos: every 3rd grouped
    call raises mid-batch.  Delivery invariants (one envelope per
    ticket, per-client order, all ok) must hold exactly as in the
    healthy soak."""
    orig = api.execute_group
    calls = dict(n=0)

    def chaotic(cells, runner_cache=None):
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            raise RuntimeError("chaos: injected mid-batch failure")
        return orig(cells, runner_cache=runner_cache)

    monkeypatch.setattr(api, "execute_group", chaotic)
    pools = spec_pool()
    trace = synthetic_trace(n_per_structure=32, seed=13, dt=1e-3,
                            clients=4, pools=pools)
    svc = CertificationService(max_batch=8, max_wait=0.25)
    envs = replay_trace(svc, trace)

    assert len(envs) == len(trace) == 96
    assert len({e.ticket for e in envs}) == 96
    assert all(e.status == "ok" for e in envs)
    submitted, served = {}, {}
    for a in trace:
        submitted.setdefault(a.client_id, []).append(a.spec)
    for e in envs:
        served.setdefault(e.client_id, []).append(e)
    for cid, stream in served.items():
        assert [e.seq for e in stream] == list(range(len(stream)))
        assert [e.spec for e in stream] == submitted[cid]
    stats = svc.stats()
    assert stats["group_failures"] > 0, "chaos never fired"
    assert stats["dead_letters"] == 0 and stats["pending"] == 0
    assert stats["completed"] == 96

    # served results remain bit-identical to direct execution
    refs = {}
    for pool in pools:
        for spec in pool:
            refs[spec.to_json()] = api.plan(spec).execute()
    for e in envs:
        ref = refs[e.spec.to_json()]
        assert e.result.ledger.typed_stream() == ref.ledger.typed_stream()


def test_faulted_specs_serve_identically(monkeypatch):
    """RunSpecs with an active faults= axis flow through the service
    (grouped by the faults component of the key) and serve the same
    recovery-priced stream as direct execution."""
    faulted = dict(SMALL, rounds=10,
                   faults="inject:seed=2,drop=0.2,flip=0.2")
    clean = dict(SMALL, rounds=10)
    svc = CertificationService(max_batch=2, max_wait=10.0)
    svc.submit(faulted, client_id="c", now=0.0)
    svc.submit(clean, client_id="c", now=0.0)
    envs = svc.drain(0.0)
    assert len(envs) == 2 and all(e.status == "ok" for e in envs)
    # distinct group keys: the faulted spec never pools with the clean one
    assert svc.stats()["batches"] == 2
    ref_f = api.plan(api.RunSpec(**faulted)).execute()
    ref_c = api.plan(api.RunSpec(**clean)).execute()
    by_faults = {e.spec.faults: e for e in envs}
    env_f = by_faults["inject:seed=2,drop=0.2,flip=0.2"]
    env_c = by_faults["none"]
    assert env_f.result.ledger.typed_stream() == \
        ref_f.ledger.typed_stream()
    assert env_f.result.ledger.retransmissions() > 0
    assert env_c.result.ledger.typed_stream() == \
        ref_c.ledger.typed_stream()
    assert env_c.result.ledger.retransmissions() == 0
