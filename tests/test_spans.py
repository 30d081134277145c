"""The program's spans and compile counter (``repro.metrics.spans``), and
the device scopes beside them: what they record, and that they change
nothing the certifier computes."""
from __future__ import annotations

import contextlib
import json
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.metrics import spans
from repro.serve import CertificationService, replay_trace
from repro.serve.queue import SubmissionQueue

SMALL = dict(instance="thm2_chain",
             instance_params=dict(d=6, kappa=8.0, lam=0.5, m=2),
             algorithm="dagd", rounds=5, eps=[1e-1])


def test_nesting_and_self_time():
    before = spans.snapshot()
    with spans.span("test.outer"):
        time.sleep(0.02)
        with spans.span("test.inner"):
            time.sleep(0.03)
        with spans.span("test.inner"):
            pass
    moved = spans.since(before)["spans"]
    outer, inner = moved["test.outer"], moved["test.inner"]
    assert outer["count"] == 1 and inner["count"] == 2
    assert inner["self_s"] == pytest.approx(inner["total_s"])
    assert outer["total_s"] >= 0.05 and inner["total_s"] >= 0.03
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - inner["total_s"], abs=1e-6)
    assert 0.02 <= outer["self_s"] < outer["total_s"]


def test_a_span_that_raises_is_still_counted():
    before = spans.snapshot()
    with pytest.raises(ValueError):
        with spans.span("test.raises"):
            raise ValueError("boom")
    assert spans.since(before)["spans"]["test.raises"]["count"] == 1
    assert spans.current() == ""


def test_a_compile_counts_under_the_innermost_span():
    before = spans.snapshot()
    with spans.span("test.outer"):
        with spans.span("test.compiles"):
            # a function no other test compiles
            jax.jit(lambda x: jnp.sin(x) * 3.25 + 0.125)(
                jnp.arange(7.0)).block_until_ready()
    compiles = spans.since(before)["compiles"]
    assert compiles["test.compiles"]["count"] >= 1
    assert compiles["test.compiles"]["seconds"] > 0
    assert "test.outer" not in compiles


def test_a_span_starts_no_profiler():
    with spans.span("test.no_profiler", ticket="t000001"):
        pass
    with pytest.raises(RuntimeError):
        jax.profiler.stop_trace()       # nothing was started


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out.extend((ev.name, dict(ev.stats)) for ev in line.events
                           if ev.name.startswith("repro."))
    return out


def test_admission_spans_carry_the_ticket_into_the_trace(tmp_path):
    """Under a profiler the spans land on the host plane with their ids;
    spans nested in ``repro.admit`` inherit its ticket, and each compile
    leaves a ``repro.compile`` marker naming the span that compiled."""
    q = SubmissionQueue()
    q.admit(SMALL, client_id="a")                # t000001, compiles warm
    jax.profiler.start_trace(str(tmp_path))
    try:
        q.admit(dict(SMALL, instance_params=dict(SMALL["instance_params"],
                                                 d=10)), client_id="a")
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    names = [n for n, _ in events]
    for name in ("repro.admit", "repro.parse", "repro.plan",
                 "repro.instance_build", "repro.instance.data",
                 "repro.prepare_cell", "repro.cell.dist", "repro.cell.trace"):
        assert name in names, name
    for name, args in events:
        if name != "repro.compile":
            assert args.get("ticket") == "t000002", (name, args)
    markers = [args for name, args in events if name == "repro.compile"]
    assert markers, "a new shape compiles in admission"
    assert all(m["span"].startswith("repro.") and m["seconds"] >= 0
               for m in markers)


def test_admission_children_cover_it():
    q = SubmissionQueue()
    before = spans.snapshot()
    q.admit(dict(SMALL, instance_params=dict(SMALL["instance_params"],
                                             d=12)), client_id="a")
    moved = spans.since(before)["spans"]
    admit = moved["repro.admit"]
    assert admit["count"] == 1
    children = sum(moved[n]["total_s"] for n in (
        "repro.parse", "repro.plan", "repro.instance_build",
        "repro.prepare_cell"))
    assert children == pytest.approx(admit["total_s"] - admit["self_s"])
    assert moved["repro.instance_build"]["count"] == 1
    assert moved["repro.prepare_cell"]["total_s"] == pytest.approx(
        moved["repro.cell.dist"]["total_s"]
        + moved["repro.cell.trace"]["total_s"]
        + moved["repro.prepare_cell"]["self_s"])


# --------------------------------------------------------------------------
# The scopes change nothing the certifier computes
# --------------------------------------------------------------------------

@contextlib.contextmanager
def _no_scopes(monkeypatch):
    """``jax.named_scope`` as a no-op: the program as it was without its
    device scopes."""
    with monkeypatch.context() as mp:
        mp.setattr(jax, "named_scope",
                   lambda name: contextlib.nullcontext())
        yield


@pytest.mark.parametrize("spec", [
    dict(SMALL, rounds=40, eps=[1e-4]),
    # composed oracles (a block wider than one tile): the scoped padding
    dict(instance="logistic",
         instance_params=dict(n=600, d=40, m=2, lam=1e-2, ref_iters=50),
         algorithm="dagd", rounds=6, eps=[1e-3], eps_mode="rel"),
])
def test_scopes_leave_ledger_gaps_and_group_key_unchanged(monkeypatch, spec):
    runs = {}
    for scoped in (True, False):
        jax.clear_caches()            # each side traces its own programs
        with contextlib.ExitStack() as stack:
            if not scoped:
                stack.enter_context(_no_scopes(monkeypatch))
            pl = api.plan(api.RunSpec(**spec))
            cell = api.prepare_cell(pl)
            res = pl.execute()
            (grouped,) = api.execute_group([cell])
            runs[scoped] = (cell.group_key(), res, grouped)
    (k1, r1, g1), (k0, r0, g0) = runs[True], runs[False]
    assert k1 == k0
    for a, b in ((r1, r0), (g1, g0), (r1, g1)):
        assert a.ledger.typed_stream() == b.ledger.typed_stream()
        assert a.ledger.round_marks == b.ledger.round_marks
    np.testing.assert_array_equal(r1.gaps, r0.gaps)
    np.testing.assert_array_equal(g1.gaps, g0.gaps)
    np.testing.assert_array_equal(np.asarray(r1.w), np.asarray(r0.w))


def test_soak_counters_are_exact_with_spans():
    """The 201-spec soak of ``tests/test_serve.py``: its exact cache and
    service counters hold with the spans in place, and the spans count
    one admission, one verdict and one release per spec and one
    ``repro.execute_group`` per batch."""
    from test_serve import _soak_trace
    _, trace = _soak_trace()
    svc = CertificationService(max_batch=8, max_wait=0.25,
                               cache_capacity=32)
    before = spans.snapshot()
    envs = replay_trace(svc, trace)
    moved = spans.since(before)["spans"]
    assert len(envs) == 201
    st = svc.cache.stats()
    assert (st.executions, st.misses, st.hits) == (33, 6, 27)
    assert svc.stats()["batches"] == 33
    for name in ("repro.admit", "repro.verdicts", "repro.release",
                 "repro.instance_build", "repro.prepare_cell"):
        assert moved[name]["count"] == 201, name
    for name in ("repro.execute_group", "repro.runner", "repro.run",
                 "repro.ledger_replay"):
        assert moved[name]["count"] == 33, name


def test_serve_cli_prints_the_spans_beside_its_stats(tmp_path, capsys):
    from repro.serve.__main__ import main as serve_main
    path = tmp_path / "specs.jsonl"
    path.write_text(json.dumps(dict(client_id="c", spec=SMALL)) + "\n")
    assert serve_main(["--input", str(path)]) == 0
    err = capsys.readouterr().err.splitlines()
    (line,) = [ln for ln in err if ln.startswith("[spans] ")]
    snap = json.loads(line[len("[spans] "):])
    for name in ("repro.admit", "repro.execute_group", "repro.release"):
        assert snap["spans"][name]["count"] >= 1
    assert any(ln.startswith("[serve] ") for ln in err)
