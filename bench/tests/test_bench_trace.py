"""The trace reduction, checked on a small trace recorded on a TPU v5e:
two 8-round dagd solves (logistic, n = 4,096, d = 2,000, m = 4, composed
oracles), each inside a ``bench.execute`` span and followed by a 10 ms
``bench.gap_to_host`` span in which the device had nothing to do."""
from __future__ import annotations

import pathlib

import pytest

from harness import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    return trace.from_profile(ProfileData.from_file(
        str(DATA / "small-solve.xplane.pb")))


def test_device_plane_and_host_spans_are_found(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    names = [s[0] for s in recorded.spans]
    assert names == ["bench.execute", "bench.gap_to_host"] * 2
    lo, hi = recorded.window()          # no bench.window span: the extent
    assert lo == recorded.spans[0][1] and hi == recorded.spans[-1][2]


def test_while_loops_are_containers_not_ops(recorded):
    ops = recorded.ops["/device:TPU:0"]
    assert len(ops) == 386              # 388 events, 2 of them the loops
    assert not any(name.startswith("%while") for name, _, _ in ops)


@pytest.mark.parametrize("kernel,seconds", [
    ("feature_matvec", 0.001113811), ("fused_pgrad", 0.00117681)])
def test_kernel_seconds_by_role(recorded, kernel, seconds):
    got = trace.op_seconds(recorded, lambda n: kernel in trace.label(n))
    assert got == pytest.approx(seconds, rel=1e-9)
    calls = [n for n, _, _ in recorded.ops["/device:TPU:0"]
             if kernel in trace.label(n)]
    assert len(calls) == 16             # 2 solves x 8 rounds


def test_busy_is_the_union_inside_the_window(recorded):
    busy = trace.busy_seconds(recorded)
    lo, hi = recorded.window()
    assert 0 < busy < (hi - lo) / 1e9
    assert busy == pytest.approx(0.002761978, rel=1e-9)


def test_top_ops_and_idle_gaps_are_named(recorded):
    top = trace.top_ops(recorded)
    assert [name for name, _ in top[:2]] == ["vmap_jit_fused_pgrad__",
                                             "vmap_jit_feature_matvec__"]
    assert len(top) == 10
    gaps = trace.idle_gaps(recorded)
    assert [name for name, _ in gaps[:2]] == ["bench.gap_to_host"] * 2
    assert all(s >= 0.010 for _, s in gaps[:2])
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)


def test_leaves_and_union_on_synthetic_intervals():
    evs = [("loop", 0, 100), ("a", 0, 30), ("b", 30, 60), ("c", 55, 70),
           ("d", 90, 120), ("kernel", 200, 300), ("tiny", 250, 251)]
    # the loop's body fills it; a kernel that holds one tiny op stays
    assert [n for n, _, _ in trace.leaves(evs)] == ["a", "b", "c", "d",
                                                    "kernel", "tiny"]
    assert trace.union(trace.leaves(evs), 5, 260) == [(5, 70), (90, 120),
                                                     (200, 260)]


@pytest.mark.parametrize("name,label", [
    ("%vmap_jit_feature_matvec__.7 = f32[4,4096,128]{2,1,0} custom-call("
     "f32[4,4096,512]{2,1,0} %pad.34)", "vmap_jit_feature_matvec__"),
    ("%pad.34 = f32[4,4096,512]{2,1,0} pad(f32[4,4096,500]{2,1,0} %x)",
     "pad"),
    ("%slice_reduce_fusion.2 = f32[4096]{0} fusion(f32[4,4096,128]{2,1,0} "
     "%vmap_jit_feature_matvec__.7)", "slice_reduce_fusion"),
])
def test_label_is_the_instruction_not_its_operands(name, label):
    assert trace.label(name) == label


def _synthetic(first_s, last_s, window_s=10.0):
    """A trace whose device ops run from ``first_s`` to ``last_s`` of a
    ``window_s`` window."""
    ns = 1e9
    ops = [("%fusion.1 = f32[8] fusion()", t * ns, (t + 0.01) * ns)
           for t in [first_s + k * (last_s - 0.01 - first_s) / 9
                     for k in range(10)]]
    return trace.Trace(ops={"/device:TPU:0": ops},
                       spans=[("bench.window", 0.0, window_s * ns)])


@pytest.mark.parametrize("first,last,covered", [
    (0.05, 9.9, True),            # ops from the start to the end
    (0.9, 9.2, True),             # late and early by less than a tenth
    (2.5, 9.9, False),            # the oldest events were dropped
    (0.05, 7.0, False),           # the newest were
])
def test_a_trace_that_starts_late_does_not_cover_its_window(first, last,
                                                            covered):
    t = _synthetic(first, last)
    assert trace.covers(t) is covered
    lead, tail = trace.coverage(t)
    assert lead == pytest.approx(first) and tail == pytest.approx(10 - last)


def test_the_recorded_trace_ends_in_its_host_gap(recorded):
    lead, tail = trace.coverage(recorded)
    gap = recorded.spans[-1]
    assert gap[0] == "bench.gap_to_host"
    assert lead == pytest.approx(4.1e-7, rel=1e-6)
    assert tail == pytest.approx(0.012387049, rel=1e-9)
    assert tail > (gap[2] - gap[1]) / 1e9    # the solve's host tail too


def test_no_trace_reads_no_busy_time_nor_idle_share():
    class _Run:
        device_trace = None
    assert trace.busy_and_window(_Run()) is None
    assert trace.idle_share(_Run()) is None


def test_an_untraced_run_never_starts_the_profiler(tmp_path):
    class _Run:
        trace, seconds, work_dir = False, 51.0, tmp_path / "w"
        device_trace = None
    run = _Run()
    with trace.window(run, tail_s=20) as mark:
        for elapsed in (0.0, 31.0, 40.0):
            mark(elapsed)
    assert run.device_trace is None and not run.work_dir.exists()
