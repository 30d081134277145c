"""The reader of ``gemv_ms.epsilon``: device milliseconds a round of the
ops under the program's ``repro.gemv`` scope (the oracle kernels' B = 1
body), checked on synthetic traces written in the profiler's format and
on the trace recorded on a TPU v5e before the program had that body
(``small-program.xplane.pb``)."""
from __future__ import annotations

import shutil

import pytest

from test_bench_program_trace import DATA, MS, _Run, reader, write_xplane

GEMV = "gemv_ms.epsilon"
B1 = "jit(run)/while/body/closed_call/vmap(jit({0}))/repro.gemv/{0}/" \
     "pallas_call:"


def test_gemv_ms_is_the_b1_kernels_time_per_round(tmp_path):
    ops = [(B1.format("feature_matvec"), 10 * MS, 14 * MS),
           (B1.format("fused_pgrad"), 20 * MS, 25 * MS),
           ("jit(run)/while/body/repro.gap/reduce:", 30 * MS, 34 * MS),
           ("jit(run)/while/body/feature_matvec:", 40 * MS, 47 * MS),
           (B1.format("fused_pgrad"), 995 * MS, 1010 * MS)]
    run = _Run(write_xplane(tmp_path, ops=ops), rounds=4)
    # the last op is clipped to the window's end; the unscoped kernel
    # (a B > 1 call, the MXU body) does not count
    assert reader(GEMV).read(run) == pytest.approx((4 + 5 + 5) / 4)


@pytest.mark.parametrize("scope", ["repro.gap", "repro.pad"])
def test_gemv_ms_reads_zero_where_only_other_scopes_reach_the_trace(
        tmp_path, scope):
    """A program without the B = 1 body (the parent of the change that
    brought it) carries the other scopes: it reads 0, not nothing."""
    ops = [(f"jit(run)/while/body/{scope}/dot_general:", 0, 10 * MS),
           ("jit(run)/while/body/closed_call/vmap(jit(feature_matvec))/"
            "pallas_call:", 10 * MS, 20 * MS)]
    run = _Run(write_xplane(tmp_path, ops=ops))
    assert reader(GEMV).read(run) == 0.0


@pytest.mark.parametrize("case", ["not covered", "tail only", "no scope"])
def test_gemv_ms_reads_nothing_without_a_whole_window_scoped_trace(
        tmp_path, case):
    scoped = [(B1.format("fused_pgrad"), 0, 10 * MS)]
    ops = [("jit(run)/while/body/fused_pgrad:", 0, 10 * MS)] \
        if case == "no scope" else scoped
    run = _Run(write_xplane(tmp_path, ops=ops),
               covered=case != "not covered",
               traced_from=31.0 if case == "tail only" else 0.0)
    assert reader(GEMV).read(run) is None


def test_gemv_ms_on_the_recorded_trace_of_a_program_without_it(tmp_path):
    """``small-program.xplane.pb`` was recorded before the B = 1 body:
    its kernels ran the MXU body under ``repro.pad`` and no op carries
    ``repro.gemv``."""
    profile = tmp_path / "plugins" / "profile" / "t"
    profile.mkdir(parents=True)
    shutil.copy(DATA / "small-program.xplane.pb", profile)
    assert reader(GEMV).read(_Run(tmp_path, rounds=2 * 8)) == 0.0
