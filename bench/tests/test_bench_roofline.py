"""The work of a round and of each kernel role, from the configuration's
shapes, and the readers that turn it into shares of the peak."""
from __future__ import annotations

import pathlib

import pytest

from harness import roofline, trace
from harness.cells import load_cell, peaks_for
from harness.state import Run

V5E = peaks_for("TPU v5 lite")
DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_epsilon_pass_is_bytes_bound():
    ops, nbytes = roofline.dense_pass(400_000, 2_000)
    assert ops == 1.6e9                        # 2 n d
    assert nbytes == 4 * (800_000_000 + 402_000)    # 3.2 GB + vectors
    least = roofline.least_seconds(ops, nbytes, V5E)
    assert least == pytest.approx(3_201_608_000 / 819e9)    # 3.909 ms
    assert roofline.bound_by(ops, nbytes, V5E) == "bytes"
    tiny_ops, tiny_bytes = roofline.dense_pass(16, 16)
    assert roofline.least_seconds(1e15, tiny_bytes, V5E) == 1e15 / 197e12
    assert roofline.bound_by(1e15, tiny_bytes, V5E) == "operations"
    assert tiny_ops == 512


def _run(counters, device_trace=None, n=400_000, d=2_000):
    cell = load_cell("epsilon-dagd")
    cell.config = dict(cell.config,
                       instance_params=dict(cell.config["instance_params"],
                                            n=n, d=d))
    return Run(cell=cell, seed=0, seconds=1.0, trace=True, start=0.0,
               work_dir=DATA, peaks=V5E, counters=counters,
               device_trace=device_trace)


def test_round_mfu_is_rate_times_least_round_time():
    run = _run({"rounds": 600, "window_s": 18.6})
    got = load_cell("epsilon-dagd").reader("round_mfu.epsilon").read(run)
    assert got == pytest.approx(100 * (3_201_608_000 / 819e9) * 600 / 18.6)
    assert 0 < got < 100
    assert load_cell("epsilon-dagd").reader("round_mfu.epsilon").read(
        _run({})) is None


@pytest.mark.parametrize("metric,seconds", [
    ("feature_matvec_roofline", 0.001113811),
    ("fused_pgrad_roofline", 0.00117681)])
def test_kernel_roofline_on_the_recorded_trace(metric, seconds):
    from jax.profiler import ProfileData
    recorded = trace.from_profile(ProfileData.from_file(
        str(DATA / "small-solve.xplane.pb")))
    run = _run({"rounds": 16}, recorded, n=4096, d=2000)
    got = load_cell("epsilon-dagd").reader(metric).read(run)
    least = 4 * (4096 * 2000 + 4096 + 2000) / 819e9
    assert got == pytest.approx(100 * least * 16 / seconds)
    assert 0 < got <= 100
    empty = trace.Trace(ops={"/device:TPU:0": []}, spans=recorded.spans)
    assert load_cell("epsilon-dagd").reader(metric).read(
        _run({"rounds": 16}, empty)) is None
    # a trace of the window's tail does not hold the window's rounds
    assert load_cell("epsilon-dagd").reader(metric).read(
        _run({"rounds": 16, "traced_from_s": 31.0}, recorded,
             n=4096, d=2000)) is None
