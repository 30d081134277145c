"""A whole run of the epsilon cell's path on the CPU at a small size,
with the chip check skipped: sound, it reads ``correct``; with the timed
path broken underneath, once for each fault the cell can have, it does
not."""
from __future__ import annotations

import dataclasses
import json
import re

import numpy as np
import pytest

from bench_testlib import TINY_ROUNDS, load_run_module

ARGS = ["--workload", "epsilon-dagd", "--seed", "2147483653",
        "--seconds", "0.5", "--trace", "0"]


def _run(root, capsys, with_err=False):
    rc = load_run_module().main(ARGS, require_chip=False, root=root)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    result = json.loads(out.out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith("check ")
    return (result, out.err) if with_err else result


def test_sound_run_is_correct_and_rates_the_whole_window(tiny_root, capsys):
    result, err = _run(tiny_root, capsys, with_err=True)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"setup_s", "rounds_per_s"}
    for check in result["checks"].values():
        assert check["value"] <= check["limit"]
    # every round of every solve over the whole window, the last solve's
    # overrun past --seconds included
    solves, rounds, seconds = re.search(
        r"window: (\d+) solves, (\d+) rounds in ([\d.]+) s", err).groups()
    assert int(solves) == result["attempted"]
    assert int(rounds) == int(solves) * TINY_ROUNDS
    assert float(seconds) >= 0.5
    assert result["metrics"]["rounds_per_s"]["value"] == pytest.approx(
        int(rounds) / float(seconds), rel=1e-3)


def _state_unchanged(res):
    return dataclasses.replace(res, w=res.w * 0.0,
                               gaps=np.full_like(res.gaps, res.gaps[0]))


def _answer_altered(res):
    w = np.asarray(res.w).copy()
    w[0] += 1e-3 * np.max(np.abs(w))
    return dataclasses.replace(res, w=w)


@pytest.mark.parametrize("alter", [_state_unchanged, _answer_altered],
                         ids=["state_unchanged", "answer_altered"])
def test_altered_result_is_not_correct(tiny_root, capsys, monkeypatch,
                                       alter):
    """Each solve's result altered where ``ExecutionPlan.execute``
    produces it."""
    from repro.api.plan import ExecutionPlan
    real = ExecutionPlan.execute
    monkeypatch.setattr(ExecutionPlan, "execute",
                        lambda self, *a, **k: alter(real(self, *a, **k)))
    assert _run(tiny_root, capsys)["correct"] is False


def test_half_the_rows_left_out_is_not_correct(tiny_root, capsys,
                                               monkeypatch):
    """The loss term over half of the rows, the mean taken over the
    rest."""
    from repro.core import runtime
    real = runtime.LocalDistERM._loss_term

    def half(self, which, z):
        term = real(self, which, z)
        keep = (np.arange(term.shape[0]) % 2 == 0).astype(np.float32)
        return term * keep * 2.0

    monkeypatch.setattr(runtime.LocalDistERM, "_loss_term", half)
    assert _run(tiny_root, capsys)["correct"] is False


def test_exchange_left_out_is_not_correct(tiny_root, capsys, monkeypatch):
    """The ReduceAll metered but not performed: each round uses machine
    0's summand alone."""
    from repro.core import comm
    real = comm.LocalCommunicator.reduce_all

    def local_only(self, x_stacked, tag="", pretransformed=False):
        real(self, x_stacked, tag=tag, pretransformed=pretransformed)
        return x_stacked[0]

    monkeypatch.setattr(comm.LocalCommunicator, "reduce_all", local_only)
    assert _run(tiny_root, capsys)["correct"] is False
