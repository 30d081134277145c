"""The open-loop service traffic: the generator, the whole-window
latency and rate, the stream checks, and a whole run of the thm2 cell's
path on the CPU at a small size, sound and with faults planted."""
from __future__ import annotations

import collections
import dataclasses
import json

import numpy as np
import pytest

from bench_testlib import load_run_module
from harness import serve_loop as S

STRUCTURES = [["dagd", "identity"], ["dgd", "identity"], ["dagd", "fp16"]]


def _schedule(seed, rate=4.0, seconds=40.0):
    return S.arrivals(seed, rate, seconds, 4, [16.0, 256.0], STRUCTURES)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_arrivals_are_the_same_work_in_another_order(seed):
    got, again, base = _schedule(seed), _schedule(seed), _schedule(1)
    assert got == again
    assert len(got) == 160
    assert [a.t for a in got] == sorted(a.t for a in got) and got[0].t == 0
    # the same arrival times, kappas and structures as any seed
    assert [a.t for a in got] == [a.t for a in base]
    assert sorted(a.kappa for a in got) == sorted(a.kappa for a in base)
    assert collections.Counter((a.algorithm, a.channel) for a in got) == \
        collections.Counter((a.algorithm, a.channel) for a in base)
    assert all(16.0 <= a.kappa <= 256.0 for a in got)
    assert [a.client for a in got[:5]] == ["c0", "c1", "c2", "c3", "c0"]
    if seed != 1:
        assert got != base


def test_mean_gap_is_the_rate():
    gaps = np.diff([a.t for a in _schedule(3, rate=5.0, seconds=200.0)])
    assert gaps.mean() == pytest.approx(1 / 5.0, rel=0.05)


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert S.percentile(values, 95) == 95
    assert S.percentile(values, 90) == 90
    assert S.percentile([3.0], 90) == 3.0
    assert S.percentile(list(range(1, 11)), 90) == 9


@dataclasses.dataclass
class _Env:
    ticket: str
    client_id: str
    seq: int
    status: str = "ok"


def test_tail_and_rate_take_every_spec_late_and_drained_too():
    """Latency runs from when a spec was due; a spec drained after the
    window still counts, and the rate spans the whole window."""
    due = {f"t{i}": (10.0 + i, None) for i in range(20)}
    released = [(_Env(f"t{i}", "c0", i), 10.0 + i + 0.1) for i in range(19)]
    released.append((_Env("t19", "c0", 19), 10.0 + 19 + 5.0))   # drained
    released.append((_Env("warm", "warm", 0), 9.0))      # set-up, not due
    w = S.Window(released, due, [], [], [], start=10.0, end=34.0)
    kept, latencies, rate, tail = S.summarize(w, 95)
    assert len(kept) == 20 and max(latencies) == pytest.approx(5.0)
    assert tail == pytest.approx(0.1)                        # 19th of 20
    kept, latencies, rate, tail = S.summarize(w, 99)
    assert tail == pytest.approx(5.0)
    assert rate == pytest.approx(20 / 24.0)


def test_stream_errors_count_loss_duplication_and_reordering():
    due = {t: None for t in ("a", "b", "c", "d")}
    good = [_Env("a", "x", 0), _Env("b", "y", 0), _Env("c", "x", 1),
            _Env("d", "y", 1)]
    assert S._stream_errors(good, due) == 0
    assert S._stream_errors(good[:3], due) == 1                  # lost
    assert S._stream_errors(good + [good[0]], due) == 2          # dup+order
    assert S._stream_errors([good[2], good[1], good[0], good[3]], due) == 1


ARGS = ["--workload", "thm2-serve", "--seed", "2147483653",
        "--seconds", "1", "--trace", "0"]


def _run(root, capsys):
    rc = load_run_module().main(ARGS, require_chip=False, root=root)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    result = json.loads(out.out.strip().splitlines()[-1])
    assert list(result)[-1] == "checks"
    return result


def test_sound_service_run_is_correct(tiny_root, capsys):
    result = _run(tiny_root, capsys)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] == 20 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "specs_per_s"}


def _certified_flipped(env):
    v = dict(env.verdicts[0], certified=not env.verdicts[0]["certified"])
    return [dataclasses.replace(env, verdicts=[v])]


def _lost(env):
    return []


def _rounds_altered(env):
    v = dict(env.verdicts[0], measured_rounds=None)      # eps "unreached"
    return [dataclasses.replace(env, verdicts=[v])]


def _state_unchanged(env):
    return [dataclasses.replace(
        env, result=dataclasses.replace(env.result, w=env.result.w * 0.0))]


@pytest.mark.parametrize("alter", [_certified_flipped, _lost,
                                   _rounds_altered, _state_unchanged],
                         ids=["certified_flipped", "lost", "rounds_altered",
                              "state_unchanged"])
def test_faulty_service_run_is_not_correct(tiny_root, capsys, monkeypatch,
                                           alter):
    """The first envelope the window's clients get, altered (or lost)
    where the service releases it."""
    from repro.serve.service import CertificationService
    done = []

    def wrap(real):
        def released(self, now):
            out = []
            for env in real(self, now):
                if env.client_id != "warm" and not done:
                    done.append(env)
                    out.extend(alter(env))
                else:
                    out.append(env)
            return out
        return released

    for name in ("step", "drain"):
        monkeypatch.setattr(CertificationService, name,
                            wrap(getattr(CertificationService, name)))
    assert _run(tiny_root, capsys)["correct"] is False
    assert done


def test_exchange_left_out_is_not_correct(tiny_root, capsys, monkeypatch):
    from repro.core import comm
    real = comm.LocalCommunicator.reduce_all

    def local_only(self, x_stacked, tag="", pretransformed=False):
        real(self, x_stacked, tag=tag, pretransformed=pretransformed)
        return x_stacked[0]

    monkeypatch.setattr(comm.LocalCommunicator, "reduce_all", local_only)
    assert _run(tiny_root, capsys)["correct"] is False
