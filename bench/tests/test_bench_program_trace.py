"""The program's spans and device scopes as the benchmark reads them
(``harness.program_trace``) and the per-layer readers built on them:
checked on synthetic traces written in the profiler's format, on the
trace recorded on a TPU v5e before the program had any
(``small-solve.xplane.pb``) and on one recorded there with them
(``small-program.xplane.pb``, made by ``record_program_trace.py``)."""
from __future__ import annotations

import os
import pathlib
import shutil

import pytest

from bench_testlib import BENCH
from harness import program_trace, trace
from harness.cells import load_module

DATA = pathlib.Path(__file__).resolve().parent / "data"
SERVE = ("admit_instance_ms.serve", "admit_cell_ms.serve",
         "ledger_replay_ms.serve", "compiles_per_spec.serve")
EPSILON = ("gap_ms.epsilon", "pad_ms.epsilon")
MS = 1e6                                      # ns


def reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py", f"metric_{name}")


class _Run:
    """What the readers take from a run."""

    def __init__(self, work_dir, covered=True, rounds=10, traced_from=0.0):
        self.work_dir = work_dir
        self.device_trace = object() if covered else None
        self.counters = {"rounds": rounds, "traced_from_s": traced_from}
        self.notes = []

    def note(self, line):
        self.notes.append(line)


def write_xplane(work_dir, spans=(), ops=(), window=(0.0, 1000 * MS)):
    """A trace as the profiler writes it: ``spans`` (name, start_ns,
    end_ns, args) on a host plane inside a ``bench.window`` span, ``ops``
    (op_name, start_ns, end_ns) on a TPU's ``XLA Ops`` line."""
    space = program_trace.parse(b"")
    ids = {}

    def stat_id(plane, name):
        if (plane.name, name) not in ids:
            ids[plane.name, name] = len(ids) + 1
            entry = plane.stat_metadata.add(key=ids[plane.name, name])
            entry.value.id, entry.value.name = ids[plane.name, name], name
        return ids[plane.name, name]

    def event_id(plane, name, stats=()):
        key = (plane.name, "event", name)
        if key not in ids:
            ids[key] = len(ids) + 1
            entry = plane.event_metadata.add(key=ids[key])
            entry.value.id, entry.value.name = ids[key], name
            for k, v in stats:
                entry.value.stats.add(metadata_id=stat_id(plane, k),
                                      ref_value=stat_id(plane, v))
        return ids[key]

    host = space.planes.add(name="/host:CPU")
    line = host.lines.add(name="python", timestamp_ns=0)
    for name, s, e, args in [("bench.window", *window, {})] + list(spans):
        ev = line.events.add(metadata_id=event_id(host, name),
                             offset_ps=int(s * 1e3),
                             duration_ps=int((e - s) * 1e3))
        for k, v in args.items():
            st = ev.stats.add(metadata_id=stat_id(host, k))
            if isinstance(v, str):
                st.ref_value = stat_id(host, v)
            elif isinstance(v, float):
                st.double_value = v
            else:
                st.int64_value = v
    device = space.planes.add(name="/device:TPU:0")
    ops_line = device.lines.add(name=trace.OPS_LINE, timestamp_ns=0)
    for i, (op_name, s, e) in enumerate(ops):
        meta = event_id(device, f"%op.{i} = f32[8] fusion()",
                        [(program_trace.OP_NAME_STAT, op_name)])
        ops_line.events.add(metadata_id=meta, offset_ps=int(s * 1e3),
                            duration_ps=int((e - s) * 1e3))
    out = pathlib.Path(work_dir) / "plugins" / "profile" / "t"
    out.mkdir(parents=True, exist_ok=True)
    (out / "host.xplane.pb").write_bytes(space.SerializeToString())
    return work_dir


def _admission(start, ticket, instance=40.0, cell=30.0, parse=1.0):
    """One admission's spans: ``repro.admit`` around its children, ms."""
    ms = lambda t: (start + t) * MS                               # noqa: E731
    ids = {"ticket": ticket}
    end = parse + instance + cell + 2.0
    return [("repro.admit", ms(0), ms(end), ids),
            ("repro.parse", ms(0), ms(parse), ids),
            ("repro.instance_build", ms(parse), ms(parse + instance), ids),
            ("repro.prepare_cell", ms(parse + instance),
             ms(parse + instance + cell), ids),
            ("repro.compile", ms(parse + instance + 5),
             ms(parse + instance + 5), {"span": "repro.cell.dist",
                                        "seconds": 0.004})]


def _read(names, run):
    return {n: reader(n).read(run) for n in names}


def test_no_covering_trace_reads_nothing(tmp_path):
    work = write_xplane(tmp_path, spans=_admission(10, "t000001"),
                        ops=[("jit(run)/repro.gap/dot", 0, MS)])
    got = _read(SERVE + EPSILON, _Run(work, covered=False))
    assert got == {n: None for n in SERVE + EPSILON}


def test_admission_split_counts_only_spans_inside_the_window(tmp_path):
    spans = (_admission(-20, "t000001")             # straddles the start
             + _admission(100, "t000002", instance=50.0, cell=20.0)
             + _admission(300, "t000003", instance=30.0, cell=40.0)
             + _admission(990, "t000004"))          # straddles the end
    spans += [("repro.ledger_replay", 200 * MS, 203 * MS, {}),
              ("repro.ledger_replay", 400 * MS, 401 * MS, {}),
              ("repro.release", 204 * MS, 205 * MS, {"ticket": "t000002"}),
              ("repro.release", 402 * MS, 403 * MS, {"ticket": "t000003"})]
    run = _Run(write_xplane(tmp_path, spans=spans))
    got = _read(SERVE, run)
    assert got["admit_instance_ms.serve"] == pytest.approx(40.0)
    assert got["admit_cell_ms.serve"] == pytest.approx(30.0)
    assert got["ledger_replay_ms.serve"] == pytest.approx(2.0)
    # every marker inside the window counts, the straddling admission's
    # too: 3 markers over 2 admissions
    assert got["compiles_per_spec.serve"] == pytest.approx(1.5)
    assert any("repro.cell.dist" in n for n in run.notes)
    t = program_trace.load(run)
    assert [s.args["ticket"] for s in t.named("repro.admit")] == [
        "t000002", "t000003"]
    assert t.named("repro.compile")[0].args["seconds"] == 0.004


def test_a_program_without_spans_reads_nothing(tmp_path):
    """The benchmark laid over a program older than its spans."""
    run = _Run(write_xplane(tmp_path, ops=[("jit(run)/dot", 0, MS)]))
    assert _read(SERVE, run) == {n: None for n in SERVE}


def test_scoped_device_time_per_round(tmp_path):
    ops = [("jit(run)/while/body/repro.gap/dot_general:", 10 * MS, 14 * MS),
           ("jit(run)/while/body/closed_call/vmap(jit(fused_pgrad))/"
            "jit(_pad)/repro.pad/pad:", 20 * MS, 30 * MS),
           ("jit(run)/while/body/repro.pad/pad:", 40 * MS, 42 * MS),
           ("jit(run)/while/body/feature_matvec:", 50 * MS, 57 * MS),
           ("jit(run)/while/body/repro.gap/reduce:", 995 * MS, 1010 * MS)]
    got = _read(EPSILON, _Run(write_xplane(tmp_path, ops=ops), rounds=4))
    # the gap's second op is clipped to the window's end
    assert got["gap_ms.epsilon"] == pytest.approx((4 + 5) / 4)
    assert got["pad_ms.epsilon"] == pytest.approx((10 + 2) / 4)


def test_no_scope_in_the_trace_reads_nothing(tmp_path):
    ops = [("jit(run)/while/body/pad:", 0, 10 * MS),
           ("jit(run)/while/body/dot_general:", 10 * MS, 20 * MS)]
    got = _read(EPSILON, _Run(write_xplane(tmp_path, ops=ops)))
    assert got == {n: None for n in EPSILON}


def test_a_scope_whose_ops_are_gone_reads_zero(tmp_path):
    ops = [("jit(run)/while/body/repro.gap/dot_general:", 0, 10 * MS)]
    got = _read(EPSILON, _Run(write_xplane(tmp_path, ops=ops)))
    assert got == {"gap_ms.epsilon": pytest.approx(1.0),
                   "pad_ms.epsilon": 0.0}


def test_a_trace_of_part_of_the_window_reads_no_device_time(tmp_path):
    ops = [("jit(run)/repro.gap/dot", 0, 10 * MS)]
    run = _Run(write_xplane(tmp_path, ops=ops), traced_from=31.0)
    assert _read(EPSILON, run) == {n: None for n in EPSILON}


def test_the_newest_trace_of_the_work_dir_is_read(tmp_path):
    old = write_xplane(tmp_path / "old", spans=_admission(10, "t000001"))
    new = write_xplane(tmp_path / "new", spans=_admission(10, "t000002"))
    shutil.copy(next(old.glob("plugins/profile/t/*.xplane.pb")),
                new / "plugins" / "profile" / "t" / "older.xplane.pb")
    older = new / "plugins" / "profile" / "t" / "older.xplane.pb"
    os.utime(older, (1, 1))
    t = program_trace.load(_Run(new))
    assert t.named("repro.admit")[0].args["ticket"] == "t000002"


# --------------------------------------------------------------------------
# Recorded on a TPU v5e
# --------------------------------------------------------------------------

def test_op_names_of_a_trace_without_scopes():
    """``small-solve.xplane.pb`` (two 8-round dagd solves, before the
    program had spans or scopes): every device op that ``harness.trace``
    counts, at the same times, each with its HLO ``op_name``."""
    got = program_trace.read_file(DATA / "small-solve.xplane.pb")
    from jax.profiler import ProfileData
    ref = trace.from_profile(ProfileData.from_file(
        str(DATA / "small-solve.xplane.pb")))
    ops = got.device_ops()["/device:TPU:0"]
    assert len(ops) == len(ref.ops["/device:TPU:0"]) == 386
    assert [t for _, s, e in ops for t in (s, e)] == pytest.approx(
        [t for _, s, e in ref.ops["/device:TPU:0"] for t in (s, e)],
        rel=0, abs=2.0)                 # ProfileData keeps whole ns
    names = [n for n, _, _ in ops]
    for kernel in ("fused_pgrad", "feature_matvec"):
        assert names.count("jit(run)/while/body/closed_call/"
                           f"vmap(jit({kernel}))/pallas_call:") == 16
    assert all(n.startswith("jit(") for n in names if n)
    assert not got.scoped() and not got.spans


@pytest.fixture(scope="module")
def program():
    """``small-program.xplane.pb``: two 8-round dagd solves with the
    in-scan gap (composed oracles) and a service admitting, running and
    releasing two Theorem 2 specs, recorded on a TPU v5e by
    ``record_program_trace.py``."""
    return program_trace.read_file(DATA / "small-program.xplane.pb")


def test_recorded_spans_nest_and_carry_their_tickets(program):
    admits = program.named("repro.admit")
    assert [a.args["ticket"] for a in admits] == ["t000001", "t000002"]
    for a in admits:
        inside = [s for s in program.spans if s is not a
                  and a.start <= s.start and s.end <= a.end]
        names = {s.name for s in inside}
        assert {"repro.parse", "repro.plan", "repro.instance_build",
                "repro.instance.data", "repro.prepare_cell",
                "repro.cell.dist", "repro.cell.trace"} <= names
        assert all(s.args.get("ticket") == a.args["ticket"]
                   for s in inside if s.name != program_trace.COMPILE_MARKER)
        children = sum(s.ms for s in inside if s.name in (
            "repro.parse", "repro.plan", "repro.instance_build",
            "repro.prepare_cell"))
        assert children >= 0.9 * a.ms
    assert len(program.named("repro.execute")) == 2
    for name in ("repro.execute_group", "repro.verdicts", "repro.release",
                 "repro.runner", "repro.run", "repro.ledger_replay"):
        assert program.named(name), name
    (group,) = program.named("repro.execute_group")
    assert group.args["width"] == 2 and "key" in group.args


def test_recorded_compiles_name_their_span(program):
    markers = program.named(program_trace.COMPILE_MARKER)
    assert markers
    assert all(m.args["span"].startswith("repro.") and m.args["seconds"] >= 0
               for m in markers)
    first = program.named("repro.admit")[0]
    assert any(first.start <= m.start <= first.end for m in markers)


def test_recorded_scopes_and_kernel_names_reach_the_device(program):
    names = [n for evs in program.device_ops().values() for n, _, _ in evs]
    assert program.scoped()
    assert any("repro.gap" in n for n in names)
    assert any("repro.pad" in n for n in names)
    for kernel in ("feature_matvec", "fused_pgrad", "fused_round_step"):
        assert any(kernel in n for n in names), kernel


def test_readers_on_the_recorded_trace(tmp_path):
    profile = tmp_path / "plugins" / "profile" / "t"
    profile.mkdir(parents=True)
    shutil.copy(DATA / "small-program.xplane.pb", profile)
    run = _Run(tmp_path, rounds=2 * 8)
    got = _read(SERVE + EPSILON, run)
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["gap_ms.epsilon"] > 0 and got["pad_ms.epsilon"] > 0
    assert got["admit_instance_ms.serve"] > 0
    assert got["admit_cell_ms.serve"] > 0
    assert got["compiles_per_spec.serve"] > 0
