"""The four-chip cell ``ocr-dagd-x4`` (PASCAL ``ocr``, 3,500,000 x 1,156
f32, one 289-column block a chip): found by name, placed ``sharded`` by
the program from what it observes, and its per-layer readers checked on
synthetic four-chip traces written in the profiler's format."""
from __future__ import annotations

import pathlib

import pytest

from harness import program_trace, roofline, trace
from harness.cells import load_cell, peaks_for

MS = 1e6                                      # ns
CELL = "ocr-dagd-x4"
V5E = peaks_for("TPU v5 lite")
V5E_BYTES_LIMIT = 16_909_334_528              # memory_stats() of one v5e
CHIPS = [f"/device:TPU:{i}" for i in range(4)]
REDUCE_ALL = ("jit(run)/while/body/shard_map/comm[i=0;r=1;k=reduce_all;"
              "d=m2a;s=3500000;t=float32;b=32;w=identity;g=z]/psum:")
GAP = "jit(run)/while/body/shard_map/repro.gap/reduce_sum:"
KERNEL = "jit(run)/while/body/shard_map/feature_matvec:"


class _Run:
    """What the readers take from a run of the cell."""

    def __init__(self, work_dir=None, device_trace=None, rounds=10,
                 window_s=1.0, traced_from=0.0, phases=None):
        self.cell = load_cell(CELL)
        self.work_dir = work_dir
        self.device_trace = device_trace
        self.counters = {"rounds": rounds, "window_s": window_s,
                         "traced_from_s": traced_from}
        self.peaks = V5E
        self.phases = phases or {}

    def note(self, line):
        pass


def _read(name, run):
    return run.cell.reader(name).read(run)


def write_xplane(work_dir, ops_by_chip, window=(0.0, 1000 * MS)):
    """A trace as the profiler writes it: a ``bench.window`` span on the
    host plane, and on each chip's ``XLA Ops`` line its ops, each
    (op_name, start_ns, end_ns)."""
    space = program_trace.parse(b"")

    def add(table, key, name):
        entry = table.add(key=key)
        entry.value.id, entry.value.name = key, name
        return entry

    host = space.planes.add(name="/host:CPU")
    add(host.event_metadata, 1, "bench.window")
    host.lines.add(name="python", timestamp_ns=0).events.add(
        metadata_id=1, offset_ps=int(window[0] * 1e3),
        duration_ps=int((window[1] - window[0]) * 1e3))
    for chip, ops in ops_by_chip.items():
        plane = space.planes.add(name=chip)
        add(plane.stat_metadata, 1, program_trace.OP_NAME_STAT)
        line = plane.lines.add(name=trace.OPS_LINE, timestamp_ns=0)
        for i, (op_name, s, e) in enumerate(ops):
            add(plane.event_metadata, 100 + i, f"%op.{i} = f32[8] fusion()"
                ).value.stats.add(metadata_id=1, ref_value=1000 + i)
            add(plane.stat_metadata, 1000 + i, op_name)
            line.events.add(metadata_id=100 + i, offset_ps=int(s * 1e3),
                            duration_ps=int((e - s) * 1e3))
    out = pathlib.Path(work_dir) / "plugins" / "profile" / "t"
    out.mkdir(parents=True, exist_ok=True)
    (out / "host.xplane.pb").write_bytes(space.SerializeToString())
    return work_dir


def test_ocr_cell_is_found_by_name():
    cell = load_cell(CELL)
    assert cell.chips == 4 and cell.config_name == "ocr-logistic"
    assert cell.config["instance"] == "logistic"
    assert cell.config["instance_params"] == dict(
        n=3_500_000, d=1_156, m=4, lam=1e-6, ref_iters=500)
    assert cell.config["precision"] == "highest"
    assert cell.traffic == load_cell("epsilon-dagd").traffic
    assert cell.traffic["driver"] == "solve_loop"
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                    "rounds_per_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "feature_matvec_roofline", "fused_pgrad_roofline",
        "reduce_all_ms.ocr", "round_mfu.ocr", "gap_ms.ocr",
        "device_idle.ocr", "instance_build_s.ocr"}


def test_ocr_resolves_sharded_on_a_four_chip_v5e(monkeypatch):
    """``auto`` shards ``ocr`` (16.2 GB and its tile copy above one
    v5e's memory, four devices) and keeps ``epsilon`` local."""
    from repro.api import _resolve
    from repro.experiments.instances import instance_shape
    monkeypatch.setattr(_resolve, "device_bytes_limit",
                        lambda: V5E_BYTES_LIMIT)
    caps = {"devices": 4}
    for cell, want in ((CELL, "sharded"), ("epsilon-dagd", "local")):
        cfg = load_cell(cell).config
        shape = instance_shape(cfg["instance"], cfg["instance_params"])
        assert _resolve.resolve_placement(
            "auto", shape=shape, caps=caps) == want, cell
    ocr = instance_shape("logistic",
                         load_cell(CELL).config["instance_params"])
    assert _resolve.resolve_placement("auto", shape=ocr,
                                      caps={"devices": 1}) == "local"


def _ocr_trace(tmp_path):
    """Four chips, four rounds: each chip runs its ReduceAll, its gap
    ops and its kernel, a chip's times differing from the next."""
    return write_xplane(tmp_path, {
        chip: [(REDUCE_ALL, 10 * MS, (10 + 0.5 * (i + 1)) * MS),
               (GAP, 20 * MS, (20 + 2 * (i + 1)) * MS),
               (KERNEL, 40 * MS, 60 * MS),
               (REDUCE_ALL, 990 * MS, 1010 * MS)]    # clipped at the end
        for i, chip in enumerate(CHIPS)})


def test_reduce_all_and_gap_are_per_chip_per_round(tmp_path):
    run = _Run(_ocr_trace(tmp_path), device_trace=object(), rounds=4)
    # per chip, the ReduceAll's ops: 0.5 (i + 1) + 10 ms clipped
    reduce_all = sum(0.5 * (i + 1) + 10 for i in range(4)) / 4 / 4
    gap = sum(2 * (i + 1) for i in range(4)) / 4 / 4
    assert _read("reduce_all_ms.ocr", run) == pytest.approx(reduce_all)
    assert _read("gap_ms.ocr", run) == pytest.approx(gap)
    # the measure's psum is not the metered ReduceAll
    assert "k=reduce_all" not in GAP


@pytest.mark.parametrize("name", ["reduce_all_ms.ocr", "gap_ms.ocr"])
def test_ocr_scopes_read_nothing_without_a_covering_scoped_trace(
        tmp_path, name):
    scoped = _ocr_trace(tmp_path / "scoped")
    bare = write_xplane(tmp_path / "bare", {
        chip: [("jit(run)/while/body/psum:", 0, MS)] for chip in CHIPS})
    assert _read(name, _Run(scoped, device_trace=None)) is None
    assert _read(name, _Run(scoped, device_trace=object(),
                            traced_from=31.0)) is None
    assert _read(name, _Run(bare, device_trace=object())) is None


def test_round_mfu_divides_by_the_chips():
    run = _Run(rounds=1_200, window_s=51.243)
    p = run.cell.config["instance_params"]
    one_chip = 100 * roofline.least_seconds(
        *roofline.dense_pass(p["n"], p["d"]), V5E) * 1_200 / 51.243
    got = _read("round_mfu.ocr", run)
    assert got == pytest.approx(one_chip / 4)
    assert 0 < got < 100
    run.cell.chips = 1
    assert _read("round_mfu.ocr", run) == pytest.approx(one_chip)
    assert _read("round_mfu.ocr", _Run(rounds=0)) is None


def test_device_idle_is_averaged_over_the_chips():
    window = [("bench.window", 0.0, 100 * MS)]
    busy = {chip: [("op", 0.0, (10 + 10 * i) * MS)]
            for i, chip in enumerate(CHIPS)}          # 10, 20, 30, 40 ms
    run = _Run(device_trace=trace.Trace(ops=busy, spans=window))
    assert _read("device_idle.ocr", run) == pytest.approx(75.0)
    assert _read("device_idle.ocr", _Run()) is None


@pytest.mark.parametrize("metric", ["feature_matvec_roofline",
                                    "fused_pgrad_roofline"])
def test_kernel_roofline_is_a_per_chip_share(metric):
    """Device time summed over four chips against the whole matrix's pass
    at one chip's peak: the share of one chip's own block and peak, but
    for the R^n vector each chip reads (0.26% here)."""
    kernel = metric[:-len("_roofline")]
    busy = {chip: [(f"%c = custom-call() kernel_name=\"{kernel}\"", 0.0,
                    4 * 13.33 * MS)] for chip in CHIPS}
    run = _Run(device_trace=trace.Trace(
        ops=busy, spans=[("bench.window", 0.0, 100 * MS)]), rounds=4)
    p = run.cell.config["instance_params"]
    whole = roofline.least_seconds(*roofline.dense_pass(p["n"], p["d"]), V5E)
    got = _read(metric, run)
    assert got == pytest.approx(100 * whole * 4 / (4 * 4 * 13.33e-3))
    per_chip = roofline.least_seconds(
        *roofline.dense_pass(p["n"], p["d"] // 4), V5E)
    assert got == pytest.approx(100 * per_chip / 13.33e-3, rel=3e-3)


def test_instance_build_reads_its_set_up_phase():
    assert _read("instance_build_s.ocr",
                 _Run(phases={"instance_build": 9.04})) == 9.04
    assert _read("instance_build_s.ocr", _Run()) is None
