"""The channel layer: quantizer round-trip/unbiasedness properties and
the identity-channel invariants the certification harness leans on.

Two contracts are pinned here.  (1) The transforms themselves: casts
round-trip within their precision, int8 stochastic rounding is unbiased
given uniform offsets and lands on the scale grid, top-k keeps exactly k
survivors, and the wire-bit arithmetic is pure shape x dtype math.
(2) The identity channel is *invisible*: with ``channel="identity"``
every ledger stream — legacy tuple and typed tail alike — is
bit-identical to the default build across the {python, scan} x
{einsum, kernel} product, so nothing under ``docs/results/`` can depend
on the channel subsystem existing.

Property tests use hypothesis when installed; otherwise the
deterministic fallback shim in ``tests/_hypothesis_fallback.py`` replays
a fixed spread of examples.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.channel import (CHANNELS, Channel, _hash_uniform,
                                parse_channel, stochastic_round)
from repro.core.engine import ENGINES, run_program
from repro.core.runtime import ORACLE_BACKENDS, LocalDistERM
from repro.experiments.instances import build_instance
from repro.experiments.registry import get_algorithm


def _payload(n, seed, scale=1.0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(n).astype(np.float32) * scale)


# --------------------------------------------------------------------------
# parse/registry
# --------------------------------------------------------------------------

def test_parse_channel_names_and_canonicalization():
    assert parse_channel(None).name == "identity"
    assert parse_channel("identity").lossless
    assert parse_channel("topk").name == "topk:0.1"
    assert parse_channel("topk:0.25").rho == 0.25
    ch = parse_channel("int8")
    assert parse_channel(ch) is ch              # Channel passes through
    for bad in ("zip", "fp8", "topk:0", "topk:1.5", "int8:7"):
        with pytest.raises(ValueError):
            parse_channel(bad)


def test_parse_channel_error_messages():
    """The error paths name the actual problem, not a generic list."""
    with pytest.raises(ValueError, match="empty topk keep fraction"):
        parse_channel("topk:")
    # a bare stage that lost its "sched:" prefix gets pointed at it
    with pytest.raises(ValueError, match="did you mean 'sched:int8@5'"):
        parse_channel("int8@5")
    with pytest.raises(ValueError, match="empty schedule"):
        parse_channel("sched:")
    with pytest.raises(ValueError, match="doubled or trailing comma"):
        parse_channel("sched:int8@0,,fp16@5")
    with pytest.raises(ValueError, match="missing"):
        parse_channel("sched:int8")


def test_resolve_channel_env_errors_name_the_env_var(monkeypatch):
    """A typo'd REPRO_CHANNEL must not surface as a caller error."""
    from repro.api import _resolve
    monkeypatch.setenv(_resolve.CHANNEL_ENV, "topk:")
    with pytest.raises(ValueError, match="REPRO_CHANNEL"):
        _resolve.resolve_channel(None)
    # an explicit argument wins over the env var and keeps the plain error
    monkeypatch.setenv(_resolve.CHANNEL_ENV, "int8")
    assert _resolve.resolve_channel("fp16") == "fp16"
    with pytest.raises(ValueError) as ei:
        _resolve.resolve_channel("nope")
    assert "REPRO_CHANNEL" not in str(ei.value)


def test_channel_lists_mirror_api_resolver():
    """core.channel owns the catalogue; the leaf resolver mirrors it."""
    from repro.api import _resolve
    assert _resolve.CHANNELS == CHANNELS
    assert _resolve.resolve_channel(None) == "identity"
    assert _resolve.resolve_channel("topk") == "topk:0.1"
    with pytest.raises(ValueError):
        _resolve.resolve_channel("nope")


def test_resolve_channel_env_var(monkeypatch):
    from repro.api import CHANNEL_ENV, _resolve
    monkeypatch.setenv(CHANNEL_ENV, "fp16")
    assert _resolve.resolve_channel(None) == "fp16"
    assert _resolve.resolve_channel("int8") == "int8"   # explicit wins
    monkeypatch.delenv(CHANNEL_ENV)
    assert _resolve.resolve_channel(None) == "identity"


# --------------------------------------------------------------------------
# transform properties
# --------------------------------------------------------------------------

@given(n=st.integers(4, 300), seed=st.integers(0, 99),
       scale=st.floats(1e-3, 1e3))
@settings(max_examples=6, deadline=None)
def test_half_precision_roundtrip_and_idempotence(n, seed, scale):
    x = _payload(n, seed, scale)
    for name, rel in (("fp16", 1e-3), ("bf16", 8e-3)):
        ch = parse_channel(name)
        y = ch.apply(x)
        np.testing.assert_allclose(y, x, rtol=rel, atol=rel * scale)
        np.testing.assert_array_equal(ch.apply(y), y)   # idempotent


def _f32(bits):
    return np.asarray(bits, dtype=np.uint32).view(np.float32)


def _same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got.view(np.uint32)[~nan],
                                  want.view(np.uint32)[~nan])


# 0, -0, f32 and half subnormals, the half normal/subnormal edge, +-max
# half (65504), the overflow edge (65519.996 rounds down, 65520 up),
# +-inf and NaN, plus random bit patterns across every exponent.
_HALF_EDGES = np.concatenate([
    np.array([0.0, -0.0, 1e-45, -1e-45, 1e-39, 2.0 ** -25, 2.0 ** -24,
              1.5 * 2.0 ** -24, 2.5 * 2.0 ** -24, 3 * 2.0 ** -25,
              2.0 ** -14, 2.0 ** -14 * (1 - 2.0 ** -11), 6.1e-5, 1.0,
              1.0 + 2.0 ** -11, 1.0 + 3 * 2.0 ** -11, 65504.0, -65504.0,
              65519.996, 65520.0, -65520.0, 1e6, -3.4e38, np.inf, -np.inf,
              np.nan], np.float32),
    _f32(np.random.RandomState(0).randint(0, 2 ** 32, 200_000,
                                          dtype=np.uint64)),
])


def test_fp16_transform_matches_the_half_cast_bit_for_bit():
    """The fp16 wire rounds to the half grid with int32 ops (Mosaic has
    no f16 vector cast); it must equal the plain cast it replaced."""
    x = jnp.asarray(_HALF_EDGES)
    _same_bits(jax.jit(parse_channel("fp16").apply)(x),
               x.astype(jnp.float16).astype(jnp.float32))


def test_int8_uniforms_match_the_unsigned_cast_bit_for_bit():
    """The hash uniforms go through int32 before f32 (Mosaic converts
    only signed ints); below 2^24 that is the same value as the old
    uint32 -> f32 cast."""
    x = jnp.asarray(_HALF_EDGES)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    h = bits ^ jnp.uint32(0x9E3779B9)
    h = (h ^ (h >> 16)) * jnp.uint32(0x45D9F3B)
    h = (h ^ (h >> 16)) * jnp.uint32(0x45D9F3B)
    h = h ^ (h >> 16)
    want = (h >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    _same_bits(_hash_uniform(x), want)


def test_stochastic_round_unbiased_under_uniform_offsets():
    """E_u[floor(y + u)] == y for u ~ U[0,1): checked on a dense uniform
    grid, where the empirical mean converges at 1/N exactly."""
    N = 4096
    u = (jnp.arange(N, dtype=jnp.float32) + 0.5) / N
    for y in (0.0, 0.25, 2.37, -1.62, 100.499):
        mean = float(jnp.mean(stochastic_round(jnp.full((N,), y), u)))
        assert abs(mean - y) <= 1.5 / N + 1e-4, (y, mean)


@given(n=st.integers(4, 300), seed=st.integers(0, 99),
       scale=st.floats(1e-3, 1e3))
@settings(max_examples=6, deadline=None)
def test_int8_lands_on_grid_within_one_step(n, seed, scale):
    x = _payload(n, seed, scale)
    y = parse_channel("int8").apply(x)
    s = float(jnp.max(jnp.abs(x))) / 127.0
    # every output is an integer multiple of the per-message scale...
    np.testing.assert_allclose(np.asarray(y) / s,
                               np.round(np.asarray(y) / s),
                               atol=1e-3)
    # ...within one grid step of the input (stochastic rounding moves
    # at most one step), and the all-zero message is preserved exactly
    assert float(jnp.max(jnp.abs(y - x))) <= s * (1 + 1e-5)
    np.testing.assert_array_equal(
        parse_channel("int8").apply(jnp.zeros(8)), jnp.zeros(8))


@given(n=st.integers(4, 300), seed=st.integers(0, 99),
       rho=st.floats(0.05, 1.0))
@settings(max_examples=6, deadline=None)
def test_topk_keeps_exactly_k_largest(n, seed, rho):
    x = _payload(n, seed)
    ch = parse_channel(f"topk:{rho:g}")
    y = np.asarray(ch.apply(x))
    k = ch.topk_k(n)
    assert int(np.sum(y != 0)) == min(k, int(np.sum(np.asarray(x) != 0)))
    # the survivors are the k largest magnitudes, passed through exactly
    kept = np.nonzero(y)[0]
    thresh = np.sort(np.abs(np.asarray(x)))[-k]
    assert np.all(np.abs(np.asarray(x))[kept] >= thresh - 1e-7)
    np.testing.assert_array_equal(y[kept], np.asarray(x)[kept])


def test_all_to_all_broadcast_prices_per_machine_messages():
    """A local all-to-all broadcast is m per-machine messages: its wire
    bits are m x wire_bits(per-machine elems), not wire_bits(total) —
    the two differ for channels with per-message overhead (int8's scale,
    topk's per-message k)."""
    from repro.core.comm import LocalCommunicator
    m, per = 4, 8
    for name in ("identity", "fp16", "int8", "topk:0.25"):
        comm = LocalCommunicator(m, channel=name)
        comm.all_to_all_broadcast(jnp.ones((m, per)), tag="blocks")
        (rec,) = comm.ledger.records
        ch = parse_channel(name)
        assert rec.elems == m * per                      # legacy total
        assert rec.bits == m * ch.wire_bits(per, 4), name
        assert rec.direction == "worker->all"


def test_wire_bits_arithmetic():
    assert parse_channel("identity").wire_bits(100, 4) == 3200
    assert parse_channel("fp16").wire_bits(100, 4) == 1600
    assert parse_channel("bf16").wire_bits(100, 4) == 1600
    assert parse_channel("int8").wire_bits(100, 4) == 800 + 32
    assert parse_channel("topk:0.1").wire_bits(100, 4) == 10 * (32 + 32)
    assert parse_channel("topk:0.1").wire_bits(3, 4) == 1 * 64  # k >= 1


# --------------------------------------------------------------------------
# identity channel == channel-free build, across engines x backends
# --------------------------------------------------------------------------

def _typed_stream(dist):
    led = dist.comm.ledger
    return led.rounds, led.round_marks, led.typed_stream()


def _run(bundle, backend, engine, channel):
    algo = get_algorithm("dagd")
    dist = LocalDistERM(bundle.prob, bundle.part, backend=backend,
                        channel=channel)
    program = algo.program(dist, rounds=8, **algo.make_kwargs(bundle.ctx))
    run_program(dist, program, engine=engine)
    return _typed_stream(dist)


def test_identity_channel_streams_bit_identical_across_matrix():
    bundle = build_instance("random_ridge", n=24, d=32, m=4)
    ref = _run(bundle, "einsum", "python", None)
    for backend in ORACLE_BACKENDS:
        for engine in ENGINES:
            for channel in (None, "identity"):
                assert _run(bundle, backend, engine, channel) == ref, \
                    (backend, engine, channel)


def test_lossy_channel_changes_bits_not_legacy_stream():
    bundle = build_instance("random_ridge", n=24, d=32, m=4)
    _, _, ref = _run(bundle, "einsum", "scan", None)
    legacy_ref = [(r[0], r[1], r[2], r[4]) for r in ref]
    for channel in ("fp16", "bf16", "int8", "topk:0.25"):
        rounds, marks, recs = _run(bundle, "einsum", "scan", channel)
        assert [(r[0], r[1], r[2], r[4]) for r in recs] == legacy_ref
        # vector payloads got cheaper; the stream shape did not move
        assert sum(r[3] for r in recs) < sum(r[3] for r in ref), channel
        assert len(marks) == rounds == 8


# --------------------------------------------------------------------------
# the channel axis through the api facade
# --------------------------------------------------------------------------

TINY = dict(instance="thm2_chain",
            instance_params=dict(d=24, kappa=16.0, lam=0.5, m=4),
            algorithm="dagd", rounds=60, eps=(1e-3,))


def test_api_channel_resolution_and_serialization():
    from repro.api import PlanError, RunSpec, plan
    spec = RunSpec(**TINY, channel="topk")
    assert RunSpec.from_json(spec.to_json()) == spec
    pl = plan(spec)
    assert pl.channel == "topk:0.1"     # canonicalized at plan time
    assert plan(RunSpec(**TINY)).channel == "identity"
    with pytest.raises(PlanError, match="unknown channel"):
        plan(RunSpec(**TINY, channel="zip"))
    # a pre-channel (v1) spec dict still loads, defaulting to auto
    v1 = {**spec.to_dict(), "schema_version": 1}
    del v1["channel"]
    assert RunSpec.from_dict(v1).channel == "auto"


def test_api_run_meters_channel_bits():
    from repro.api import RunSpec, run
    ident = run(RunSpec(**TINY))
    int8 = run(RunSpec(**TINY, channel="int8"))
    assert int8.channel == "int8"
    assert ident.stream() == int8.stream()     # legacy stream invariant
    assert int8.ledger.total_bits() < ident.ledger.total_bits()
    assert ident.ledger.total_bits() == 8 * ident.ledger.total_bytes()


def test_execute_batch_groups_by_channel():
    """Same-channel cells group; mixed channels fall back (never merge),
    and the batched ledger — marks included — matches sequential."""
    from repro.api import RunSpec, execute_batch, plan
    k2 = {**TINY, "instance_params": dict(d=24, kappa=64.0, lam=0.5, m=4)}
    same = [plan(RunSpec(**TINY, channel="fp16")),
            plan(RunSpec(**k2, channel="fp16"))]
    res = execute_batch(same)
    assert all(r.batched for r in res)
    seq = plan(RunSpec(**TINY, channel="fp16")).execute()
    assert res[0].stream() == seq.stream()
    assert res[0].ledger.total_bits() == seq.ledger.total_bits()
    assert res[0].ledger.round_marks == seq.ledger.round_marks

    mixed = [plan(RunSpec(**TINY)), plan(RunSpec(**k2, channel="fp16"))]
    assert [r.batched for r in execute_batch(mixed)] == [False, False]


@pytest.mark.parametrize("channel", ["fp16", "sched:int8@0,fp16@1"])
def test_sharded_placement_accepts_channel(channel):
    """The sharded placement meters the local run's stream record for
    record, round marks included; under a round-scheduled channel each
    round is priced at its own stage.  The int8 stage carries round 0's
    upload, A 0 = 0, so the iterates stay comparable at the fp16
    tolerance: on later rounds int8's rounding would turn the last-ulp
    differences between the local einsum and the shard's matvec into
    whole quanta."""
    from repro.api import RunSpec, run
    base = dict(instance="random_ridge",
                instance_params=dict(n=16, d=12, m=1),
                algorithm="dagd", rounds=6, measure="none")
    loc = run(RunSpec(**base, channel=channel))
    sh = run(RunSpec(**base, channel=channel, placement="sharded"))
    assert sh.channel == channel
    assert sh.ledger.total_bits() == loc.ledger.total_bits()
    assert sh.ledger.typed_stream() == loc.ledger.typed_stream()
    assert sh.ledger.round_marks == loc.ledger.round_marks
    assert len(sh.ledger.round_marks) == sh.ledger.rounds
    np.testing.assert_allclose(np.asarray(sh.w), np.asarray(loc.w),
                               atol=1e-5, rtol=1e-5)
