"""The single capability resolver for the five execution axes.

Every run in the repo is positioned on five orthogonal axes:

  * **placement** — where the machines live: ``local`` (m simulated
    machines, blocks stacked on a leading axis) or ``sharded`` (machine j
    = mesh slice j inside ``shard_map``);
  * **oracle backend** — how the per-machine work inside
    ``response``/``pgrad``/``phvp`` is computed: ``einsum`` (plain jnp
    contractions), ``kernel`` (the Pallas GEMV kernels) or
    ``fused`` (the kernels plus the whole-round fused step of
    ``kernels/fused_round.py`` where a cell supports it);
  * **round engine** — how rounds are driven: ``python`` (per-call loop)
    or ``scan`` (one ``lax.scan``-compiled XLA program per segment);
  * **channel** — what the per-machine uploads cost on the wire:
    ``identity`` (exact f32) or a lossy transform (``fp16``/``bf16``/
    ``int8``/``topk[:rho]``), a round-indexed schedule of those
    (``sched:<ch>@<round>,...``) or a gap-adaptive spec
    (``gap:<ch0>,<ch>@<thr>,...``) — see ``core.channel``;
  * **faults** — seeded fault injection (``core.faults`` grammar), off
    by default.

Axis *policy* (vocabulary, env var, default rule, error wording) lives
in one declarative table, ``api/_axes.py``; this module binds the table
to ``capabilities()`` and keeps the historical ``resolve_*`` names.
``repro.api.plan`` calls these at *plan time*, so environment variables
are consulted when a run is planned, never at import time, and a
resolved ``ExecutionPlan`` carries concrete choices from then on.
``core.runtime``/``core.engine`` keep their historical ``resolve_*``
names as delegating shims.

This module must stay a leaf (stdlib + jax only): ``repro.core``'s shims
reach it at call time through the ``repro.api`` package (which imports
the whole facade), so any load-time dependency from here back into
``repro.core`` or ``repro.experiments`` would recreate the import cycle
the call-time indirection avoids.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax

from . import _axes


ORACLE_BACKENDS = _axes.AXES_BY_NAME["backend"].options
ENGINES = _axes.AXES_BY_NAME["engine"].options
PLACEMENTS = _axes.AXES_BY_NAME["placement"].options
# Canonical list lives in repro.core.channel (the transform
# implementations); mirrored in the axis table so the resolver stays a
# leaf at load time. tests/test_channel.py pins equality.
CHANNELS = _axes.AXES_BY_NAME["channel"].options

BACKEND_ENV = _axes.AXES_BY_NAME["backend"].env
ENGINE_ENV = _axes.AXES_BY_NAME["engine"].env
CHANNEL_ENV = _axes.AXES_BY_NAME["channel"].env
FAULTS_ENV = _axes.AXES_BY_NAME["faults"].env


def capabilities() -> Dict[str, object]:
    """What the current process can actually execute.

    ``kernel_compiled`` — the Pallas kernels compile for TPU; everywhere
    else they run in interpret mode (correct but slow), which is why
    ``auto`` only picks ``fused`` on TPU.  ``devices`` bounds the mesh a
    ``sharded`` placement can build.
    """
    platform = jax.default_backend()
    return dict(platform=platform,
                devices=jax.device_count(),
                kernel_compiled=(platform == "tpu"))


def resolve_oracle_backend(backend: Optional[str] = None, *,
                           caps: Optional[dict] = None) -> str:
    """``None``/``"auto"`` -> the ``REPRO_ORACLE_BACKEND`` env var, then
    the platform default (``fused`` on TPU, ``einsum`` elsewhere)."""
    return _axes.resolve(_axes.AXES_BY_NAME["backend"], backend,
                         caps=caps if caps is not None else capabilities)


def resolve_engine(engine: Optional[str] = None) -> str:
    """``None``/``"auto"`` -> the ``REPRO_ROUND_ENGINE`` env var, then
    ``scan`` — the compiled engine is the production default on every
    platform; the python engine exists for debugging and parity."""
    return _axes.resolve(_axes.AXES_BY_NAME["engine"], engine)


def resolve_channel(channel: Optional[str] = None) -> str:
    """``None``/``"auto"`` -> the ``REPRO_CHANNEL`` env var, then
    ``identity`` — lossy channels are an explicit opt-in because they
    change the optimization trajectory, not just its cost.  Returns the
    *canonical name* (e.g. ``"topk:0.1"``); raises ``ValueError`` on an
    unknown channel (labelled with the env var when it came from one)."""
    return _axes.resolve(_axes.AXES_BY_NAME["channel"], channel)


def resolve_faults(faults: Optional[str] = None) -> str:
    """``"none"``/``None`` -> no faults (the default: fault injection is
    an explicit opt-in; unlike the other axes, the env var is consulted
    only for ``"auto"``, so a stray ``REPRO_FAULTS`` can never perturb a
    spec that didn't ask).  Returns the *canonical name* (idempotent
    under re-parse); raises ``ValueError`` on a malformed spec."""
    return _axes.resolve(_axes.AXES_BY_NAME["faults"], faults)


def device_bytes_limit() -> Optional[int]:
    """One device's memory as its runtime reports it (``bytes_limit``),
    or None where it reports none (the CPU)."""
    return (jax.devices()[0].memory_stats() or {}).get("bytes_limit")


def dense_footprint(n: int, d: int, m: int) -> int:
    """Bytes one device needs to run an n x d float32 instance of m
    machines locally: A itself, plus the copy of every machine's block
    in the row-major tiles of 128 lanes the oracle kernels read."""
    d_j = -(-d // m)
    return 4 * n * d + 4 * n * m * (-(-d_j // 128) * 128)


def resolve_placement(placement: Optional[str] = None, *,
                      shape: Optional[Tuple[int, int, int]] = None,
                      caps: Optional[dict] = None) -> str:
    """``None``/``"auto"`` -> ``local`` for every instance one device
    can hold, and ``sharded`` for one it cannot: where the instance's
    ``shape`` (n, d, m) gives a ``dense_footprint`` above one device's
    memory and the host has at least m devices (the mesh is m of them,
    one machine each).  The choice follows from what the process
    observes, never from an option; both placements meter the same
    ledger, record for record and mark for mark
    (``tests/test_sharded_instance.py`` and the conformance suites pin
    it), so switching changes where the data lies and nothing a
    certificate reads."""
    axis = _axes.AXES_BY_NAME["placement"]
    resolved = _axes.resolve(axis, placement)
    if placement not in axis.auto_values or shape is None:
        return resolved
    n, d, m = shape
    caps = caps if caps is not None else capabilities()
    if caps["devices"] < m:
        return resolved
    limit = device_bytes_limit()
    if limit is not None and dense_footprint(n, d, m) > limit:
        return "sharded"
    return resolved
