"""The round program of linear first-order algorithms, from their update.

Every first-order algorithm in the family F^{lam,L} runs the same round:
reduce the response, take the masked partial gradient, apply a
block-local update.  ``dgd``, ``dagd`` and ``prox_dagd`` define only
that update; ``linear_round_program`` builds the round from it, composed
from the oracles or fused.  When the dist's oracle backend offers the
whole-round ``round_step`` capability (the ``fused`` backend,
``kernels/fused_round.py``), that round can run as ONE Pallas kernel per
machine — but only after a rotation: the composed step computes this
round's upload *inside* the round, while the fused kernel emits next
round's upload (already channel-transformed) in the same pass that read
A_j.  So the fused program's carry holds ``zloc`` — machine j's pending
upload — and each round is: reduce the carried uploads
(``pretransformed=True``: byte-identical record/pricing/faults, no
second transform), then one kernel.

Round 0's pending upload is A·0 = 0, and every in-kernel channel maps 0
to 0 (int8's scale is 0 -> zeros; the half casts are exact at 0), so the
zeros init reproduces the composed round-0 message bit-for-bit.  The
kernel applies channel stage ``rnd + 1`` to the upload it emits — the
stage the composed path would apply when that upload is actually sent.

The ledger cannot tell the difference by construction (metadata-only
records, identical tags/shapes/pricing); the iterates agree with the
composed ``kernel`` backend's to f32 rounding, since the whole-block
dots and the composed tilings may round their sums differently in the
last ulp (``tests/test_ledger_invariance.py`` pins both).
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax.numpy as jnp

from ..engine import RoundProgram, Segment


def linear_round_program(dist, rounds: int, update, *,
                         xs: Optional[np.ndarray] = None,
                         name: str = "") -> RoundProgram:
    """The RoundProgram of a response->pgrad->update round: the fused
    one-kernel program where the dist's backend can rotate the round,
    else the composed one, both built from the same ``update``.

    ``update(x, y, g, coeff) -> (x_new, y_new)`` is the algorithm's
    block-local update (elementwise over the coordinate blocks; the
    fused program traces it into the kernel body).  The round reduces
    the response at ``y`` and emits ``x_new`` as its iterate.  ``xs``
    optionally supplies the per-round ``coeff`` input (e.g. FISTA
    momentum coefficients).
    """
    zero = dist.zeros_like_w()
    no_coeff = jnp.float32(0.0)
    maker = getattr(dist, "fused_round_step", None)  # local placement only
    step_fn = maker(update) if maker is not None else None
    if step_fn is None:
        def step(dist, carry, x):
            x_c, y_c = carry
            z = dist.response(y_c)
            g = dist.pgrad(y_c, z)
            x_n, y_n = update(x_c, y_c, g, x if xs is not None else no_coeff)
            dist.end_round()
            return (x_n, y_n), x_n

        init = (zero, zero)
    else:
        def step(dist, carry, x):
            x_c, y_c, zloc = carry
            z = dist.reduce_response(zloc)
            coeff = x if xs is not None else no_coeff
            rnd = dist.comm._round_index()
            x_n, y_n, zloc_n = step_fn(z, x_c, y_c, coeff, rnd)
            dist.end_round()
            return (x_n, y_n, zloc_n), x_n

        init = (zero, zero, jnp.zeros((dist.part.m, dist.n)))
    return RoundProgram(init=init,
                        segments=[Segment(step, rounds, xs=xs, name=name)],
                        final=lambda c: c[0])
