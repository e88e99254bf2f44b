"""Batch-of-beams: the host planner for searching B compatible beams
through one dispatch stream.

PR 13's ``accel_batch`` planner proved the repo's recipe for batching
one axis of the search: quantized batch rungs so compile signatures
stay bounded, host-side planning so the device never sees a refusal,
and a per-item degradation path.  This module applies the same recipe
one axis up — BEAMS instead of DM trials — for the small-beam-survey
regime (FAST parallel-PRESTO scale: thousands of small beams/day)
where per-dispatch overhead, not per-beam compute, dominates the
wall clock.

The acceptance contract is *exact* per-beam candidate parity and
*byte-identical* checkpoint artifacts whether a beam ran batched or
solo, which decides how the beam axis rides the device programs (the
executor's one pass loop, ``search/executor.py::_plan_loop``):

  * stage 1 (subbanding) and stage 2 (dedispersion) run PER BEAM
    with the solo programs — the only form
    whose per-beam float arithmetic is the solo path's on every
    platform;
  * the spectral stages (fused SP detrend/boxcar, FFT/whiten, lo
    harmonic stages, the batched FDAS) are row-independent per DM
    trial — the loop hands them ``B*chunk`` rows (beam-major) instead
    of ``chunk``, the exact trick ``accel_batch`` uses for DM rows,
    with per-beam slices bit-equal by construction.

Signature discipline: coalesced row counts are ``B * chunk`` where
``chunk`` is the SOLO pass chunk size (chunk boundaries must match
the solo path or per-pass checkpoint artifacts would differ), so the
compile-signature multiplier is exactly the set of beam-group sizes.
Those are quantized to the shared :data:`~tpulsar.kernels.accel_batch.
BATCH_QUANTA` ladder: a fleet batching 5 beams dispatches groups of
(4, 1), never a one-off 5-wide program.

Per-beam degradation: a beam that cannot ride the batch (checkpoint
resume state, incompatible geometry, a poisoned input, or any failure
inside the coalesced section) FALLS OUT to the single-beam call — it
never fails its batchmates, and its solo results are byte-identical
to the batched ones it would have produced.  That rule lives in the
executor (search_beam_batch); this module only plans.

Planning is pure host arithmetic (no jax import), mirrored by the AOT
registry's shape-builders so the gate compiles the exact coalesced
signatures a batched run dispatches.
"""

from __future__ import annotations

import dataclasses
import os

from tpulsar.kernels.accel_batch import quantize_batch

#: default coalesced working-set budget (bytes) the beam planner
#: sizes B against — the beam-batch analogue of SearchParams.
#: spectral_hbm_budget, covering the B resident channel blocks plus
#: the coalesced per-chunk transients (TPULSAR_BEAM_BATCH_BYTES
#: overrides)
DEFAULT_BEAM_BUDGET = 8 << 30


def beam_batch_cap() -> int:
    """The operator's beam-batch cap: ``TPULSAR_BEAM_BATCH`` pins the
    largest coalesced beam group (0 or unset = planner budget only;
    1 = coalescing off, every beam runs the solo path).  Invalid
    values fail loudly — a silently ignored pin would un-pin a bench
    A/B."""
    raw = os.environ.get("TPULSAR_BEAM_BATCH", "").strip()
    if not raw:
        return 0
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(
            f"TPULSAR_BEAM_BATCH must be an integer >= 0, got {raw!r}")
    if val < 0:
        raise ValueError(
            f"TPULSAR_BEAM_BATCH must be >= 0, got {val}")
    return val


def beam_budget_bytes() -> int:
    """The coalesced working-set budget (TPULSAR_BEAM_BATCH_BYTES
    over the built-in default)."""
    raw = os.environ.get("TPULSAR_BEAM_BATCH_BYTES", "").strip()
    if not raw:
        return DEFAULT_BEAM_BUDGET
    try:
        val = int(float(raw))
    except ValueError:
        raise ValueError(
            f"TPULSAR_BEAM_BATCH_BYTES must be a byte count, got "
            f"{raw!r}")
    if val <= 0:
        raise ValueError(
            f"TPULSAR_BEAM_BATCH_BYTES must be > 0, got {val}")
    return val


# ------------------------------------------------------------- compat key

def compat_key(nchan: int, nsamp: int, dt: float, f_lo: float,
               f_hi: float, nsub: int, plan, params,
               zap_digest: str = "") -> str:
    """The beam-compatibility fingerprint: two beams may share a
    coalesced dispatch exactly when every STATIC input to the device
    programs matches — channel count, sample count, sample time, band
    edges, the DDplan geometry, the search params, and the zaplist
    (the whiten stage's keep mask is zap-derived).  Sky position and
    baryv deliberately do NOT key: they only shape per-beam host-side
    masks/refinement, which stay per-beam either way.

    The same function fingerprints a ticket at submission (clients
    that know their beam geometry stamp ``compat`` so the claim path
    can pick batchmates) and verifies it at stage-in — a ticket whose
    DECLARED key lied simply falls out of the batch to the solo
    path."""
    from tpulsar.checkpoint import hashing

    geom = [(s.lodm, s.dmstep, s.dms_per_pass, s.numpasses, s.numsub,
             s.downsamp) for s in plan]
    prov = sorted(params.provenance().items())
    blob = repr((int(nchan), int(nsamp), float(dt), float(f_lo),
                 float(f_hi), int(nsub), geom, prov,
                 zap_digest)).encode()
    return hashing.sha256_bytes(blob)[:16]


def zaplist_digest(zaplist) -> str:
    """Stable digest of a zaplist array ('' = no zaplist)."""
    import numpy as np

    from tpulsar.checkpoint import hashing
    if zaplist is None:
        return ""
    return hashing.sha256_bytes(
        np.ascontiguousarray(np.asarray(zaplist, np.float64))
        .tobytes())[:16]


# --------------------------------------------------------------- planning

@dataclasses.dataclass(frozen=True)
class BeamBatchPlan:
    """The host-side beam grouping for one coalesced search: which
    beam indices share each dispatch group.  Every group size is a
    :data:`BATCH_QUANTA` rung, so a survey fleet's coalesced programs
    compile at a handful of widths no matter how admission batches
    arrive."""

    nbeams: int
    groups: tuple[tuple[int, ...], ...]

    @property
    def b_max(self) -> int:
        return max((len(g) for g in self.groups), default=0)


def plan_beam_groups(nbeams: int, cap: int = 0) -> BeamBatchPlan:
    """Greedy ladder decomposition of ``nbeams`` into quantized
    groups no wider than ``cap`` (0 = no cap): 5 beams at cap 0 plan
    as (4, 1); 7 at cap 3 as (3, 3, 1).  Unlike the DM-batch planner
    there are no clamped tails — re-covering a beam would recompute
    (and re-checkpoint) real per-beam science, so ragged remainders
    drop to the next rung down instead."""
    if nbeams < 1:
        raise ValueError(f"nbeams must be >= 1, got {nbeams}")
    if cap < 0:
        raise ValueError(f"cap must be >= 0, got {cap}")
    groups = []
    start = 0
    while start < nbeams:
        left = nbeams - start
        b = quantize_batch(left if cap == 0 else max(1, min(cap,
                                                            left)))
        groups.append(tuple(range(start, start + b)))
        start += b
    return BeamBatchPlan(nbeams=nbeams, groups=tuple(groups))


def budget_beams(block_bytes: int, chunk_rows: int, nfft: int,
                 budget: int | None = None) -> int:
    """How many beams the coalesced working set affords: each beam
    keeps its channel block resident for the whole search (the fold
    stage re-subbands from it) and contributes ``chunk_rows`` rows of
    spectral transients per in-flight chunk (the same per-trial byte
    model as executor._budget_dm_chunk, x2 chunks in flight)."""
    if budget is None:
        budget = beam_budget_bytes()
    per_trial = 32 * nfft                  # executor's per-trial model
    per_beam = (3 * max(1, block_bytes)    # block + subbands + series
                + 2 * chunk_rows * per_trial)
    return max(1, int(budget // max(1, per_beam)))
