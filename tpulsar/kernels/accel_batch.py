"""Host-side batch planner for the batched FDAS acceleration search.

The batched hi-accel path (kernels/accel.py) correlates ALL
z-templates against a batch of B whitened DM-trial spectra in one
fused jitted program (overlap-save correlation -> harmonic-stage sums
-> block-max top-k, the full (B, nz, 2*nbins) plane never round-trips
to Python).  What this module owns is everything about B that must be
decided HOST-side, before any program is traced:

  * the memory-budgeted batch size — ``plane_dm_chunk`` turns the
    plane-dtype/HBM machinery into a row count; here that row count becomes an
    INPUT to batch planning, never a refusal;
  * SIGNATURE QUANTIZATION — both the batch size and the spectra
    block's row count are snapped to a fixed ladder
    (:data:`BATCH_QUANTA`), so a 57-pass survey beam whose pass
    chunks arrive with ragged row counts (the executor's even-split
    leaves a full-chunk and a remainder shape per step, and small
    passes arrive whole) dedupes to a handful of compile signatures
    instead of one program per distinct row count.  Ragged tails
    inside a batch sweep never compile anything either: the last
    dispatch is CLAMPED to re-cover earlier rows (``starts``) at the
    same static shape;
  * the dispatch schedule itself (:class:`BatchPlan`): which row
    offsets are dispatched, at what static batch size.

Quantized spectra blocks are PADDED with zero rows up to the next
ladder rung.  Pad rows are shape stabilizers only — no
:class:`BatchPlan` start ever covers them, so they are never
correlated, never reduced, and never surface as candidates; the cost
is a few spectrum-rows of device memory, KBs-to-MBs against the GB
planes the budget actually tracks.

The AOT registry's shape-builders (tpulsar/aot/registry.py) call the
same :func:`batch_rows` / :func:`quantize_rows_up` used at runtime,
so the gate compiles exactly the quantized signatures the measured
run dispatches — the gate-vs-child lockstep discipline every other
program family already follows.

Pure host arithmetic: no jax import, so planning (and its tests) run
without touching a backend.
"""

from __future__ import annotations

import dataclasses

#: the signature ladder: 2^k and 1.5 * 2^k rungs, ratio <= 2
#: between neighbours (2 only at 1->2; <= 1.5 from rung 2 up) —
#: quantizing a batch size DOWN costs at most 2x dispatches (50% more
#: from rung 2 up), quantizing a row count UP pads at most the same
#: fraction of extra rows (pad rows are never dispatched; only their
#: bytes exist).
BATCH_QUANTA: tuple[int, ...] = (
    1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128, 192, 256,
    384, 512)


def quantize_batch(n: int) -> int:
    """Largest ladder rung <= n (n >= 1): the static batch size a
    budget of n rows actually dispatches at."""
    if n < 1:
        raise ValueError(f"batch size must be >= 1, got {n}")
    best = BATCH_QUANTA[0]
    for q in BATCH_QUANTA:
        if q > n:
            break
        best = q
    return best


def quantize_rows_up(n: int) -> int:
    """Smallest ladder rung >= n: the padded row count a spectra
    block of n DM trials is shaped to.  Above the ladder's top rung
    the count passes through unquantized (such blocks are beyond any
    survey pass chunk; refusing would be worse than one signature)."""
    if n < 1:
        raise ValueError(f"row count must be >= 1, got {n}")
    for q in BATCH_QUANTA:
        if q >= n:
            return q
    return n


@dataclasses.dataclass(frozen=True)
class BatchPlan:
    """The host-side dispatch schedule for one DM block.

    ``b`` is the quantized static batch size every dispatch uses;
    ``starts`` the row offsets, with the final start CLAMPED to
    ``ndms - b`` so the ragged tail re-covers already-searched rows
    at the same compile signature instead of tracing a smaller
    program.  ``padded_rows`` is the quantized row count the spectra
    block is zero-padded to before the first dispatch (its rows
    ``>= ndms`` are never inside any start's window)."""

    ndms: int
    b: int
    starts: tuple[int, ...]
    padded_rows: int

    @property
    def nbatches(self) -> int:
        return len(self.starts)

    def rows_of(self, s0: int) -> range:
        """The real DM rows batch ``s0`` resolves (clamped tails
        re-cover rows an earlier batch already filled; writing them
        again is idempotent)."""
        return range(s0, s0 + self.b)


def _clamped_starts(ndms: int, b: int) -> tuple[int, ...]:
    return tuple(min(c0, ndms - b) for c0 in range(0, ndms, b))


def plan_batches(ndms: int, budget_rows: int) -> BatchPlan:
    """Schedule ``ndms`` DM trials under a ``budget_rows`` batch-size
    budget (from ``accel.plane_dm_chunk``): quantized batch size,
    clamped tail, quantized padded block shape."""
    if ndms < 1:
        raise ValueError(f"ndms must be >= 1, got {ndms}")
    b = quantize_batch(max(1, min(budget_rows, ndms)))
    return BatchPlan(ndms=ndms, b=b, starts=_clamped_starts(ndms, b),
                     padded_rows=quantize_rows_up(ndms))


def plan_batches_explicit(ndms: int, b: int) -> BatchPlan:
    """Schedule with an EXPLICIT batch size (diagnostic/test
    control): ``b`` is honoured exactly — no ladder quantization —
    only the padded block shape still snaps; same clamped-tail
    starts discipline as :func:`plan_batches`."""
    if ndms < 1:
        raise ValueError(f"ndms must be >= 1, got {ndms}")
    b = max(1, min(b, ndms))
    return BatchPlan(ndms=ndms, b=b, starts=_clamped_starts(ndms, b),
                     padded_rows=quantize_rows_up(ndms))


def batch_rows(rows: int, nbins: int, nz: int) -> int:
    """The quantized batch size a ``rows``-trial block at this plane
    geometry dispatches with — the ONE arithmetic the runtime
    (``accel.accel_search_batch``) and the AOT gate's shape-builders
    share, so the gate compiles the exact ``nrows`` static the
    measured run uses."""
    from tpulsar.kernels import accel as ak

    return quantize_batch(max(1, min(ak.plane_dm_chunk(nbins, nz),
                                     rows)))
