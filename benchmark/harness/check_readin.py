"""The comparison that decides ``correct`` in a read-in cell.

Made after the window has closed, on its LAST read-in call: the block
``search_beam`` handed to ``search_block`` (resident, masked), the mask
artifact and the candidate list it wrote, against the plain reference
(``reference_readin.py``) reading the same file.  Every number is
printed beside its limit; the limits are the traffic file's
``tolerances`` (and the configuration's, for the pulsar), set from the
readings in ``PERF.md`` section 2.

What is compared, and over what:

  * ``block_step_gap``: ``data * qscale + qoff`` against the plain
    float64 decode, in quantisation steps, on EVERY sample the mask
    leaves of seeded tiles of one mask interval x all channels (plus
    the file's first and last interval and the one with the burst).
    Sample by sample over all 3.8 G samples the float64 decode alone
    would take longer than the window.
  * ``block_mean_gap``: the same gap of every unmasked cell's MEAN,
    over the whole block (the program's from per-cell sums taken on
    the device, the reference's from ``cell_stats``): what the tiles
    cannot see, a stretch of the beam misplaced, moves the means of
    its cells by 0.7 steps rms.  Both are bounded by half a step.
  * ``reference_stats_gap``: the reference held to itself, its device
    float32 cell means and deviations against NumPy float64 over the
    tiles' decode, in steps (a reference the compiler got wrong must
    not pass for the program's fault, nor hide one).
  * ``fill_mismatch``: masked cells that do not hold their channel's
    rounded ``chan_fill`` in every sample (the cells' min and max).
  * ``chan_fill_gap``: the fill level against the mean the reference
    takes over the cells the program's mask leaves.
  * ``mask_z_gap``: over the cells where the program's flag and the
    reference's (largest |z| over 4.0) differ, how far the reference's
    z lies from the threshold: 0 when they agree everywhere, and small
    while only cells on the threshold flip.
  * the injected channel and interval are zapped whole; the masked
    fraction lies in the stated range; the injected pulsar is in the
    ``.accelcands`` the call wrote.

``control`` puts the reference with every nibble's low bit dropped in
the program's place (its decode in the tiles, its cell means, its own
flags and fill) and reports ITS numbers under the same limits.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark.harness import check as slice_check
from benchmark.harness import reference_readin as ref
from benchmark.harness.check import Number
from benchmark.harness.generate import seed_rng


def _cell_reduce(x, block_len: int):
    import jax.numpy as jnp

    nchan, T = x.shape
    n = T // block_len
    c = x[:, : n * block_len].reshape(nchan, n, block_len)
    return (c.astype(jnp.int32).sum(-1), c.min(-1), c.max(-1))


def resident_cells(data, block_len: int, chan_chunk: int = 120):
    """Per-cell (sum, min, max) of the resident block, each (nint,
    nchan) on the host, taken on the device a group of channels at a
    time."""
    import jax

    fn = jax.jit(_cell_reduce, static_argnames=("block_len",))
    parts = [fn(data[c0: c0 + chan_chunk], block_len=block_len)
             for c0 in range(0, data.shape[0], chan_chunk)]
    parts = jax.device_get(parts)
    return tuple(np.concatenate([p[k] for p in parts]).T for k in range(3))


def load_mask(path: str) -> dict:
    with np.load(path) as z:
        art = {k: np.asarray(z[k]) for k in z.files}
    if art["qscale"].size == 0:
        raise SystemExit("benchmark: the read-in left a float32 block "
                         "(no qscale in the mask artifact): the cell "
                         "measures the quantised uint8 path")
    art["full"] = (art["cell_mask"] | art["bad_channels"][None, :]
                   | art["bad_blocks"][:, None])
    return art


def pick_tiles(seed: int, nint: int, ntiles: int, burst: int) -> list[int]:
    rng = seed_rng(seed + 1)
    drawn = rng.choice(nint, size=min(ntiles, nint), replace=False)
    return sorted({int(i) for i in drawn} | {0, nint - 1, int(burst)})


def _max(x) -> float:
    return float(np.max(x)) if np.size(x) else 0.0


def fill_level(mean, good):
    """Each channel's mean over its `good` cells (0 where it has none)."""
    return (np.where(good, mean, 0.0).sum(axis=0)
            / np.maximum(good.sum(axis=0), 1))


def mask_gaps(flags, fill, mean, zmax, thr: float, good):
    """-> (mask_z_gap, chan_fill_gap) of one candidate mask (`flags`,
    `fill`) against the reference's statistics; `good` = the cells the
    program's mask leaves."""
    differ = flags != (zmax > thr)
    return (_max(np.abs(zmax - thr)[differ]),
            _max(np.abs(fill - fill_level(mean, good))[good.any(axis=0)]))


def check(cell, beam, call, seed: int, control: bool = False) -> dict:
    """-> {"correct", "numbers", "control"} as ``check.check`` gives."""
    tol = cell.traffic["tolerances"]
    thr = float(cell.config["search_params"]["rfi_threshold"])
    numbers = [
        Number("trials_not_searched",
               float(call.ntrials_given - call.ntrials_done), 0.0,
               n=call.ntrials_given),
        Number("degraded_or_rescued_flags",
               float(len(call.degraded) + len(call.rescued)), 0.0),
        Number("native_unpacker_missing",
               0.0 if beam.native_unpacker else 1.0, 0.0)]

    art = load_mask(os.path.join(call.resultsdir,
                                 f"{call.basenm}_rfifind.npz"))
    L = int(art["block_len"])
    qs, qo, full = art["qscale"], art["qoff"], art["full"]
    nint, nchan = full.shape
    f = ref.open_psrfits4(beam.path)
    data = call.data
    if tuple(data.shape) != (nchan, f["nrows"] * f["nsblk"]):
        raise SystemExit(f"benchmark: search_block got a block of shape "
                         f"{tuple(data.shape)}, the file holds "
                         f"{(nchan, f['nrows'] * f['nsblk'])}")

    # the resident block, sample by sample in the tiles
    step_gap, ctrl_gap, nsamp = 0.0, 0.0, 0
    tiles = pick_tiles(seed, nint, int(tol["tiles"]), beam.rfi["interval"])
    tile_mean, tile_std = [], []
    for i in tiles:
        want = ref.decode_psrfits4(f, i * L, (i + 1) * L)
        scaled = (want - qo[:, None]) / qs[:, None]
        tile_mean.append(scaled.mean(axis=1))
        tile_std.append(scaled.std(axis=1))
        got = np.asarray(data[:, i * L:(i + 1) * L]).astype(np.float64) \
            * qs[:, None] + qo[:, None]
        keep = ~full[i]
        step_gap = max(step_gap, _max(
            (np.abs(got - want) / qs[:, None])[keep]))
        nsamp += int(keep.sum()) * L
        if control:
            low = ref.decode_psrfits4(f, i * L, (i + 1) * L,
                                      drop_low_bit=True)
            ctrl_gap = max(ctrl_gap, _max(
                (np.abs(low - want) / qs[:, None])[keep]))
    numbers.append(Number("block_step_gap", step_gap,
                          float(tol["block_step_gap"]), n=nsamp))

    # every cell of the resident block: its mean, and its fill
    csum, cmin, cmax = resident_cells(data, L)
    call.data = data = None               # the program's block is freed
    rfill = np.rint(art["chan_fill"]).astype(cmin.dtype)[None, :]
    numbers.append(Number("fill_mismatch", float(np.sum(
        full & ((cmin != rfill) | (cmax != rfill)))), 0.0,
        n=int(full.sum())))
    mean, std, maxpow = ref.cell_stats(f, qs, qo, L)
    # the reference against itself: its float32 statistics, taken on
    # the device, against NumPy float64 over the tiles' decode
    numbers.append(Number("reference_stats_gap", max(
        _max(np.abs(mean[tiles] - np.asarray(tile_mean))),
        _max(np.abs(std[tiles] - np.asarray(tile_std)))),
        float(tol["reference_stats_gap"]), n=len(tiles) * nchan))
    numbers.append(Number("block_mean_gap",
                          _max(np.abs(csum / L - mean)[~full]),
                          float(tol["block_mean_gap"]),
                          n=int((~full).sum())))

    # the mask against the plain statistics
    zmax = ref.cell_zmax(mean, std, maxpow)
    z_gap, fill_gap = mask_gaps(art["cell_mask"], art["chan_fill"], mean,
                                zmax, thr, ~full)
    frac = float(full.mean())
    lo, hi = (float(v) for v in tol["masked_fraction"])
    numbers += [
        Number("chan_fill_gap", fill_gap, float(tol["chan_fill_gap"]),
               n=nchan),
        Number("mask_z_gap", z_gap, float(tol["mask_z_gap"]),
               n=int(full.size)),
        Number("rfi_channel_unflagged", 0.0 if art["bad_channels"][
            beam.rfi["channel"]] else 1.0, 0.0),
        Number("rfi_interval_unflagged", 0.0 if art["bad_blocks"][
            beam.rfi["interval"]] else 1.0, 0.0),
        Number("masked_fraction", frac, hi, n=int(full.size)),
        Number("masked_fraction_short", max(0.0, lo - frac), 0.0)]

    # the injected pulsar in the candidate list the call wrote
    cands = ref.read_accelcands(os.path.join(call.resultsdir,
                                             f"{call.basenm}.accelcands"))
    numbers += slice_check.recovery(
        cands, beam.psr, beam.T_s, slice_check.pass_table(beam.plan),
        False, cell.config["tolerances"])

    out = {"correct": all(n.ok for n in numbers),
           "numbers": [n.as_dict() for n in numbers]}
    if control:
        lmean, lstd, lpow = ref.cell_stats(f, qs, qo, L, drop_low_bit=True)
        lz = ref.cell_zmax(lmean, lstd, lpow)
        lflags = lz > thr
        z_gap, fill_gap = mask_gaps(lflags, fill_level(lmean, ~lflags),
                                    mean, zmax, thr, ~full)
        out["control"] = [n.as_dict() for n in (
            Number("block_step_gap", ctrl_gap,
                   float(tol["block_step_gap"]), n=nsamp),
            Number("block_mean_gap", _max(np.abs(lmean - mean)[~full]),
                   float(tol["block_mean_gap"]), n=int((~full).sum())),
            Number("chan_fill_gap", fill_gap, float(tol["chan_fill_gap"]),
                   n=nchan),
            Number("mask_z_gap", z_gap, float(tol["mask_z_gap"]),
                   n=int(full.size)))]
    return out
