"""The per-beam search executor — tpulsar's scientific core.

Reproduces the stage sequence of the reference's search driver
(lib/python/PALFA2_presto_search.py: obs_info :231, set_up_job :444,
search_job :468, clean_up :691) with the PRESTO subprocess chain
replaced by the TPU kernels:

  rfifind            -> kernels.rfi.find_rfi / apply_mask
  prepsubband -sub   -> kernels.dedisperse.form_subbands
  prepsubband        -> kernels.dedisperse.dedisperse_subbands
  single_pulse_search-> kernels.singlepulse.single_pulse_search
  realfft/zapbirds/
  rednoise/accelsearch(z=0) -> kernels.fourier.periodicity_search
  accelsearch(z>0)   -> kernels.accel.accel_search_one
  sifting            -> search.sifting
  prepfold           -> kernels.fold.fold_and_optimize

Artifacts written to the results directory mirror the reference's
output contract (so the uploader layer parses them the same way):
  <base>_rfifind.npz             RFI mask
  <base>.accelcands              sifted candidate list
  <base>_DM*.singlepulse         per-DM single-pulse events
  <base>_DM*.inf                 per-DM series metadata
  <base>_cand*.pfd.npz/.bestprof folded candidates
  search_params.txt              config provenance (python-literal)
  <base>.report                  per-stage timing breakdown
  <base>_*.tgz                   result-class tarballs
"""

from __future__ import annotations

import dataclasses
import os
import tarfile
import typing

import jax
import jax.numpy as jnp
import numpy as np

from tpulsar.io import accelcands, datafile
from tpulsar.kernels import accel as accel_k
from tpulsar.obs import telemetry
from tpulsar.obs import trace as trace_mod
from tpulsar.parallel import mesh as pmesh
from tpulsar.kernels import dedisperse as dd
from tpulsar.kernels import fold as fold_k
from tpulsar.kernels import fourier as fr
from tpulsar.kernels import rfi as rfi_k
from tpulsar.kernels import singlepulse as sp_k
from tpulsar.plan import ddplan
from tpulsar.search import degraded, sifting
from tpulsar.search.report import StageTimers


@dataclasses.dataclass
class SearchParams:
    """Search configuration (defaults mirror the reference's searching
    config, lib/python/config/searching_example.py)."""
    nsub: int = 96
    rfifind_blocklen: int = 2048
    rfi_threshold: float = 4.0
    lo_accel_numharm: int = 16      # :21-27
    lo_accel_zmax: int = 0
    hi_accel_numharm: int = 8
    hi_accel_zmax: int = 50
    run_hi_accel: bool = True
    topk_per_stage: int = 32
    sp_threshold: float = 5.0       # singlepulse_threshold
    sp_widths: tuple[int, ...] = sp_k.DEFAULT_WIDTHS
    sp_detrend: str = "median"      # SP baseline estimator: exact
    #                                 "median" (PRESTO parity) |
    #                                 "median_sub4" | "clipped_mean"
    #                                 (see kernels/singlepulse.py;
    #                                 TPULSAR_SP_DETREND overrides for
    #                                 the on-chip A/B)
    sifting: sifting.SiftParams = dataclasses.field(
        default_factory=sifting.SiftParams)
    to_prepfold_sigma: float = 6.0  # :44
    max_cands_to_fold: int = 100    # :45
    fold_by_rules: bool = True      # period-tier nbin/npart/extents +
    #                                 subband fold with a DM search
    #                                 axis (PALFA2_presto_search.py:
    #                                 195-211); False = fixed-geometry
    #                                 series fold below
    fold_batched: bool = True       # fold candidates per originating
    #                                 plan pass, tier-batched into one
    #                                 device program (kernels/
    #                                 fold_batch.py — prepfold folds
    #                                 the pass's subband files too,
    #                                 :168-175); False = the
    #                                 per-candidate loop
    fold_nbin: int = 64
    fold_npart: int = 32
    max_dms_per_chunk: int = 128    # device memory blocking; the
    #                                 effective chunk is additionally
    #                                 capped so the per-chunk series +
    #                                 spectrum + whitening buffers fit
    #                                 spectral_hbm_budget (a full Mock
    #                                 beam at 128 trials would need
    #                                 ~11 GB of transients)
    spectral_hbm_budget: int = 6 << 30
    seq_shard_min_bytes: int = 2 << 30  # a beam laid over the mesh:
    #                                 a pass's subbands at or under this
    #                                 many bytes go to every chip whole
    #                                 ("replicate"), over it each chip
    #                                 dedisperses its own subbands for
    #                                 every trial ("partial"); a whole
    #                                 block's subbands always replicate
    dm_shards: int = 1              # the layout: each pass's DM trials
    #                                 sharded over a (beam=1, dm=N) mesh
    #                                 of the first N local devices
    #                                 (dm_mesh); 1 = one device.  Fewer
    #                                 local devices than N raises
    #                                 before any work: no fallback
    block_quantize: str = "auto"    # read beams as uint8 with a
    #                                 per-channel affine map: "on"
    #                                 always, "off" never (float32),
    #                                 "auto" when the float32 block
    #                                 would exceed block_quantize_min
    #                                 (a full Mock beam is ~15 GB as
    #                                 float32 — the device's HBM)
    block_quantize_min: int = 1 << 30
    refine_cands: bool = True       # sub-bin (r, z) refinement of the
    #                                 reported candidates (harmpolish)
    make_plots: bool = True         # fold + single-pulse PNGs
    low_T_to_search_s: float = 0.0  # skip observations shorter than
    #                                 this (reference set_up_job guard,
    #                                 PALFA2_presto_search.py:450);
    #                                 0 = search everything
    dm_min: float = 0.0             # DM trial window: the plan is
    dm_max: float = 0.0             # trimmed to [dm_min, dm_max] at
    #                                 whole-pass granularity
    #                                 (ddplan.trim_plan; DDplan2b's
    #                                 -l/-d args); dm_max 0 = no cap

    def __post_init__(self):
        if self.block_quantize not in ("on", "off", "auto"):
            raise ValueError(
                f"block_quantize must be 'on'/'off'/'auto', got "
                f"{self.block_quantize!r}")
        if int(self.dm_shards) != self.dm_shards or self.dm_shards < 1:
            raise ValueError(
                f"dm_shards must be a whole number >= 1, got "
                f"{self.dm_shards!r}")

    def provenance(self) -> dict:
        d = dataclasses.asdict(self)
        d["sifting"] = dataclasses.asdict(self.sifting)
        return d

    @classmethod
    def from_config(cls, searching) -> "SearchParams":
        """Build from a SearchingConfig domain, so queue-launched
        workers honour the operator's searching settings (the
        reference wires config.searching straight into the search
        module, PALFA2_presto_search.py:26-41)."""
        return cls(
            nsub=searching.nsub,
            lo_accel_numharm=searching.lo_accel_numharm,
            lo_accel_zmax=searching.lo_accel_zmax,
            hi_accel_numharm=searching.hi_accel_numharm,
            hi_accel_zmax=searching.hi_accel_zmax,
            run_hi_accel=searching.use_hi_accel
            and searching.hi_accel_zmax > 0,
            sp_threshold=searching.singlepulse_threshold,
            sifting=sifting.SiftParams(
                sigma_threshold=searching.sifting_sigma_threshold,
                r_err=searching.sifting_r_err,
                min_num_dms=searching.sifting_min_num_dms,
                low_dm_cutoff=searching.sifting_low_dm_cutoff),
            to_prepfold_sigma=searching.to_prepfold_sigma,
            max_cands_to_fold=searching.max_cands_to_fold,
            low_T_to_search_s=searching.low_T_to_search,
            dm_min=searching.dm_min,
            dm_max=searching.dm_max,
            dm_shards=searching.dm_shards)


class TooShortToSearchError(ValueError):
    """Observation below the low_T_to_search threshold."""


_DM_MESHES: dict[int, object] = {}


def dm_mesh(dm_shards: int):
    """The (beam=1, dm=dm_shards) mesh of SearchParams.dm_shards: over
    the first `dm_shards` local devices, built once a process.  A
    process with fewer devices raises here, before any work: the
    layout is the deployment's, and a beam searched on fewer chips
    than it states is a different deployment, not a fallback."""
    if dm_shards not in _DM_MESHES:
        devs = jax.local_devices()
        if len(devs) < dm_shards:
            raise RuntimeError(
                f"dm_shards={dm_shards} needs {dm_shards} local "
                f"devices, this process has {len(devs)} "
                f"({devs[0].platform}): start it on a host with "
                f"{dm_shards} chips or set searching.dm_shards to "
                f"what the host has")
        _DM_MESHES[dm_shards] = pmesh.make_mesh(
            n_beam=1, n_dm=dm_shards, devices=devs[:dm_shards])
    return _DM_MESHES[dm_shards]


def _layout_mesh(params: "SearchParams", mesh):
    """The mesh a search runs on: the caller's, else the one the
    parameters' layout states, else None (one device)."""
    if mesh is None and params.dm_shards > 1:
        mesh = dm_mesh(params.dm_shards)
    return mesh


@dataclasses.dataclass
class SearchOutcome:
    basenm: str
    resultsdir: str
    candidates: list[sifting.Candidate]
    folded: list[fold_k.FoldResult]
    sp_events: np.ndarray
    masked_fraction: float
    num_dm_trials: int
    timers: StageTimers
    #: persistent compilation-cache traffic attributable to THIS beam
    #: (the runtime monitor's counter delta, same numbers as the
    #: results dir's metrics.json).  A warm worker's steady state is
    #: compile_misses == 0; any other value is a recompile the AOT
    #: gate / resident cache should have absorbed.
    compile_hits: int = 0
    compile_misses: int = 0


def search_beam(fns: list[str], workdir: str, resultsdir: str,
                params: SearchParams | None = None,
                zaplist: np.ndarray | None = None,
                plan: list[ddplan.DedispStep] | None = None,
                baryv: float | None = None,
                checkpoint_dir: str | None = None,
                checkpoint_journal=None,
                mesh=None) -> SearchOutcome:
    """Search one beam end-to-end and write the results directory.

    baryv: average barycentric velocity (v/c, positive receding) of
    the observation.  None (default) computes it from the beam header
    the way the reference does at obs_info time
    (PALFA2_presto_search.py:43-57,269); pass 0.0 explicitly to
    disable barycentric correction.

    checkpoint_dir: pass-level crash resume (tpulsar/checkpoint/) —
    the RFI mask, every DDplan pass's partials, the sifted list, and
    each folded candidate are durably checkpointed with sha256
    manifest entries, and a re-entered search verifies the manifest
    and recomputes only what is missing or corrupt.
    checkpoint_journal: optional ``callable(event, **extra)`` wired to
    the spool journal (the serve worker stamps ticket/worker/attempt)
    — carries ``resume`` / ``pass_complete`` / ``checkpoint_invalid``
    / ``checkpoint_disabled`` events.
    """
    _activate_runtime()
    params = params or SearchParams()
    mesh = _layout_mesh(params, mesh)   # too few devices: raises here
    if trace_mod.enabled():
        # one trace file per beam: clear events at beam start so the
        # saved <basenm>_trace.json rollup matches THIS beam's
        # .report (tools/trace_summarize.py's 5% contract), not an
        # accumulation over every beam this process searched
        trace_mod.start(clear=True)
    # registry baseline: the metrics.json artifact below is the DELTA
    # over this beam, so a long-lived worker never attributes beam
    # A's refusals/retries to beam B's results directory
    metrics_base = telemetry.metrics.REGISTRY.snapshot()
    os.makedirs(workdir, exist_ok=True)
    os.makedirs(resultsdir, exist_ok=True)

    obj, si, basenm, plan, nsub, baryv, data_id = _beam_geometry(
        fns, params, plan, baryv)
    timers = StageTimers()
    store = None
    if checkpoint_dir:
        # opened HERE (not in search_block) so the RFI mask and the
        # fold artifacts checkpoint too, not just the pass loop
        store = _open_checkpoint(
            checkpoint_dir,
            _ckpt_fingerprint(plan, params, zaplist, baryv, nsub,
                              data_id=data_id),
            checkpoint_journal)

    data, mask = _read_and_mask(si, params, basenm, resultsdir,
                                store, timers)

    result = search_block(data, si.freqs, si.dt, plan, params,
                          zaplist=zaplist, baryv=baryv, nsub=nsub,
                          timers=timers, checkpoint=store, mesh=mesh)
    final, folded, sp_events, num_trials = result
    return _finalize_results(
        resultsdir, basenm, obj, si, plan, params, zaplist, baryv,
        data, mask, final, folded, sp_events, num_trials, timers,
        metrics_base)


def _beam_geometry(fns, params, plan, baryv):
    """Header-derived per-beam facts every path (solo and batched)
    needs before any device work: the data object, the DDplan, the
    effective nsub, the barycentric velocity, and the checkpoint
    data_id (file names/sizes/MJD + block shape — another beam's
    dumps must never be resumed)."""
    obj = datafile.autogen_dataobj(fns)
    si = obj.specinfo
    if baryv is None:
        baryv = _compute_baryv(si)
    if si.T < params.low_T_to_search_s:
        raise TooShortToSearchError(
            f"observation is {si.T:.1f} s < low_T_to_search "
            f"{params.low_T_to_search_s:.1f} s "
            f"(reference PALFA2_presto_search.py:450)")
    basenm = os.path.splitext(os.path.basename(sorted(fns)[0]))[0]
    nsub = params.nsub if si.num_channels % params.nsub == 0 else \
        ddplan.largest_divisor_leq(si.num_channels, params.nsub)
    if plan is None:
        plan, _obs, nsub = ddplan.plan_for(
            si, lodm=params.dm_min,
            hidm=params.dm_max if params.dm_max > 0 else 1000.0,
            numsub=params.nsub)
    shape_id = (f"({si.num_channels}, {int(si.N)})|{si.dt!r}|"
                f"{si.freqs[0]!r}|{si.freqs[-1]!r}")
    data_id = ";".join(
        f"{os.path.basename(fn)}:{os.path.getsize(fn)}" for fn in
        sorted(fns)) + f"|mjd={float(si.start_MJD[0])!r}" \
        + "|" + shape_id
    return obj, si, basenm, plan, nsub, baryv, data_id


def _activate_runtime() -> None:
    """One-time runtime activation every beam entry point shares.

    JAX_PLATFORMS must win BEFORE the first jnp use initializes the
    backend — a library caller pinned to CPU must not take the
    accelerator (a chip belongs to one process).  The
    persistent-cache monitor is installed in the
    same breath so every in-line XLA compile emits
    compile_cache_hit/miss counters and a backend_compile trace
    event — a recompile the AOT gate should have absorbed can no
    longer hide inside a stage timing."""
    import tpulsar

    tpulsar.apply_platform_env()
    from tpulsar.aot import cachedir as _cachedir
    from tpulsar.aot import warmstart as _warmstart

    _cachedir.activate_if_configured()
    _warmstart.install_runtime_monitor()


#: the largest beam block (bytes, as read) the read-in puts whole on one
#: chip when the search has a mesh: its transpose there holds two
#: copies of it, and set-up the block beside the masked block
READIN_WHOLE_MAX_BYTES = 6 << 30


def _readin_devices(block, params) -> list:
    """The devices the read-in lays the beam over: the search's mesh
    (`dm_shards` > 1) for a block over one chip's budget, provided the
    channels and the subbands divide into whole shares; else one."""
    n = params.dm_shards
    nchan = block.shape[1]
    nsub = (params.nsub if nchan % params.nsub == 0
            else ddplan.largest_divisor_leq(nchan, params.nsub))
    if n > 1 and block.nbytes > READIN_WHOLE_MAX_BYTES and nsub % n == 0:
        return list(dm_mesh(n).devices.flat)
    return [None]


def _place_shares(block, devs):
    """The (T, nchan) block as read -> ONE (nchan, T) array laid over
    `devs` by channels: each chip is sent its columns of the
    time-major block and turns them itself (`rfi.channel_major`), so
    no chip ever holds more than twice its share."""
    T, nchan = block.shape
    w = nchan // len(devs)
    shares = [rfi_k.channel_major(jax.device_put(
        np.ascontiguousarray(block[:, d * w:(d + 1) * w]), dev))
        for d, dev in enumerate(devs)]
    return jax.make_array_from_single_device_arrays(
        (nchan, T), jax.sharding.NamedSharding(
            jax.sharding.Mesh(np.asarray(devs), ("chan",)),
            jax.sharding.PartitionSpec("chan", None)), shares)


def _read_and_mask(si, params, basenm, resultsdir, store, timers):
    """Read the beam block and apply the RFI mask (checkpoint-aware):
    returns the masked (nchan, T) device array and the RFIMask.  The
    mask artifact lands in resultsdir and — when a store is open — in
    the checkpoint manifest, so a resumed beam rewrites the
    byte-identical mask file without recomputing find_rfi."""
    f32_bytes = int(si.N) * si.num_channels * 4
    quantize = (params.block_quantize == "on"
                or (params.block_quantize == "auto"
                    and f32_bytes > params.block_quantize_min))
    if quantize:
        block, qscale, qoff = si.read_all_uint8()
    else:
        block = si.read_all()                 # (T, nchan) ascending freq
        qscale = qoff = None
    with timers.timing("rfifind"):
        # One transfer of the block as it was read, (T, nchan), and
        # one transpose ON the chip: the block lives on device
        # channel-major in its native dtype (uint8 beams stay 4x
        # smaller) and never transposes again.  The time-major device
        # copy lives for that one call (2 x the block, under the
        # block + masked block further down).
        devs = _readin_devices(block, params)
        with trace_mod.span("readin-place", bytes=block.nbytes,
                            transposed="device", devices=len(devs)):
            if len(devs) == 1:
                data = rfi_k.channel_major(jnp.asarray(block))  # (nchan, T)
            else:
                data = _place_shares(block, devs)
            del block
            trace_mod.fence(data)
        mask_path = os.path.join(resultsdir, f"{basenm}_rfifind.npz")
        payload = store.load("rfi_mask") if store is not None else None
        if payload is not None:
            # resume: the verified checkpoint payload IS the output
            # artifact — byte-identical mask file, no find_rfi compute
            with open(mask_path, "wb") as fh:
                fh.write(payload)
            mask = rfi_k.RFIMask.load(mask_path)
        else:
            mask = rfi_k.find_rfi_chan(data, si.dt,
                                       block_len=params.rfifind_blocklen,
                                       threshold=params.rfi_threshold)
            # the quantization affine travels with the mask: chan_fill
            # (and any folded-profile amplitudes downstream) are in
            # quantized units, and without the map a mask saved from a
            # quantized run could not be re-applied to float32 data
            mask.save(mask_path, qscale=qscale, qoff=qoff)
            if store is not None:
                with open(mask_path, "rb") as fh:
                    store.save("rfi_mask", fh.read(), kind="stage",
                               ext=".npz")
        # mask.block_len, not the configured one: find_rfi clamps it
        # for observations shorter than a block
        data = rfi_k.apply_mask_chan(
            data, jnp.asarray(mask.full_mask()),
            jnp.asarray(mask.chan_fill), mask.block_len)
    return data, mask


def _finalize_results(resultsdir, basenm, obj, si, plan, params,
                      zaplist, baryv, data, mask, final, folded,
                      sp_events, num_trials, timers,
                      metrics_base, metrics_extra=None
                      ) -> SearchOutcome:
    """Write the per-beam results directory (artifacts, provenance,
    report, telemetry delta, tarballs) and build the SearchOutcome —
    shared verbatim by the solo and the batch-of-beams paths, so a
    beam's output layout cannot depend on which path searched it."""
    accelcands.write_candlist(
        final, os.path.join(resultsdir, f"{basenm}.accelcands"),
        baryv=baryv)
    if zaplist is not None and len(zaplist):
        # the zaplist used travels with the results (the reference
        # keeps it beside the beam for the zap-percentage diagnostics,
        # diagnostics.py:452-520)
        with open(os.path.join(resultsdir, f"{basenm}.zaplist"),
                  "w") as fh:
            fh.write("# freq_Hz width_Hz (zaplist used)\n")
            for freq, width in np.atleast_2d(zaplist):
                fh.write(f"{freq:12.4f} {width:10.4f}\n")
    _write_sp_files(resultsdir, basenm, sp_events)
    for step in plan:
        for ppass in step.passes():
            _write_inf_files(resultsdir, basenm, si,
                             np.asarray(ppass.dms), si.dt * step.downsamp,
                             data.shape[1] // step.downsamp)
    for i, res in enumerate(folded):
        stem = os.path.join(resultsdir, f"{basenm}_cand{i+1}")
        np.savez_compressed(
            stem + ".pfd.npz", profile=res.profile,
            subints=res.subints, period_s=res.period_s,
            pdot=res.pdot, dm=res.dm,
            reduced_chi2=res.reduced_chi2)
        with open(stem + ".bestprof", "w") as fh:
            fh.write(res.bestprof_text(si.source))

    if params.make_plots:
        with timers.timing("plotting"):
            from tpulsar.search import plots
            for i, res in enumerate(folded):
                plots.prepfold_plot(
                    res,
                    os.path.join(resultsdir, f"{basenm}_cand{i+1}.png"),
                    source=si.source,
                    extra_title=f"{basenm} cand {i+1}")
            plots.single_pulse_plots(
                sp_events, resultsdir, basenm,
                t_obs=data.shape[1] * si.dt)

    _write_header_json(resultsdir, obj)
    deg = degraded.snapshot()
    resc = degraded.provenance_snapshot()
    _write_search_params(resultsdir, params, basenm, si, num_trials,
                         baryv=baryv, degraded_modes=deg,
                         rescued_modes=resc)
    timers.write_report(os.path.join(resultsdir, f"{basenm}.report"),
                        basenm, degraded=deg, rescued=resc)
    # telemetry artifacts ride with the beam: the Chrome-trace file
    # (TPULSAR_TRACE=1 — load into ui.perfetto.dev, or summarize with
    # tools/trace_summarize.py / `tpulsar trace <dir>`) and the
    # per-beam metrics delta, so retry/rescue/circuit counters for
    # THIS beam are inspectable per results directory, not only in
    # daemon exports
    if trace_mod.enabled():
        trace_mod.save(os.path.join(resultsdir,
                                    f"{basenm}_trace.json"))
    import json as _json
    mdelta = telemetry.metrics.diff_snapshots(
        telemetry.metrics.REGISTRY.snapshot(), metrics_base)
    if metrics_extra is not None:
        # batch path: the group-shared plan-loop delta composed with
        # this beam's own finish-phase delta (metrics_base was taken
        # at the START of this beam's finish, not the group's)
        mdelta = telemetry.metrics.merge_deltas(metrics_extra, mdelta)
    with open(os.path.join(resultsdir, "metrics.json"), "w") as fh:
        _json.dump(mdelta, fh, indent=1)
    _tar_result_classes(resultsdir, basenm)

    def _counter_total(name: str) -> int:
        return int(sum((mdelta.get(name) or {}).get("series",
                                                    {}).values()))

    return SearchOutcome(basenm=basenm, resultsdir=resultsdir,
                         candidates=final, folded=folded,
                         sp_events=sp_events,
                         masked_fraction=mask.masked_fraction,
                         num_dm_trials=num_trials, timers=timers,
                         compile_hits=_counter_total(
                             "tpulsar_compile_cache_hits_total"),
                         compile_misses=_counter_total(
                             "tpulsar_compile_cache_misses_total"))


# ------------------------------------------------------ batch of beams

@dataclasses.dataclass
class BeamSpec:
    """One beam's inputs to :func:`search_beam_batch` — exactly the
    arguments :func:`search_beam` takes, as data."""
    fns: list[str]
    workdir: str
    resultsdir: str
    zaplist: np.ndarray | None = None
    baryv: float | None = None
    checkpoint_dir: str | None = None
    checkpoint_journal: object = None
    #: ticket id / display label for telemetry and error reporting
    label: str = ""


@dataclasses.dataclass
class BeamBatchResult:
    """Per-beam outcome of a batch dispatch: the SearchOutcome (or the
    per-beam error — one beam's failure never fails its batchmates),
    plus which path actually searched it."""
    spec: BeamSpec
    outcome: SearchOutcome | None = None
    error: BaseException | None = None
    path: str = "solo"             # "batched" | "solo"
    group_size: int = 1
    fallout: str = ""              # why a beam left the batch


def search_beam_batch(specs: list[BeamSpec],
                      params: SearchParams | None = None,
                      cap: int = 0,
                      progress_cb=None) -> list[BeamBatchResult]:
    """Search B beams, coalescing compatibility-keyed groups
    (kernels/beam_batch.py plans them) into one run of the pass loop:
    subbanding and dedispersion run per beam with the solo programs,
    and the spectral stages (fused SP detrend, FFT/whiten, lo harmonic
    stages, the batched FDAS) see ``B x chunk`` beam-major rows per
    dispatch — the accel_batch recipe one axis up.

    Per-beam results discipline is preserved: every beam keeps its own
    results directory, checkpoint store (pass artifacts sliced out of
    the batched arrays — byte-identical to a solo run's), journal
    chain, and SearchOutcome.  Per-beam degradation: a beam that
    cannot ride the batch (checkpoint resume state, incompatible
    geometry, an unreadable input, or any failure inside the coalesced
    section) falls out to the proven single-beam path — it never fails
    its batchmates.  ``cap`` pins the largest coalesced group (0 =
    TPULSAR_BEAM_BATCH, then the working-set budget); group sizes are
    quantized to the shared BATCH_QUANTA ladder either way."""
    from tpulsar.kernels import beam_batch as bb

    _activate_runtime()
    params = params or SearchParams()
    if params.dm_shards > 1 and len(specs) > 1:
        raise ValueError(
            f"dm_shards={params.dm_shards} shards ONE beam's DM trials "
            f"over the mesh; a group of {len(specs)} beams cannot ride "
            f"it: search them one at a time (search_beam) or set "
            f"dm_shards=1 to coalesce")
    results = [BeamBatchResult(spec=s) for s in specs]

    preludes: dict[int, tuple] = {}
    solo: dict[int, str] = {}
    groups: dict[str, list[int]] = {}
    for i, spec in enumerate(specs):
        try:
            pre = _beam_geometry(spec.fns, params, None, spec.baryv)
        except Exception:
            # unreadable header / too-short beam: the solo path will
            # surface the same error (or clean skip) attributably;
            # KeyboardInterrupt/SystemExit propagate — an interrupt
            # aborts the batch, it is not a per-beam defect
            solo[i] = "prelude_failed"
            continue
        preludes[i] = pre
        if spec.checkpoint_dir and _has_resume_state(
                spec.checkpoint_dir):
            # resume state binds the beam to the solo path: resuming
            # means SKIPPING completed passes, and a coalesced group
            # runs every pass for every member
            solo[i] = "resume"
            continue
        obj, si, basenm, plan, nsub, baryv, data_id = pre
        key = bb.compat_key(si.num_channels, int(si.N), float(si.dt),
                            float(si.freqs[0]), float(si.freqs[-1]),
                            nsub, plan, params,
                            zap_digest=bb.zaplist_digest(spec.zaplist))
        groups.setdefault(key, []).append(i)

    cap = cap or bb.beam_batch_cap()
    for key, idxs in groups.items():
        if cap == 1 or len(idxs) == 1:
            for i in idxs:
                solo.setdefault(i, "no_batchmates" if len(idxs) == 1
                                else "cap_1")
            continue
        obj, si, basenm, plan, nsub, baryv, data_id = preludes[idxs[0]]
        eff_cap = min(cap or len(idxs),
                      _budget_beam_cap(si, plan, params))
        gplan = bb.plan_beam_groups(len(idxs), cap=eff_cap)
        for members in gplan.groups:
            sub = [idxs[m] for m in members]
            if len(sub) == 1:
                solo.setdefault(sub[0], "ragged_remainder")
                continue
            entries = [{"spec": specs[i], "pre": preludes[i]}
                       for i in sub]
            try:
                outcomes = _search_group(entries, params,
                                         progress_cb=progress_cb)
            except Exception as e:
                import warnings
                warnings.warn(
                    f"coalesced {len(sub)}-beam group failed "
                    f"({e}); every member degrades to the solo "
                    f"path")
                for i in sub:
                    solo.setdefault(i, "group_failed")
                continue
            for i, out in zip(sub, outcomes):
                results[i].outcome = out
                results[i].path = "batched"
                results[i].group_size = len(sub)
                telemetry.beam_batch_beams_total().inc(path="batched")

    for i, reason in sorted(solo.items()):
        spec = specs[i]
        results[i].fallout = reason
        try:
            results[i].outcome = search_beam(
                spec.fns, spec.workdir, spec.resultsdir, params,
                zaplist=spec.zaplist, baryv=spec.baryv,
                checkpoint_dir=spec.checkpoint_dir,
                checkpoint_journal=spec.checkpoint_journal)
        except Exception as e:
            results[i].error = e
        telemetry.beam_batch_beams_total().inc(path="solo")
        if results[i].outcome is not None:
            telemetry.beam_batch_trials_total().inc(
                results[i].outcome.num_dm_trials, path="solo")
    return results


def _has_resume_state(checkpoint_dir: str) -> bool:
    from tpulsar import checkpoint as ckpt
    try:
        return ckpt.progress_marker(checkpoint_dir) > 0
    except OSError:
        return False


def _budget_beam_cap(si, plan, params: SearchParams) -> int:
    """How many beams the coalesced working set affords for this
    geometry (beam_batch.budget_beams with the executor's own block /
    chunk arithmetic)."""
    from tpulsar.kernels import beam_batch as bb

    f32_bytes = int(si.N) * si.num_channels * 4
    quantize = (params.block_quantize == "on"
                or (params.block_quantize == "auto"
                    and f32_bytes > params.block_quantize_min))
    block_bytes = f32_bytes // 4 if quantize else f32_bytes
    step0 = plan[0]
    nfft = ddplan.choose_n(int(si.N) // step0.downsamp)
    chunk_rows = pass_chunk_size(int(step0.dms_per_pass), nfft, params)
    return bb.budget_beams(block_bytes, chunk_rows, nfft)


def _search_group(entries: list[dict], params: SearchParams,
                  progress_cb=None) -> list[SearchOutcome]:
    """One coalesced group end to end.  All entries share a compat
    key, so the plan geometry, nsub, dt, and channel table are
    identical; what stays per-beam is the data block, the RFI mask,
    the zaplist/baryv-derived keep mask, the checkpoint store, and
    everything after the plan loop (sift/refine/fold/artifacts) —
    which runs through the exact helpers the solo path runs."""
    B = len(entries)
    specs = [e["spec"] for e in entries]
    pres = [e["pre"] for e in entries]
    _obj0, si0, _b0, plan, nsub, _bv0, _id0 = pres[0]
    freqs, dt = si0.freqs, si0.dt

    degraded.reset()
    if trace_mod.enabled():
        trace_mod.start(clear=True)
    metrics_base = telemetry.metrics.REGISTRY.snapshot()
    timers = StageTimers()

    beams, masks = [], []
    for spec, pre in zip(specs, pres):
        obj, si, basenm, _plan, _nsub, baryv, data_id = pre
        os.makedirs(spec.workdir, exist_ok=True)
        os.makedirs(spec.resultsdir, exist_ok=True)
        store = None
        if spec.checkpoint_dir:
            store = _open_checkpoint(
                spec.checkpoint_dir,
                _ckpt_fingerprint(plan, params, spec.zaplist, baryv,
                                  nsub, data_id=data_id),
                spec.checkpoint_journal)
        data, mask = _read_and_mask(si, params, basenm,
                                    spec.resultsdir, store, timers)
        beams.append(_Beam(data, spec.zaplist, baryv, store))
        masks.append(mask)

    telemetry.beam_batch_occupancy().set(B)
    with timers.collecting(), \
            trace_mod.span("search_beam_batch", nbeams=B,
                           npasses=sum(s.numpasses for s in plan)):
        _plan_loop(beams, freqs, dt, plan, params, nsub, timers,
                   progress_cb)
        telemetry.beam_batch_trials_total().inc(
            sum(b.ntrials for b in beams), path="batched")

        # per-beam attribution past this point: the plan loop's delta
        # is SHARED (one coalesced dispatch stream served the whole
        # group — every member's artifact carries it), but each
        # beam's sift/fold/finalize runs sequentially, so its
        # counters and stage seconds must land only in ITS results
        # directory, not every later batchmate's
        group_delta = telemetry.metrics.diff_snapshots(
            telemetry.metrics.REGISTRY.snapshot(), metrics_base)
        outcomes = []
        for beam, mask, spec, pre in zip(beams, masks, specs, pres):
            obj, si, basenm, _plan, _nsub, baryv, _id = pre
            finish_base = telemetry.metrics.REGISTRY.snapshot()
            timers_b = StageTimers()
            timers_b.times = dict(timers.times)
            timers_b.span_keys = set(timers.span_keys)
            with timers_b.collecting():
                final, folded, sp_events, num_trials = _sift_fold_finish(
                    beam, freqs, dt, params, nsub, timers_b, None, plan)
                outcomes.append(_finalize_results(
                    spec.resultsdir, basenm, obj, si, plan, params,
                    spec.zaplist, baryv, beam.data, mask, final,
                    folded, sp_events, num_trials, timers_b, finish_base,
                    metrics_extra=group_delta))
    return outcomes


def _budget_dm_chunk(nfft: int, hi: bool, budget: int) -> int:
    """Largest DM chunk whose per-trial spectral working set fits the
    spectral HBM budget: series (f32, nfft) + padded copy (f32, nfft)
    + complex spectrum (c64, ~nfft/2 bins = 4*nfft bytes) + powers and
    whitening scale (2x f32, ~nfft/2 = 2*nfft each) + the scaled
    spectrum (c64, ~nfft/2 = 4*nfft — ALWAYS built now: both stages
    consume it) + the interbinned half-bin grid and its largest
    harmonic-sum intermediate (2x f32, ~nfft bins = 4*nfft each).
    `hi` keeps a modest surcharge for the accel stage's top-k
    bookkeeping riding alongside (the big accel planes have their own
    budget, accel.plane_dm_chunk).  With hi OFF the pass loop keeps
    TWO chunks in flight (backpressure blocks on the chunk-before-
    last), so the second chunk's series + scaled spectrum (4 + 4
    bytes/bin/trial) ride alongside — budget for them, or the
    transient overcommit is ~25% on a device where a runtime OOM
    wedges the chip for hours (round-3 advisor finding)."""
    per_trial = (4 + 4 + 4 + 2 + 2 + 4 + 4 + 4
                 + (2 if hi else 8)) * nfft
    return max(4, int(budget // per_trial))


def search_block(data: jnp.ndarray, freqs: np.ndarray, dt: float,
                 plan: list[ddplan.DedispStep],
                 params: SearchParams | None = None,
                 zaplist: np.ndarray | None = None, baryv: float = 0.0,
                 nsub: int | None = None,
                 timers: StageTimers | None = None,
                 checkpoint_dir: str | None = None,
                 data_id: str = "",
                 checkpoint=None,
                 checkpoint_journal=None,
                 progress_cb=None,
                 mesh=None):
    """Run the plan loop + sifting + folding on an in-HBM block.

    data: (nchan, T) device array, any numeric dtype (uint8 is fine —
    conversion fuses into the subband reduction).  This is the
    benchmark surface: no file I/O, just the compute chain.

    params.dm_shards > 1: each pass's DM trials are sharded over the
    `dm` axis of a mesh of that many local devices (dm_mesh) —
    dedispersion, single-pulse, lo- and hi-accel all run per shard;
    per-trial top-k blocks are the only ICI traffic.  Candidates are
    identical to the single-device path up to float reduction order.
    mesh: a jax.sharding.Mesh with a 'dm' axis handed in by the caller
    instead (tests; ROADMAP D4 removes the keyword).

    checkpoint_dir: when set, per-pass candidate dumps (plus the
    sifted list and each folded candidate) are written there as
    sha256-manifested artifacts (tpulsar/checkpoint/) and completed
    work is verified and skipped on re-entry — pass-level resume on
    top of the reference's job-level restart unit (SURVEY.md 5.4).
    data_id should identify the input beam (file names/sizes/MJD); it
    is folded into the checkpoint fingerprint so another beam's dumps
    in the same directory are never resumed.  checkpoint: an
    already-open CheckpointStore (search_beam passes its own so the
    RFI mask checkpoints too); checkpoint_journal: see search_beam.

    progress_cb: optional callable(dict) invoked after every completed
    dedispersion pass with {pass_idx, npasses, step_idx, ntrials_done,
    ncands, stage_s} — the benchmark/monitoring hook (a killed run
    still leaves per-pass evidence; round-1 verdict weakness #1).

    Returns (candidates, folded, sp_events, num_dm_trials).
    """
    params = params or SearchParams()
    timers = timers or StageTimers()
    mesh = _layout_mesh(params, mesh)   # too few devices: raises here
    # a block laid over several devices by channels is searched on
    # exactly those, share by share: the layout is the operand's
    pmesh.require_same_devices(pmesh.channel_mesh(data), mesh)
    degraded.reset()   # this run's fallback flags only
    # TPULSAR_PROFILE=<dir>: capture a JAX profiler trace of the whole
    # block search (the TPU-era equivalent of the reference's stage
    # timers, SURVEY.md 5.1 — view with TensorBoard/xprof); with
    # TPULSAR_TRACE=1 the spans below land in it, over the device's
    # operations.  The root span: every pass/chunk/stage span of this
    # search nests under it and carries its id as `call`.
    with trace_mod.profile_session(
            os.environ.get("TPULSAR_PROFILE", "").strip()), \
            timers.collecting(), \
            trace_mod.span("search_block",
                           npasses=sum(s.numpasses for s in plan)):
        nchan = data.shape[0]
        nsub = nsub or (params.nsub if nchan % params.nsub == 0
                        else ddplan.largest_divisor_leq(nchan,
                                                        params.nsub))
        store = checkpoint
        if store is None and checkpoint_dir:
            shape_id = (f"{tuple(data.shape)}|{dt!r}|{freqs[0]!r}|"
                        f"{freqs[-1]!r}")
            store = _open_checkpoint(
                checkpoint_dir,
                _ckpt_fingerprint(plan, params, zaplist, baryv, nsub,
                                  data_id=data_id + "|" + shape_id),
                checkpoint_journal)
        beam = _Beam(data, zaplist, baryv, store)
        # a verified 'sifted' artifact short-circuits the whole plan
        # loop (+ sifting + refinement): the crash being resumed
        # happened during folding, and every pass's science is already
        # inside it
        sifted_state = (_load_decoded(store, "sifted", _decode_sifted)
                        if store is not None else None)
        if sifted_state is None:
            _plan_loop([beam], freqs, dt, plan, params, nsub, timers,
                       progress_cb, mesh)
        return _sift_fold_finish(beam, freqs, dt, params, nsub, timers,
                                 sifted_state, plan)


# ------------------------------------------------------- the pass loop

@dataclasses.dataclass
class _Beam:
    """One beam through the pass loop and into the finish: what it
    brings (block, zaplist, baryv, checkpoint store) and what its
    passes leave (raw candidates, single-pulse event chunks, trials
    searched)."""
    data: jnp.ndarray
    zaplist: np.ndarray | None = None
    baryv: float = 0.0
    store: object = None
    cands: list = dataclasses.field(default_factory=list)
    sp_chunks: list = dataclasses.field(default_factory=list)
    ntrials: int = 0


@dataclasses.dataclass
class _Pass:
    """One dedispersion pass of the loop: the geometry every beam of
    the group shares, each beam's subbands, and — on one device — how
    its DM chunks are dispatched (_plan_chunks)."""
    pass_idx: int
    dms: np.ndarray
    sub_shifts: np.ndarray
    subs: list              # per beam: (nsub, T_ds) device subbands
    T_ds: int
    dt_ds: float
    nfft: int               # FFT-friendly padded length (PRESTO
    #                         choose_N via prepsubband -numout,
    #                         PALFA2_presto_search.py:518); one per
    #                         plan step keeps compile signatures bounded
    chunk_sz: int = 0
    keeps: list | None = None       # per beam: (nbins,) zap keep mask

    @property
    def group(self) -> dict:
        return _group_attrs(len(self.subs))

    @property
    def nbins(self) -> int:
        return self.nfft // 2 + 1

    @property
    def T_s(self) -> float:
        return self.nfft * self.dt_ds


def _group_attrs(nbeams: int) -> dict:
    """What a group's `pass` / `dm_chunk` spans and progress dicts carry
    beyond a solo search's: nothing for a group of one."""
    return {"nbeams": nbeams} if nbeams > 1 else {}


class _Chunk(typing.NamedTuple):
    """What one dispatched DM chunk leaves for the pass end."""
    dms: np.ndarray
    sp_pair: tuple          # device: single-pulse top-k (snr, idx)
    lo_res: dict            # device: lo stage top-k per harmonic stage
    hi_cands: list | None   # host: per beam, the hi stage's candidates


def _plan_loop(beams: list[_Beam], freqs, dt, plan, params, nsub,
               timers, progress_cb=None, mesh=None) -> None:
    """The pass loop, for one beam or a group of compatible ones: a
    solo search is a group of one.  Every beam's raw candidates,
    single-pulse events and trial count accumulate on its _Beam.

    With one beam the loop dispatches the solo programs on the solo
    arguments.  With B > 1, stage 1/2 still run per beam with those
    programs (bit-parity bounds what may coalesce) and the spectral
    stages see B*chunk beam-major rows; chunk boundaries are the solo
    pass_chunk_size, so a beam's candidate order — and its per-pass
    checkpoint artifacts — are byte-identical to a run of its own.
    mesh (one beam only) shards each pass's DM trials instead."""
    B = len(beams)
    group = _group_attrs(B)
    npasses = sum(s.numpasses for s in plan)
    # devices the (first) beam's block is laid over: 1 = whole on one
    shares = pmesh.channel_mesh(beams[0].data)
    block_shards = 1 if shares is None else shares.size
    pass_idx = -1
    for step_idx, step in enumerate(plan):
        for ppass in step.passes():
            pass_idx += 1
            if _resume_pass(beams, pass_idx):
                continue
            marks = [(len(b.cands), len(b.sp_chunks), b.ntrials)
                     for b in beams]
            # one span per dedispersion pass: its chunks, stages and
            # pass-end host halves nest under it; progress_cb stays
            # outside (the caller's clock stops there)
            with trace_mod.span("pass", pass_idx=pass_idx,
                                step_idx=step_idx,
                                downsamp=int(step.downsamp),
                                ntrials=len(ppass.dms),
                                block_shards=block_shards, **group):
                ps = _stage1(beams, freqs, dt, nsub, step, ppass,
                             pass_idx, timers, whole=mesh is None)
                if mesh is not None:
                    _sharded_pass(mesh, ps, beams[0], params, timers)
                else:
                    _chunked_pass(ps, beams, params, timers)
                del ps
                _checkpoint_pass(beams, marks, pass_idx, npasses)
            telemetry.passes_total().inc(B)
            telemetry.dm_trials_total().inc(B * len(ppass.dms))
            if progress_cb is not None:
                progress_cb({
                    "pass_idx": pass_idx + 1, "npasses": npasses,
                    "step_idx": step_idx,
                    "ntrials_done": beams[0].ntrials,
                    "ncands": sum(len(b.cands) for b in beams),
                    "stage_s": {k: round(v, 2)
                                for k, v in timers.times.items() if v},
                    **group})


def _resume_pass(beams: list[_Beam], pass_idx: int) -> bool:
    """True when EVERY beam's store holds a verified pass `pass_idx`:
    its partials come from there and the pass is not run.  A group's
    stores never do — search_beam_batch sends a beam with resume state
    to a search of its own, because resuming means skipping passes and
    a group runs every pass for every member."""
    done = []
    for beam in beams:
        got = (_load_decoded(beam.store, f"pass_{pass_idx:04d}",
                             _decode_pass)
               if beam.store is not None else None)
        if got is None:
            return False
        done.append(got)
    for beam, (cands, events, ntr) in zip(beams, done):
        beam.cands.extend(cands)
        if len(events):
            beam.sp_chunks.append(events)
        beam.ntrials += ntr
    return True


def _stage1(beams, freqs, dt, nsub, step, ppass, pass_idx,
            timers, whole: bool = True) -> _Pass:
    """Stage 1 of a pass: each beam's block to subbands at the pass's
    sub-DM, with the solo program.  A block laid over several devices
    leaves its subbands laid over them (share by share); `whole` (the
    one-device pass loop) brings them to the first of those."""
    dms = np.asarray(ppass.dms)
    with timers.timing("subbanding"):
        chan_shifts, sub_shifts = dd.plan_pass_shifts(
            freqs, nsub, ppass.subdm, dms, dt, step.downsamp)
        subs = [dd.form_subbands(b.data, jnp.asarray(chan_shifts),
                                 nsub, step.downsamp) for b in beams]
        if whole:
            subs = [pmesh.on_first_device(s) for s in subs]
    T_ds = int(subs[0].shape[1])
    return _Pass(pass_idx=pass_idx, dms=dms, sub_shifts=sub_shifts,
                 subs=subs, T_ds=T_ds, dt_ds=dt * step.downsamp,
                 nfft=ddplan.choose_n(T_ds))


def _sharded_pass(mesh, ps: _Pass, beam: _Beam, params, timers) -> None:
    """One pass of one beam with its DM trials sharded over the mesh:
    three sibling stages under the `pass` span, `mesh-place`,
    `sharded-search` and `mesh-candidates` (_search_pass_sharded)."""
    cands, events = _search_pass_sharded(
        mesh, ps.subs[0], ps.sub_shifts, ps.dms, ps.dt_ds, params,
        beam.zaplist, beam.baryv, timers=timers, pass_idx=ps.pass_idx)
    beam.cands.extend(cands)
    if len(events):
        beam.sp_chunks.append(events)
    beam.ntrials += len(ps.dms)


def _chunked_pass(ps: _Pass, beams, params, timers) -> None:
    """One pass on one device: DM chunks dispatched with two in
    flight, then one drain and the host halves."""
    _plan_chunks(ps, beams, params)
    # SP and lo-stage device outputs are DEFERRED to one device_get
    # per pass (_drain_pass): a per-chunk blocking np.asarray cost one
    # host<->device round-trip per output.  Only top-k-sized blocks
    # are held, so the deferral is KBs per chunk.  The hi stage stays
    # inline: its internal windowed drain is the per-chunk sync that
    # bounds device memory.
    pending: list[_Chunk] = []
    for lo in range(0, len(ps.dms), ps.chunk_sz):
        if len(pending) >= 2:
            # Backpressure: without any host sync in the loop (hi
            # off), async dispatch would let every chunk's full-size
            # series/wspec buffers be enqueued concurrently —
            # pass_chunk_size budgets for ~one chunk resident.
            # Blocking on the chunk-before-last's lo output (the last
            # consumer of its wspec) bounds it to two chunks in flight
            # while still overlapping dispatch with compute (with hi
            # on the accel drain already finished it; this is then
            # instant).
            with timers.timing("pipeline-wait"):
                jax.block_until_ready(pending[-2].lo_res)
        pending.append(_dispatch_chunk(ps, lo, params, timers))
    _drain_pass(ps, beams, pending, params, timers)
    telemetry.dedisp_trials_total().inc(len(beams) * len(ps.dms))


def _plan_chunks(ps: _Pass, beams, params) -> None:
    """How the pass's DM chunks run: their size, and each beam's zap
    keep mask at this pass's spectrum length."""
    ps.chunk_sz = pass_chunk_size(len(ps.dms), ps.nfft, params)
    if any(b.zaplist is not None for b in beams):
        ps.keeps = [fr.zap_mask(ps.nbins, ps.T_s, b.zaplist, b.baryv)
                    if b.zaplist is not None
                    else np.ones(ps.nbins, bool) for b in beams]


def _beam_major(parts: list):
    """Per-beam row blocks as one beam-major array; a group of one
    keeps its array (no copy program)."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                            axis=0)


def _dispatch_chunk(ps: _Pass, lo: int, params, timers) -> _Chunk:
    """Enqueue one DM chunk's device work — stage 2, single pulse,
    FFT/whiten, lo- and hi-accelsearch — over every beam's rows.
    Nothing here waits for the device but the hi stage's own drain.

    The stage timers carry opt-in device attribution
    (TPULSAR_TRACE_SYNC=1): each fence makes its scope's exit clock
    include the device compute the enqueue started."""
    dm_chunk = ps.dms[lo: lo + ps.chunk_sz]
    n = len(dm_chunk)
    B = len(ps.subs)
    hi = params.run_hi_accel and params.hi_accel_zmax > 0
    # hi_rows: DM rows per hi-accel chunk program as accel_search_batch
    # dispatches them (the planner's own arithmetic), 0 with hi-accel
    # off.  dd_calls x dd_rows: a beam's stage-2 program calls for
    # this chunk and rows a call, dd_groups the subband groups a call
    # sums over; the Pallas wrapper writes what it dispatched (0 where
    # it did not run: the XLA scan).  lo_form/lo_tile,
    # sp_form/sp_tile: the lo stage's harmonic sums and the boxcar ladder
    # as lowered ("tiled" + the kernel's tile on a TPU; _dispatch_attrs)
    hi_rows = (_hi_rows(B * n, ps.T_ds, params)
               if trace_mod.enabled() else 0)
    with trace_mod.span("dm_chunk", pass_idx=ps.pass_idx, lo=int(lo),
                        n=int(n), hi_rows=hi_rows, dd_calls=0,
                        dd_rows=0, dd_groups=0, lo_form="", lo_tile=0,
                        sp_form="", sp_tile=0, **ps.group):
        with timers.timing("dedispersing"):
            shifts = jnp.asarray(ps.sub_shifts[lo: lo + n])
            series = _beam_major([dd.dedisperse_subbands(s, shifts)
                                  for s in ps.subs])
            trace_mod.fence(series)

        with timers.timing("single-pulse"):
            # the device half of single_pulse_search; the host half
            # (events_from_topk) runs at pass end
            sp_pair = sp_k.device_search(
                series, tuple(params.sp_widths),
                estimator=params.sp_detrend)
            trace_mod.fence(sp_pair)

        with timers.timing("FFT"):
            # One fused pad->rfft->whiten->scale program per chunk; the
            # whitened COMPLEX spectrum is shared by the lo stage
            # (interbinned powers) and the hi stage (correlation
            # input).  Zapped bins have wpow==0 so they vanish from
            # both.  One beam: its 1-D (nbins,) keep mask.  A group:
            # 2-D per-row masks (batchmates share a zap digest, but
            # baryv, which shapes the mask, is per beam).
            if ps.keeps is None:
                wspec = fr.whitened_spectrum(series, nfft=ps.nfft)
            else:
                keep = (ps.keeps[0] if B == 1 else np.concatenate(
                    [np.broadcast_to(k, (n, ps.nbins))
                     for k in ps.keeps]))
                wspec = fr.whitened_spectrum_masked(
                    series, jnp.asarray(keep), nfft=ps.nfft)
            trace_mod.fence(wspec)

        with timers.timing("lo-accelsearch"):
            # half-bin detection grid (PRESTO ACCEL_DR=0.5 via
            # interbinning) — bin indices are in half-bin units, hence
            # bin_scale=0.5 at the pass end.  One program; on a TPU XLA
            # writes the interbinned grid once for the sums' kernel
            lo_stages = tuple(fr.harmonic_stages(params.lo_accel_numharm))
            lo_res = fr.lo_stage_candidates(
                wspec, lo_stages, params.topk_per_stage)
            # what ran, on the chunk's span (docs/operations.md)
            if trace_mod.enabled():
                trace_mod.annotate("dm_chunk", **_dispatch_attrs(
                    series.shape, params.sp_widths, wspec.shape,
                    lo_stages, wspec))
            trace_mod.fence(lo_res)

        hi_cands = None
        if hi:
            with timers.timing("hi-accelsearch"):
                hi_cands = _hi_accel_chunk(wspec, dm_chunk, B, ps.T_s,
                                           params)
        del wspec
    return _Chunk(dm_chunk, sp_pair, lo_res, hi_cands)


def _beam_rows(res: dict, b: int, n: int, nbeams: int) -> dict:
    """Beam b's n rows of a {stage: (arrays, ...)} result stacked
    beam-major; the result itself for a group of one."""
    if nbeams == 1:
        return res
    sl = slice(b * n, (b + 1) * n)
    return {h: tuple(np.asarray(a)[sl] for a in t)
            for h, t in res.items()}


def _drain_pass(ps: _Pass, beams, pending: list[_Chunk], params,
                timers) -> None:
    """Pass end: one transfer per stage family (charged to its own
    timer: the first get blocks on ALL the pass's queued device work,
    so attributing it to a compute stage would skew stage_s), then the
    host halves per beam in chunk order — a beam's candidate and event
    order is that of a loop over its chunks alone."""
    B = len(beams)
    with timers.timing("pipeline-drain"):
        sp_host = jax.device_get([c.sp_pair for c in pending])
        lo_host = jax.device_get([c.lo_res for c in pending])
    sigma_fn = _lo_sigma_fn(ps.nbins)
    for chunk, (snrs, idx), res_h in zip(pending, sp_host, lo_host):
        n = len(chunk.dms)
        for b, beam in enumerate(beams):
            rows = slice(b * n, (b + 1) * n)    # all of them for B = 1
            with timers.timing("single-pulse"), \
                    trace_mod.span("sp-events"):
                ev = sp_k.events_from_topk(
                    snrs[:, rows], idx[:, rows], chunk.dms, ps.dt_ds,
                    threshold=params.sp_threshold,
                    widths=tuple(params.sp_widths))
                if len(ev):
                    beam.sp_chunks.append(ev)
                trace_mod.annotate(events=len(ev))
            with timers.timing("lo-accelsearch"), \
                    trace_mod.span("lo-candidates"):
                lo_cands = sifting.make_candidates(
                    _beam_rows(res_h, b, n, B), chunk.dms, ps.T_s,
                    sigma_fn, sigma_min=params.sifting.sigma_threshold,
                    bin_scale=0.5)
                trace_mod.annotate(cands=len(lo_cands))
            beam.cands.extend(lo_cands)
            if chunk.hi_cands is not None:
                beam.cands.extend(chunk.hi_cands[b])
            beam.ntrials += n


def _checkpoint_pass(beams, marks, pass_idx: int, npasses: int) -> None:
    """Each beam's partials of the pass just run (everything past its
    mark) into its own store."""
    for beam, (c0, s0, t0) in zip(beams, marks):
        if beam.store is None:
            continue
        ntr_pass = beam.ntrials - t0
        with trace_mod.span("pass-checkpoint"):
            payload = _encode_pass(
                beam.cands[c0:],
                (np.concatenate(beam.sp_chunks[s0:])
                 if len(beam.sp_chunks) > s0 else _EMPTY_SP),
                ntr_pass)
            durable = beam.store.save(
                f"pass_{pass_idx:04d}", payload,
                kind="pass", ext=".npz", pass_idx=pass_idx)
            trace_mod.annotate(bytes=len(payload),
                               durable=bool(durable))
        if durable:
            # journaled ONLY once the artifact is durable: the chaos
            # verifier's no_pass_rerun invariant treats this event as
            # "never recompute pass k again"
            beam.store.journal("pass_complete", pass_idx=pass_idx,
                               npasses=npasses, ntrials=ntr_pass)


@trace_mod.span("finish")
def _sift_fold_finish(beam: _Beam, freqs, dt, params, nsub, timers,
                      sifted_state, plan):
    """Everything after the plan loop, for one beam — sift, refine,
    checkpoint the sifted list, fold (checkpoint-aware) — whether it
    went through the loop alone or in a group."""
    data, zaplist, baryv, store = (beam.data, beam.zaplist, beam.baryv,
                                   beam.store)
    all_cands, sp_chunks, num_trials = (beam.cands, beam.sp_chunks,
                                        beam.ntrials)
    nfft_full = ddplan.choose_n(data.shape[1])
    T_s_full = nfft_full * dt

    def _series(dm: float):
        # stage 1 over the whole block again, and one stage-2 row
        with trace_mod.span("refine-series", dm=float(dm)):
            return _dedisperse_single(data, freqs, nsub, dm, dt)

    _series_for = _BoundedCache(_series)

    if sifted_state is not None:
        # resumed past every pass AND past sift/refine: the verified
        # artifact carries the refined, sigma-sorted list (plus the SP
        # events and the trial count) exactly as the original attempt
        # computed them — the crash happened during folding
        final, sp_events, num_trials = sifted_state
    else:
        with timers.timing("sifting"):
            final = sifting.sift(all_cands, params.sifting)
            trace_mod.annotate(n_in=len(all_cands), n_out=len(final))

        sp_events = (np.concatenate(sp_chunks) if sp_chunks
                     else _EMPTY_SP)

        # One consistent bin scale for the reported r column:
        # candidates from different plan passes carry pass-local
        # (downsampled, padded) bin units; normalize everything to the
        # full-resolution padded scale via the invariant frequency.
        for c in final:
            c.r = c.freq_hz * T_s_full

        # Sub-bin refinement of the reported candidates (PRESTO's
        # harmpolish stage; round-1 verdict missing #3): each
        # fold-worthy candidate's (r, z) is optimized on a
        # full-resolution series for its DM, and its sigma recomputed
        # from the refined power.  The per-DM series are processed
        # group-by-group and only a few are cached (a long beam's
        # full-resolution series is ~GBs across 100 candidates' DMs).
        to_refine = [c for c in final
                     if c.sigma >= params.to_prepfold_sigma]
        to_refine = to_refine[: params.max_cands_to_fold]

        if params.refine_cands and to_refine:
            from tpulsar.search import refine

            with timers.timing("refinement"):
                trace_mod.annotate(n=len(to_refine))
                # lo/hi identity by DETECTION z — refinement perturbs
                # z off exact zero, which must not flip a lo candidate
                # onto the hi search's nz-times-larger trial count
                was_hi = {id(c): abs(c.z) >= accel_k.DZ / 2
                          for c in to_refine}
                keep_full = fr.zap_mask(nfft_full // 2 + 1, T_s_full,
                                        zaplist, baryv) \
                    if zaplist is not None else None
                by_dm: dict[float, list] = {}
                for c in to_refine:
                    by_dm.setdefault(c.dm, []).append(c)
                for dm, group in by_dm.items():
                    refine.refine_candidates(
                        group, {dm: _series_for(dm)}, dt, nfft_full,
                        keep_mask=keep_full)
                nz_hi = (len(_get_bank(params.hi_accel_zmax).zs)
                         if params.run_hi_accel
                         and params.hi_accel_zmax > 0
                         else 1)
                nbins_full = nfft_full // 2 + 1
                for c in to_refine:
                    # trial count approximated with the full-res bin
                    # count (pass-local counts differ by <= the
                    # downsample factor: a few 0.1 sigma at most)
                    nind = max(1, (nbins_full
                                   * (nz_hi if was_hi[id(c)] else 1))
                               // c.numharm)
                    c.sigma = float(fr.sigma_from_power(
                        c.power, c.numharm, numindep=nind))
                final.sort(key=lambda c: -c.sigma)
        if store is not None:
            store.save("sifted",
                       _encode_sifted(final, sp_events, num_trials),
                       kind="stage", ext=".npz")

    # Fold the top of the (possibly re-ranked) list.  Because final is
    # sigma-descending and the fold set is its >=threshold prefix,
    # folded[k] corresponds to final[k] — the _cand{k+1} artifacts and
    # the .accelcands rows stay in one-to-one order (the uploader
    # pairs them by index).
    to_fold = [c for c in final if c.sigma >= params.to_prepfold_sigma]
    to_fold = to_fold[: params.max_cands_to_fold]
    folded_by_idx: dict[int, fold_k.FoldResult] = {}
    if store is not None:
        # each already-folded candidate is its own verified artifact:
        # a crash at fold k resumes at fold k, not fold 0.  Artifacts
        # are keyed by POSITION, so each carries its candidate's
        # (input period, dm) identity — if the sifted list was
        # regenerated since the folds were written (e.g. its artifact
        # failed to save and a recomputed pass shifted the sigma
        # ordering), position k may name a DIFFERENT candidate, and a
        # sha-valid fold must not be attributed to it
        for k in range(len(to_fold)):
            payload = store.load(f"fold_{k:04d}")
            if payload is None:
                continue
            dec = _decode_fold(payload)
            if dec is None:
                store.discard(f"fold_{k:04d}",
                              reason="undecodable payload")
                continue
            res, ident = dec
            if ident != (to_fold[k].period_s, to_fold[k].dm):
                store.discard(f"fold_{k:04d}",
                              reason="candidate identity mismatch "
                                     "(sifted list regenerated)")
                continue
            folded_by_idx[k] = res

    def _save_fold(k: int) -> None:
        if store is not None:
            store.save(f"fold_{k:04d}",
                       _encode_fold(folded_by_idx[k], to_fold[k]),
                       kind="fold", ext=".npz", cand=k)

    def _subbands_for(dm: float):
        ch_sh, sub_sh = dd.plan_pass_shifts(freqs, nsub, dm, [dm],
                                            dt, 1)
        with trace_mod.span("fold-subbands", dm=float(dm), downsamp=1):
            subb = _subbands_on_one(data, ch_sh, nsub, 1)
            trace_mod.fence(subb)
        return subb, sub_sh[0]

    with timers.timing("folding"):
        trace_mod.annotate(n=len(to_fold))
        if params.fold_by_rules and params.fold_batched and to_fold:
            # Tier-batched pass-grouped folding: candidates fold from
            # their originating pass's subband geometry (subdm +
            # downsamp — the same form_subbands program the search
            # passes already compiled), one device program per tier.
            from tpulsar.kernels import fold_batch as fbk

            missing = [k for k in range(len(to_fold))
                       if k not in folded_by_idx]
            if missing:
                folded_by_idx.update(fbk.fold_candidates_by_pass(
                    data, freqs, dt, plan,
                    [(k, to_fold[k].period_s, to_fold[k].dm)
                     for k in missing],
                    nsub, _subbands_on_one))
                for k in missing:
                    _save_fold(k)
            folded = [folded_by_idx[k] for k in range(len(to_fold))]
            return final, folded, sp_events, num_trials

        # group by DM so each DM's subband block is formed once even
        # when same-DM candidates interleave in the sigma ordering
        fold_groups: dict[float, list[int]] = {}
        for k, c in enumerate(to_fold):
            if k not in folded_by_idx:
                fold_groups.setdefault(c.dm, []).append(k)
        for dm, idxs in fold_groups.items():
            if params.fold_by_rules:
                # fold from subbands so the DM axis is a per-subband
                # phase rotation (the reference folds subband files
                # for the same reason, PALFA2_presto_search.py:168-175)
                subb_f, sub_sh0 = _subbands_for(dm)
                subrefs = dd.subband_reference_freqs(freqs, nsub)
                for k in idxs:
                    c = to_fold[k]
                    folded_by_idx[k] = fold_k.fold_subbands_and_optimize(
                        subb_f, subrefs, dt, c.period_s, dm=dm,
                        rules=fold_k.fold_rules(c.period_s),
                        sub_shifts_dm0=sub_sh0)
                    _save_fold(k)
                del subb_f
            else:
                for k in idxs:
                    c = to_fold[k]
                    folded_by_idx[k] = fold_k.fold_and_optimize(
                        _series_for(c.dm), dt, c.period_s, dm=c.dm,
                        nbin=params.fold_nbin, npart=params.fold_npart)
                    _save_fold(k)
    folded = [folded_by_idx[k] for k in range(len(to_fold))]

    return final, folded, sp_events, num_trials


# ------------------------------------------------------------------ helpers

def _mesh_rows_budget(nfft: int, budget: int) -> int:
    """Most DM rows a device of the mesh takes in one call of the fused
    pass program (parallel/mesh.sharded_pass_fn) at series length
    nfft: single pulse, spectra and the lo stage of all its rows are
    ONE program's temporaries there, 65-85 bytes a sample a row by the
    chip's compiler (26 rows at nfft 6,144,000 asked 12.4-13.1 GiB of
    a v5e beside the beam's share; PERF.md section 6, PR 43) against
    the ~32 that `_budget_dm_chunk` counts for the one-device loop's
    separate programs."""
    return max(1, int(budget // (80 * nfft)))


def pass_chunk_size(ndms: int, nfft: int, params: SearchParams) -> int:
    """The DM-chunk size a pass actually runs with: the HBM budget and
    max_dms_per_chunk cap, then an even split so every chunk of the
    pass shares one compile signature (76 trials at a 51-trial budget
    run as 38+38, not 51+25).  tools/aot_check.py compiles gate
    programs at this exact shape — keep the two in lockstep."""
    chunk_sz = min(params.max_dms_per_chunk,
                   _budget_dm_chunk(
                       nfft,
                       hi=params.run_hi_accel and params.hi_accel_zmax > 0,
                       budget=params.spectral_hbm_budget))
    chunk_sz = min(chunk_sz, ndms)
    n_chunks = -(-ndms // chunk_sz)
    return -(-ndms // n_chunks)


class _BoundedCache:
    """Tiny LRU-bounded memo for per-DM device arrays (a long
    beam's full-resolution series is too big to keep one per
    candidate DM).

    LRU, not FIFO: refinement revisits the handful of hottest DM
    values as same-DM candidates interleave in the sigma ordering, so
    FIFO evicted exactly the series about to be re-requested.  A hit
    re-inserts the key (dicts iterate in insertion order, so the
    first key is always the least recently USED, not the oldest)."""

    def __init__(self, fn, capacity: int = 4):
        self._fn = fn
        self._cap = capacity
        self._d: dict = {}

    def __call__(self, key):
        if key in self._d:
            self._d[key] = self._d.pop(key)     # touch: move to MRU
        else:
            while len(self._d) >= self._cap:
                self._d.pop(next(iter(self._d)))
            self._d[key] = self._fn(key)
        return self._d[key]


def _lo_sigma_fn(nbins: int):
    """Stage sigma with the zero-accel search's trial count: the
    search examined ~nbins/h independent summed powers per DM per
    stage (PRESTO passes the same counts to candidate_sigma)."""
    return lambda p, h: fr.sigma_from_power(
        p, h, numindep=max(1, nbins // h))


def _hi_sigma_fn(nbins: int, nz: int):
    """Stage sigma with the accelerated search's (r, z) plane trial
    count."""
    return lambda p, h: fr.sigma_from_power(
        p, h, numindep=max(1, (nbins * nz) // h))


_EMPTY_SP = np.empty(0, dtype=sp_k.SP_EVENT_DTYPE)

_CAND_FIELDS = ("r", "z", "sigma", "power", "numharm", "dm",
                "period_s", "freq_hz")


def _ckpt_fingerprint(plan, params, zaplist, baryv, nsub,
                      data_id: str = "") -> str:
    """Configuration + input fingerprint stored with the checkpoints:
    dumps from a different search configuration OR a different beam
    must not be resumed."""
    from tpulsar.checkpoint import hashing
    zap = (np.asarray(zaplist).tobytes() if zaplist is not None
           else b"none")
    blob = repr((
        [(s.lodm, s.dmstep, s.dms_per_pass, s.numpasses, s.numsub,
          s.downsamp) for s in plan],
        sorted(params.provenance().items()), baryv, nsub, data_id,
    )).encode() + zap
    return hashing.sha256_bytes(blob)


def _open_checkpoint(ckdir: str, fingerprint: str, journal=None):
    """Open the beam's CheckpointStore (tpulsar/checkpoint/) and
    journal the ``resume`` event when it holds prior artifacts — the
    auditable record that this attempt started from saved work."""
    import warnings

    from tpulsar import checkpoint as ckpt_mod

    store = ckpt_mod.CheckpointStore(
        ckdir, fingerprint, journal=journal,
        warn=lambda msg: warnings.warn(msg, stacklevel=2))
    ent = store.entries()
    if ent:
        store.journal("resume", artifacts=len(ent),
                      passes_done=len(store.entries(kind="pass")))
    return store


def _npz_bytes(**arrays) -> bytes:
    import io
    buf = io.BytesIO()
    np.savez_compressed(buf, **arrays)
    return buf.getvalue()


def _load_decoded(store, key: str, decode):
    """Verified load + decode.  A payload whose BYTES verify but
    whose layout no longer decodes (a payload-format drift shipped
    without a SCHEMA bump) must be DISCARDED through the store —
    journaling the ``checkpoint_invalid`` excuse — not silently
    dropped: the recompute journals a second ``pass_complete``, and
    without the excuse the no_pass_rerun invariant would flag a
    healthy, correctly-recovering beam."""
    payload = store.load(key)
    if payload is None:
        return None
    out = decode(payload)
    if out is None:
        store.discard(key, reason="undecodable payload")
    return out


def _encode_pass(cands: list[sifting.Candidate], events: np.ndarray,
                 ntrials: int) -> bytes:
    """One pass's partials as an npz payload (the checkpoint layer
    stores bytes; the sha256 manifest entry guards them)."""
    arrs = {f: np.asarray([getattr(c, f) for c in cands])
            for f in _CAND_FIELDS}
    return _npz_bytes(events=events, ntrials=np.int64(ntrials), **arrs)


def _decode_pass(payload: bytes | None):
    """(cands, events, ntrials) from a verified pass payload, else
    None (an undecodable payload is recomputed like a missing one)."""
    if payload is None:
        return None
    import io
    try:
        with np.load(io.BytesIO(payload), allow_pickle=False) as z:
            n = len(z["sigma"])
            cands = [sifting.Candidate(**{
                f: (int if f == "numharm" else float)(z[f][i])
                for f in _CAND_FIELDS}) for i in range(n)]
            return cands, z["events"], int(z["ntrials"])
    except (OSError, ValueError, KeyError):
        return None


def _encode_sifted(final: list[sifting.Candidate],
                   sp_events: np.ndarray, num_trials: int) -> bytes:
    """The post-refinement sigma-sorted list, WITH each candidate's
    DM-hit history (the uploader reports num_dm_hits) plus the beam's
    SP events and trial count — everything the fold stage and the
    artifact writers need, so a fold-stage crash resumes here."""
    arrs = {f: np.asarray([getattr(c, f) for c in final])
            for f in _CAND_FIELDS}
    hit_counts = np.asarray([len(c.dm_hits) for c in final], np.int64)
    flat = [pair for c in final for pair in c.dm_hits]
    hits = (np.asarray(flat, np.float64).reshape(-1, 2) if flat
            else np.zeros((0, 2), np.float64))
    return _npz_bytes(events=sp_events, ntrials=np.int64(num_trials),
                      hit_counts=hit_counts, hits=hits, **arrs)


def _decode_sifted(payload: bytes | None):
    if payload is None:
        return None
    import io
    try:
        with np.load(io.BytesIO(payload), allow_pickle=False) as z:
            n = len(z["sigma"])
            hit_counts, hits = z["hit_counts"], z["hits"]
            cands, off = [], 0
            for i in range(n):
                c = sifting.Candidate(**{
                    f: (int if f == "numharm" else float)(z[f][i])
                    for f in _CAND_FIELDS})
                k = int(hit_counts[i])
                c.dm_hits = [(float(dm), float(sg))
                             for dm, sg in hits[off:off + k]]
                off += k
                cands.append(c)
            return cands, z["events"], int(z["ntrials"])
    except (OSError, ValueError, KeyError):
        return None


def _encode_fold(res: fold_k.FoldResult,
                 cand: sifting.Candidate) -> bytes:
    """A fold result PLUS the identity of the candidate it folded
    (the sift-time input period/dm): FoldResult carries only the
    optimized values, and the float round trip back to the input is
    not exact — so the binding is stored, not derived."""
    return _npz_bytes(
        profile=res.profile, subints=res.subints,
        scalars=np.asarray(
            [res.period_s, res.pdot, res.dm, res.reduced_chi2,
             res.delta_p, res.delta_pdot, res.delta_dm], np.float64),
        geom=np.asarray([res.nbin, res.npart], np.int64),
        cand_ident=np.asarray([cand.period_s, cand.dm], np.float64))


def _decode_fold(payload: bytes | None):
    """(FoldResult, (input_period_s, input_dm)) or None."""
    if payload is None:
        return None
    import io
    try:
        with np.load(io.BytesIO(payload), allow_pickle=False) as z:
            s, g, ident = z["scalars"], z["geom"], z["cand_ident"]
            return fold_k.FoldResult(
                period_s=float(s[0]), pdot=float(s[1]), dm=float(s[2]),
                nbin=int(g[0]), npart=int(g[1]), profile=z["profile"],
                subints=z["subints"], reduced_chi2=float(s[3]),
                delta_p=float(s[4]), delta_pdot=float(s[5]),
                delta_dm=float(s[6])), (float(ident[0]),
                                        float(ident[1]))
    except (OSError, ValueError, KeyError):
        return None


def _compute_baryv(si) -> float:
    """Average barycentric velocity for the observation from the beam
    header, like the reference's obs_info (PALFA2_presto_search.py:269).
    Unknown telescopes get 0.0 (topocentric reporting) with a warning
    rather than a failed search."""
    from tpulsar.astro import barycenter
    try:
        return barycenter.average_baryv(
            si.ra2000, si.dec2000, float(si.start_MJD[0]), float(si.T),
            obs=si.telescope)
    except ValueError:
        import warnings
        warnings.warn(
            f"no observatory coordinates for telescope "
            f"{si.telescope!r}; candidate frequencies will be "
            f"topocentric (baryv=0)")
        return 0.0


def _subbands_on_one(data, chan_shifts, nsub, downsamp):
    """Stage 1 for the finish: the solo call on a block on one device;
    a laid-out block's subbands are formed share by share like a
    pass's and then brought whole to its first device, where refine
    and fold read them."""
    return pmesh.on_first_device(dd.form_subbands(
        data, jnp.asarray(chan_shifts), nsub, downsamp))


def _dedisperse_single(data, freqs, nsub, dm, dt):
    """One full-resolution DM series for folding."""
    chan_shifts, sub_shifts = dd.plan_pass_shifts(freqs, nsub, dm, [dm],
                                                  dt, 1)
    subb = _subbands_on_one(data, chan_shifts, nsub, 1)
    return np.asarray(dd.dedisperse_subbands(
        subb, jnp.asarray(sub_shifts)))[0]


def _hi_rows(ndms: int, T: int, params: SearchParams) -> int:
    """DM rows per hi-accel chunk program for a chunk of `ndms` series
    of length T, by the batch planner's own arithmetic
    (accel_batch.batch_rows); 0 with hi-accel off."""
    if not (params.run_hi_accel and params.hi_accel_zmax > 0):
        return 0
    from tpulsar.kernels import accel_batch

    return accel_batch.batch_rows(
        ndms, ddplan.choose_n(T) // 2 + 1,
        len(accel_k.z_grid(params.hi_accel_zmax)))


def _hi_accel_chunk(wspec, dm_chunk, nbeams: int, T_s,
                    params: SearchParams) -> list[list]:
    """accelsearch zmax>0 over one DM chunk of already-whitened
    complex spectra (device-batched; shared with the lo stage):
    `nbeams x len(dm_chunk)` beam-major rows in, each beam's
    candidates out.  Rows are independent in accel_search_batch (the
    accel_batch parity contract), so a beam's slice of a stacked
    dispatch is bit-identical to a dispatch of its own.

    A refused dispatch of ONE beam's rows goes down the ladder
    (_rescue_refused_chunk: host rescue -> zero-fill).  A refused
    stacked dispatch degrades PER BEAM: each beam's rows go through
    this function alone, so one beam's poisoned spectra never cost a
    batchmate its hi-accel science."""
    bank = _get_bank(params.hi_accel_zmax)
    n = len(dm_chunk)
    try:
        res = accel_k.accel_search_batch(
            wspec, bank, max_numharm=params.hi_accel_numharm,
            topk=params.topk_per_stage)
    except accel_k.AccelStageRefused as exc:
        if nbeams > 1:
            return [_hi_accel_chunk(wspec[b * n:(b + 1) * n], dm_chunk,
                                    1, T_s, params)[0]
                    for b in range(nbeams)]
        res = _rescue_refused_chunk(wspec, bank, dm_chunk, params, exc)
        if res is None:
            return [[]]
    else:
        # clean chunks must feed the denominator too (n=0), per beam,
        # or the recorded loss fraction always reads 100% of the
        # counted chunks — count()'s own documented contract
        for _ in range(nbeams):
            degraded.count("accel_hi_chunk_skipped", 0, n)

    # z~0 rows are the lo search's job (z_min_abs); sub-threshold rows
    # never become Python objects (sigma_min pre-filter).  The
    # correlation plane is numbetween=2 interpolated: r indices are
    # half-bin units (bin_scale).
    sigma_fn = _hi_sigma_fn(wspec.shape[-1], len(bank.zs))
    out = []
    for b in range(nbeams):
        with trace_mod.span("accel-candidates"):
            cands = sifting.make_candidates(
                _beam_rows(res, b, n, nbeams), dm_chunk, T_s, sigma_fn,
                sigma_min=params.sifting.sigma_threshold,
                z_min_abs=accel_k.DZ / 2, bin_scale=0.5)
            trace_mod.annotate(cands=len(cands))
        out.append(cands)
    return out


def _rescue_refused_chunk(wspec, bank, dm_chunk, params: SearchParams,
                          exc):
    """The ladder under one beam's hi-accel chunk that the runtime
    refused outright (UNIMPLEMENTED): its result, or None when the
    chunk's hi stage is skipped.

    Last resort before losing science: recompute the WHOLE chunk on
    the host CPU backend — slower, but a complete beam.  Skipped when
    the kernel's own per-row rescue already ran on these exact spectra
    and recovered nothing (rescue_exhausted): repeating the doomed
    recompute would double the cost of the skip that is coming anyway.
    Only when no rescue is possible does the chunk's hi stage skip
    loudly: the beam keeps its SP, lo, fold, and other chunks' hi
    science instead of dying with nothing recorded."""
    import time as _time
    import warnings

    from tpulsar.resilience import rescue
    chunk_res = None
    t_rescue = _time.perf_counter()
    if not getattr(exc, "rescue_exhausted", False):
        chunk_res = rescue.rescue_accel_chunk(
            wspec, bank, max_numharm=params.hi_accel_numharm,
            topk=params.topk_per_stage)
    if chunk_res is not None:
        # observed only when the rescue DELIVERED rows — the
        # trials counter and this histogram must describe the
        # same calls or the derived per-path dm_trials_per_sec
        # skews toward zero on a fleet with failing rescues
        telemetry.accel_stage_seconds().observe(
            _time.perf_counter() - t_rescue, path="rescued")
    if chunk_res is None:
        degraded.count("accel_hi_chunk_skipped", len(dm_chunk),
                       len(dm_chunk), extra=str(exc)[:160])
        telemetry.rescue_rows_total().inc(len(dm_chunk),
                                          outcome="lost")
        warnings.warn(f"hi-accel chunk skipped: {exc}")
        return None
    res, lost_rows = chunk_res
    n_ok = len(dm_chunk) - len(lost_rows)
    telemetry.rescue_rows_total().inc(n_ok, outcome="rescued")
    if n_ok:
        # the kernel raised before its own trials accounting, so
        # the chunk-rescued rows are counted HERE, once
        telemetry.accel_batch_trials_total().inc(n_ok,
                                                 path="rescued")
    if lost_rows:
        telemetry.rescue_rows_total().inc(len(lost_rows),
                                          outcome="lost")
    degraded.provenance_count(
        "accel_rows_rescued", n_ok, len(dm_chunk),
        extra="whole chunk refused by the runtime; recomputed on "
              "the host CPU backend — rescued rows were slower "
              "but complete")
    # lost_rows feed the LOSS ledger (and clean rescues feed its
    # denominator, n=0): a partial chunk rescue is partial
    # coverage, never dressed as complete
    degraded.count(
        "accel_rows_zero_filled", len(lost_rows), len(dm_chunk),
        extra="chunk-rescue recompute failed for these rows; "
              "powers zero-filled — hi-accel coverage is PARTIAL")
    degraded.count("accel_hi_chunk_skipped", 0, len(dm_chunk))
    warnings.warn(
        f"hi-accel chunk refused by the runtime and recomputed "
        f"on the host CPU backend ({n_ok}/{len(dm_chunk)} rows"
        + (f"; {len(lost_rows)} rows lost and zero-filled"
           if lost_rows else "")
        + f"; provenance recorded): {exc}")
    return res


_BANK_CACHE: dict[int, accel_k.TemplateBank] = {}


def _get_bank(zmax: int) -> accel_k.TemplateBank:
    if zmax not in _BANK_CACHE:
        _BANK_CACHE[zmax] = accel_k.build_template_bank(float(zmax))
    return _BANK_CACHE[zmax]


_SHARDED_FN_CACHE: dict[tuple, object] = {}


def _mesh_exchange(mesh, subb, form: str, whole, rows: int, timers):
    """The one exchange of a laid-out beam's pass, a stage of its own
    beside `mesh-place`: stage 1's subbands, laid over the mesh by
    subband, into the operand stage 2 reads (`_search_pass_sharded`
    says which form when).  `bytes` is what crosses between chips,
    summed over them: a copy to each of the others ("replicate"), or
    the partial sums of the pass's `rows` series that the chunk
    programs' reduce-scatters will move ("partial": no byte moves
    here, the pieces are handed on under the mesh's own sharding)."""
    n = int(mesh.shape["dm"])
    with timers.timing("mesh-exchange"):
        if form == "partial":
            out = pmesh.as_dm_rows(mesh, subb)
            moved = (n - 1) * rows * int(subb.shape[1]) * 4
        else:
            out = jax.block_until_ready(pmesh.reshard(subb, whole))
            moved = (n - 1) * subb.nbytes
        trace_mod.annotate("mesh-exchange", bytes=moved, form=form,
                           devices=n)
    telemetry.mesh_exchange_bytes_total().inc(moved, form=form)
    return out


def _search_pass_sharded(mesh, subb, sub_shifts, dms, dt_ds,
                         params: SearchParams, zaplist, baryv,
                         timers: StageTimers | None = None,
                         pass_idx: int = 0):
    """One dedispersion pass with the DM axis sharded over the mesh.

    Runs the same pipeline as the single-device chunk loop —
    dedisperse, SP boxcars, whiten, lo harmonic stages, hi z-template
    correlation — as ONE fused sharded program per DM chunk, then
    converts the gathered top-k blocks with the same host code.
    Returns (candidates, sp_events).

    Three stages, so that each cost has a name: `mesh-place` puts the
    operands every device reads whole (subband block, keep mask, bank,
    taps) on the mesh ONCE a pass; `sharded-search` is the chunk calls
    and their blocking fetches, a `mesh_chunk` span a call with a
    `mesh-fetch` child; `mesh-candidates` is the host halves.  A call
    computes `chunk` rows whatever is left of the pass (one compile:
    the last call is clamped back over rows already searched, and the
    table is padded to the mesh): tpulsar_mesh_rows_total counts the
    rows that were a trial's first search, and the rest.

    Robustness gates carry over from the single-device path: stage 2
    is the Pallas kernel exactly when dedisperse_subbands would take
    it, and the hi stage runs sharded only while the process's batched
    path holds (accel._batch_path_usable); when it does not, the hi
    stage goes down the single-device route after the pass, in the
    one-device loop's chunks (pass_chunk_size), and is refused where a
    laid-out beam's subbands stayed where they lie (form "partial").
    """
    from tpulsar.kernels import pallas_dd

    n_dm = int(mesh.shape["dm"])
    T_ds = int(subb.shape[-1])
    nfft = ddplan.choose_n(T_ds)
    nbins = nfft // 2 + 1
    T_s = nfft * dt_ds
    hi = params.run_hi_accel and params.hi_accel_zmax > 0
    hi_sharded = hi and accel_k._batch_path_usable()
    if hi_sharded:
        from tpulsar.resilience import faults
        if faults.targets_prefix("accel."):
            # a fault spec naming an accel dispatch point pins the
            # single-device hi route: the fused sharded program never
            # dispatches per-row/per-chunk accel work, so the fault —
            # and the retry/rescue path it exists to exercise — would
            # never fire under it
            hi_sharded = False
    bank = _get_bank(params.hi_accel_zmax) if hi else None
    nz = len(bank.zs) if hi else 0
    use_pallas = pallas_dd.use_pallas()
    smax = int(np.asarray(sub_shifts).max(initial=0))
    dd_pad = dd._pad_bucket(smax)
    # a trial's series is searched whole on one device: its spectral
    # tail (complex spectrum, powers, whitened copy, padded series)
    # has to fit there, and nothing here splits ONE series
    tail = 16 * nbins + 4 * nfft
    if tail > params.spectral_hbm_budget:
        raise ValueError(
            f"one DM trial's spectral tail at nfft={nfft} takes {tail} "
            f"bytes a device, over spectral_hbm_budget="
            f"{params.spectral_hbm_budget}: a series this long cannot "
            f"be searched on this mesh")
    # Subbands that arrive laid over the mesh BY SUBBAND (stage 1 of
    # a beam laid out by channels) are brought into stage 2's operand
    # by ONE exchange a pass, `mesh-exchange`, in one of two forms:
    # "replicate" (at or under seq_shard_min_bytes: every chip a whole
    # copy, gathered from the pieces, then a whole block's program) or
    # "partial" (they stay where they are: each chip sums its own
    # subbands for every row and a reduce-scatter inside the chunk
    # program leaves it its rows, mesh._partial_dd).  A whole block's
    # subbands are replicated by `mesh-place`.
    laid_out = pmesh.channel_mesh(subb) is not None
    form = None if not laid_out else (
        "partial" if subb.nbytes > params.seq_shard_min_bytes
        else "replicate")
    stage_s = 0
    if use_pallas:
        stage_s = pallas_dd.stage_overhang(smax)
    spec = pmesh.PassSpec(
        nfft=nfft,
        max_numharm=params.lo_accel_numharm,
        topk=params.topk_per_stage,
        sp_widths=tuple(params.sp_widths), sp_topk=sp_k.DEFAULT_TOPK,
        sp_detrend=sp_k.detrend_estimator(params.sp_detrend),
        whiten_est=fr.whiten_estimator(),
        hi=hi_sharded, hi_numharm=params.hi_accel_numharm,
        hi_seg=bank.seg if hi_sharded else 0,
        hi_step=bank.step if hi_sharded else 0,
        hi_width=bank.width if hi_sharded else 0,
        hi_nz=nz if hi_sharded else 0,
        pallas_dd=use_pallas, dd_stage_s=stage_s,
        dd_interpret=use_pallas and not pallas_dd.is_tpu_backend(),
        dd_pad=dd_pad, sub_sharded=form == "partial")
    key = (mesh, spec)
    if key not in _SHARDED_FN_CACHE:
        _SHARDED_FN_CACHE[key] = pmesh.sharded_pass_fn(mesh, spec)
    fn = _SHARDED_FN_CACHE[key]

    timers = timers or StageTimers()
    keep = fr.zap_mask(nbins, T_s, zaplist, baryv) \
        if zaplist is not None else np.ones(nbins, bool)
    # the operands every device reads whole, placed once a pass (left
    # to the jitted program, device 0's 1.4 GiB subband block is
    # copied to the mesh at every chunk call).  The hi stage's taps
    # only where it correlates directly (a TPU mesh).
    whole = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec())
    padded = pmesh.shard_dm_table(np.asarray(sub_shifts), n_dm)
    if laid_out:
        subb_m = _mesh_exchange(mesh, subb, form, whole, len(padded),
                                timers)
    with timers.timing("mesh-place"):
        if not laid_out:
            subb_m = jax.device_put(subb, whole)
        keep_arr = jax.device_put(keep.astype(np.float32), whole)
        bank_arr = jax.device_put(
            bank.bank_fft if hi_sharded
            else np.zeros((1, 1), np.complex64), whole)
        taps_arr = None
        if hi_sharded and accel_k.corr_form() == "direct":
            taps_arr = jax.device_put(accel_k.corr_taps(bank), whole)
        placed = [a for a in (None if laid_out else subb_m, keep_arr,
                              bank_arr, taps_arr) if a is not None]
        jax.block_until_ready(placed)
        nplaced = sum(sh.data.nbytes for a in placed
                      for sh in a.addressable_shards)
        trace_mod.annotate("mesh-place", bytes=nplaced, devices=n_dm)
    telemetry.mesh_bytes_placed_total().inc(nplaced)

    ndms_pad, ndms = len(padded), len(dms)
    # Chunk size: multiple of the dm axis, bounded by the per-device
    # accel-plane HBM budget and the configured DM chunk.
    chunk = params.max_dms_per_chunk
    if hi_sharded:
        # as many rows a device as the plane budget holds (6 at Mock's
        # ds=1, 8 / 4 at FAST GPPS's ds=2 / ds=1), all in ONE program
        chunk = min(chunk, n_dm * accel_k.plane_dm_chunk(
            nbins, nz, max_chunk=32))
    chunk = max(n_dm, (chunk // n_dm) * n_dm)
    chunk = min(chunk, ndms_pad)
    # ... and by the fused program's own working set a device: where a
    # call's rows would pass it (FAST GPPS's ds=1 pass with hi-accel
    # off: 26 rows a device, two calls of 13), the pass is split evenly
    cap = n_dm * _mesh_rows_budget(nfft, params.spectral_hbm_budget)
    if chunk > cap:
        calls = -(-ndms_pad // cap)
        chunk = n_dm * -(-ndms_pad // (calls * n_dm))

    stages_lo = fr.harmonic_stages(params.lo_accel_numharm)
    stages_hi = fr.harmonic_stages(params.hi_accel_numharm) if hi else []
    lo_vals = np.empty((len(stages_lo), ndms_pad, params.topk_per_stage),
                       np.float32)
    lo_bins = np.empty_like(lo_vals, dtype=np.int64)
    sp_snr = np.empty((len(params.sp_widths), ndms_pad,
                       sp_k.DEFAULT_TOPK), np.float32)
    sp_idx = np.empty_like(sp_snr, dtype=np.int64)
    if hi_sharded:
        hi_vals = np.empty((ndms_pad, len(stages_hi),
                            params.topk_per_stage), np.float32)
        hi_rbins = np.empty_like(hi_vals, dtype=np.int32)
        hi_zidx = np.empty_like(hi_rbins)

    with timers.timing("sharded-search"):
        for c0 in range(0, ndms_pad, chunk):
            s0 = min(c0, ndms_pad - chunk)   # clamp: keep one compile
            # trials this call is the first to search; the clamped
            # rows before c0 and the table's padding are recomputed
            nfirst = max(0, min(c0 + chunk, ndms) - c0)
            with trace_mod.span("mesh_chunk", pass_idx=pass_idx, lo=c0,
                                n=nfirst, rows=chunk,
                                rows_per_device=chunk // n_dm,
                                devices=n_dm, hi=hi_sharded,
                                lo_form="", lo_tile=0, sp_form="", sp_tile=0):
                out = fn(subb_m, jnp.asarray(padded[s0:s0 + chunk]),
                         keep_arr, bank_arr, taps_arr)
                if trace_mod.enabled():     # the forms a device's rows got
                    trace_mod.annotate(**_dispatch_attrs(
                        (chunk // n_dm, T_ds), params.sp_widths,
                        (chunk // n_dm, nbins), stages_lo, mesh),
                        **_mesh_call_attrs(form, hi_sharded, nbins, nz,
                                           chunk // n_dm))
                # the program's seconds apart from the transfers': the
                # first fetch would block on the same program anyway
                with trace_mod.span("mesh-wait", rows=chunk):
                    jax.block_until_ready(out)
                sl = slice(s0, s0 + chunk)
                with trace_mod.span(
                        "mesh-fetch",
                        bytes=sum(x.nbytes for x in out.values())):
                    lo_vals[:, sl] = np.asarray(out["lo_vals"])
                    lo_bins[:, sl] = np.asarray(out["lo_bins"])
                    sp_snr[:, sl] = np.asarray(out["sp_snr"])
                    sp_idx[:, sl] = np.asarray(out["sp_idx"])
                    if hi_sharded:
                        hi_vals[sl] = np.asarray(out["hi_vals"])
                        hi_rbins[sl] = np.asarray(out["hi_rbins"])
                        hi_zidx[sl] = np.asarray(out["hi_zidx"])
            telemetry.mesh_rows_total().inc(nfirst, kind="searched")
            telemetry.mesh_rows_total().inc(chunk - nfirst,
                                            kind="recomputed")
            if hi_sharded:
                telemetry.mesh_hi_rows_total().inc(nfirst, path="fused")
    del subb_m

    with timers.timing("mesh-candidates"):
        # both stages search the numbetween=2 half-bin grid (bin_scale)
        lo_res = {h: (lo_vals[si, :ndms], lo_bins[si, :ndms])
                  for si, h in enumerate(stages_lo)}
        cands = sifting.make_candidates(
            lo_res, dms, T_s, _lo_sigma_fn(nbins),
            sigma_min=params.sifting.sigma_threshold, bin_scale=0.5)
        if hi_sharded:
            zs = np.asarray(bank.zs)
            hi_res = {h: (hi_vals[:ndms, si], hi_rbins[:ndms, si],
                          zs[hi_zidx[:ndms, si]])
                      for si, h in enumerate(stages_hi)}
            cands.extend(sifting.make_candidates(
                hi_res, dms, T_s, _hi_sigma_fn(nbins, nz),
                sigma_min=params.sifting.sigma_threshold,
                z_min_abs=accel_k.DZ / 2, bin_scale=0.5))
        events = sp_k.events_from_topk(
            sp_snr[:, :ndms], sp_idx[:, :ndms], dms, dt_ds,
            threshold=params.sp_threshold,
            widths=tuple(params.sp_widths))
        trace_mod.annotate("mesh-candidates", cands=len(cands),
                           events=len(events))
    if hi and not hi_sharded:
        # Batched path pinned off: run the hi stage through the
        # single-device route (accel_search_batch -> its own proven
        # per-DM fallback), re-dedispersing in chunks.  Slower, but
        # correct on runtimes that reject the batched shapes.
        if form == "partial":
            # subbands the exchange left where they lie because no chip
            # is given a whole copy of them are not gathered onto one
            # chip here either, beside its share of the beam
            raise ValueError(
                f"hi-accel's single-device route on a laid-out beam "
                f"would bring the pass's {subb.nbytes} bytes of subbands "
                f"whole onto one chip, over seq_shard_min_bytes="
                f"{params.seq_shard_min_bytes}: with the batched path "
                f"pinned off (accel._batch_path_usable, or a fault spec "
                f"naming accel.) a beam laid out at this size has no "
                f"hi-accel search")
        degraded.note("sharded_hi_fallback",
                      "batched accel path pinned off on the mesh path; hi "
                      "stage re-dedisperses per chunk (2x stage-2 "
                      "cost)")
        # the one-device loop's own chunk (its spectral budget), the
        # subbands brought whole to the first chip once
        rows = pass_chunk_size(ndms, nfft, params)
        subb_one = pmesh.on_first_device(subb)
        with timers.timing("sharded-search"):
            for lo in range(0, ndms, rows):
                dm_chunk = dms[lo: lo + rows]
                series = dd.dedisperse_subbands(
                    subb_one,
                    jnp.asarray(np.asarray(sub_shifts)
                                [lo: lo + len(dm_chunk)]))
                # bool mask, NOT float32: the bool-mask program is the
                # one the AOT gate pre-compiles (whitened_powers casts
                # internally, so the result is identical)
                wspec = fr.whitened_spectrum_masked(
                    series, jnp.asarray(keep), nfft=nfft)
                cands.extend(_hi_accel_chunk(wspec, dm_chunk, 1, T_s,
                                             params)[0])
                telemetry.mesh_hi_rows_total().inc(len(dm_chunk),
                                                   path="fallback")
    return cands, events


def _write_inf_files(resultsdir, basenm, si, dms, dt, nsamp) -> None:
    """Minimal .inf metadata per DM series (PRESTO-inf-like keys)."""
    for dm in np.atleast_1d(dms):
        path = os.path.join(resultsdir, f"{basenm}_DM{dm:.2f}.inf")
        with open(path, "w") as fh:
            fh.write(f" Data file name without suffix          =  "
                     f"{basenm}_DM{dm:.2f}\n")
            fh.write(f" Telescope used                         =  "
                     f"{si.telescope}\n")
            fh.write(f" Object being observed                  =  "
                     f"{si.source}\n")
            fh.write(f" Epoch of observation (MJD)             =  "
                     f"{si.start_MJD[0]:.15f}\n")
            fh.write(f" Width of each time series bin (sec)    =  {dt!r}\n")
            fh.write(f" Number of bins in the time series      =  {nsamp}\n")
            fh.write(f" Dispersion measure (cm-3 pc)           =  {dm}\n")


def _write_sp_files(resultsdir, basenm, events: np.ndarray) -> None:
    for dm in np.unique(events["dm"]) if len(events) else []:
        sp_k.write_singlepulse_file(
            os.path.join(resultsdir, f"{basenm}_DM{dm:.2f}.singlepulse"),
            events, dm)
    np.savez_compressed(os.path.join(resultsdir, f"{basenm}_sp.npz"),
                        events=events)


def _write_header_json(resultsdir, obj) -> None:
    """Beam header record for the uploader (the reference re-derives
    this by re-reading raw files at upload time, header.py:239; we
    write it once at search time)."""
    import json
    si = obj.specinfo
    hdr = {
        "obs_name": getattr(obj, "obs_name", si.source),
        "beam_id": int(obj.beam_id) if obj.beam_id is not None else -1,
        "original_file": obj.original_file,
        "source_name": obj.source_name,
        "ra_deg": float(si.ra2000),
        "dec_deg": float(si.dec2000),
        "gal_l": obj.galactic_longitude,
        "gal_b": obj.galactic_latitude,
        "obstime_s": float(si.T),
        "timestamp_mjd": obj.timestamp_mjd,
        "center_freq_mhz": si.fctr,
        "bw_mhz": float(si.BW),
        "num_channels": si.num_channels,
        "sample_time_us": obj.sample_time,
        "project_id": obj.project_id,
        "observers": obj.observers,
        "file_size": obj.file_size,
        "data_size": int(obj.data_size),
        "num_samples": int(si.N),
        "telescope": si.telescope,
        "backend": si.backend,
    }
    with open(os.path.join(resultsdir, "header.json"), "w") as fh:
        json.dump(hdr, fh, indent=1)


def _write_search_params(resultsdir, params, basenm, si, num_trials,
                         baryv: float = 0.0,
                         degraded_modes: dict | None = None,
                         rescued_modes: dict | None = None) -> None:
    """Provenance dump, python-literal assignments like the reference's
    search_params.txt (PALFA2_presto_search.py:695-700).
    degraded_modes: fallback-path flags (science lost / slower path).
    rescued_modes: host-rescue provenance (e.g. accel_rows_rescued) —
    work the primary device refused that was recomputed on another
    device: the science is complete, only its origin differs, so it is
    recorded separately from the loss ledger."""
    with open(os.path.join(resultsdir, "search_params.txt"), "w") as fh:
        fh.write(f"basenm = {basenm!r}\n")
        fh.write(f"source = {si.source!r}\n")
        fh.write(f"backend = {si.backend!r}\n")
        fh.write(f"num_dm_trials = {num_trials}\n")
        fh.write(f"baryv = {baryv!r}\n")
        fh.write(f"degraded_modes = {dict(degraded_modes or {})!r}\n")
        fh.write(f"rescued_modes = {dict(rescued_modes or {})!r}\n")
        for k, v in params.provenance().items():
            fh.write(f"{k} = {v!r}\n")


_TAR_CLASSES = (("_pfd.tgz", "_cand*.pfd.npz"),
                ("_bestprof.tgz", "_cand*.bestprof"),
                ("_singlepulse.tgz", "_DM*.singlepulse"),
                ("_inf.tgz", "_DM*.inf"),
                ("_accelcands.tgz", ".accelcands"))


def _tar_result_classes(resultsdir: str, basenm: str) -> None:
    """Tar up result classes like the reference's clean_up
    (PALFA2_presto_search.py:702-724), removing the loose .inf files
    (they can number in the thousands)."""
    import glob
    for suffix, pattern in _TAR_CLASSES:
        files = sorted(glob.glob(os.path.join(resultsdir,
                                              f"{basenm}{pattern}")))
        if not files:
            continue
        tarpath = os.path.join(resultsdir, f"{basenm}{suffix}")
        with tarfile.open(tarpath, "w:gz") as tf:
            for f in files:
                tf.add(f, arcname=os.path.basename(f))
        if suffix in ("_inf.tgz", "_singlepulse.tgz"):
            for f in files:
                os.remove(f)


def _dispatch_attrs(series_shape, sp_widths, spec_shape, lo_stages,
                    on) -> dict:
    """The forms a chunk's single-pulse and lo-stage programs were
    lowered in, for its span (docs/operations.md): sp_form / sp_tile of
    boxcar_search over a `series_shape` series, lo_form / lo_tile of
    lo_stage_candidates over a `spec_shape` spectrum, for the platform
    of the devices `on` (an operand, or the mesh) lives on.  Down here
    so that no line above a Pallas call site moves (a kernel's
    compile-cache key holds its call stack)."""
    platform = (on.devices.flat[0] if isinstance(on, jax.sharding.Mesh)
                else next(iter(on.devices()))).platform
    return {**sp_k.sp_dispatch_attrs(*series_shape, tuple(sp_widths),
                                     platform),
            **fr.lo_dispatch_attrs(*spec_shape, tuple(lo_stages), platform)}


def _mesh_call_attrs(form, hi_sharded: bool, nbins: int, nz: int,
                     rows_per_device: int) -> dict:
    """What a traced `mesh_chunk` says of its pass beyond the rows:
    the exchange `form` its subbands came by ("none" for a whole
    block, which `mesh-place` replicates) and, where the hi stage ran
    in the call, `plane_bytes`: what a device's rows hold live there by
    `accel.plane_row_bytes`' count, the count that `plane_dm_chunk`
    sized the rows with (so a pass of many small calls says why)."""
    attrs = {"form": form or "none"}
    if hi_sharded:
        attrs["plane_bytes"] = rows_per_device * accel_k.plane_row_bytes(
            nbins, nz, accel_k.corr_z_pieces())
    return attrs
