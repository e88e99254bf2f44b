"""Declarative registry of every jitted program in the pipeline.

One table maps a program name to the EXACT module-level jitted
callable the runtime invokes, and one set of shape-builders derives
the canonical compile shapes from ``SearchParams``/``DDPlan``/scale.
The AOT gate (tpulsar.aot.warmstart / tools/aot_check.py) and the
runtime both consume
this table, so the gate-vs-child drift that cost the round-5 campaign
a 160.6 s silent recompile cannot recur by omission: a jit site is
either registered here or on the commented :data:`EXEMPT_SITES` list,
and tests/test_aot.py walks the package ASTs to enforce exactly that.

Why "the exact module-level callable" is load-bearing: a wrapping
lambda lowers to a different HLO module name (``jit__lambda`` vs
``jit_<fn>``), so its persistent-cache entry never serves the
measured run — the round-3 pitfall that three modules used to dodge
by hand-maintained convention (kernels/accel.py module-level jits,
search/refine.py exposing ``_gather_jit``, tools/aot_check.py's
``check()`` docstring).  The registry resolver returns the attribute
itself, so there is no wrapper to get wrong.

Import discipline: the table and its accessors are stdlib-only —
``tpulsar aot ls`` and the completeness test run without jax.  The
shape-builders (:func:`make_context`, :func:`gate_groups`) import
numpy/jax/kernels lazily; they are only called by a process that is
about to compile.
"""

from __future__ import annotations

import dataclasses
import importlib
import os
import sys
import typing

# ------------------------------------------------------------------
# headline beam geometry (the survey's Mock beam — shared with
# bench.py and previously re-declared by tools/aot_check.py)
# ------------------------------------------------------------------
NCHAN = 960
TSAMP = 65.476e-6
T_FULL = 3_932_160
FCTR, BW = 1375.5, 322.617

#: samples-per-scale quantum: nsamp is truncated to a multiple of
#: this so every downsamp in the survey plan divides it
NSAMP_QUANTUM = 30720


def block_dtype_name() -> str:
    """Validated TPULSAR_BENCH_DTYPE (no jax import — parents must be
    able to fail fast on a misconfig without dialing the accelerator).
    bench.py delegates here so the measured child, the focused
    configs, and the AOT gate interpret the knob identically."""
    val = os.environ.get("TPULSAR_BENCH_DTYPE", "uint8")
    if val in ("uint8", "bfloat16"):
        return val
    raise SystemExit(
        f"TPULSAR_BENCH_DTYPE must be uint8|bfloat16, got {val!r}")


def block_dtype():
    """The device block dtype as a jnp dtype (lazy jax import)."""
    import jax.numpy as jnp

    return (jnp.uint8 if block_dtype_name() == "uint8"
            else jnp.bfloat16)


# ------------------------------------------------------------------
# the program table
# ------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Program:
    """One registered jitted program.

    ``module``.``attr`` is the module-level jitted callable itself —
    or, when ``factory`` is True, a zero-argument callable returning
    it (search/refine.py builds its gather jit lazily so importing
    the module stays jax-free).  ``site`` is the jit site this entry
    covers, as ``<repo-relative-path>::<function-name>`` — the key the
    AST completeness test matches on.  ``statics`` documents the
    static-argument schema (names for keyword statics, positional
    count otherwise)."""

    name: str
    module: str
    attr: str
    site: str
    statics: tuple[str, ...] = ()
    factory: bool = False
    doc: str = ""


def _k(mod: str, attr: str, statics: tuple[str, ...] = (),
       doc: str = "", name_attr: str | None = None) -> Program:
    """Kernel-module entry helper: name ``<mod>.<attr>``, site derived
    from the module path."""
    return Program(
        name=f"{mod}.{name_attr or attr}",
        module=f"tpulsar.kernels.{mod}",
        attr=attr,
        site=f"tpulsar/kernels/{mod}.py::{attr}",
        statics=statics,
        doc=doc,
    )


#: every registered program.  Grouped by module; the gate set (the
#: programs with shape-builders in gate_groups) is a subset — the
#: rest are registered for identity (diagnostics resolve the exact
#: callable through here) and for the completeness test.
PROGRAMS: tuple[Program, ...] = (
    # ---- kernels/rfi.py
    _k("rfi", "_cell_stats_chan", ("block_len", "chunk"),
       doc="per-cell channel stats for the RFI mask"),
    _k("rfi", "channel_major",
       doc="the read-in's transpose of the block as read, on the chip"),
    _k("rfi", "apply_mask_chan", ("block_len",),
       doc="channelwise mask application at block granularity"),
    _k("rfi", "apply_mask", ("block_len", "chunk"),
       doc="whole-block mask application (chunked variant)"),
    # ---- kernels/dedisperse.py
    _k("dedisperse", "_shift_rows", ("pad",)),
    _k("dedisperse", "_form_subbands_jit", ("nsub", "downsamp", "pad"),
       doc="stage-1 subband formation — THE round-5 recompile victim"),
    _k("dedisperse", "_dedisperse_subbands_scan", ("pad",),
       doc="stage-2 XLA-scan dedispersion over DM trials"),
    _k("dedisperse", "dedisperse_window_scan", ("out_len",)),
    # ---- kernels/pallas_dd.py (the stage-1/2 tiers of a TPU backend)
    _k("pallas_dd", "_segment_layout", ("seg",)),
    _k("pallas_dd", "_dedisperse_chunk",
       ("window", "group", "unroll", "vmem_bytes", "interpret")),
    _k("pallas_dd", "_segment_slab", ("n_blocks", "seg", "head")),
    _k("pallas_dd", "_form_subbands_block",
       ("nsub", "block_t", "window", "group", "vmem_bytes",
        "interpret")),
    # ---- kernels/fourier.py
    _k("fourier", "pad_series", ("nfft",)),
    _k("fourier", "complex_spectrum", ()),
    _k("fourier", "power_spectrum", ()),
    _k("fourier", "_whiten_powers_jit", ("edges", "estimator"),
       doc="rednoise whitening; fourier.whiten_powers is the "
           "resolving wrapper, not the program"),
    _k("fourier", "whitened_spectrum", ("nfft",),
       doc="fused pad->rfft->whiten->scale stage program"),
    _k("fourier", "whitened_spectrum_masked", ("nfft",)),
    _k("fourier", "interbin_powers", ()),
    _k("fourier", "harmonic_sum", ("numharm",)),
    _k("fourier", "blockmax_topk", ("topk", "block_r")),
    _k("fourier", "_lo_block_maxima", ("stages", "interpret"),
       doc="the lo stage's tiled harmonic-sum kernel (Pallas, "
           "lo_harmsum): per-stage block maxima; traced inside "
           "lo_stage_candidates where that is lowered for a TPU, whose "
           "gate shapes carry it (its tile derives from the array's "
           "shape: fourier.lo_harmsum_plan)"),
    _k("fourier", "all_stage_candidates", ("stages", "topk")),
    _k("fourier", "lo_stage_candidates", ("stages", "topk")),
    # ---- kernels/singlepulse.py
    _k("singlepulse", "normalize_series", ("detrend_block", "estimator")),
    _k("singlepulse", "boxcar_search", ("widths", "topk")),
    _k("singlepulse", "_ladder_block_maxima", ("widths", "interpret"),
       doc="the boxcar ladder's tiled kernel (Pallas, sp_boxcar): every "
           "width's 32-sample block maxima in one pass; traced inside "
           "boxcar_search where that is lowered for a TPU, whose gate "
           "shapes carry it (its row group derives from the series' "
           "shape: singlepulse.sp_boxcar_plan)"),
    # ---- kernels/fold.py
    _k("fold", "_fold_with_bins", ("nbin", "npart")),
    _k("fold", "_shift_and_sum", ("nbin",)),
    _k("fold", "_grid_chi2", ("nbin",)),
    _k("fold", "_fold_subbands_with_bins", ("nbin", "npart", "nsub")),
    _k("fold", "_dm_grid_chi2", ("nbin",)),
    _k("fold", "_shift_sum_cube", ("nbin",)),
    # ---- kernels/fold_batch.py
    _k("fold_batch", "_fold_and_optimize_batch",
       ("nbin", "npart", "L", "j0")),
    # ---- kernels/accel.py
    _k("accel", "_correlate_segments", ("seg", "step", "width")),
    _k("accel", "_harmonic_sum_plane", ("numharm", "nz"),
       doc="the strided harmonic sum of one plane: the test oracle of "
           "_harmsum_zmax, run by no search"),
    _k("accel", "_harmsum_zmax", ("stages", "nz", "interpret"),
       doc="the tiled harmonic-sum kernel (Pallas): per-stage max and "
           "argmax over z; traced inside the chunk and row programs, "
           "whose gate shapes carry it (its tile derives from the "
           "plane's shape: accel.harmsum_plan)"),
    _k("accel", "_accel_plane_topk",
       ("seg", "step", "width", "nz", "max_numharm", "topk")),
    _k("accel", "_correlate_block", ("seg", "step", "width", "nz")),
    _k("accel", "_corr_plane", ("width", "nz", "interpret"),
       doc="the direct correlation kernel (Pallas, corr_plane): the "
           "chunk program's plane on a TPU; traced inside the chunk "
           "program, whose gate shapes carry it (its tiles derive "
           "from the chunk's shape: accel.corr_plan)"),
    _k("accel", "_correlate_pieces", ("seg", "step", "width", "nz")),
    _k("accel", "_correlate_zpieces", ("seg", "step", "width", "nz"),
       doc="overlap-save powers still split by z-chunk (tuple, no "
           "concatenate) — the native ZSegSrc consumer's input"),
    _k("accel", "_split_block",
       doc="a spectra block's real and imaginary parts, once per "
           "block: what the chunk programs slice their rows from"),
    _k("accel", "_pad_block", ("rows",),
       doc="zero-pad a spectra block to a quantized row count "
           "(accel_batch ladder) so ragged pass chunks reuse "
           "chunk/row-program compile signatures"),
    _k("accel", "_accel_block_topk",
       ("seg", "step", "width", "nz", "max_numharm", "topk")),
    _k("accel", "accel_chunk_topk",
       ("nrows", "seg", "step", "width", "nz", "max_numharm", "topk"),
       doc="module-level jit on purpose: a wrapper lambda breaks the "
           "persistent-cache key (see module docstring)"),
    _k("accel", "accel_row_topk",
       ("seg", "step", "width", "nz", "max_numharm", "topk")),
    # ---- search/refine.py (lazy factory: the module imports jax-free)
    Program(
        name="refine.gather",
        module="tpulsar.search.refine",
        attr="_gather_jit",
        site="tpulsar/search/refine.py::_gather_jit",
        statics=("width",),
        factory=True,
        doc="refinement window gather; width from _WIDTH_BUCKETS, "
            "count always _NWIN"),
    # ---- bench.py (repo-root module): the beam synthesizer the
    # measured run executes.  Outside the package AST walk, but the
    # gate still compiles it through the registry so the synth
    # program cannot drift either.
    Program(
        name="bench.gen_block_chunk",
        module="bench",
        attr="gen_block_chunk",
        site="",
        statics=("n", "nc", "dtype"),
        doc="per-channel-chunk beam synthesizer (noise + injected "
            "pulsar), jitted with the same statics bench.make_block "
            "uses"),
)


#: jit sites that are deliberately NOT in the registry, with the
#: reason.  Every entry here is a closure built at run time around a
#: concrete device mesh (shard_map captures the Mesh object), so
#: there is no module-level callable to register — these programs are
#: exercised by the multichip rehearsal (MULTICHIP_*.json), not the
#: single-chip AOT gate.  tests/test_aot.py fails if a new jit site
#: is neither registered nor listed here.
EXEMPT_SITES: dict[str, str] = {
    "tpulsar/parallel/mesh.py::sharded_search_step":
        "per-mesh shard_map closure (jit(step) captures the Mesh)",
    "tpulsar/parallel/mesh.py::sharded_pass_fn":
        "per-mesh shard_map closure over PassSpec",
    "tpulsar/parallel/mesh.py::reshard":
        "per-sharding identity program (an all-gather between two "
        "layouts of a laid-out beam's subbands)",
    "tpulsar/kernels/rfi.py::_cell_stats_shares":
        "per-mesh shard_map of _cell_stats_chan over a laid-out beam's "
        "channel shares",
    "tpulsar/kernels/dedisperse.py::_form_subbands_shares":
        "per-mesh shard_map of _form_subbands_jit over a laid-out "
        "beam's channel shares",
    "tpulsar/kernels/pallas_dd.py::_share_programs":
        "per-mesh shard_map of the two stage-1 slab programs over a "
        "laid-out beam's channel shares",
}


def programs() -> tuple[Program, ...]:
    return PROGRAMS


def get(name: str) -> Program:
    for p in PROGRAMS:
        if p.name == name:
            return p
    raise KeyError(f"no registered AOT program {name!r} "
                   f"(tpulsar aot ls prints the registry)")


def registered_sites() -> frozenset[str]:
    return frozenset(p.site for p in PROGRAMS if p.site)


def jitted(name: str):
    """Resolve a registered program to its jitted callable — the very
    object the runtime calls, never a wrapper (see module docstring
    for why that identity is the whole point)."""
    prog = get(name)
    if prog.module == "bench":
        return _bench_gen_jit()
    mod = importlib.import_module(prog.module)
    obj = getattr(mod, prog.attr)
    if prog.factory:
        obj = obj()
    return obj


def _bench_gen_jit():
    """bench.gen_block_chunk jitted with the same statics
    bench.make_block applies (bench lives at the repo root, not in
    the package)."""
    from functools import partial

    import jax

    from tpulsar.aot import cachedir

    try:
        import bench as bench_mod
    except ImportError:
        sys.path.insert(0, cachedir.repo_root())
        import bench as bench_mod
    return partial(jax.jit, static_argnames=("n", "nc", "dtype"))(
        bench_mod.gen_block_chunk)


# ------------------------------------------------------------------
# shape-builders: canonical compile instances from SearchParams /
# DDPlan / scale (ported verbatim from tools/aot_check.py, which is
# now a thin wrapper over tpulsar.aot)
# ------------------------------------------------------------------

class Instance(typing.NamedTuple):
    """One compile instance: a registered program plus the concrete
    ShapeDtypeStructs/statics to lower it at.  ``label`` is the
    display + manifest key (unique within a gate profile)."""

    program: str
    label: str
    args: tuple
    kwargs: dict


@dataclasses.dataclass
class GateContext:
    """Derived geometry every shape-builder consumes."""

    scale: float
    accel: bool
    nsamp: int
    nblocks: int
    freqs: "object"          # np.ndarray (lazy numpy)
    plan: list
    params: "object"         # executor.SearchParams
    blk_dtype: "object"      # jnp dtype
    #: > 1 = also gate the batch-of-beams coalesced programs at this
    #: admission batch size (group sizes ride BATCH_QUANTA)
    nbeams: int = 0


def make_context(scale: float = 1.0, accel: bool = False,
                 plan_name: str = "pdev",
                 nbeams: int = 0) -> GateContext:
    import numpy as np

    from tpulsar.plan import ddplan
    from tpulsar.search import executor as ex

    nsamp = int(T_FULL * scale)
    nsamp -= nsamp % NSAMP_QUANTUM
    freqs = (FCTR - BW / 2) + (np.arange(NCHAN) + 0.5) * (BW / NCHAN)
    return GateContext(
        scale=scale, accel=accel, nsamp=nsamp,
        nblocks=nsamp // 2048, freqs=freqs,
        plan=ddplan.survey_plan(plan_name),
        params=ex.SearchParams(run_hi_accel=accel),
        blk_dtype=block_dtype(),
        nbeams=nbeams,
    )


def gate_groups(ctx: GateContext, config: int = 0,
                fast: bool = False) -> list[tuple[str, list[Instance]]]:
    """The gate program set as (group-header, instances) in compile
    order.  ``config`` in (1, 3, 4) selects the focused bench
    config's exact programs; otherwise the headline survey-plan set.
    ``fast`` keeps only the maximal-footprint subset (bench.py's
    pre-flight; see tools/aot_check.py --fast for the dominance
    argument)."""
    groups: list[tuple[str, list[Instance]]] = [
        ("synth:", _synth_instances(ctx))]
    if config in (1, 3, 4):
        groups += _config_groups(ctx, config)
    else:
        groups += _headline_groups(ctx, fast=fast)
        groups.append(("stream (STREAM_PROFILE):",
                       _stream_instances(ctx)))
    if ctx.nbeams > 1:
        groups += _beam_batch_groups(ctx)
    return groups


def _beam_batch_groups(ctx: GateContext
                       ) -> list[tuple[str, list[Instance]]]:
    """The batch-of-beams coalesced signatures an ``nbeams``-wide
    admission batch dispatches: beam-group sizes from the SAME
    plan_beam_groups ladder decomposition the executor runs, and the
    row-batched spectral stages at B x chunk rows — the
    gate-vs-runtime lockstep discipline, one axis up.  Stage 1/2 run
    per beam with the solo programs (the pass loop's rule), so their
    instances are the solo groups' above."""
    import jax.numpy as jnp

    from tpulsar.kernels import beam_batch as bb
    from tpulsar.kernels import singlepulse as sp_k
    from tpulsar.kernels import fourier as fr

    _sp = ctx.params
    rungs = sorted({len(g) for g in bb.plan_beam_groups(
        ctx.nbeams).groups if len(g) > 1})
    groups: list[tuple[str, list[Instance]]] = []
    geoms = step_geometries(ctx)
    for B in rungs:
        insts: list[Instance] = []
        for step, T_ds, ndms, _pads, nfft, chunk in geoms:
            nbins = nfft // 2 + 1
            sizes = [min(chunk, ndms)]
            if chunk < ndms and ndms % chunk:
                sizes.append(ndms % chunk)
            for rows in sizes:
                sers = _sds((B * rows, T_ds), jnp.float32)
                tag = f"B={B} ds={step.downsamp} rows={rows}"
                insts += [
                    Instance("singlepulse.normalize_series",
                             f"bb_sp_normalize {tag}", (sers,),
                             dict(estimator=sp_k.detrend_estimator())),
                    Instance("singlepulse.boxcar_search",
                             f"bb_sp_boxcars {tag}",
                             (sers, tuple(_sp.sp_widths),
                              sp_k.DEFAULT_TOPK), {}),
                    Instance("fourier.whitened_spectrum",
                             f"bb_whitened_spectrum {tag}", (sers,),
                             dict(nfft=nfft)),
                    # the zaplist path: the batch loop passes a 2-D
                    # per-ROW keep mask (batchmates share a zap
                    # digest but baryv — which shapes the mask — is
                    # per-beam), unlike the solo loop's 1-D (nbins,)
                    Instance("fourier.whitened_spectrum_masked",
                             f"bb_whitened_spectrum_masked {tag}",
                             (sers, _sds((B * rows, nbins),
                                         jnp.bool_)),
                             dict(nfft=nfft)),
                    Instance("fourier.lo_stage_candidates",
                             f"bb_lo_stages {tag}",
                             (_sds((B * rows, nbins), jnp.complex64),
                              tuple(fr.harmonic_stages(
                                  _sp.lo_accel_numharm)),
                              _sp.topk_per_stage), {}),
                ]
        groups.append((f"beam-batch B={B}:", insts))
    return groups


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _synth_instances(ctx: GateContext) -> list[Instance]:
    import jax.numpy as jnp

    return [Instance(
        "bench.gen_block_chunk", "make_block_chunk",
        (_sds((2,), jnp.uint32), _sds((120,), jnp.float32)),
        dict(n=ctx.nsamp, nc=120, dtype=ctx.blk_dtype))]


def _rfi_instances(ctx: GateContext) -> list[Instance]:
    import jax.numpy as jnp

    blk = _sds((NCHAN, ctx.nsamp), ctx.blk_dtype)
    return [
        Instance("rfi.channel_major", "channel_major",
                 (_sds((ctx.nsamp, NCHAN), ctx.blk_dtype),), {}),
        Instance("rfi._cell_stats_chan", "cell_stats_chan",
                 (blk,), dict(block_len=2048)),
        Instance("rfi.apply_mask_chan", "apply_mask_chan",
                 (blk, _sds((ctx.nblocks, NCHAN), jnp.bool_),
                  _sds((NCHAN,), jnp.float32)),
                 dict(block_len=2048)),
    ]


def _stream_instances(ctx: GateContext) -> list[Instance]:
    """The streaming plane's static signatures (stream/dedisp_state,
    stream/trigger at STREAM_PROFILE geometry): ONE emission-window
    scan per session plus the span-shaped SP pair.  Gated here so a
    warm serve worker compiles nothing at stream-session start —
    the per-chunk latency SLO has no room for a first-chunk lowering.
    Scale-independent: the stream geometry is fixed by the profile,
    not the gate's ``--scale``."""
    import jax.numpy as jnp

    from tpulsar.kernels import singlepulse as sp_k
    from tpulsar.stream import STREAM_PROFILE
    from tpulsar.stream import dedisp_state as dds

    g = STREAM_PROFILE
    shifts = dds.shift_table(g)
    width = int(g["chunk_len"]) + dds.pad_bucket(
        int(shifts.max(initial=0)))
    span = int(g["span_chunks"]) * int(g["chunk_len"])
    win = _sds((int(g["nchan"]), width), jnp.float32)
    sers = _sds((int(g["ndms"]), span), jnp.float32)
    return [
        Instance("dedisperse.dedisperse_window_scan",
                 "stream_window_scan",
                 (win, _sds(shifts.shape, jnp.int32)),
                 dict(out_len=int(g["chunk_len"]))),
        Instance("singlepulse.normalize_series", "stream_sp_normalize",
                 (sers,), dict(estimator=sp_k.detrend_estimator())),
        Instance("singlepulse.boxcar_search", "stream_sp_boxcars",
                 (sers,), {}),
    ]


def _config_groups(ctx: GateContext,
                   config: int) -> list[tuple[str, list[Instance]]]:
    """Focused-config gate: the exact programs
    bench.run_focused_config(cfg) will execute (one 128/32-trial pass
    at ds=1 on the full-length block, XLA formulations)."""
    import jax.numpy as jnp
    import numpy as np

    from tpulsar.kernels import dedisperse as dd
    from tpulsar.kernels import fourier as fr
    from tpulsar.kernels import singlepulse as sp_k

    nsamp = ctx.nsamp
    blk = _sds((NCHAN, nsamp), ctx.blk_dtype)
    dms = np.arange(128) * 2.0
    if config == 3:
        dms = dms[:32]
    ch_sh, sub_sh = dd.plan_pass_shifts(ctx.freqs, 96, 140.0, dms,
                                        TSAMP, 1)
    pad1 = dd._pad_bucket(int(ch_sh.max(initial=0)))
    pad2 = dd._pad_bucket(int(sub_sh.max(initial=0)))
    ndms = sub_sh.shape[0]

    insts: list[Instance] = []
    if config == 1:
        insts += _rfi_instances(ctx)
    insts += [
        Instance("dedisperse._form_subbands_jit", "form_subbands",
                 (blk, _sds((NCHAN,), jnp.int32)),
                 dict(nsub=96, downsamp=1, pad=pad1)),
        Instance("dedisperse._dedisperse_subbands_scan",
                 "dedisperse_scan",
                 (_sds((96, nsamp), jnp.float32),
                  _sds((ndms, 96), jnp.int32)),
                 dict(pad=pad2)),
    ]
    if config == 4:
        # estimator resolved exactly as the measured run resolves it
        # (TPULSAR_SP_DETREND is inherited by this subprocess) — a
        # different estimator is a different static-arg program and
        # must not reach the chip ungated
        sers = _sds((ndms, nsamp), jnp.float32)
        insts += [
            Instance("singlepulse.normalize_series", "sp_normalize",
                     (sers,),
                     dict(estimator=sp_k.detrend_estimator())),
            Instance("singlepulse.boxcar_search", "sp_boxcars",
                     (sers,), {}),
        ]
    groups = [(f"config {config} (ndms={ndms}, T={nsamp}):", insts)]
    if config == 3:
        from tpulsar.kernels import accel as ak

        nbins = nsamp // 2 + 1
        sers = _sds((ndms, nsamp), jnp.float32)
        pows = _sds((ndms, nbins), jnp.float32)
        insts += [
            Instance("fourier.complex_spectrum", "complex_spectrum",
                     (sers,), {}),
            # the exact jitted callable with the estimator resolved
            # as the measured run resolves it
            # (TPULSAR_WHITEN_ESTIMATOR is inherited by this
            # subprocess) — fr.whiten_powers is the resolving
            # wrapper, not the program
            Instance("fourier._whiten_powers_jit", "whiten_powers",
                     (pows,),
                     dict(edges=tuple(int(e) for e in
                                      fr._block_edges(nbins)),
                          estimator=fr.whiten_estimator())),
        ]
        from tpulsar.kernels import accel_batch as abp
        from tpulsar.plan import ddplan
        from tpulsar.search import executor

        # the deep search as search_block dispatches it (the
        # benchmark's palfa_mock_z200 configuration): a ds=1 pass of 76
        # trials splits into the chunks pass_chunk_size gives, each
        # chunk's spectra block is padded to the ladder, and the
        # planner's own arithmetic (abp.batch_rows) sets the rows of a
        # chunk program — the gate compiles the signatures the run
        # dispatches, topk included
        deep = executor.SearchParams(hi_accel_zmax=200,
                                     hi_accel_numharm=16)
        bank = ak.build_template_bank(float(deep.hi_accel_zmax))
        nz = len(bank.zs)
        nfft = ddplan.choose_n(nsamp)
        nbins = nfft // 2 + 1
        rows = executor.pass_chunk_size(76, nfft, deep)
        dmc = abp.batch_rows(rows, nbins, nz)
        q_rows = abp.quantize_rows_up(rows)
        spec_sh = _sds((q_rows, nbins), jnp.complex64)
        bank_sh = _sds(bank.bank_fft.shape, jnp.complex64)
        i32 = _sds((), jnp.int32)
        accel_kw = dict(seg=bank.seg, step=bank.step, width=bank.width,
                        nz=nz, max_numharm=deep.hi_accel_numharm,
                        topk=deep.topk_per_stage)
        # accel_search_batch's chunk/row programs: full (quantized)
        # spectra argument + dynamic slice (the argument buffer is
        # part of the gated footprint)
        accel_insts = _chunk_instance(
            "_z200", spec_sh, bank_sh, bank, nz,
            dict(nrows=dmc, **accel_kw)) + [
            Instance("accel.accel_row_topk", "accel_row_z200",
                     (spec_sh, bank_sh, i32), dict(accel_kw)),
        ]
        if q_rows != rows:
            accel_insts.append(Instance(
                "accel._pad_block", "accel_pad_z200",
                (_sds((rows, nbins), jnp.complex64),),
                dict(rows=q_rows)))
        accel_insts += _accel_native_instances(
            dmc, nbins, bank, nz, label="z200")
        groups.append((f"accel z200 (nz={nz}, nbins={nbins}, "
                       f"dm_chunk={dmc}):", accel_insts))
    return groups


def _chunk_instance(tag: str, spec_sh, bank_sh, bank,
                    nz: int, kw: dict) -> list[Instance]:
    """accel_chunk_topk with the operands accel.chunk_operands gives
    it in this process (corr_form): for the direct form the block's
    float32 parts and the taps, with the _split_block that makes the
    parts; for the FFT form the complex block and no taps."""
    import jax.numpy as jnp

    from tpulsar.kernels import accel as ak

    i32 = _sds((), jnp.int32)
    if ak.corr_form() != "direct":
        return [Instance("accel.accel_chunk_topk", f"accel_chunk{tag}",
                         (spec_sh, bank_sh, None, i32), kw)]
    part_sh = _sds(spec_sh.shape, jnp.float32)
    taps_sh = _sds(ak.corr_taps_shape(nz, bank.width), jnp.float32)
    return [Instance("accel._split_block", f"accel_split{tag}",
                     (spec_sh,), {}),
            Instance("accel.accel_chunk_topk", f"accel_chunk{tag}",
                     ((part_sh, part_sh), bank_sh, taps_sh, i32), kw)]


def _accel_native_instances(dmc: int, nbins: int, bank, nz: int,
                            label: str) -> list[Instance]:
    """The CPU product path's jitted front end: on the CPU backend
    with a native toolchain, accel_search_batch routes each batch
    through _correlate_zpieces and the native ZSegSrc consumer — the
    gate must compile that exact program or every batch of a CPU
    measured run recompiles it in-line.  A loadable but STALE library
    (no z-chunked entrypoint — the clock-skewed-copy case
    native.has_accel_zsegs guards) makes the runtime fall back to the
    assembled-pieces layout, so the gate mirrors the SAME branch and
    registers _correlate_pieces at the batch shape instead: gating on
    load() alone would compile a program the run never dispatches
    while the one it does dispatch recompiles in-line on every batch.
    Skipped on accelerator backends (the native path never engages
    there) and when the native library cannot build."""
    import jax

    from tpulsar import native

    if jax.default_backend() != "cpu" or native.load() is None:
        return []
    import jax.numpy as jnp

    args = (_sds((dmc, nbins), jnp.complex64),
            _sds(bank.bank_fft.shape, jnp.complex64))
    statics = dict(seg=bank.seg, step=bank.step, width=bank.width,
                   nz=nz)
    if native.has_accel_zsegs():
        return [Instance("accel._correlate_zpieces",
                         f"accel_zpieces {label}", args, statics)]
    return [Instance("accel._correlate_pieces",
                     f"accel_pieces_batch {label}", args, statics)]


def step_geometries(ctx: GateContext) -> list[tuple]:
    """Per-step geometry (step, T_ds, ndms, pad_pairs, nfft, chunk).

    pad_pairs spans EVERY pass of the step: the pad bucket grows with
    the pass sub-DM, so a step's later passes use larger buckets than
    its first — gating only the first pass left most passes' block
    programs to compile in-line on the chip.  ``chunk`` is the
    executor's own arithmetic (budget + even split) via
    executor.pass_chunk_size, mirroring the measured run's accel
    setting — with the hi stage off it budgets a ~4/3 LARGER chunk,
    and the gate must compile that exact shape."""
    import numpy as np

    from tpulsar.kernels import dedisperse as dd
    from tpulsar.plan import ddplan
    from tpulsar.search import executor as ex

    geoms = []
    for step in ctx.plan:
        T_ds = ctx.nsamp // step.downsamp
        pad_pairs = set()
        ndms = step.dms_per_pass
        for ppass in step.passes():
            ch_sh, sub_sh = dd.plan_pass_shifts(
                ctx.freqs, step.numsub, ppass.subdm,
                np.asarray(ppass.dms), TSAMP, step.downsamp)
            ndms = sub_sh.shape[0]
            pad_pairs.add((dd._pad_bucket(int(ch_sh.max(initial=0))),
                           dd._pad_bucket(int(sub_sh.max(initial=0)))))
        nfft = ddplan.choose_n(T_ds)
        chunk = ex.pass_chunk_size(ndms=ndms, nfft=nfft,
                                   params=ctx.params)
        geoms.append((step, T_ds, ndms, pad_pairs, nfft, chunk))
    return geoms


def _headline_groups(ctx: GateContext,
                     fast: bool) -> list[tuple[str, list[Instance]]]:
    import jax.numpy as jnp
    import numpy as np

    from tpulsar.kernels import dedisperse as dd
    from tpulsar.kernels import fourier as fr
    from tpulsar.kernels import singlepulse as sp_k
    from tpulsar.plan import ddplan
    from tpulsar.search import refine as _refine

    _sp = ctx.params
    blk = _sds((NCHAN, ctx.nsamp), ctx.blk_dtype)
    groups: list[tuple[str, list[Instance]]] = [
        ("rfi:", _rfi_instances(ctx))]

    geoms = step_geometries(ctx)
    if fast:
        # ds=1 dominates every higher-downsamp variant of the block
        # programs (same code, strictly larger shapes).  The
        # sp/spectrum pair needs TWO argmaxes: sp_boxcars scales with
        # chunk*T_ds but spectrum+whiten with chunk*nfft, and
        # choose_n padding can make those maxima land on different
        # steps — gate both (deduped) so neither program family can
        # hide an ungated maximal footprint
        block_geoms = [
            (s, t, n, {max(pp)}, f, c)
            for s, t, n, pp, f, c in geoms if s.downsamp == 1][:1]
        sp_geoms = list({id(g): g for g in (
            max(geoms, key=lambda g: g[5] * g[1]),    # chunk*T_ds
            max(geoms, key=lambda g: g[5] * g[4]),    # chunk*nfft
        )}.values())
    else:
        block_geoms = sp_geoms = geoms

    for step, T_ds, ndms, pad_pairs, nfft, chunk in block_geoms:
        insts = []
        for pad1, pad2 in sorted(pad_pairs):
            tag = f"ds={step.downsamp}"
            insts += _stage1_instances(blk, step.numsub,
                                       step.downsamp, pad1, tag)
            # stage 2 is called once per DM chunk, with its rows
            for rows in sorted({chunk, ndms % chunk} - {0},
                               reverse=True):
                insts += _stage2_instances(step.numsub, T_ds, rows,
                                           pad2, tag)
        groups.append((f"step downsamp={step.downsamp} (T'={T_ds}, "
                       f"ndms={ndms}, pads={sorted(pad_pairs)}):",
                       insts))

    if ctx.accel:
        from tpulsar.kernels import accel as ak

        bank = ak.build_template_bank(float(_sp.hi_accel_zmax))
        nz = len(bank.zs)
        bank_sh = _sds(bank.bank_fft.shape, jnp.complex64)
        i32 = _sds((), jnp.int32)
    for step, T_ds, ndms, _pads, nfft, chunk in sp_geoms:
        nbins = nfft // 2 + 1
        # The executor's chunk loop (range(0, ndms, chunk)) produces
        # TWO row counts per step when chunk doesn't divide
        # dms_per_pass: the full chunk and the remainder — each a
        # distinct compiled program for every stage.  The 03:49-style
        # silent in-line compiles that survived the first
        # direct-lower gate were exactly the remainder-shape
        # programs.
        sizes = [min(chunk, ndms)]
        if chunk < ndms and ndms % chunk:
            sizes.append(ndms % chunk)
        insts = []
        for rows in sizes:
            sers = _sds((rows, T_ds), jnp.float32)
            tag = f"ds={step.downsamp} rows={rows}"
            # estimator resolved exactly as the measured run
            # resolves it (TPULSAR_SP_DETREND inherited by this
            # subprocess)
            insts += [
                Instance("singlepulse.normalize_series",
                         f"sp_normalize {tag}", (sers,),
                         dict(estimator=sp_k.detrend_estimator())),
                Instance("singlepulse.boxcar_search",
                         f"sp_boxcars {tag}",
                         (sers, tuple(_sp.sp_widths),
                          sp_k.DEFAULT_TOPK), {}),
                # the fused pad->rfft->whiten->scale stage program,
                # both with and without a zaplist keep-mask
                # (search_beam always passes a zaplist; bench's
                # search_block does not)
                Instance("fourier.whitened_spectrum",
                         f"whitened_spectrum {tag}", (sers,),
                         dict(nfft=nfft)),
                Instance("fourier.whitened_spectrum_masked",
                         f"whitened_spectrum_masked {tag}",
                         (sers, _sds((nbins,), jnp.bool_)),
                         dict(nfft=nfft)),
                Instance("fourier.lo_stage_candidates",
                         f"lo_stages {tag}",
                         (_sds((rows, nbins), jnp.complex64),
                          tuple(fr.harmonic_stages(
                              _sp.lo_accel_numharm)),
                          _sp.topk_per_stage), {}),
            ]
            if ctx.accel:
                # the hi stage runs at EVERY step geometry (the
                # executor calls _hi_accel_pass inside the chunk
                # loop of every pass) — but the batch planner
                # QUANTIZES both the batch size and the spectra
                # block's row count (kernels/accel_batch.py), so the
                # ragged pass-chunk row counts collapse onto the
                # signature ladder here exactly as they do at
                # runtime, and tests/test_accel_batch.py pins the
                # sweep's compile count to this gate set
                from tpulsar.kernels import accel_batch as abp

                dmc = abp.batch_rows(rows, nbins, nz)
                q_rows = abp.quantize_rows_up(rows)
                spec_sh = _sds((q_rows, nbins), jnp.complex64)
                insts += _chunk_instance(
                    f" {tag}", spec_sh, bank_sh, bank, nz,
                    dict(nrows=dmc, seg=bank.seg, step=bank.step,
                         width=bank.width, nz=nz,
                         max_numharm=_sp.hi_accel_numharm,
                         topk=_sp.topk_per_stage)) + [
                    Instance("accel.accel_row_topk",
                             f"accel_row {tag}",
                             (spec_sh, bank_sh, i32),
                             dict(seg=bank.seg, step=bank.step,
                                  width=bank.width, nz=nz,
                                  max_numharm=_sp.hi_accel_numharm,
                                  topk=_sp.topk_per_stage)),
                ]
                if q_rows != rows:
                    insts.append(Instance(
                        "accel._pad_block", f"accel_pad {tag}",
                        (_sds((rows, nbins), jnp.complex64),),
                        dict(rows=q_rows)))
                insts += _accel_native_instances(
                    dmc, nbins, bank, nz, label=tag)
        groups.append(("", insts))

    # Refinement + fold prep: each fold-worthy candidate gets ONE
    # full-resolution DM series (_dedisperse_single: single-DM
    # subband + dedisperse at ds=1) and a rows=1 spectral family
    # (refine_candidates) — distinct programs from the chunked pass
    # shapes above.
    nfft_full = ddplan.choose_n(ctx.nsamp)
    nbins_full = nfft_full // 2 + 1
    insts = [
        Instance("fourier.whitened_spectrum",
                 "whitened_spectrum rows=1",
                 (_sds((1, ctx.nsamp), jnp.float32),),
                 dict(nfft=nfft_full)),
        Instance("fourier.whitened_spectrum_masked",
                 "whitened_spectrum_masked rows=1",
                 (_sds((1, ctx.nsamp), jnp.float32),
                  _sds((nbins_full,), jnp.bool_)),
                 dict(nfft=nfft_full)),
    ]
    # refine_candidates' window gather: the one runtime device
    # program that used to sit outside the gate (round-3 advisor
    # finding).  Its (count, width) space is closed — count is
    # always refine._NWIN, width one of refine._WIDTH_BUCKETS — so
    # gate every member against the full-resolution spectrum shape.
    for w in _refine._WIDTH_BUCKETS:
        insts.append(Instance(
            "refine.gather", f"refine_gather width={w}",
            (_sds((nbins_full,), jnp.complex64),
             _sds((_refine._NWIN,), jnp.int32)),
            dict(width=w)))
    groups.append(("refinement/fold prep (single-DM, full "
                   "resolution):", insts))

    # Dense sweep over the single-DM pad buckets: pad buckets are
    # powers of two, so the LOW buckets occupy DM intervals much
    # narrower than a coarse sample spacing (the (256, 512) pair
    # lives in DM ~15-31 alone) — 2048 samples bound the missable
    # interval to ~0.5 DM, far below any bucket's width.
    pads = set()
    for dmval in np.linspace(0.0, ctx.plan[-1].hidm, 2048):
        ch, sb = dd.plan_pass_shifts(ctx.freqs, 96, float(dmval),
                                     [float(dmval)], TSAMP, 1)
        pads.add((dd._pad_bucket(int(ch.max(initial=0))),
                  dd._pad_bucket(int(sb.max(initial=0)))))
    insts = []
    for p1, p2 in sorted(pads):
        insts += _stage1_instances(blk, 96, 1, p1, "1dm")
        insts += _stage2_instances(96, ctx.nsamp, 1, p2, "1dm")
    # the sweep's pad pairs repeat each bucket: one instance per label
    groups.append(("", list({i.label: i for i in insts}.values())))
    return groups


def _stage1_instances(blk, nsub: int, downsamp: int, pad: int,
                      tag: str) -> list[Instance]:
    """Subband formation for one pad bucket, in the family the runtime
    dispatches (dedisperse.form_subbands): the Pallas slab programs
    where that tier is on — a TPU backend — and the XLA map
    elsewhere."""
    import jax.numpy as jnp

    from tpulsar.kernels import pallas_dd

    nchan, T = blk.shape
    if not pallas_dd.use_pallas_sb():
        return [Instance("dedisperse._form_subbands_jit",
                         f"form_subbands {tag} pad={pad}",
                         (blk, _sds((nchan,), jnp.int32)),
                         dict(nsub=nsub, downsamp=downsamp, pad=pad))]
    itemsize = jnp.dtype(blk.dtype).itemsize
    S = pallas_dd.stage_overhang(pad)
    plan = pallas_dd.stage1_plan(nchan, nsub, S, itemsize)
    insts = {}
    for slab in pallas_dd.stage1_slabs(
            T, nchan, itemsize, plan.block_t, S):
        n_blocks = slab.n_blocks
        body, rest = (b - a for a, b in (slab.body, slab.rest))
        insts[n_blocks, body, rest] = [
            Instance("pallas_dd._segment_slab",
                     f"pallas_segment_slab {tag} S={S} "
                     f"blocks={n_blocks} body={body} rest={rest}",
                     (_sds((nchan, body), blk.dtype),
                      _sds((nchan, rest), blk.dtype)),
                     dict(n_blocks=n_blocks, seg=plan.seg,
                          head=plan.head)),
            Instance("pallas_dd._form_subbands_block",
                     f"pallas_subbands {tag} S={S} "
                     f"blocks={n_blocks}",
                     (_sds((nchan, n_blocks * 8, plan.seg), blk.dtype),
                      _sds((nchan, 8, plan.head), blk.dtype),
                      _sds((nsub, nchan // nsub), jnp.int32)),
                     dict(plan.kernel_args(), nsub=nsub,
                          interpret=False)),
        ]
    return [i for pair in insts.values() for i in pair]


def _stage2_instances(nsub: int, T: int, rows: int, pad: int,
                      tag: str) -> list[Instance]:
    """Stage-2 dedispersion of a ``rows``-trial chunk for one pad
    bucket, in the family dedisperse.dedisperse_subbands dispatches:
    the Pallas kernel (the segment layout, then one program per
    distinct row count of pallas_dd.stage2_plan's calls) where that
    tier is on, the XLA scan elsewhere."""
    import jax.numpy as jnp

    from tpulsar.kernels import pallas_dd

    if not pallas_dd.use_pallas():
        return [Instance("dedisperse._dedisperse_subbands_scan",
                         (f"dedisperse_1dm pad={pad}" if tag == "1dm"
                          else f"dedisperse_scan {tag} pad={pad}"),
                         (_sds((nsub, T), jnp.float32),
                          _sds((rows, nsub), jnp.int32)),
                         dict(pad=pad))]
    S = pallas_dd.stage_overhang(pad)
    plan = pallas_dd.stage2_plan(nsub, S, rows, T)
    segs = _sds((nsub, plan.n_seg, plan.seg), jnp.float32)
    edge = _sds((nsub, 8, 128), jnp.float32)
    return [Instance("pallas_dd._segment_layout",
                     f"pallas_segments {tag} seg={plan.seg}",
                     (_sds((nsub, T), jnp.float32),),
                     dict(seg=plan.seg))] + [
        Instance("pallas_dd._dedisperse_chunk",
                 f"pallas_dedisperse {tag} S={S} seg={plan.seg} rows={n}",
                 (segs, edge, _sds((n, nsub), jnp.int32)),
                 dict(plan.kernel_args(), interpret=False))
        for n in sorted(set(plan.call_rows(rows)), reverse=True)]
