"""Ask the chip's compiler, without the chip: the kernels of the main
path compiled for a DESCRIBED v5e at the survey's widths.

The TPU compiler is installed next to the CPU backend and compiles for
a topology that is described, not attached.  Nothing executes, so
these say nothing about results or speed — they catch what interpret
mode cannot: a Mosaic lowering the chip refuses, a scoped-VMEM
overrun, an XLA program that does not fit.

Only one process may load the TPU library, so the topology is
described inside a module-scoped fixture of THIS file (never at
import, never in conftest.py) and every case compiles in the test's
own process.  This is the one file of such tests: under pytest-xdist
a second file could land on another worker and skip there in silence.
"""

import pytest

import jax
import jax.numpy as jnp

# the survey's Mock beam (tpulsar.aot.registry) and hi-accel settings
NSAMP = 3_932_160
NSUB = 96
ZMAX = 50.0


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without the chip: keep it off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _whitening_loops(text, nbins):
    """The loops of a compiled program that belong to the whitening: a
    `while` under its scope or over an nbins-long carry (a search for
    each bin's block, `while (s32[], s32[nbins], s32[nbins], ..)`,
    would run in every call of the program).  The compiler's own loop
    over an FFT's rows is not one of them."""
    return [line for line in text.splitlines() if " while(" in line
            and ("whiten" in line or f"[{nbins}]" in line)]


@pytest.mark.parametrize("nsub,T,rows,overhang,group", [
    (NSUB, NSAMP, 19, 256, 96),       # Mock ds=1: a 38-row chunk's call
    (NSUB, NSAMP, 32, 256, 96),
    (NSUB, NSAMP, 19, 2048, 96),
    (NSUB, NSAMP, 32, 2048, 96),
    (64, 4_194_304, 19, 256, 64),     # WAPP ds=1
    (NSUB, 393_216, 26, 2048, 96),    # Mock ds=10, its real overhang
    (NSUB, NSAMP, 1, 256, 96),        # one row at full resolution
    (NSUB, NSAMP, 1, 8192, 96),       # ... at the AOT gate's deepest
    (NSUB, NSAMP, 1, 16384, 48),      # overhangs: the last two take
    (NSUB, NSAMP, 1, 32768, 24),      # the subbands in groups
    (128, 1_361_920, 26, 256, 128),   # GBNCC ds=1, DM 0-0.3
    (128, 680_960, 26, 8192, 64),     # ... ds=2, DM 52: two groups of
    (128, 85_120, 26, 8192, 64),      # 64 in a search pass, to ds=16
    (128, 1_361_920, 26, 16384, 32),  # ... the end of its ds=1 step
])
def test_stage2_dedispersion_kernel(one_chip, nsub, T, rows, overhang,
                                    group):
    """pallas_dd._dedisperse_chunk at the geometry pallas_dd.stage2_plan
    derives for the survey's shapes: Mosaic takes it, and the scoped
    VMEM it is given is the plan's own request."""
    from tpulsar.kernels import pallas_dd

    plan = pallas_dd.stage2_plan(nsub, overhang, rows, T)
    assert (plan.calls, plan.rows) == (1, rows)
    compiled = pallas_dd._dedisperse_chunk.lower(
        _sds(one_chip, (nsub, plan.n_seg, plan.seg), jnp.float32),
        _sds(one_chip, (nsub, 8, 128), jnp.float32),
        _sds(one_chip, (rows, nsub), jnp.int32),
        interpret=False, **plan.kernel_args()).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert plan.vmem_bytes <= pallas_dd.STAGE2_VMEM_BUDGET
    assert plan.group == group
    # the custom call's scoped-memory request, as XLA prints it
    assert f'"size":"{plan.vmem_bytes}"' in text


@pytest.mark.parametrize("nchan,nsub,overhang,want", [
    (960, 96, 256, (32768, 48)),         # Mock: two groups of 48
    (960, 96, 1024, (32768, 48)),
    (256, 64, 256, (32768, 64)),         # WAPP width: every subband
    (4096, 128, 256, (32768, 16)),       # GBNCC: groups of 16 subbands
    (4096, 128, 2048, (32768, 8)),       # ... 8 at its deepest overhang
])
def test_stage1_subband_kernel(one_chip, nchan, nsub, overhang, want):
    """pallas_dd._form_subbands_block on one uint8 slab in its segment
    layout, at the block length and subband group
    pallas_dd.stage1_plan picks for that width: Mosaic takes it (the
    int32 widening of 8-bit tiles, the sublane rotates of the slab's
    fill) inside the scoped VMEM the plan states."""
    from tpulsar.kernels import pallas_dd

    plan = pallas_dd.stage1_plan(nchan, nsub, overhang, 1)
    assert (plan.block_t, plan.group) == want
    n_blocks = 7 if nchan == 4096 else 30        # a 1 GB slab's
    compiled = pallas_dd._form_subbands_block.lower(
        _sds(one_chip, (nchan, n_blocks * 8, plan.seg), jnp.uint8),
        _sds(one_chip, (nchan, 8, plan.head), jnp.uint8),
        _sds(one_chip, (nsub, nchan // nsub), jnp.int32),
        nsub=nsub, interpret=False, **plan.kernel_args()).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f'"size":"{plan.vmem_bytes}"' in text


@pytest.fixture
def tpu_accel_branch(monkeypatch):
    """accel.py asks jax.default_backend() at trace time and would take
    its CPU branch here (f32 plane, z-chunk 16, rows counted for the
    FFT form): steer it to what the chip takes."""
    from tpulsar.kernels import accel

    monkeypatch.setattr(accel, "_PLANE_DTYPE_RESOLVED", jnp.bfloat16)
    monkeypatch.setattr(accel, "_Z_CHUNK_RESOLVED", 4)
    monkeypatch.setattr(accel, "corr_form", lambda: "direct")
    return accel


@pytest.mark.parametrize("program", ["chunk", "row"])
def test_hi_accel_programs(one_chip, tpu_accel_branch, program):
    """accel_chunk_topk / accel_row_topk at the survey's segment
    length, nz = 51 and 8 harmonics; nbins is cut (the full 1,966,081
    bins compile in about a minute)."""
    accel = tpu_accel_branch
    bank = accel.build_template_bank(ZMAX)
    nz = len(bank.zs)
    assert (nz, bank.seg) == (51, 8192)
    nbins = 16_385
    spec, bank_fft, c0 = (
        _sds(one_chip, (4, nbins), jnp.complex64),
        _sds(one_chip, bank.bank_fft.shape, jnp.complex64),
        _sds(one_chip, (), jnp.int32))
    taps = _sds(one_chip, accel.corr_taps_shape(nz, bank.width),
                jnp.float32)
    kw = dict(seg=bank.seg, step=bank.step, width=bank.width, nz=nz,
              max_numharm=8, topk=32)
    if program == "chunk":
        part = _sds(one_chip, (4, nbins), jnp.float32)
        lowered = accel.accel_chunk_topk.lower((part, part), bank_fft,
                                               taps, c0, nrows=2, **kw)
    else:
        lowered = accel.accel_row_topk.lower(spec, bank_fft, c0, **kw)
    text = lowered.compile().as_text()
    # the plane really is bf16 on this branch, and its harmonic sums
    # are the Mosaic kernel (lax.platform_dependent took the TPU side);
    # the chunk program's correlation is the other kernel and no FFT is
    # left in it, the per-DM row program keeps the overlap-save FFTs
    assert "bf16" in text
    assert text.count("tpu_custom_call") == {"chunk": 2, "row": 1}[program]
    assert ("jit(fft)" in text) == (program == "row")


@pytest.mark.parametrize("zmax,numharm,nbins,rows", [
    (50.0, 8, 1_966_081, 1),     # Mock ds=1: mock_ds1_hiaccel's chunk
    (200.0, 16, 1_966_081, 1),   # BASELINE config 3: z200_ds1_hiaccel's
    (50.0, 8, 2_097_153, 1),     # WAPP ds=1 (wapp_ds1_hiaccel is owed)
    (50.0, 8, 1_966_081, 6),     # what a mesh device's hi stage holds
    (200.0, 16, 1_966_081, 2),
    (50.0, 8, 737_281, 1),       # GBNCC's 120 s at ds=1: gbncc120_hiaccel
    (50.0, 8, 3_072_001, 4),     # FAST GPPS ds=1, a mesh device's rows
])
def test_hi_accel_chunk_program_at_full_width(one_chip, tpu_accel_branch,
                                              zmax, numharm, nbins, rows):
    """The WHOLE chunk program at the survey's width, with the row
    plane_dm_chunk gives it (and with the rows it gives a device of the
    DM-sharded mesh): it compiles for the chip, the correlation and the
    harmonic sums are the two Mosaic kernels with no FFT beside them,
    and the temporaries the compiler counts are what plane_dm_chunk
    budgets the rows by (plane_row_bytes), within 20%."""
    accel = tpu_accel_branch
    bank = accel.build_template_bank(zmax)
    nz = len(bank.zs)
    assert rows == accel.plane_dm_chunk(nbins, nz,
                                        max_chunk=None if rows == 1 else 32)
    counted = rows * accel.plane_row_bytes(nbins, nz, None)
    assert counted <= accel.PLANE_HBM_BUDGET      # fits by its own count
    part = _sds(one_chip, (48, nbins), jnp.float32)
    compiled = accel.accel_chunk_topk.lower(
        (part, part), _sds(one_chip, bank.bank_fft.shape, jnp.complex64),
        _sds(one_chip, accel.corr_taps_shape(nz, bank.width), jnp.float32),
        _sds(one_chip, (), jnp.int32), nrows=rows, seg=bank.seg,
        step=bank.step, width=bank.width, nz=nz, max_numharm=numharm,
        topk=32).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 and "fft" not in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert abs(temp - counted) <= 0.2 * counted, (temp, counted)


@pytest.mark.parametrize("zmax,numharm", [(50.0, 8), (200.0, 16)])
def test_dm_sharded_pass_program_on_four_chips(v5e, tpu_accel_branch,
                                               zmax, numharm):
    """The DM-sharded mesh program (parallel/mesh.sharded_pass_fn: the
    whole pass in one program, the hi stage the chunk program's
    _accel_block_topk) for the four chips of a described v5e 2x2, at
    the Mock ds=1 width, with the rows a device that
    executor._search_pass_sharded gives it: its hi stage is the two
    Mosaic kernels there too, with no inverse FFT beside them, the
    rows' planes fit the budget by the count they were sized with, and
    the program fits a chip."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpulsar.kernels import fourier as fr
    from tpulsar.kernels import pallas_dd
    from tpulsar.kernels import singlepulse as sp_k
    from tpulsar.parallel import mesh as pmesh
    from tpulsar.plan import ddplan
    from tpulsar.search import executor

    accel = tpu_accel_branch
    mesh = Mesh(np.asarray(v5e.devices).reshape(1, 4), ("beam", "dm"))
    params = executor.SearchParams(hi_accel_zmax=zmax,
                                   hi_accel_numharm=numharm)
    bank = accel.build_template_bank(zmax)
    nz = len(bank.zs)
    nfft = ddplan.choose_n(NSAMP)
    nbins = nfft // 2 + 1
    rows = accel.plane_dm_chunk(nbins, nz, max_chunk=32)
    assert rows == {51: 6, 201: 2}[nz]
    assert (rows * accel.plane_row_bytes(nbins, nz, None)
            * (1 + accel.PLANE_COUNT_SLACK) <= accel.PLANE_HBM_BUDGET)
    spec = pmesh.PassSpec(
        nfft=nfft, max_numharm=params.lo_accel_numharm,
        topk=params.topk_per_stage, sp_widths=tuple(params.sp_widths),
        sp_topk=sp_k.DEFAULT_TOPK,
        sp_detrend=sp_k.detrend_estimator(params.sp_detrend),
        whiten_est=fr.whiten_estimator(), hi=True, hi_numharm=numharm,
        hi_seg=bank.seg, hi_step=bank.step, hi_width=bank.width,
        hi_nz=nz, pallas_dd=True,
        dd_stage_s=pallas_dd.stage_overhang(200), dd_interpret=False,
        dd_pad=256)

    def sds(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes)))

    compiled = pmesh.sharded_pass_fn(mesh, spec).lower(
        sds((NSUB, NSAMP), jnp.float32),
        sds((4 * rows, NSUB), jnp.int32, "dm", None),
        sds((nbins,), jnp.float32),
        sds(bank.bank_fft.shape, jnp.complex64),
        sds(accel.corr_taps_shape(nz, bank.width), jnp.float32)).compile()
    text = compiled.as_text()
    assert "corr_plane" in text and "harmsum_zmax" in text
    # the lo stage's kernel at a device's rows, and no decimated copy
    assert "lo_harmsum" in text and f"f32[{nbins},{rows}]" not in text
    # the boxcar ladder's kernel inside shard_map, at a device's rows
    assert "sp_boxcar" in text and f",{NSAMP // 32},32]" not in text
    assert "fft_type=IFFT" not in text and "all-gather" in text
    assert not _whitening_loops(text, nbins)    # the solo program's form
    mem = compiled.memory_analysis()        # bytes on each device
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 14 << 30


@pytest.mark.parametrize("rows,zmax,nbins", [
    (2, 50.0, 1_966_081), (1, 200.0, 1_966_081), (2, 50.0, 2_097_153),
    (2, 8.0, 65_537),            # a toy bank 64 bins wide (chip_smoke's)
    (1, 900.0, 1_966_081),       # the widest corr_plan tiles: 1024 bins
])
def test_hi_accel_correlation_kernel(one_chip, tpu_accel_branch, rows,
                                     zmax, nbins):
    """accel._corr_plane alone: Mosaic takes the shifted window reads,
    the three float32 products, the sublane-strided relayout and the
    scoped VMEM corr_plan asks for, at every width a bank has and at
    the widest it does not refuse."""
    accel = tpu_accel_branch
    width, nz = accel.template_width(zmax), len(accel.z_grid(zmax))
    plan = accel.corr_plan(nbins, nz, width, rows)
    part = _sds(one_chip, (rows, nbins), jnp.float32)
    compiled = accel._corr_plane.lower(
        part, part,
        _sds(one_chip, accel.corr_taps_shape(nz, width), jnp.float32),
        width=width, nz=nz, interpret=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "corr_plane" in text
    assert f'"size":"{plan.vmem_limit}"' in text


@pytest.mark.parametrize("nd,nz,ncols,numharm", [
    (2, 51, 3_932_162, 8),       # Mock ds=1: the benchmark's chunk
    (2, 51, 1_966_082, 8),       # Mock ds=2
    (2, 51, 4_194_306, 8),       # WAPP ds=1
    (1, 201, 3_932_162, 16),     # zmax 200 (BASELINE config 3)
])
def test_hi_accel_harmsum_kernel(one_chip, nd, nz, ncols, numharm):
    """accel._harmsum_zmax at the survey's full plane widths: Mosaic
    takes the blocks, the strided z reads and the scoped-VMEM limit
    that harmsum_plan derives (interpret mode cannot say)."""
    from tpulsar.kernels import accel
    from tpulsar.kernels.fourier import harmonic_stages

    stages = tuple(harmonic_stages(numharm))
    compiled = accel._harmsum_zmax.lower(
        _sds(one_chip, (nd, nz, ncols), jnp.bfloat16), stages=stages,
        nz=nz, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows,ncols,tile", [
    (38, 3_932_162, 1024),       # Mock ds=1: the executor's chunk
    (38, 4_194_306, 1024),       # WAPP ds=1
    (64, 1_966_082, 1024),       # Mock ds=2: two row groups of 32
    (102, 1_361_922, 1024),      # GBNCC ds=1: three groups of 40
    (6, 3_932_162, 2048),        # a mesh device's rows
    (1, 3_932_162, 2048),        # periodicity_search's one series
])
def test_lo_harmsum_kernel(one_chip, rows, ncols, tile):
    """fourier._lo_block_maxima at the survey's half-bin grids: Mosaic
    takes the blocks, the six-pass selection products, the lane
    rotates of the block maxima and the scoped-VMEM limit that
    lo_harmsum_plan derives (interpret mode cannot say)."""
    from tpulsar.kernels import fourier as fr

    stages = tuple(fr.harmonic_stages(16))
    plan = fr.lo_harmsum_plan(rows, ncols, stages)
    assert plan.tile == tile
    text = fr._lo_block_maxima.lower(
        _sds(one_chip, (rows, ncols), jnp.float32), stages=stages,
        interpret=False).compile().as_text()
    assert "tpu_custom_call" in text and "lo_harmsum" in text
    assert f'"size":"{plan.vmem_limit}"' in text


@pytest.mark.parametrize("rows,nbins", [
    (38, NSAMP // 2 + 1),        # a ds=1 pass chunk
    (64, NSAMP // 4 + 1),        # a ds=2 pass chunk: two row groups
])
def test_lo_stage_program_holds_the_kernel_and_no_gather(one_chip, rows,
                                                         nbins):
    """lo_stage_candidates lowered for a v5e at a pass chunk of up to
    64 rows: the kernel is in it (chosen by lax.platform_dependent, no
    knob) and the strided form's decimated copies are not, in either
    layout."""
    from tpulsar.kernels import fourier as fr

    text = fr.lo_stage_candidates.lower(
        _sds(one_chip, (rows, nbins), jnp.complex64),
        tuple(fr.harmonic_stages(16)), 64).compile().as_text()
    assert "lo_harmsum" in text
    # no decimated copy (the gathers left are top-k's, of (rows, 64))
    assert f"f32[{nbins},{rows}]" not in text


def test_lo_stage_program_keeps_the_strided_form_past_64_rows(one_chip):
    """At a GBNCC pass chunk (102 rows) fourier.lo_form leaves the
    strided form to the TPU: XLA carries the rows on the lanes there,
    80% of them filled, and the kernel read 51.1 ms a call against
    39.6 (PERF.md, PR 39)."""
    from tpulsar.kernels import fourier as fr

    text = fr.lo_stage_candidates.lower(
        _sds(one_chip, (102, 680_961), jnp.complex64),
        tuple(fr.harmonic_stages(16)), 64).compile().as_text()
    assert "lo_harmsum" not in text


def test_whitening_program(one_chip):
    """The fused pad -> rfft -> whiten -> scale program at a ds=1 pass
    chunk (38 trials of the full series): the chip's form of it holds
    no search for the bins' blocks and no gather at all."""
    from tpulsar.kernels import fourier as fr
    from tpulsar.plan import ddplan

    nfft = ddplan.choose_n(NSAMP)
    compiled = fr.whitened_spectrum.lower(
        _sds(one_chip, (38, NSAMP), jnp.float32), nfft=nfft).compile()
    nbins = nfft // 2 + 1
    assert compiled.memory_analysis().output_size_in_bytes >= \
        38 * nbins * 8
    text = compiled.as_text()
    assert not _whitening_loops(text, nbins) and " gather(" not in text


@pytest.mark.parametrize("rows,T", [
    (38, NSAMP),                # a Mock ds=1 pass chunk
    (64, 1_966_080),            # Mock ds=2
    (76, 167_772),              # WAPP ds=25: the shortest, ragged
    (102, 1_361_920),           # GBNCC ds=1: three row groups
    (6, NSAMP),                 # a mesh device's rows
])
def test_single_pulse_programs(one_chip, rows, T):
    """Detrend + boxcar ladder at the cells' pass chunks.  The ladder
    is ONE pass over the series in the kernel sp_boxcar: no loop the
    compiler made (the cumulative-sum form re-tiled the series in a
    `while` a width), no array with a minor dimension of 32 (its
    blocks on 128 lanes), and what it holds beside its operand is the
    block maxima (4.2 GB at 38 rows until PR 44)."""
    from tpulsar.kernels import singlepulse as sp_k

    series = _sds(one_chip, (rows, T), jnp.float32)
    sp_k.normalize_series.lower(
        series, estimator=sp_k.detrend_estimator()).compile()
    compiled = sp_k.boxcar_search.lower(
        series, tuple(sp_k.DEFAULT_WIDTHS),
        sp_k.DEFAULT_TOPK).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "sp_boxcar" in text
    assert " while(" not in text and ",32]{" not in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.5e9
    assert temp < 3 * 2 * len(sp_k.DEFAULT_WIDTHS) * rows * (T // 32) * 4


@pytest.mark.parametrize("nsamp,nchan,dtype", [
    (NSAMP, 960, jnp.uint8),            # a Mock beam, as read_all_uint8
    (1_464_320, 4096, jnp.uint8),       # a full GBNCC pointing, 5.6 GiB
    (262_144, 960, jnp.float32),        # a beam under block_quantize_min
])
def test_read_in_transpose_fits_beside_its_input(one_chip, nsamp, nchan,
                                                 dtype):
    """The read-in sends the block as read, (T, nchan), and turns it
    channel-major on the chip: the time-major input (its lanes padded
    to 128 channels) and the output together stay under what the mask
    holds later (block + masked block), and under the chip."""
    from tpulsar.kernels import rfi

    compiled = rfi.channel_major.lower(
        _sds(one_chip, (nsamp, nchan), dtype)).compile()
    mem = compiled.memory_analysis()
    block = nsamp * nchan * jnp.dtype(dtype).itemsize
    assert mem.output_size_in_bytes == block
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert held <= 2.2 * block and held < 15.7 * 2 ** 30


# --------------- a beam laid over the four chips by channels (FAST GPPS)

GPPS_NCHAN, GPPS_NSUB, GPPS_NSAMP = 2048, 128, 6_103_040


def _collectives(text):
    return {k for k in ("all-gather", "all-to-all", "all-reduce",
                        "reduce-scatter", "collective-permute")
            if f" {k}(" in text or f" {k}-start(" in text}


@pytest.mark.parametrize("overhang,group", [(256, 32), (2048, 16)])
def test_stage1_share_programs_on_four_chips(v5e, overhang, group):
    """Stage 1 of a 2048-channel beam laid over the four chips of a
    described v5e 2x2 by channels (pallas_dd._share_programs: the slab's
    layout and the kernel under shard_map, at a SHARE's geometry, 512
    channels and 32 subbands): Mosaic takes the kernel at 16 channels a
    subband, nothing crosses between chips, and a 1 GB slab a chip
    fits."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpulsar.kernels import pallas_dd

    mesh = Mesh(np.asarray(v5e.devices), ("chan",))
    plan = pallas_dd.stage1_plan(GPPS_NCHAN // 4, GPPS_NSUB // 4, overhang,
                                 1)
    assert (plan.block_t, plan.group) == (32768, group)
    slab = pallas_dd.stage1_slabs(GPPS_NSAMP, GPPS_NCHAN // 4, 1,
                                  plan.block_t, overhang)[0]
    assert slab.n_blocks == 59

    def sds(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes)))

    segment, block = pallas_dd._share_programs(
        mesh, slab.n_blocks, plan.seg, plan.head, GPPS_NSUB // 4, False,
        tuple(sorted(plan.kernel_args().items())))
    body = sds((GPPS_NCHAN, slab.body[1] - slab.body[0]), jnp.uint8,
               "chan", None)
    rest = sds((GPPS_NCHAN, slab.rest[1] - slab.rest[0]), jnp.uint8,
               "chan", None)
    laid = segment.lower(body, rest).compile()
    assert not _collectives(laid.as_text())
    segs, tail = jax.eval_shape(segment, body, rest)
    compiled = block.lower(
        sds(segs.shape, segs.dtype, "chan", None, None),
        sds(tail.shape, tail.dtype, "chan", None, None),
        sds((GPPS_NSUB, GPPS_NCHAN // GPPS_NSUB), jnp.int32, "chan",
            None)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and not _collectives(text)
    assert f'"size":"{plan.vmem_bytes}"' in text
    mem = compiled.memory_analysis()        # bytes on each device
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 2 << 30


def test_partial_form_pass_program_on_four_chips(v5e):
    """The mesh pass of a laid-out beam at FAST's ds=1 (128 subbands
    of 6,103,040 samples left where stage 1 formed them, 32 a chip;
    hi-accel off; the rows a device executor._mesh_rows_budget gives):
    stage 2 is the solo kernel over a chip's own subbands, the partial
    sums cross in ONE all-to-all a group (a psum_scatter there compiles
    to an all-reduce of the whole group: twice the bytes and 3 GiB
    more), and the program fits beside the beam's 2.91 GiB share."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpulsar.kernels import fourier as fr
    from tpulsar.kernels import pallas_dd
    from tpulsar.kernels import singlepulse as sp_k
    from tpulsar.parallel import mesh as pmesh
    from tpulsar.plan import ddplan
    from tpulsar.search import executor

    mesh = Mesh(np.asarray(v5e.devices).reshape(1, 4), ("beam", "dm"))
    params = executor.SearchParams(run_hi_accel=False)
    nfft = ddplan.choose_n(GPPS_NSAMP)
    nbins = nfft // 2 + 1
    rows = executor._mesh_rows_budget(nfft, params.spectral_hbm_budget)
    assert (nfft, rows) == (6_144_000, 13)
    assert pmesh.partial_groups(rows, 4, GPPS_NSAMP) == rows   # one group
    spec = pmesh.PassSpec(
        nfft=nfft, max_numharm=params.lo_accel_numharm,
        topk=params.topk_per_stage, sp_widths=tuple(params.sp_widths),
        sp_topk=sp_k.DEFAULT_TOPK,
        sp_detrend=sp_k.detrend_estimator(params.sp_detrend),
        whiten_est=fr.whiten_estimator(), hi=False, pallas_dd=True,
        dd_stage_s=pallas_dd.stage_overhang(93), dd_interpret=False,
        dd_pad=256, sub_sharded=True)

    def sds(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes)))

    compiled = pmesh.sharded_pass_fn(mesh, spec).lower(
        sds((GPPS_NSUB, GPPS_NSAMP), jnp.float32, "dm", None),
        sds((4 * rows, GPPS_NSUB), jnp.int32, None, "dm"),
        sds((nbins,), jnp.float32), sds((1, 1), jnp.complex64),
        None).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert _collectives(text) == {"all-to-all", "all-gather"}
    assert text.count(" all-to-all(") == 1
    mem = compiled.memory_analysis()        # bytes on each device
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 8 << 30


@pytest.mark.parametrize("nchan,nsamp,devices", [
    (4096, 1_464_320, 1),        # GBNCC's 120 s pointing, 5.59 GiB
    (960, NSAMP, 1),             # a Mock beam
    (GPPS_NCHAN, GPPS_NSAMP, 4),  # FAST GPPS laid over four chips
])
def test_masking_a_beam_holds_the_input_and_the_output_only(
        v5e, nchan, nsamp, devices):
    """rfi.apply_mask_chan at a beam's size: no temporary beside the
    block and its masked copy (one fused select over the reshaped
    block compiles with a third copy: 3 x 5.59 GiB at GBNCC's 120 s,
    which ran out of memory on the chip, PR 48), and a block laid over
    the chips by channels is masked share by share, no collective."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpulsar.kernels import rfi

    mesh = Mesh(np.asarray(v5e.devices[:devices]), ("chan",))

    def sds(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes)))

    compiled = rfi.apply_mask_chan.lower(
        sds((nchan, nsamp), jnp.uint8, "chan", None),
        sds((nsamp // 2048, nchan), jnp.bool_),
        sds((nchan,), jnp.float32), block_len=2048).compile()
    mem = compiled.memory_analysis()        # bytes on each device
    share = nchan * nsamp // devices
    assert mem.output_size_in_bytes >= share
    assert mem.temp_size_in_bytes < share // 100
    assert not _collectives(compiled.as_text())


@pytest.mark.parametrize("downsamp,form,rows,smax", [
    (2, "replicate", 8, 3420), (1, "partial", 4, 93)])
def test_hi_accel_over_the_laid_out_beam_on_four_chips(
        v5e, tpu_accel_branch, downsamp, form, rows, smax):
    """gpps_hiaccel_mesh4's two fused pass programs (FAST GPPS's first
    ds=2 pass, subbands a whole copy a chip, and its first ds=1 pass,
    subbands left where stage 1 formed them) with the hi stage inside,
    at the rows a device executor._search_pass_sharded gives them: the
    least of plane_dm_chunk's count and _mesh_rows_budget's.  The two
    hi kernels are there beside stage 2's, the partial form's sums
    cross in one all-to-all, and each program fits a chip beside the
    beam's 2.91 GiB share and the pass's subbands."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from tpulsar.kernels import dedisperse as dd
    from tpulsar.kernels import fourier as fr
    from tpulsar.kernels import pallas_dd
    from tpulsar.kernels import singlepulse as sp_k
    from tpulsar.parallel import mesh as pmesh
    from tpulsar.plan import ddplan
    from tpulsar.search import executor

    accel = tpu_accel_branch
    mesh = Mesh(np.asarray(v5e.devices).reshape(1, 4), ("beam", "dm"))
    params = executor.SearchParams()
    bank = accel.build_template_bank(ZMAX)
    nz = len(bank.zs)
    T = GPPS_NSAMP // downsamp
    nfft = ddplan.choose_n(T)
    nbins = nfft // 2 + 1
    assert rows == min(
        accel.plane_dm_chunk(nbins, nz, max_chunk=32),
        executor._mesh_rows_budget(nfft, params.spectral_hbm_budget))
    assert (GPPS_NSUB * T * 4 > params.seq_shard_min_bytes) == \
        (form == "partial")
    spec = pmesh.PassSpec(
        nfft=nfft, max_numharm=params.lo_accel_numharm,
        topk=params.topk_per_stage, sp_widths=tuple(params.sp_widths),
        sp_topk=sp_k.DEFAULT_TOPK,
        sp_detrend=sp_k.detrend_estimator(params.sp_detrend),
        whiten_est=fr.whiten_estimator(), hi=True,
        hi_numharm=params.hi_accel_numharm, hi_seg=bank.seg,
        hi_step=bank.step, hi_width=bank.width, hi_nz=nz, pallas_dd=True,
        dd_stage_s=pallas_dd.stage_overhang(smax), dd_interpret=False,
        dd_pad=dd._pad_bucket(smax), sub_sharded=form == "partial")

    def sds(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, P(*axes)))

    if form == "partial":
        subb = sds((GPPS_NSUB, T), jnp.float32, "dm", None)
        table = sds((4 * rows, GPPS_NSUB), jnp.int32, None, "dm")
    else:
        subb = sds((GPPS_NSUB, T), jnp.float32)
        table = sds((4 * rows, GPPS_NSUB), jnp.int32, "dm", None)
    compiled = pmesh.sharded_pass_fn(mesh, spec).lower(
        subb, table, sds((nbins,), jnp.float32),
        sds(bank.bank_fft.shape, jnp.complex64),
        sds(accel.corr_taps_shape(nz, bank.width), jnp.float32)).compile()
    text = compiled.as_text()
    assert "corr_plane" in text and "harmsum_zmax" in text
    assert "lo_harmsum" in text and "sp_boxcar" in text
    assert "fft_type=IFFT" not in text
    assert _collectives(text) == (
        {"all-to-all", "all-gather"} if form == "partial"
        else {"all-gather"})
    mem = compiled.memory_analysis()        # bytes on each device
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 9 << 30

