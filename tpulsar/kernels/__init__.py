"""JAX/XLA/Pallas compute kernels — the TPU replacements for the
PRESTO C executables the reference shells out to (SURVEY.md section 2.3):

  rfi.py          <- rfifind          (time-freq stats + mask)
  dedisperse.py   <- prepsubband      (subbands + incoherent dedispersion)
  pallas_dd.py    <- prepsubband      (the chip's stage-1 / stage-2
                                       kernels, _kernel_sb / _kernel_dd)
  fourier.py      <- realfft, zapbirds, rednoise + zero-accel periodicity
  accel.py        <- accelsearch      (Fourier-domain acceleration search)
  singlepulse.py  <- single_pulse_search (boxcar matched filter)
  fold.py         <- prepfold         (candidate folding + optimization)
"""
