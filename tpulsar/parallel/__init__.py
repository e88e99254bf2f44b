"""Parallel layer: device meshes and the sharded per-pass search.

The reference's only parallelism is embarrassingly-parallel batch jobs
(SURVEY.md section 2.4).  Here parallelism is first-class and TPU-
native: a (beam, dm) jax.sharding.Mesh carries data-parallel beams and
DM-trial sharding over ICI; a beam too large for one chip is laid
over the mesh by channels and its subbands reach stage 2 by one
exchange a pass ("replicate" or "partial").
"""

from tpulsar.parallel.mesh import (  # noqa: F401
    make_mesh,
    sharded_search_step,
    SearchStepSpec,
)
