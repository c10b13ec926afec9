"""Device compute primitives: XLA programs written in ``jax.numpy``/``lax``
(there are no Pallas kernels in the tree).

This is the TPU-native replacement for the reference's netlib-BLAS hot loops
(SURVEY.md section 2: the MLlib ALS normal-equation solves and the
``F2jBLAS.sdot`` scoring loop in ``recommenders/ALSRecommender.scala:51``).
"""

from albedo_tpu.ops.als import als_half_sweep, gramian, solve_bucket
from albedo_tpu.ops.topk import topk_scores

__all__ = ["als_half_sweep", "gramian", "solve_bucket", "topk_scores"]
