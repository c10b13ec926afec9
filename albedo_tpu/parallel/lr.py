"""Data-parallel logistic-regression training over the device mesh.

Reference parity: MLlib LR's distributed L-BFGS — per-partition gradient sums
``treeAggregate``d to the driver every iteration
(``LogisticRegressionRanker.scala:330-337``, SURVEY.md §2.5). TPU-native
version: the feature batch is laid out row-sharded over the mesh's ``data``
axis and parameters replicated; the SAME jitted loss as the single-device path
then compiles with XLA-inserted psums over ICI for every weighted reduction —
sharding annotations replace hand-written collectives.

Padding rows carry weight 0, so ``sum(w * ce) / sum(w)`` is invariant.
"""

from __future__ import annotations

import jax
import numpy as np

from albedo_tpu.features.assembler import FeatureMatrix
from albedo_tpu.ops.sparse_linear import expanded_batch
from albedo_tpu.parallel.mesh import DATA_AXIS, pad_rows_to, row_sharded


def shard_feature_batch(
    fm: FeatureMatrix,
    labels: np.ndarray,
    weights: np.ndarray,
    mesh,
    axis: str = DATA_AXIS,
):
    """Pad rows to a shard-count multiple and upload row-sharded.

    Returns ``(batch, labels, weights)`` device arrays shaped like
    ``ops.sparse_linear.feature_batch`` output; padding rows have weight 0 and
    bag indices -1 (fully masked).
    """
    n_shards = mesh.shape[axis]
    sharding = row_sharded(mesh, axis)

    def put(x: np.ndarray):
        return jax.device_put(pad_rows_to(np.asarray(x), n_shards), sharding)

    # The expanded rectangle every device slices evenly (the factored vec
    # layout would replicate the distinct vectors and shard only the rep
    # gather — a later optimization; parity with the single-device fit is
    # what matters here, and params/scales span the same logical width
    # either way).
    n_pad = -(-fm.n_rows // n_shards) * n_shards
    batch = {
        k: jax.device_put(v, sharding) for k, v in expanded_batch(fm, n_pad).items()
    }
    y = put(np.asarray(labels, dtype=np.float32))
    w = put(np.asarray(weights, dtype=np.float32))
    return batch, y, w
