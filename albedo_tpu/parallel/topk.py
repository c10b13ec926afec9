"""Item-axis-sharded top-k retrieval.

The reference's retrieval blockifies both factor tables and cross-joins blocks
on Spark executors (``recommenders/ALSRecommender.scala:21-61``); the "long"
axis being scaled is the item dimension (SURVEY.md section 2.5). TPU-native:
shard the item-factor table over the mesh's ``item`` axis; each device scores
its shard with one ``(U, r) @ (r, I/D)`` MXU GEMM, keeps a local top-k, then a
k-per-device candidate ``all_gather`` (tiny: ``U x D*k``) merges to the global
top-k. Communication is O(U * D * k), never O(U * I) — the score matrix is
never materialized globally or gathered.

Users stream through in caller-sized blocks (the ``data`` axis of the same
mesh can shard the user rows too, via ``in_specs``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from albedo_tpu.parallel.mesh import DATA_AXIS, ITEM_AXIS


@functools.lru_cache(maxsize=64)
def make_sharded_topk(
    mesh: Mesh,
    k: int,
    item_axis: str = ITEM_AXIS,
    data_axis: str | None = DATA_AXIS,
    with_exclude: bool = False,
):
    """Build a jitted sharded top-k scorer for this mesh.

    Returns ``fn(user_factors (U, r), item_factors_padded (I_pad, r)[, exclude
    (U, E)]) -> (scores (U, k), item_idx (U, k))``. ``I_pad`` must be divisible
    by the item-axis size; pad rows must be all-zero AND callers must pass
    ``n_items`` so pads are masked. User rows are sharded over ``data_axis``
    when given (U divisible by that axis size).
    """
    u_spec = P(data_axis) if data_axis else P()

    def local(uf, vf_local, n_items, exclude):
        shard = jax.lax.axis_index(item_axis)
        block = vf_local.shape[0]
        start = shard * block
        global_ids = start + jnp.arange(block, dtype=jnp.int32)
        scores = uf @ vf_local.T                          # (U/D_d, I/D_i) MXU
        neg_inf = jnp.asarray(-jnp.inf, scores.dtype)
        scores = jnp.where(global_ids[None, :] < n_items, scores, neg_inf)
        if exclude is not None:
            local_idx = exclude - start                   # (U/D_d, E)
            oob = (local_idx < 0) | (local_idx >= block) | (exclude < 0)
            local_idx = jnp.where(oob, block, local_idx)
            hit = jnp.zeros(scores.shape, bool)
            rows = jnp.arange(scores.shape[0])[:, None]
            hit = hit.at[rows, local_idx].set(True, mode="drop")
            scores = jnp.where(hit, neg_inf, scores)
        # A shard can hold fewer than k items; the global top-k only needs
        # min(k, block) candidates from each shard.
        k_local = min(k, block)
        vals, idx = jax.lax.top_k(scores, k_local)        # local top-k
        idx = jnp.take(global_ids, idx)
        # Candidate merge: k_local per device -> (U/D_d, D_i*k_local).
        all_vals = jax.lax.all_gather(vals, item_axis, axis=1, tiled=True)
        all_idx = jax.lax.all_gather(idx, item_axis, axis=1, tiled=True)
        if all_vals.shape[1] < k:  # total (padded) catalog smaller than k
            fill = k - all_vals.shape[1]
            all_vals = jnp.pad(all_vals, ((0, 0), (0, fill)), constant_values=-jnp.inf)
            all_idx = jnp.pad(all_idx, ((0, 0), (0, fill)), constant_values=-1)
        out_v, pos = jax.lax.top_k(all_vals, k)
        out_i = jnp.take_along_axis(all_idx, pos, axis=1)
        # Slots that never saw a real item (k > catalog) carry -inf; report
        # index -1 rather than a padded/masked item id.
        out_i = jnp.where(jnp.isneginf(out_v), -1, out_i)
        return out_v, out_i

    # After the candidate all_gather every item shard computes the same merged
    # top-k, so the outputs are replicated over `item_axis`; the varying-axes
    # checker can't infer that, hence check_vma=False.
    if with_exclude:
        fn = shard_map(
            lambda uf, vf, n, ex: local(uf, vf, n, ex),
            mesh=mesh,
            in_specs=(u_spec, P(item_axis, None), P(), u_spec),
            out_specs=(u_spec, u_spec),
            check_vma=False,
        )
    else:
        fn = shard_map(
            lambda uf, vf, n: local(uf, vf, n, None),
            mesh=mesh,
            in_specs=(u_spec, P(item_axis, None), P()),
            out_specs=(u_spec, u_spec),
            check_vma=False,
        )
    # The jitted callable is acquired exclusively through the persistent AOT
    # layer (``sharded_topk_scores`` below — the retrieval bank's sharded
    # query path), so per-shape executables survive process boundaries with
    # the same fingerprint-verified reuse every other serving program gets.
    return jax.jit(fn)


def _padded_device(arr, multiple: int, fill=0):
    """``arr`` padded on axis 0 to a device-count multiple, as a device
    array. An ALREADY-ALIGNED array skips the host round trip entirely —
    that is what lets callers (the retrieval bank's mesh path) pre-pad and
    pin their tables once at build and pass the resident array per query
    instead of paying a full host->device copy of the table per batch."""
    import numpy as np

    from albedo_tpu.parallel.mesh import pad_rows_to

    if arr.shape[0] % multiple == 0:
        return jnp.asarray(arr)  # no-op for device arrays, upload for host
    return jnp.asarray(pad_rows_to(np.asarray(arr), multiple, fill=fill))


def sharded_topk_scores(
    user_factors: jax.Array,
    item_factors: jax.Array,
    k: int,
    mesh: Mesh,
    exclude_idx: jax.Array | None = None,
    n_items: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Sharded MIPS top-k through the persistent AOT layer.

    Pads the item table to the item-axis size and the user rows to the
    data-axis size, strips the user padding from the result. ``n_items``
    declares how many leading item rows are REAL when the caller passes a
    pre-padded (device-resident) table — pad rows must be zero and are
    masked out of the top-k. Executables are keyed by (padded shapes, k,
    mesh, backend) and cached through ``utils.aot`` — memory LRU, disk
    export where serializable, fingerprint verification — so a serving
    process re-dispatches without re-tracing.
    """
    from albedo_tpu.utils.aot import persistent_aot_call

    n_items = item_factors.shape[0] if n_items is None else int(n_items)
    n_users = user_factors.shape[0]
    d_item = mesh.shape[ITEM_AXIS]
    d_data = mesh.shape[DATA_AXIS]
    vf = _padded_device(item_factors, d_item)
    uf = _padded_device(user_factors, d_data)
    dev = mesh.devices.flat[0]
    if exclude_idx is not None:
        ex = _padded_device(exclude_idx, d_data, fill=-1)
        fn = make_sharded_topk(mesh, k, with_exclude=True)
        args = (uf, vf, jnp.int32(n_items), ex)
        ex_shape = tuple(ex.shape)
    else:
        fn = make_sharded_topk(mesh, k)
        args = (uf, vf, jnp.int32(n_items))
        ex_shape = ()
    key_parts = (
        "sharded_topk", k, tuple(uf.shape), tuple(vf.shape), ex_shape,
        str(uf.dtype), getattr(dev, "device_kind", "?"), repr(mesh),
        jax.default_backend(),
    )
    (vals, idx), _, _ = persistent_aot_call(
        fn, args, None, None, key_parts, name="sharded_topk"
    )
    return vals[:n_users], idx[:n_users]
