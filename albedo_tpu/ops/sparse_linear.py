"""Block-sparse linear model kernels over a ``FeatureMatrix``.

The reference's ranker trains Spark MLlib ``LogisticRegression`` on a giant
sparse vector assembled from one-hots over every categorical (including
``user_id``/``repo_id``) plus count-vectors and word2vec blocks
(``LogisticRegressionRanker.scala:176-235``). The TPU-native layout keeps the
blocks separate (``features/assembler.py``): the linear form

``logit = b + dense @ w_dense + sum_f W_cat[f][idx_f] + sum_f <bag_val, W_bag[f][bag_idx]>``

is mathematically the one-hot dot product, computed as weight-row gathers and
masked reductions — fixed shapes, no million-wide vectors.

Standardization (Spark ``setStandardization(true)``): features are implicitly
scaled by ``1/std`` (no centering, preserving sparsity, as MLlib). Training
optimizes the coefficients of the SCALED features with the L2 penalty applied
to them (MLlib's convention), which is what makes regParam=0.7 reproduce the
reference's AUC; ``fold_scales`` converts back to raw-space coefficients.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from albedo_tpu.features.assembler import FeatureMatrix

Params = dict[str, Any]


def feature_batch(fm: FeatureMatrix) -> dict[str, jnp.ndarray]:
    """Upload a FeatureMatrix's arrays as a flat dict of device arrays.

    Bag fields are laid out as DUAL-SORTED flat arrays rather than the padded
    ``(N, L)`` arrays the host keeps: the padded-gather formulation costs a
    random-order 49M-element gather forward and a random scatter-add backward
    on TPU — measured ~95% of the LR fit (1.62 s vs 0.20 s per value_and_grad
    at bench scale). The flat layout carries a row-sorted copy (+ row indptr)
    for the forward and a vocab-sorted copy (+ vocab indptr) for the weight
    gradient, so BOTH directions reduce by the cumsum-difference trick over
    only the real entries (``_bag_term``) — no scatter at all.

    Vector (embedding) fields upload FACTORED: the (U, D) distinct vectors,
    the (N,) rep gather, and a rep-sorted order + indptr so the backward of
    the per-row gather is a cumsum-difference segment sum (``_rep_term``),
    not a TPU scatter-add. The mesh path
    (``parallel.lr.shard_feature_batch``) keeps the padded/expanded layout —
    a row-shardable rectangle — and ``block_logits`` consumes either.
    """
    batch: dict[str, jnp.ndarray] = {"dense": jnp.asarray(fm.dense)}
    for f in fm.vec_fields():  # canonical sorted order (see vec_fields)
        rep, order, indptr = _rep_layout(fm.vec_rep[f], fm.vec[f].shape[0])
        batch[f"vecflat:{f}:vec"] = jnp.asarray(fm.vec[f])
        batch[f"vecflat:{f}:rep"] = jnp.asarray(rep)
        batch[f"vecflat:{f}:order"] = jnp.asarray(order)
        batch[f"vecflat:{f}:indptr"] = jnp.asarray(indptr)
    for f, v in fm.cat.items():
        batch[f"cat:{f}"] = jnp.asarray(v)
    flat = fm.flat_bags()
    for f in fm.bag_idx:
        rows, vocab, vals = flat[f]
        # Flats are over the STORED rows — the ~50-80x smaller distinct-
        # document set for factored fields (fm.bag_rep), whose per-distinct
        # sums expand to data rows through the same _rep_term machinery as
        # the vec fields (the two custom VJPs compose under autodiff).
        n = fm.bag_idx[f].shape[0]
        order = np.argsort(vocab, kind="stable")
        # Vocab indptr spans the FULL weight table, so the backward
        # cumsum-difference yields a gradient shaped exactly like the table.
        v_size = fm.bag_sizes[f]
        r_indptr = np.zeros(n + 1, np.int32)
        np.cumsum(np.bincount(rows, minlength=n), out=r_indptr[1:])
        v_indptr = np.zeros(v_size + 1, np.int32)
        np.cumsum(np.bincount(vocab, minlength=v_size), out=v_indptr[1:])
        batch[f"bagflat:{f}:r_vocab"] = jnp.asarray(vocab)              # row-sorted
        batch[f"bagflat:{f}:r_val"] = jnp.asarray(vals)
        batch[f"bagflat:{f}:r_indptr"] = jnp.asarray(r_indptr)
        batch[f"bagflat:{f}:v_rows"] = jnp.asarray(rows[order].astype(np.int32))
        batch[f"bagflat:{f}:v_val"] = jnp.asarray(vals[order])          # vocab-sorted
        batch[f"bagflat:{f}:v_indptr"] = jnp.asarray(v_indptr)
        bag_rep = fm.bag_rep.get(f)
        if bag_rep is not None:
            rep, rorder, rindptr = _rep_layout(bag_rep, n)
            batch[f"bagrep:{f}:rep"] = jnp.asarray(rep)
            batch[f"bagrep:{f}:order"] = jnp.asarray(rorder)
            batch[f"bagrep:{f}:indptr"] = jnp.asarray(rindptr)
    return batch


def expanded_batch(fm: FeatureMatrix, n_rows: int) -> dict[str, np.ndarray]:
    """The RECTANGULAR batch layout, padded to ``n_rows`` rows (host arrays).

    Every array is row-aligned — the dense block with vec fields expanded,
    one index per cat field, ``(n_rows, pad_f)`` per bag field — so its
    shapes depend on the row count ALONE (the assembler fixes each bag
    field's pad width). Two users of that property: the mesh fit
    (``parallel.lr.shard_feature_batch``: a rectangle shards evenly by rows)
    and request-sized inference (``LogisticRegressionModel.
    decision_function``: one executable per row bucket, instead of one per
    request — the flat layout's array sizes are the batch's entry and
    distinct-document counts, which differ for every request). Padding rows
    are all-masked bags, index-0 cats and zero dense rows; callers drop
    their logits (inference) or weight them 0 (training)."""
    if n_rows < fm.n_rows:
        raise ValueError(f"cannot pad {fm.n_rows} rows down to {n_rows}")

    def rows(x: np.ndarray, fill=0) -> np.ndarray:
        x = np.asarray(x)
        pad = np.full((n_rows - x.shape[0], *x.shape[1:]), fill, dtype=x.dtype)
        return np.concatenate([x, pad], axis=0) if pad.shape[0] else x

    batch = {"dense": rows(fm.expanded_dense().astype(np.float32))}
    for f, v in fm.cat.items():
        batch[f"cat:{f}"] = rows(v)
    for f in fm.bag_idx:
        idx, val = fm.expanded_bag(f)  # per-row view of factored fields
        batch[f"bag_idx:{f}"] = rows(idx, fill=-1)
        batch[f"bag_val:{f}"] = rows(val)
    return batch


def _rep_layout(rep: np.ndarray, n_distinct: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The ``_rep_term`` input layout for a (N,) rep vector: ``(rep int32,
    rep-sorted row order, (n_distinct+1,) segment indptr)`` — shared by the
    vec and factored-bag feeders so the two gather VJP layouts cannot drift."""
    rep = np.asarray(rep).astype(np.int32)
    order = np.argsort(rep, kind="stable").astype(np.int32)
    indptr = np.zeros(n_distinct + 1, np.int32)
    np.cumsum(np.bincount(rep, minlength=n_distinct), out=indptr[1:])
    return rep, order, indptr


def init_params(fm: FeatureMatrix) -> Params:
    # Host-side zeros: they ride to the device as jit-call arguments instead
    # of one eager jnp.zeros dispatch per field.
    p: Params = {
        "bias": np.float32(0.0),
        # One flat coefficient vector for the LOGICAL dense block
        # [scalars | vec fields] — the factored storage changes the batch
        # layout only, never the parameter/scales/coefficients structure.
        "dense": np.zeros((fm.dense_width,), np.float32),
    }
    for f, size in fm.cat_sizes.items():
        p[f"cat:{f}"] = np.zeros((size,), np.float32)
    for f, size in fm.bag_sizes.items():
        p[f"bag:{f}"] = np.zeros((size,), np.float32)
    return p


def inverse_std_scales(fm: FeatureMatrix) -> Params:
    """Per-feature ``1/std`` in the same structure as the params (host side).

    One-hot/bag columns get the std of their expanded 0/1(or count) column;
    constant features get scale 0 so their (useless) coefficient is frozen at
    zero effect, mirroring MLlib's handling of zero-variance features.
    """
    n = max(1, fm.n_rows)
    # MLlib's MultivariateOnlineSummarizer standardizes by the UNBIASED sample
    # std (n-1 denominator); population→sample correction factor n/(n-1).
    bessel = n / (n - 1) if n > 1 else 1.0

    def inv(std: np.ndarray) -> np.ndarray:
        return np.where(std > 0, 1.0 / np.maximum(std, 1e-12), 0.0).astype(np.float32)

    scales: Params = {"bias": np.float32(1.0)}
    ddof = 1 if n > 1 else 0
    # Scalar block: f64 ACCUMULATION without materializing an f64 copy (the
    # astype copied 1.3 GB at r5 ranker bench scale).
    std_parts = [fm.dense.std(axis=0, dtype=np.float64, ddof=ddof)]
    for f in fm.vec_fields():  # canonical order must match block_logits offsets
        # Factored vec field: moments of the EXPANDED column are count-
        # weighted moments over the distinct vectors — O(U*D), not O(N*D).
        v = fm.vec[f].astype(np.float64)
        counts = np.bincount(fm.vec_rep[f], minlength=v.shape[0]).astype(np.float64)
        mean = counts @ v / n
        var = counts @ (v**2) / n - mean**2
        if ddof:
            var = var * (n / (n - 1))
        std_parts.append(np.sqrt(np.maximum(var, 0)))
    scales["dense"] = inv(np.concatenate(std_parts) if len(std_parts) > 1 else std_parts[0])
    for f, size in fm.cat_sizes.items():
        p = np.bincount(fm.cat[f], minlength=size) / n
        scales[f"cat:{f}"] = inv(np.sqrt(p * (1 - p) * bessel))
    flat = fm.flat_bags()
    for f, size in fm.bag_sizes.items():
        rows, cols, vals64 = flat[f]
        cols = cols.astype(np.int64)
        vals = vals64.astype(np.float64)
        # Factored fields store one row per DISTINCT document; the expanded
        # moments weight each distinct row by its multiplicity.
        rep = fm.bag_rep.get(f)
        if rep is None:
            mult = None
        else:
            mult = np.bincount(rep, minlength=fm.bag_idx[f].shape[0]).astype(np.float64)
        # The expanded column value is the SUM of a row's entries for that
        # index, so moments must be over per-(row, col) sums. Entries are
        # row-major; when indices are sorted-unique within each row (what
        # CountVectorizer emits) the O(n) adjacency check proves there is
        # nothing to aggregate and the key-sort pass is skipped entirely.
        same_row = rows[1:] == rows[:-1]
        within_sorted = not np.any(same_row & (cols[1:] < cols[:-1]))
        has_dup = within_sorted and bool(np.any(same_row & (cols[1:] == cols[:-1])))
        if within_sorted and not has_dup:
            w1 = vals if mult is None else vals * mult[rows]
            w2 = vals**2 if mult is None else vals**2 * mult[rows]
            s1 = np.bincount(cols, weights=w1, minlength=size)
            s2 = np.bincount(cols, weights=w2, minlength=size)
        else:
            key = rows.astype(np.int64) * size + cols
            order = np.argsort(key, kind="stable")
            key_s, vals_s = key[order], vals[order]
            uniq, start = np.unique(key_s, return_index=True)
            agg = np.add.reduceat(vals_s, start) if start.size else np.zeros(0)
            col_of = uniq % size
            m_of = 1.0 if mult is None else mult[uniq // size]
            s1 = np.bincount(col_of, weights=agg * m_of, minlength=size)
            s2 = np.bincount(col_of, weights=agg**2 * m_of, minlength=size)
        mean = s1 / n
        var = (s2 / n - mean**2) * bessel
        scales[f"bag:{f}"] = inv(np.sqrt(np.maximum(var, 0)))
    return scales


def dense_center(fm: FeatureMatrix) -> np.ndarray:
    """Per-column means of the dense block (host side).

    MLlib standardizes WITHOUT centering to preserve sparsity; that is fine in
    its float64 aggregator, but in float32 a near-constant large-magnitude
    column (e.g. document-embedding dims on homogeneous text) standardizes to
    a huge constant offset that destroys the optimizer's conditioning. The
    dense block is already dense, so centering it is free; the objective is
    unchanged (the bias absorbs the shift) and the L2 penalty still applies to
    the same standardized coefficients.
    """
    n = max(1, fm.n_rows)
    parts = [fm.dense.mean(axis=0, dtype=np.float64)]
    for f in fm.vec_fields():  # canonical order must match block_logits offsets
        counts = np.bincount(fm.vec_rep[f], minlength=fm.vec[f].shape[0])
        parts.append(counts.astype(np.float64) @ fm.vec[f].astype(np.float64) / n)
    out = np.concatenate(parts) if len(parts) > 1 else parts[0]
    return out.astype(np.float32)


def _segment_sums(data: jnp.ndarray, indptr: jnp.ndarray) -> jnp.ndarray:
    """Sorted-segment sums via the cumsum-difference trick: an exclusive
    cumsum gathered at segment boundaries. No scatter — TPU scatters and
    large random gathers both measured ~100x slower than this streaming
    formulation for the bag blocks.

    Precision (ADVICE r4 #3): a float32 cumsum costs ~eps * |running prefix|
    per segment. Since r5 the streams are SHORT — factored bags collapse the
    flat entries to the distinct-document set (~270k vs 17M at ranker bench
    scale) and the _rep_term backward runs over one grad value per data row
    (~382k, entries ~1/N each, prefix O(1)) — so the absolute error stays
    ~1e-6..1e-5, far inside LR tolerance. Guarded by a bench-scale f64-parity
    test (tests/test_models.py::test_segment_sums_precision_at_scale) rather
    than an f64 cumsum, which would need global jax_enable_x64."""
    c = jnp.concatenate([jnp.zeros(1, data.dtype), jnp.cumsum(data)])
    return c[indptr[1:]] - c[indptr[:-1]]


def _bag_term(
    w: jnp.ndarray,           # (V,) effective bag weights (params * scales)
    r_vocab: jnp.ndarray, r_val: jnp.ndarray, r_indptr: jnp.ndarray,
    v_rows: jnp.ndarray, v_val: jnp.ndarray, v_indptr: jnp.ndarray,
) -> jnp.ndarray:
    """Per-row bag logit contribution with a cumsum-difference VJP.

    Forward: per-row sums of ``w[r_vocab] * r_val`` over the row-sorted flat
    entries. Backward wrt ``w``: the SAME reduction over the vocab-sorted
    copy. Plain autodiff of the padded form emits a random scatter-add (and
    its forward a 49M-element random gather) — measured 8x slower end-to-end
    at bench scale on TPU."""

    @jax.custom_vjp
    def term(w):
        return _segment_sums(w[r_vocab] * r_val, r_indptr)

    def fwd(w):
        return term(w), None

    def bwd(_, g):
        # v_indptr spans the full weight table, so this is (V,) exactly.
        return (_segment_sums(g[v_rows] * v_val, v_indptr),)

    term.defvjp(fwd, bwd)
    return term(w)


def _rep_term(
    lu: jnp.ndarray,          # (U,) per-distinct-vector logit contributions
    rep: jnp.ndarray,         # (N,) representative index per row
    order: jnp.ndarray,       # (N,) row indices sorted by rep
    indptr: jnp.ndarray,      # (U+1,) rep segment boundaries in `order`
) -> jnp.ndarray:
    """Expand per-distinct values to rows with a segment-sum VJP.

    Forward: the (N,) gather ``lu[rep]``. Backward wrt ``lu``: plain autodiff
    would emit a scatter-add over N rows into U slots (TPU scatters measured
    ~100x slower than streaming); the rep-sorted order + indptr reduce it to
    the same cumsum-difference trick as the bag fields."""

    @jax.custom_vjp
    def term(lu):
        return lu[rep]

    def fwd(lu):
        return term(lu), None

    def bwd(_, g):
        return (_segment_sums(g[order], indptr),)

    term.defvjp(fwd, bwd)
    return term(lu)


def block_logits(
    params: Params,
    scales: Params,
    batch: dict[str, jnp.ndarray],
    center: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """(N,) logits; ``params`` are standardized-space coefficients and
    ``scales`` the per-feature 1/std factors (use all-ones for raw space).
    ``center`` (optional) is subtracted from the dense block before scaling.

    The logical dense block is [scalars | vec fields]; ``params["dense"]``
    and ``scales["dense"]`` span the full width. When the batch carries
    factored ``vecflat:`` fields (``feature_batch``), each field's term is
    computed per DISTINCT vector — O(U*D) instead of O(N*D) — then expanded
    by a gather; the expanded layout (``shard_feature_batch``) computes the
    same affine form directly. Bag fields likewise arrive flat-dual-sorted
    (fast VJP) or padded (row-shardable)."""
    w_dense = params["dense"] * scales["dense"]
    d_scalar = batch["dense"].shape[1]
    dense = batch["dense"] if center is None else batch["dense"] - center[:d_scalar]
    logits = params["bias"] + dense @ w_dense[:d_scalar]
    off = d_scalar
    # EXPLICIT sorted field order: scales/center/dense_names are laid out in
    # sorted(vec) order (FeatureMatrix.vec_fields) and jax reconstructs dict
    # pytrees sorted-by-key inside jit anyway — an insertion-order iteration
    # here would silently pair one field's values with another's moments and
    # coefficient slice whenever vector_cols aren't alphabetical.
    vec_fields = sorted(
        key[len("vecflat:"):-len(":vec")]
        for key in batch
        if key.startswith("vecflat:") and key.endswith(":vec")
    )
    for f in vec_fields:
        arr = batch[f"vecflat:{f}:vec"]
        d = arr.shape[1]
        w_f = w_dense[off:off + d]
        # Center BEFORE the contraction: ``vec @ w - c @ w`` cancels two
        # large near-equal dots per distinct vector (w2v dims are
        # near-constant — the exact conditioning problem dense_center
        # exists for; computing it the cancelling way sent the r5 bench
        # fit from 31 to 163 L-BFGS iterations).
        vals = arr if center is None else arr - center[off:off + d]
        lu = vals @ w_f
        p = f"vecflat:{f}:"
        logits = logits + _rep_term(
            lu, batch[p + "rep"], batch[p + "order"], batch[p + "indptr"]
        )
        off += d
    for key, arr in batch.items():
        if key.startswith("cat:"):
            f = key[len("cat:"):]
            w = params[f"cat:{f}"] * scales[f"cat:{f}"]
            logits = logits + w[arr]
        elif key.startswith("bagflat:") and key.endswith(":r_vocab"):
            f = key[len("bagflat:"):-len(":r_vocab")]
            w = params[f"bag:{f}"] * scales[f"bag:{f}"]
            p = f"bagflat:{f}:"
            term = _bag_term(
                w,
                batch[p + "r_vocab"], batch[p + "r_val"], batch[p + "r_indptr"],
                batch[p + "v_rows"], batch[p + "v_val"], batch[p + "v_indptr"],
            )
            rp = f"bagrep:{f}:"
            if rp + "rep" in batch:
                # Factored field: `term` is per DISTINCT document; expand to
                # data rows (the two custom VJPs compose under autodiff).
                term = _rep_term(
                    term, batch[rp + "rep"], batch[rp + "order"], batch[rp + "indptr"]
                )
            logits = logits + term
        elif key.startswith("bag_idx:"):
            f = key[len("bag_idx:"):]
            w = params[f"bag:{f}"] * scales[f"bag:{f}"]
            idx = arr
            val = batch[f"bag_val:{f}"]
            safe = jnp.where(idx < 0, 0, idx)
            contrib = jnp.where(idx < 0, 0.0, w[safe] * val)
            logits = logits + contrib.sum(axis=1)
    return logits


def weighted_logloss(
    params: Params,
    scales: Params,
    batch: dict[str, jnp.ndarray],
    labels: jnp.ndarray,
    weights: jnp.ndarray,
    reg: float,
    center: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """MLlib objective: (sum_i w_i * ce_i) / sum_i w_i + 0.5 * reg * ||beta_std||^2
    (bias unpenalized)."""
    logits = block_logits(params, scales, batch, center=center)
    # Pre-clip to a finite range: if a line-search trial overshoots params so
    # far the logits overflow to inf, the straight-through correction below
    # would be inf - inf = nan. 1e6 is exactly representable in float32, so
    # clipped + (35 - clipped) still evaluates to exactly 35.
    logits = jnp.clip(logits, -1e6, 1e6)
    # Straight-through clip: cap the CE value so an L-BFGS line-search
    # overshoot can't produce inf - inf = nan, while keeping the gradient of
    # out-of-range (badly misclassified) samples alive.
    logits = logits + jax.lax.stop_gradient(jnp.clip(logits, -35.0, 35.0) - logits)
    ce = jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    data = jnp.sum(weights * ce) / jnp.sum(weights)
    pen = sum(
        jnp.sum(v**2) for k, v in params.items() if k != "bias"
    )
    return data + 0.5 * reg * pen


def fold_scales(params: Params, scales: Params) -> Params:
    """Convert standardized-space coefficients to raw-space (beta = beta_std / std)."""
    return jax.tree.map(lambda p, s: p * s, params, scales)
