"""Word2Vec: skip-gram embeddings trained on device.

Reference parity: ``Word2VecCorpusBuilder.scala:74-83`` — Spark MLlib
``Word2Vec`` with vectorSize=200, windowSize=5, minCount=10, maxIter=30 over
the user+repo text corpus, and ``Word2VecModel.transform`` averaging word
vectors per document as the text-column featurizer
(``LogisticRegressionRanker.scala:210-215``).

TPU-first design: MLlib trains hierarchical-softmax skip-gram with per-worker
Hogwild updates and averages the tables; here it's skip-gram with NEGATIVE
SAMPLING — a fixed-shape batched objective (gathers + one (B, k+1) logits
einsum) that XLA fuses onto the MXU, instead of data-dependent Huffman-tree
walks that would defeat jit. Pairs are built once on host; the training loop
is a ``lax.scan`` over minibatches with negatives drawn per step on device.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pandas as pd

from albedo_tpu.datasets.ragged import segment_positions
from albedo_tpu.features.pipeline import Transformer, memo_map
from albedo_tpu.parallel.mesh import DATA_AXIS, replicated
from albedo_tpu.utils.aot import persistent_aot_executable

# The Adam state rides the ``w2v_epoch`` program's signature, and optax's
# state NamedTuples are types ``jax.export`` cannot name on its own: without
# these registrations the export compiles but fails to serialize, and the
# program never reaches the AOT disk layer the ALS and LR programs use.
for _state in (optax.ScaleByAdamState, optax.EmptyState):
    jax.export.register_namedtuple_serialization(
        _state, serialized_name=f"optax.{_state.__name__}"
    )


def skipgram_pairs(
    ids: np.ndarray, lengths: np.ndarray, b: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized skip-gram (center, context) pair construction.

    ``ids``: all sentences' token ids concatenated, shape (T,).
    ``lengths``: tokens per sentence, sum = T.
    ``b``: per-position dynamic window radius (word2vec's b ~ uniform[1, w]).

    Emits exactly the pairs the textbook per-position loop emits — for every
    position i, every j in [i-b_i, i+b_i] within the same sentence, j != i —
    but as 2·max(b) masked passes over the flat corpus instead of a Python
    triple loop (the round-1 hot spot flagged in VERDICT.md). Pair order is
    offset-major rather than position-major; training shuffles every epoch so
    only the multiset matters (pinned by the parity test vs the naive loop).
    """
    ids = np.asarray(ids, dtype=np.int32)
    lengths = np.asarray(lengths, dtype=np.int64)
    b = np.asarray(b)
    if ids.size == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.int32)
    pos = segment_positions(lengths)
    slen = np.repeat(lengths, lengths)
    max_b = int(b.max()) if b.size else 0
    centers_parts, contexts_parts = [], []
    for d in range(-max_b, max_b + 1):
        if d == 0:
            continue
        mask = (abs(d) <= b) & (pos + d >= 0) & (pos + d < slen)
        idx = np.nonzero(mask)[0]
        centers_parts.append(ids[idx])
        contexts_parts.append(ids[idx + d])
    return (
        np.concatenate(centers_parts) if centers_parts else np.zeros(0, np.int32),
        np.concatenate(contexts_parts) if contexts_parts else np.zeros(0, np.int32),
    )


@dataclasses.dataclass
class Word2VecModel(Transformer):
    """Fitted embeddings + the document-averaging transformer."""

    vocab: list[str]
    vectors: np.ndarray  # (V, dim) float32
    input_col: str = "words"
    output_col: str = "words__w2v"

    def __post_init__(self):
        self._index = {w: i for i, w in enumerate(self.vocab)}

    @property
    def dim(self) -> int:
        return int(self.vectors.shape[1])

    def vector(self, word: str) -> np.ndarray | None:
        i = self._index.get(word)
        return None if i is None else self.vectors[i]

    def document_vector(self, words: list[str]) -> np.ndarray:
        """Mean of in-vocab word vectors (zero vector if none)."""
        idx = [self._index[w] for w in words if w in self._index]
        if not idx:
            return np.zeros(self.dim, dtype=np.float32)
        return self.vectors[idx].mean(axis=0)

    def transform(self, df: pd.DataFrame) -> pd.DataFrame:
        self.require_cols(df, [self.input_col])
        out = df.copy()
        out[self.output_col] = memo_map(
            df[self.input_col], self.document_vector, key=tuple
        )
        return out

    def find_synonyms(self, word: str, k: int = 10) -> list[tuple[str, float]]:
        """Cosine-similarity nearest words (Spark ``findSynonyms`` parity)."""
        v = self.vector(word)
        if v is None:
            return []
        norms = np.linalg.norm(self.vectors, axis=1) + 1e-9
        sims = self.vectors @ v / (norms * (np.linalg.norm(v) + 1e-9))
        order = np.argsort(-sims)
        return [
            (self.vocab[i], float(sims[i])) for i in order if self.vocab[i] != word
        ][:k]

    def to_arrays(self) -> dict[str, np.ndarray]:
        return {
            "vectors": self.vectors,
            "vocab": np.asarray(self.vocab, dtype=object),
        }


@dataclasses.dataclass
class Word2Vec:
    """Skip-gram negative-sampling estimator.

    Defaults mirror the reference corpus builder
    (``Word2VecCorpusBuilder.scala:74-83``): dim=200, window=5, min_count=10,
    max_iter=30 (epochs over the pair set).
    """

    dim: int = 200
    window: int = 5
    min_count: int = 10
    max_iter: int = 30
    negatives: int = 5
    # 0 = per-pair negatives (textbook SGNS; the parity-tested default).
    # K > 0 = ONE shared pool of K noise words per step: the negative term
    # becomes a (B, d) x (d, K) MXU GEMM instead of a (B, neg, d) gather —
    # the gather streamed ~315 MB/step at bs=65536 and dominated the fit —
    # with the negative loss scaled by negatives/K so the expected gradient
    # magnitude matches the per-pair objective. Standard large-batch
    # word2vec practice; quality is test-gated like the default path.
    shared_negatives: int = 0
    batch_size: int = 4096
    learning_rate: float = 0.025
    subsample: float = 1e-3  # frequent-word subsampling threshold (0 = off)
    seed: int = 42
    input_col: str = "words"
    output_col: str | None = None
    # Optional jax.sharding.Mesh: shard the pair batch over the mesh's "data"
    # axis with replicated embedding tables — the same layout as parallel.lr.
    # XLA inserts the ICI psums for the replicated-table gradients, replacing
    # MLlib Word2Vec's per-worker Hogwild tables + driver-side averaging
    # (Word2VecCorpusBuilder.scala:74-83 runs it as a 39-minute cluster job).
    mesh: Any | None = None

    def fit_corpus(self, sentences: list[list[str]]) -> Word2VecModel:
        rng = np.random.default_rng(self.seed)
        # Hash-factorize the flat corpus once (C speed) instead of a Python
        # Counter + per-word dict lookups; vocab order stays (-count, word).
        flat = [w for s in sentences for w in s]
        lengths = np.fromiter((len(s) for s in sentences), dtype=np.int64, count=len(sentences))
        if flat:
            codes, uniques = pd.factorize(np.asarray(flat, dtype=object), sort=False)
            uniq_counts = np.bincount(codes, minlength=len(uniques))
        else:
            codes = np.zeros(0, np.int64)
            uniques, uniq_counts = np.asarray([], dtype=object), np.zeros(0, np.int64)
        keep = uniq_counts >= self.min_count
        # (-count, word) order over the UNIQUE words only — O(V log V), not
        # corpus-sized like the old per-word Counter/dict path.
        order = np.asarray(
            sorted(np.nonzero(keep)[0], key=lambda i: (-uniq_counts[i], uniques[i])),
            dtype=np.int64,
        )
        vocab = [str(w) for w in uniques[order]]
        v_size = len(vocab)
        if v_size == 0:
            return Word2VecModel([], np.zeros((0, self.dim), np.float32), self.input_col, self.output_col or f"{self.input_col}__w2v")

        # uniq code -> vocab id (or -1 for below-min_count words).
        code_to_vocab = np.full(len(uniques), -1, dtype=np.int64)
        code_to_vocab[order] = np.arange(v_size)
        token_ids = code_to_vocab[codes]

        freq = uniq_counts[order].astype(np.float64)
        total = freq.sum()

        # Frequent-word subsampling (word2vec's t-threshold keep probability).
        if self.subsample > 0:
            f = freq / total
            keep_p = np.minimum(1.0, np.sqrt(self.subsample / f) + self.subsample / f)
        else:
            keep_p = np.ones(v_size)

        sent_id = np.repeat(np.arange(len(sentences), dtype=np.int64), lengths)
        mask = token_ids >= 0
        if self.subsample > 0:
            mask &= rng.random(token_ids.size) < keep_p[np.maximum(token_ids, 0)]
        ids_concat = token_ids[mask].astype(np.int32)
        kept_lengths = np.bincount(sent_id[mask], minlength=len(sentences))

        # Dynamic window shrink, as word2vec: b ~ uniform[1, window] per pos.
        b = rng.integers(1, self.window + 1, size=ids_concat.size)
        centers, contexts = skipgram_pairs(ids_concat, kept_lengths, b)
        if centers.size == 0:
            return Word2VecModel(vocab, np.zeros((v_size, self.dim), np.float32), self.input_col, self.output_col or f"{self.input_col}__w2v")

        # Negative-sampling distribution: unigram^0.75 (word2vec standard),
        # sampled by inverse CDF (searchsorted over the cumulative table,
        # O(B*neg*log V)). jax.random.categorical would materialize a
        # (B, neg, V) gumbel tensor per step — ~20 GB/step at refscale
        # (bs=65536, V=15k), the r5 scale-up OOM.
        p_noise = freq**0.75
        p_noise /= p_noise.sum()
        noise_cdf = jnp.asarray(np.cumsum(p_noise), dtype=jnp.float32)

        n_pairs = centers.shape[0]
        # bs is NOT rounded for the mesh: the sharded fit must run the exact
        # same minibatch boundaries as the single-device fit (parity contract).
        bs = min(self.batch_size, n_pairs)
        steps_per_epoch = n_pairs // bs

        key = jax.random.PRNGKey(self.seed)
        k_in, k_shuf = jax.random.split(key)
        scale = 0.5 / self.dim
        params = {
            "in": jax.random.uniform(k_in, (v_size, self.dim), jnp.float32, -scale, scale),
            "out": jnp.zeros((v_size, self.dim), jnp.float32),
        }
        opt = optax.adam(self.learning_rate)
        opt_state = opt.init(params)

        neg = self.negatives
        shared = self.shared_negatives

        def loss_fn(p, c_idx, o_idx, neg_idx):
            vc = p["in"][c_idx]
            if shared:
                # neg_idx: (K,) shared pool. Positive term per pair; negative
                # term = dense (B, K) logits GEMM, scaled to the per-pair
                # objective's expected magnitude.
                vo_pos = p["out"][o_idx]
                pos_logit = jnp.sum(vc * vo_pos, axis=1)
                vneg = p["out"][neg_idx]
                neg_logits = vc @ vneg.T
                pos_loss = optax.sigmoid_binary_cross_entropy(
                    pos_logit, jnp.ones_like(pos_logit)
                )
                neg_loss = optax.sigmoid_binary_cross_entropy(
                    neg_logits, jnp.zeros_like(neg_logits)
                ).sum(axis=1) * (neg / shared)
                return (pos_loss + neg_loss).mean()
            # (B, d) center vectors; (B, 1+neg, d) context rows (true + noise).
            rows = jnp.concatenate([o_idx[:, None], neg_idx], axis=1)
            vo = p["out"][rows]
            logits = jnp.einsum("bd,bkd->bk", vc, vo)
            labels = jnp.zeros_like(logits).at[:, 0].set(1.0)
            return optax.sigmoid_binary_cross_entropy(logits, labels).sum(axis=1).mean()

        # W2V only ever shards the pair batch over "data". A 2-D (data, item)
        # mesh must be FLATTENED to a 1-D data-only mesh here: with an unused
        # `item` axis in scope, GSPMD is free to re-partition the table-grad
        # reductions across it, which injects ~1e-6/step f32 reduction-order
        # noise that Adam amplifies chaotically into O(1) embedding divergence
        # within an epoch (root-caused from the dryrun_multichip sharded-vs-
        # single assert; the flat mesh is bit-stable at ~3e-7 vs single
        # device). Flattening also puts every device on the data axis — more
        # parallel, not less.
        mesh = self.mesh
        if mesh is not None and any(
            n > 1 for ax, n in mesh.shape.items() if ax != DATA_AXIS
        ):
            from jax.sharding import Mesh

            mesh = Mesh(np.asarray(mesh.devices).reshape(-1), (DATA_AXIS,))
        # Shard the minibatch dim only when it divides evenly; otherwise leave
        # layout to XLA (still correct, just less parallel) rather than change
        # bs and silently diverge from the single-device math.
        if mesh is not None and bs % int(mesh.shape[DATA_AXIS]) == 0:
            from jax.sharding import NamedSharding, PartitionSpec

            batch_sharding = NamedSharding(mesh, PartitionSpec(None, DATA_AXIS))
        else:
            batch_sharding = None

        def epoch(params, opt_state, key, centers_d, contexts_d, noise_cdf):
            key, k_perm = jax.random.split(key)
            perm = jax.random.permutation(k_perm, centers_d.shape[0])
            c_sh = centers_d[perm][: steps_per_epoch * bs].reshape(steps_per_epoch, bs)
            o_sh = contexts_d[perm][: steps_per_epoch * bs].reshape(steps_per_epoch, bs)
            if batch_sharding is not None:
                # Minibatch dim sharded over "data": the gathers and the
                # (B, 1+neg, d) logits einsum run data-parallel; the gradient
                # of the replicated tables psums over ICI.
                c_sh = jax.lax.with_sharding_constraint(c_sh, batch_sharding)
                o_sh = jax.lax.with_sharding_constraint(o_sh, batch_sharding)

            def step(carry, batch):
                p, s, k = carry
                c_idx, o_idx = batch
                k, k_neg = jax.random.split(k)
                neg_shape = (shared,) if shared else (bs, neg)
                u = jax.random.uniform(k_neg, neg_shape, jnp.float32)
                neg_idx = jnp.searchsorted(noise_cdf, u).astype(jnp.int32)
                neg_idx = jnp.minimum(neg_idx, noise_cdf.shape[0] - 1)
                loss, grads = jax.value_and_grad(loss_fn)(p, c_idx, o_idx, neg_idx)
                updates, s = opt.update(grads, s, p)
                return (optax.apply_updates(p, updates), s, k), loss

            (params, opt_state, key), losses = jax.lax.scan(
                step, (params, opt_state, key), (c_sh, o_sh)
            )
            return params, opt_state, key, losses.mean()

        if mesh is not None:
            # Pair pool replicated (it is small relative to HBM and keeps the
            # global permutation identical to the single-device run); each
            # step's minibatch is then sharded by the constraint above.
            repl = replicated(mesh)
            centers_d = jax.device_put(centers, repl)
            contexts_d = jax.device_put(contexts, repl)
            params = jax.device_put(params, repl)
            opt_state = jax.device_put(opt_state, repl)
        else:
            centers_d = jnp.asarray(centers)
            contexts_d = jnp.asarray(contexts)
        # One executable per (pair count, vocab, hyperparams) epoch shape,
        # acquired through the persistent AOT layer: a fresh process re-fitting
        # the same corpus shape skips the trace+compile, and cross-process
        # reuse stays output-fingerprint verified (graftlint R1 — this jit
        # predated utils/aot and retraced once per fit() call). noise_cdf
        # rides as an ARGUMENT so the exported HLO carries no corpus-derived
        # constant (the key could not pin a baked-in table).
        epoch_jit = jax.jit(epoch)
        epoch_args = (params, opt_state, key, centers_d, contexts_d, noise_cdf)
        compiled_epoch, _c_s, _src = persistent_aot_executable(
            epoch_jit, epoch_args, None, None,
            key_parts=(
                "w2v_epoch", jax.__version__, jax.default_backend(),
                v_size, self.dim, bs, steps_per_epoch, neg, shared,
                self.learning_rate, tuple(centers_d.shape),
                None if mesh is None else repr(mesh),
                batch_sharding is not None,
            ),
            name="w2v_epoch",
        )
        for _ in range(self.max_iter):
            params, opt_state, key, _loss = compiled_epoch(
                params, opt_state, key, centers_d, contexts_d, noise_cdf
            )

        return Word2VecModel(
            vocab,
            np.asarray(params["in"], dtype=np.float32),
            self.input_col,
            self.output_col or f"{self.input_col}__w2v",
        )

    def fit(self, df: pd.DataFrame) -> Word2VecModel:
        return self.fit_corpus(list(df[self.input_col]))
