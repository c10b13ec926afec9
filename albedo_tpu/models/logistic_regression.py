"""Weighted logistic regression on block-sparse features.

Reference parity: the ranker's ``LogisticRegression`` stage — maxIter=300,
regParam=0.7, elasticNetParam=0 (pure L2), standardization=true, instance
weights via ``weightCol`` (``LogisticRegressionRanker.scala:330-337``). MLlib
trains with data-parallel L-BFGS (per-partition gradients tree-aggregated to
the driver); here the full-batch loss lives on device and L-BFGS runs as an
``optax.lbfgs`` scan — the gradient reduction XLA emits over a sharded batch
is the ICI analogue of Spark's treeAggregate.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax

from albedo_tpu.features.assembler import FeatureMatrix
from albedo_tpu.utils import pow2_at_least
from albedo_tpu.utils.aot import persistent_aot_executable
from albedo_tpu.ops.sparse_linear import (
    Params,
    block_logits,
    dense_center,
    expanded_batch,
    feature_batch,
    fold_scales,
    init_params,
    inverse_std_scales,
    weighted_logloss,
)

# Inference logits as ONE dispatch with params/batch as ARGUMENTS. Eager
# block_logits would dispatch ~100 small ops one by one; closing over the
# batch inside a jit would bake it into the HLO as a constant — at real scale
# that bloats the program (compile time and executable size grow with the
# data) and forces a recompile for every new batch.
_block_logits_jit = jax.jit(block_logits)

# Inference layout thresholds (see LogisticRegressionModel.decision_function).
# The two-stage candidate budget is sources x top_k (~5 x 30) rows, so one
# 256-row bucket covers every online request; past 4096 rows the expanded
# dense block (rows x ~1.3k floats) stops being cheaper than a compile.
_REQUEST_ROWS = 256
_RECTANGLE_MAX_ROWS = 4096


@dataclasses.dataclass
class LogisticRegressionModel:
    params: dict[str, Any]   # standardized-space coefficients
    scales: dict[str, Any]   # 1/std per feature
    train_loss: float
    # Dense-block means subtracted before scaling (None = uncentered). See
    # ops.sparse_linear.dense_center for why centering the dense block.
    center: Any | None = None
    # L-BFGS iterations actually executed (None for the adam solver) — the
    # convergence diagnostic MLlib exposes via its training summary.
    n_iter_run: int | None = None
    # Wall-clock split of the fit: host batch/scales preparation (flat
    # layouts, standardization moments, upload dispatch), XLA compile (0 when
    # the in-process executable cache was warm — see _aot_call), and the
    # actual solve. The r4 ranker bench conflated all three inside its
    # lr_fit stage (VERDICT r4 #1).
    prep_s: float | None = None
    compile_s: float | None = None
    run_s: float | None = None

    def decision_function(self, fm: FeatureMatrix) -> np.ndarray:
        """(N,) logits on the device.

        The layout is chosen from the row count. A large batch (an AUC
        evaluation, a scoring shard) uploads the FACTORED flat layout — its
        arrays are sized by the batch's entry and distinct-document counts,
        so the program is compiled for that batch, once per job. A
        request-sized batch (the online re-rank's ~150 candidates) uploads
        the RECTANGLE padded to a power-of-two row bucket with a floor of
        ``_REQUEST_ROWS`` — shapes depend on the bucket alone, so ONE
        executable serves every request instead of each request compiling
        its own on the request path (seconds per compile on a chip, against
        a 0.5 s stage deadline)."""
        n = fm.n_rows
        if n <= _RECTANGLE_MAX_ROWS:
            batch = expanded_batch(fm, max(_REQUEST_ROWS, pow2_at_least(n)))
        else:
            batch = feature_batch(fm)
        out, _ = _aot_call(
            _block_logits_jit,
            (self.params, self.scales, batch, self.center),
            "lr_block_logits",
        )
        return np.asarray(out)[:n]

    def predict_proba(self, fm: FeatureMatrix) -> np.ndarray:
        """P(label=1), the `probability[1]` the ranker sorts by
        (``LogisticRegressionRanker.scala:434``)."""
        return 1.0 / (1.0 + np.exp(-self.decision_function(fm)))

    @property
    def coefficients(self) -> dict[str, np.ndarray]:
        """Raw-space coefficients (MLlib reports these after internal
        standardization). The dense-centering shift folds into the bias:
        ``b_raw = b_std - sum(beta_std * center / std)``."""
        folded = {k: np.asarray(v) for k, v in fold_scales(self.params, self.scales).items()}
        if self.center is not None:
            shift = float(np.sum(folded["dense"] * np.asarray(self.center)))
            folded["bias"] = np.float32(folded["bias"] - shift)
        return folded


@dataclasses.dataclass
class LogisticRegression:
    max_iter: int = 300
    reg_param: float = 0.7
    standardization: bool = True
    solver: str = "lbfgs"      # "lbfgs" (MLlib parity) or "adam"
    learning_rate: float = 0.05  # adam only
    tol: float = 1e-6          # MLlib LogisticRegression default tol
    # Optional jax.sharding.Mesh: lay the batch out row-sharded over the
    # mesh's "data" axis (albedo_tpu.parallel.lr) — XLA then inserts the ICI
    # psums that replace MLlib's gradient treeAggregate.
    mesh: Any | None = None

    def _prepare_scales(self, fm: FeatureMatrix):
        """(scales, center) under the configured standardization — shared by
        ``fit`` and ``fit_many`` so grid and single fits can never drift.
        Host arrays: they upload as jit-call arguments (no eager per-field
        jnp conversion dispatches)."""
        if self.standardization:
            scales = inverse_std_scales(fm)
            center = dense_center(fm)
        else:
            scales = jax.tree.map(np.ones_like, init_params(fm))
            scales["bias"] = np.float32(1.0)
            center = None
        return scales, center

    def fit(
        self,
        fm: FeatureMatrix,
        labels: np.ndarray,
        sample_weight: np.ndarray | None = None,
        _damped_retry: bool = False,
    ) -> LogisticRegressionModel:
        n = fm.n_rows
        t_prep = time.perf_counter()
        if sample_weight is None:
            sample_weight = np.ones(n, dtype=np.float32)
        if self.mesh is not None:
            from albedo_tpu.parallel.lr import shard_feature_batch

            batch, y, w = shard_feature_batch(fm, labels, sample_weight, self.mesh)
        else:
            batch = feature_batch(fm)
            y = jnp.asarray(labels, dtype=jnp.float32)
            w = jnp.asarray(sample_weight, dtype=jnp.float32)

        scales, center = self._prepare_scales(fm)
        params = init_params(fm)
        prep_s = time.perf_counter() - t_prep

        n_iter_run = None
        compile_s = run_s = None
        if self.solver == "lbfgs":
            # The batch rides as an ARGUMENT of a module-level jit (a closure
            # would embed it as an HLO constant and bloat the program with
            # the data) and max_iter/tol are traced scalars, so
            # the executable is cached across fits of same-shaped data
            # in-process; _aot_call separates compile from run wall-clock.
            args = (
                params, scales, center, jnp.float32(self.reg_param),
                batch, y, w, jnp.int32(self.max_iter), jnp.float32(self.tol),
            )
            t0 = time.perf_counter()
            (params, loss, n_done), compile_s = _aot_call(
                _lbfgs_fit_jit, args, "lr_lbfgs_fit"
            )
            loss = float(loss)  # d2h read: reliable completion barrier
            run_s = time.perf_counter() - t0 - compile_s
            n_iter_run = int(n_done)
        elif self.solver == "adam":
            reg = float(self.reg_param)
            data = (batch, y, w)

            def loss_fn(p, d):
                b, yy, ww = d
                return weighted_logloss(p, scales, b, yy, ww, reg, center=center)

            params, loss = _run_adam(loss_fn, params, data, self.max_iter, self.learning_rate)
        else:
            raise ValueError(f"unknown solver {self.solver!r}")

        # Divergence watchdog (utils.watchdog): the training loss is already
        # read to host as the completion barrier, so a finiteness check is
        # free. A non-finite loss (exploded L-BFGS line search, absurd adam
        # step) trips kind="lr" and re-runs ONCE with damped (10x)
        # regularization; a re-run that is still non-finite refuses to
        # produce a model rather than shipping garbage coefficients.
        from albedo_tpu.utils.watchdog import TrainingDiverged, check_lr_loss

        if not check_lr_loss(float(loss)):
            if _damped_retry:
                raise TrainingDiverged(self.max_iter, ["lr"])
            retry = dataclasses.replace(
                self, reg_param=max(float(self.reg_param) * 10.0, 1e-2)
            )
            return retry.fit(fm, labels, sample_weight, _damped_retry=True)

        return LogisticRegressionModel(
            params=params, scales=scales, train_loss=float(loss),
            center=None if center is None else np.asarray(center),
            n_iter_run=n_iter_run, prep_s=prep_s, compile_s=compile_s, run_s=run_s,
        )

    def fit_many(
        self,
        fm: FeatureMatrix,
        labels: np.ndarray,
        sample_weights: np.ndarray,   # (G, N): one row per grid point
        grid_mesh: Any | None = None,
    ) -> list[LogisticRegressionModel]:
        """Fit one model per row of ``sample_weights`` in a single vmapped
        L-BFGS solve — the ``LogisticRegressionRankerCV`` instance-weight grid
        (``LogisticRegressionRankerCV.scala:326-332``), which refits the SAME
        featurized set under different weight columns.

        The features, labels, scales, and init are shared; only the weight
        vector varies, so the grid vectorizes cleanly. With ``grid_mesh`` the
        grid axis is laid out over the mesh's data axis (padded to a device
        multiple): each device solves its own grid points — the TPU analogue
        of Spark CV's parallel fits over the cluster.
        """
        if self.solver != "lbfgs":
            raise ValueError(f"fit_many supports solver='lbfgs' only, not {self.solver!r}")
        if self.mesh is not None:
            raise ValueError(
                "fit_many shards the GRID axis via grid_mesh; combining it with "
                "a row-sharded batch (self.mesh) is not supported"
            )
        ws = np.asarray(sample_weights, dtype=np.float32)
        n_grid = ws.shape[0]
        if n_grid == 0:
            raise ValueError("sample_weights must have at least one grid row")
        t_prep = time.perf_counter()
        batch = feature_batch(fm)
        y = jnp.asarray(labels, dtype=jnp.float32)
        scales, center = self._prepare_scales(fm)
        params0 = init_params(fm)
        prep_s = time.perf_counter() - t_prep

        if grid_mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from albedo_tpu.parallel.mesh import DATA_AXIS

            n_dev = grid_mesh.shape[DATA_AXIS]
            pad = (-n_grid) % n_dev
            ws_dev = jax.device_put(
                np.concatenate([ws, np.repeat(ws[:1], pad, axis=0)]) if pad else ws,
                NamedSharding(grid_mesh, P(DATA_AXIS, None)),
            )
        else:
            ws_dev = jnp.asarray(ws)

        # Grid axis vmapped; the shared featurized batch enters unbatched as
        # an argument, not as a baked-in constant. Same AOT executable cache
        # and compile/run split as single fits.
        args = (
            params0, scales, center, jnp.float32(self.reg_param),
            batch, y, ws_dev, jnp.int32(self.max_iter), jnp.float32(self.tol),
        )
        t0 = time.perf_counter()
        (params, losses, n_dones), compile_s = _aot_call(
            _lbfgs_fit_many_jit, args, "lr_lbfgs_fit_many"
        )
        losses = np.asarray(losses)  # d2h read: reliable completion barrier
        run_s = time.perf_counter() - t0 - compile_s
        center_np = None if center is None else np.asarray(center)
        return [
            LogisticRegressionModel(
                params=jax.tree.map(lambda x, g=g: np.asarray(x[g]), params),
                scales=scales,
                train_loss=float(losses[g]),
                center=center_np,
                n_iter_run=int(n_dones[g]),
                prep_s=prep_s,
                compile_s=compile_s,
                run_s=run_s,
            )
            for g in range(n_grid)
        ]


def _finite_tree(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    ok = jnp.bool_(True)
    for leaf in leaves:
        ok = ok & jnp.all(jnp.isfinite(leaf))
    return ok


# Zoom-linesearch eval budget per L-BFGS step. optax's default (20) spends
# most of the fit inside line-search f-evals on this full-batch objective;
# capping at 8 reached the identical loss (6 decimal places, bench-scale
# synthetic and test suites) in ~2-4x less wall-clock on TPU.
MAX_LINESEARCH_STEPS = 8


# optax moved its pytree helpers to the `optax.tree` namespace; older
# releases (<= 0.2.3) only ship `optax.tree_utils` (and spell the l2 norm
# `tree_l2_norm`). Resolve once at import so the L-BFGS loop stays clean.
if hasattr(optax, "tree"):
    _tree_get, _tree_norm = optax.tree.get, optax.tree.norm
else:
    import optax.tree_utils as _otu

    _tree_get, _tree_norm = _otu.tree_get, _otu.tree_l2_norm


def _zoom_linesearch():
    """Zoom linesearch with a version-gated initial-guess strategy: 'one' is
    optax.lbfgs's own default and the documented choice for quasi-Newton
    methods ('keep' can pin later searches to an early small step and exhaust
    the reduced eval budget) — but the kwarg only exists on newer optax;
    older releases (<= 0.2.3) hard-code the equivalent behavior."""
    import inspect

    kwargs: dict = {"max_linesearch_steps": MAX_LINESEARCH_STEPS}
    params = inspect.signature(optax.scale_by_zoom_linesearch).parameters
    if "initial_guess_strategy" in params:
        kwargs["initial_guess_strategy"] = "one"
    return optax.scale_by_zoom_linesearch(**kwargs)


def _lbfgs_loop(loss_fn, params: Params, max_iter: int, tol: float):
    """Traceable L-BFGS while_loop (no jit of its own — callers jit or vmap
    it). ``loss_fn`` takes params only; any data it uses must already be traced
    values in the caller's scope, never host constants."""
    opt = optax.lbfgs(linesearch=_zoom_linesearch())
    value_and_grad = optax.value_and_grad_from_state(loss_fn)

    def run(params):
        state = opt.init(params)

        def step(carry):
            params, state, prev, i, _bad, flat = carry
            value, grad = value_and_grad(params, state=state)
            updates, state = opt.update(
                grad, state, params, value=value, grad=grad, value_fn=loss_fn
            )
            new_params = optax.apply_updates(params, updates)
            # A line-search overshoot can yield non-finite iterates (seen
            # nondeterministically with extreme instance weights); keep the
            # last finite point and stop instead of propagating nan.
            ok = jnp.isfinite(value) & _finite_tree(new_params)
            kept = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_params, params
            )
            # Count CONSECUTIVE no-progress steps: in float32, L-BFGS can sit
            # on an exact plateau for a step or two while the line search
            # re-scales, then drop again — a single tiny delta is not
            # convergence (observed: 2 flat steps then a 5e-4 drop).
            plateau = jnp.abs(prev - value) <= tol * jnp.maximum(jnp.abs(value), 1e-12)
            flat = jnp.where(plateau, flat + 1, 0)
            return kept, state, value, i + 1, ~ok, flat

        def cont(carry):
            params, state, prev, i, bad, flat = carry
            grad = _tree_get(state, "grad")
            gnorm = _tree_norm(grad)
            # Keep iterating while finite, under budget, and not converged
            # (converged = 3 consecutive value plateaus, or vanished gradient).
            return ~bad & (i < max_iter) & ((i < 2) | ((flat < 3) & (gnorm > tol)))

        init = (params, state, jnp.inf, 0, jnp.bool_(False), 0)
        params, state, value, n_done, _, _ = jax.lax.while_loop(cont, step, init)
        # Report the loss at the returned (finite) point, not the last
        # line-search value.
        return params, loss_fn(params), n_done

    return run(params)


def _lbfgs_fit_impl(params, scales, center, reg, batch, y, w, max_iter, tol):
    """The full-batch weighted-LR L-BFGS solve as a pure function of arrays.

    Everything data-like (batch pytree, labels, weights, reg, max_iter, tol)
    is a traced argument: the HLO stays small (a closed-over batch would be
    baked into the program as a constant the size of the data) and ONE
    executable serves every fit with same-shaped data, any
    max_iter/tol/reg value."""

    def loss_fn(p):
        return weighted_logloss(p, scales, batch, y, w, reg, center=center)

    return _lbfgs_loop(loss_fn, params, max_iter, tol)


_lbfgs_fit_jit = jax.jit(_lbfgs_fit_impl)


def _lbfgs_fit_many_impl(params0, scales, center, reg, batch, y, ws, max_iter, tol):
    """Vmapped grid of L-BFGS solves over weight rows (shared featurized
    batch enters unbatched; only ``ws`` carries the grid axis)."""

    def solve(w):
        def loss_fn(p):
            return weighted_logloss(p, scales, batch, y, w, reg, center=center)

        return _lbfgs_loop(loss_fn, params0, max_iter, tol)

    return jax.vmap(solve)(ws)


_lbfgs_fit_many_jit = jax.jit(_lbfgs_fit_many_impl)


def _aot_call(jitted, args, name):
    """Call ``jitted(*args)`` through the persistent AOT layer.

    Replaces the old module-private lower/compile LRU: LR executables now get
    the full ``utils.aot`` stack — bounded in-memory LRU, on-disk
    ``jax.export`` round-trip, and output-fingerprint verification — the
    same reuse discipline the ALS paths earned in PR 4 (a bare
    lower/compile rides the persistent XLA cache unguarded; graftlint R1).
    The 112.7 s ``lr_fit`` cold spot's compile component now survives
    process boundaries like the ALS one does.

    Returns ``(outputs, compile_s)`` — ``compile_s`` is 0.0 on a warm cache.
    """
    leaves, treedef = jax.tree.flatten(args)
    key_parts = (
        name, jax.__version__, jax.default_backend(), str(treedef),
        tuple(
            (
                tuple(getattr(x, "shape", ())),
                str(getattr(x, "dtype", type(x))),
                # Shardings are part of the compiled signature: an executable
                # built for replicated args must not serve mesh-sharded ones.
                str(getattr(x, "sharding", None)),
            )
            for x in leaves
        ),
    )
    compiled, compile_s, _source = persistent_aot_executable(
        jitted, args, None, None, key_parts, name=name
    )
    return compiled(*args), compile_s


def _run_adam(loss_fn, params: Params, data, max_iter: int, lr: float):
    opt = optax.adam(lr)

    # Non-default diagnostic solver (solver="adam"): rebuilt per fit by
    # closure design, never on the production ranker path — not worth an
    # AOT export surface.
    # albedo: noqa[bare-jit]
    @jax.jit
    def run(params, data):
        state = opt.init(params)

        def step(carry, _):
            params, state = carry
            loss, grads = jax.value_and_grad(lambda p: loss_fn(p, data))(params)
            updates, state = opt.update(grads, state, params)
            return (optax.apply_updates(params, updates), state), loss

        (params, _), losses = jax.lax.scan(step, (params, state), None, length=max_iter)
        return params, losses[-1]

    return run(params, data)
