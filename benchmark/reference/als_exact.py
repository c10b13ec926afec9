"""Plain reference for an implicit-feedback ALS fit whose every row is solved
EXACTLY (Hu-Koren-Volinsky confidence ``1 + alpha r``, MLlib's regularisation
scaled by the row's count): what Spark MLlib's ALS does, and what the program
does under ``solver="cholesky"``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, from the CSR/CSC of the logical
matrix: no bucket, no warm start, no line table, no library factorisation,
nothing imported from the program and nothing the program made. The seeded
init is the estimator's documented one (``normal(split(PRNGKey(seed))) /
sqrt(rank)``) and the padded blocks of rows are ``als_cg.py``'s - that file's
own functions, so that both references draw the same tables. A block's
systems are built and solved ``CHUNK_ROWS`` rows at a time, so that it fits
the chip beside nothing else, and by ONE compiled solver whatever the block's
shape (a shape's own program is a gather and two contractions).

Per row of the side being solved, with Y the other side's table::

    A = YtY + Y_r^T diag(alpha r) Y_r + reg n_r I      b = Y_r^T (1 + alpha r)
    x = A^-1 b   by A = L L^T (a column at a time), L z = b, L^T x = z

The systems of a chunk are held ``(k, k, R)``, the row of the star matrix LAST:
every step of the factorisation and of the two substitutions is then an
elementwise pass over whole lanes, exact in float32 whatever the matmul
precision, and the same code in any dtype. One sweep is the item half-sweep
(from the user table) and then the user half-sweep (from the new item table).
``dtype=bfloat16`` computes the same in bfloat16 throughout: the control of
the comparison, never the reference. ``config["cg_steps"]`` is not read.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.als_cg import build_side, csr_of, init_factors  # noqa: F401  (init_factors: the controls read it)

CHUNK_ROWS = 4096


@jax.jit
def cholesky_solve(a, b):
    """``x (k, R)`` of ``A x = b`` for ``R`` symmetric positive definite
    systems held ``a (k, k, R)``, ``b (k, R)``: the lower factor a column at a
    time (Cholesky-Banachiewicz), then the forward and the back substitution.
    The factor is kept transposed, ``up[m, i] = L[i, m]``, so that every sum
    runs over the leading axis. Plain elementwise arithmetic in the arrays'
    own dtype. One shape a dtype in a fit: ``(k, k, CHUNK_ROWS)``."""
    k = a.shape[0]
    below = jnp.arange(k)[:, None]

    def column(j, up):          # up[m] is final for m < j and zero from j on
        s = a[:, j] - jnp.sum(up * up[:, j][:, None], axis=0)      # (k, R): a_ij - sum_m L_im L_jm
        col = jnp.where(below >= j, s / jnp.sqrt(s[j])[None], 0)
        return up.at[j].set(col.astype(up.dtype))

    up = jax.lax.fori_loop(0, k, column, jnp.zeros_like(a))
    diag = jnp.diagonal(up, axis1=0, axis2=1).T                    # (k, R)

    def forward(j, z):          # L z = b; z[m] is final for m < j and zero from j on
        return z.at[j].set(((b[j] - jnp.sum(up[:, j] * z, axis=0)) / diag[j]).astype(z.dtype))

    z = jax.lax.fori_loop(0, k, forward, jnp.zeros_like(b))

    def backward(i, x):         # L^T x = z; x[m] is final for m > j and zero up to j
        j = k - 1 - i
        return x.at[j].set(((z[j] - jnp.sum(up[j] * x, axis=0)) / diag[j]).astype(x.dtype))

    return jax.lax.fori_loop(0, k, backward, jnp.zeros_like(b))


def systems(source, yty, idx, val, mask, reg, alpha, dtype):
    """``(a (k, k, R), b (k, R))`` of ``R`` padded rows: ``(R, L)`` entries
    against ``source``."""
    ein = functools.partial(jnp.einsum, preferred_element_type=dtype)
    y = source[idx]                                                # (R, L, k)
    c1 = jnp.where(mask, alpha * val, 0).astype(dtype)
    w = jnp.where(mask, 1 + alpha * val, 0).astype(dtype)
    n = mask.sum(axis=1).astype(dtype)
    eye = jnp.eye(yty.shape[0], dtype=dtype)
    a = ein("rlk,rl,rlm->kmr", y, c1, y) + yty[:, :, None] + eye[:, :, None] * (reg * n)[None, None]
    return a.astype(dtype), ein("rlk,rl->kr", y, w).astype(dtype)


@functools.partial(jax.jit, static_argnames=("dtype",))
def block_systems(source, yty, idx, val, mask, reg, alpha, dtype):
    """The systems of one padded block of rows, ``CHUNK_ROWS`` to a chunk:
    ``a (C, k, k, CHUNK_ROWS)``, ``b (C, k, CHUNK_ROWS)``. A block of fewer
    rows is one chunk, filled up with the system ``I x = 0``."""
    rows, k = idx.shape[0], yty.shape[0]
    with jax.default_matmul_precision("highest"):
        if rows < CHUNK_ROWS:
            a, b = systems(source, yty, idx, val, mask, reg, alpha, dtype)
            fill = CHUNK_ROWS - rows
            eye = jnp.broadcast_to(jnp.eye(k, dtype=dtype)[:, :, None], (k, k, fill))
            return (jnp.concatenate([a, eye], axis=-1)[None],
                    jnp.pad(b, ((0, 0), (0, fill)))[None])
        if rows % CHUNK_ROWS:
            raise ValueError(f"a block of {rows} rows is not whole chunks of {CHUNK_ROWS}")
        cut = lambda x: x.reshape(rows // CHUNK_ROWS, CHUNK_ROWS, x.shape[1])  # noqa: E731
        return jax.lax.map(
            lambda xs: systems(source, yty, *xs, reg, alpha, dtype),
            (cut(idx), cut(val), cut(mask)),
        )


def solve_block(source, yty, idx, val, mask, reg, alpha, dtype):
    """The exact factors ``(R, k)`` of one padded block of rows."""
    a, b = block_systems(source, yty, idx, val, mask, reg, alpha, dtype)
    solved = [cholesky_solve(jax.lax.dynamic_index_in_dim(a, c, keepdims=False),
                             jax.lax.dynamic_index_in_dim(b, c, keepdims=False))
              for c in range(a.shape[0])]
    return jnp.concatenate(solved, axis=1).T[: idx.shape[0]]


def half_sweep(source, target, side: dict, reg, alpha, dtype):
    with jax.default_matmul_precision("highest"):
        yty = jnp.matmul(source.T, source)
    solved = []
    for _row_of, idx, val, mask in side["blocks"]:
        for j in range(idx.shape[0]):
            solved.append(solve_block(
                source, yty, idx[j], val[j], mask[j],
                jnp.asarray(reg, dtype), jnp.asarray(alpha, dtype), dtype,
            ))
    if not solved:
        return target
    pool = jnp.concatenate(solved)
    landing = side["landing"]
    return jnp.where(landing[:, None] >= 0, pool[jnp.maximum(landing, 0)], target)


def fit(stars: dict, config: dict, seed: int, sweeps: int, dtype=jnp.float32):
    """``(user_factors, item_factors)`` as numpy float32 after ``sweeps``
    sweeps from the seeded init."""
    n_users, n_items = stars["n_users"], stars["n_items"]
    user_side = build_side(*csr_of(stars["rows"], stars["cols"], stars["vals"], n_users))
    item_side = build_side(*csr_of(stars["cols"], stars["rows"], stars["vals"], n_items))
    uf, vf = init_factors(seed, n_users, n_items, config["rank"])
    uf, vf = uf.astype(dtype), vf.astype(dtype)
    args = (config["reg_param"], config["alpha"], dtype)
    for _ in range(sweeps):
        vf = half_sweep(uf, vf, item_side, *args)
        uf = half_sweep(vf, uf, user_side, *args)
    return np.asarray(uf, np.float32), np.asarray(vf, np.float32)
