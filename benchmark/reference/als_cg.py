"""Plain reference for an implicit-feedback ALS fit with the warm-started,
Jacobi-preconditioned conjugate-gradient solve (Hu-Koren-Volinsky confidence
``1 + alpha r``, MLlib's regularisation scaled by the row's count).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision, from
the CSR/CSC of the logical matrix: no kernels, no AOT layer, nothing imported
from the program and nothing the program made — its own seeded init (the
estimator's documented one: ``normal(split(PRNGKey(seed))) / sqrt(rank)``),
its own padded blocks. Rows are grouped by length into power-of-two widths
and solved a block at a time, so that it fits beside nothing else.

Per row of the side being solved, with Y the other side's table::

    A = YtY + Y_r^T diag(alpha r) Y_r + reg n_r I      b = Y_r^T (1 + alpha r)
    x <- ``cg_steps`` steps of preconditioned CG on A x = b from the row's
         current factor, preconditioner diag(A)

One sweep is the item half-sweep (from the user table) and then the user
half-sweep (from the new item table). ``dtype=bfloat16`` computes the same in
bfloat16 throughout: the control of the comparison, never the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ENTRIES = 1 << 20


def csr_of(major: np.ndarray, minor: np.ndarray, vals: np.ndarray, n_major: int):
    """(indptr, minor sorted by major, vals) of a COO matrix."""
    order = np.argsort(major, kind="stable")
    indptr = np.zeros(n_major + 1, np.int64)
    np.cumsum(np.bincount(major, minlength=n_major), out=indptr[1:])
    return indptr, minor[order], vals[order]


def build_side(indptr: np.ndarray, indices: np.ndarray, vals: np.ndarray,
               block_entries: int = BLOCK_ENTRIES) -> dict:
    """Padded blocks of one side: rows of like length share a width L (a
    power of two, at least 8) and come ``block_entries // L`` to a block."""
    counts = np.diff(indptr)
    n_rows = counts.shape[0]
    width = np.maximum(8, 1 << np.ceil(np.log2(np.maximum(counts, 1))).astype(np.int64))
    landing = np.full(n_rows, -1, np.int64)
    blocks, offset = [], 0
    for L in np.unique(width[counts > 0]):
        rows = np.flatnonzero((width == L) & (counts > 0))
        per = max(1, block_entries // int(L))
        n_blocks = -(-rows.size // per)
        slots = n_blocks * per
        c = counts[rows]
        slot_of = np.repeat(np.arange(rows.size), c)
        starts = np.cumsum(c) - c
        pos = np.arange(int(c.sum())) - np.repeat(starts, c)
        flat = np.repeat(indptr[rows], c) + pos
        idx = np.zeros((slots, int(L)), np.int32)
        val = np.zeros((slots, int(L)), np.float32)
        mask = np.zeros((slots, int(L)), bool)
        idx[slot_of, pos] = indices[flat]
        val[slot_of, pos] = vals[flat]
        mask[slot_of, pos] = True
        row_of = np.zeros(slots, np.int32)
        row_of[: rows.size] = rows
        landing[rows] = offset + np.arange(rows.size)
        shape = (n_blocks, per, int(L))
        blocks.append(tuple(
            jnp.asarray(a.reshape(shape[: 2 + (a.ndim - 1)]))
            for a in (row_of, idx, val, mask)
        ))
        offset += slots
    return {"blocks": blocks, "landing": jnp.asarray(landing.astype(np.int32))}


@functools.partial(jax.jit, static_argnames=("cg_steps", "dtype"))
def solve_block(source, yty, x0, idx, val, mask, reg, alpha, cg_steps: int, dtype):
    """CG on one padded block of rows: (R, L) entries against ``source``."""
    hi = jax.lax.Precision.HIGHEST
    ein = functools.partial(jnp.einsum, precision=hi, preferred_element_type=dtype)
    y = source[idx]                                   # (R, L, k)
    c1 = jnp.where(mask, alpha * val, 0).astype(dtype)
    w = jnp.where(mask, 1 + alpha * val, 0).astype(dtype)
    n = mask.sum(axis=1).astype(dtype)[:, None]
    b = ein("rlk,rl->rk", y, w)
    diag = jnp.diagonal(yty)[None] + ein("rlk,rl->rk", y * y, c1) + reg * n
    diag = jnp.maximum(diag, jnp.asarray(1e-12, dtype))

    def matvec(p):
        t = c1 * ein("rlk,rk->rl", y, p)
        return jnp.matmul(p, yty, precision=hi) + ein("rlk,rl->rk", y, t) + reg * n * p

    tiny = jnp.asarray(1e-30, dtype)
    x = x0
    r = b - matvec(x)
    z = r / diag
    p = z
    rz = jnp.sum(r * z, axis=1)
    for _ in range(cg_steps):
        ap = matvec(p)
        step = rz / (jnp.sum(p * ap, axis=1) + tiny)
        x = x + step[:, None] * p
        r = r - step[:, None] * ap
        z = r / diag
        rz_new = jnp.sum(r * z, axis=1)
        p = z + (rz_new / (rz + tiny))[:, None] * p
        rz = rz_new
    return x


def half_sweep(source, target, side: dict, reg, alpha, cg_steps: int, dtype):
    yty = jnp.matmul(source.T, source, precision=jax.lax.Precision.HIGHEST)
    solved = []
    for row_of, idx, val, mask in side["blocks"]:
        for j in range(idx.shape[0]):
            solved.append(solve_block(
                source, yty, target[row_of[j]], idx[j], val[j], mask[j],
                jnp.asarray(reg, dtype), jnp.asarray(alpha, dtype), cg_steps, dtype,
            ))
    if not solved:
        return target
    pool = jnp.concatenate(solved)
    landing = side["landing"]
    return jnp.where(landing[:, None] >= 0, pool[jnp.maximum(landing, 0)], target)


def init_factors(seed: int, n_users: int, n_items: int, rank: int):
    ukey, ikey = jax.random.split(jax.random.PRNGKey(seed))
    scale = 1.0 / jnp.sqrt(jnp.float32(rank))
    return (jax.random.normal(ukey, (n_users, rank), jnp.float32) * scale,
            jax.random.normal(ikey, (n_items, rank), jnp.float32) * scale)


def fit(stars: dict, config: dict, seed: int, sweeps: int, dtype=jnp.float32):
    """``(user_factors, item_factors)`` as numpy float32 after ``sweeps``
    sweeps from the seeded init."""
    n_users, n_items = stars["n_users"], stars["n_items"]
    user_side = build_side(*csr_of(stars["rows"], stars["cols"], stars["vals"], n_users))
    item_side = build_side(*csr_of(stars["cols"], stars["rows"], stars["vals"], n_items))
    uf, vf = init_factors(seed, n_users, n_items, config["rank"])
    uf, vf = uf.astype(dtype), vf.astype(dtype)
    args = (config["reg_param"], config["alpha"], config["cg_steps"], dtype)
    for _ in range(sweeps):
        vf = half_sweep(uf, vf, item_side, *args)
        uf = half_sweep(vf, uf, user_side, *args)
    return np.asarray(uf, np.float32), np.asarray(vf, np.float32)
