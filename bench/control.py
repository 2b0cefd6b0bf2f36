"""The control of the comparison: the reference computed in bfloat16.

The configurations state float32 arithmetic over float32 coordinates
(exact answers). The nearest lower precision is bfloat16: here the
points, the queries, the differences, the squares and their sum are
all bfloat16, on the default device, and the k smallest within r are
taken by ``lax.top_k``. Its answers go through ``compare.gaps`` like
the program's, and have to come out as not correct.
"""
from __future__ import annotations

import numpy as np

_BLOCK = 128


def answers(points, queries, k: int, r: float):
    """(ids (m, k) int64, distances (m, k) float32) in bfloat16."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    p = jnp.asarray(np.asarray(points, np.float32), bf)
    r2 = jnp.asarray(r, bf) * jnp.asarray(r, bf)

    @jax.jit
    def block(p, q):
        diff = p[None, :, :] - q[:, None, :]
        d2 = jnp.sum(diff * diff, axis=-1, dtype=bf)
        d2 = jnp.where(d2 <= r2, d2, jnp.asarray(jnp.inf, bf))
        neg, idx = jax.lax.top_k(-d2, k)
        ok = jnp.isfinite(neg)
        return (jnp.where(ok, idx, -1),
                jnp.where(ok, jnp.sqrt(-neg), jnp.inf).astype(jnp.float32))

    q_all = np.asarray(queries, np.float32).reshape(-1, p.shape[1])
    out_i, out_d = [], []
    for s in range(0, len(q_all), _BLOCK):
        q = q_all[s:s + _BLOCK]
        pad = _BLOCK - len(q)
        qb = jnp.asarray(np.pad(q, ((0, pad), (0, 0))), bf)
        i, d = block(p, qb)
        out_i.append(np.asarray(i)[:len(q)])
        out_d.append(np.asarray(d)[:len(q)])
    if not out_i:
        return np.zeros((0, k), np.int64), np.zeros((0, k), np.float32)
    return (np.concatenate(out_i).astype(np.int64), np.concatenate(out_d))
