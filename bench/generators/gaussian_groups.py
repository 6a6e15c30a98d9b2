"""The paper's Synthetic-1 protocol at a stated shape.

Design: iid N(0, 1) entries made on the device in one jitted call, and
ragged groups whose count and total are the configuration's
(``data.group_sizes``).  Response: a fresh beta* on ``active_group_share``
of the groups, with ``active_feature_share`` of each chosen group's
features (at least one) set to N(0, 1) values, and y = X beta* +
``noise`` * eps.  beta* is drawn on the host as (index, value) pairs and
the responses of one call are made in one jitted product on the device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import data


def design(cfg: dict) -> data.Design:
    N, p = cfg["n_samples"], cfg["n_features"]
    rng = data.rng_for(cfg["design_seed"], 0)
    sizes = data.group_sizes(p, cfg["n_groups"], cfg["max_group_size"], rng)
    key = jax.random.PRNGKey(int(rng.integers(0, 2**31 - 1)))
    X = jax.jit(lambda k: jax.random.normal(k, (N, p), jnp.float32))(key)
    return data.Design(X=X, sizes=sizes)


def _active(cfg, sizes, r):
    """(feature indices, values) of one beta*."""
    G = len(sizes)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    chosen = np.sort(r.choice(G, max(1, int(cfg["active_group_share"] * G)),
                              replace=False))
    n_g = sizes[chosen]
    m = np.maximum(1, np.round(cfg["active_feature_share"] * n_g)).astype(
        np.int64)
    # m_g features of each chosen group without replacement: the m_g
    # smallest of n_g uniform keys
    owner = np.repeat(np.arange(len(chosen)), n_g)
    first = np.concatenate([[0], np.cumsum(n_g)[:-1]])
    offset = np.arange(len(owner)) - first[owner]
    order = np.lexsort((r.random(len(owner)), owner))
    rank = np.empty(len(owner), np.int64)
    rank[order] = offset
    keep = rank < m[owner]
    idx = starts[chosen][owner[keep]] + offset[keep]
    return idx, r.standard_normal(len(idx))


def responses(cfg: dict, design: data.Design, rngs) -> list:
    N, p = design.X.shape
    sizes = design.sizes
    n = len(rngs)
    # padded (index, value) pairs: a fixed length per call, so that one
    # program serves every call of the same count
    cap = max(1, int(cfg["active_group_share"] * len(sizes))) * max(
        1, int(round(cfg["active_feature_share"] * int(sizes.max()))))
    idx = np.zeros((n, cap), np.int32)
    val = np.zeros((n, cap), np.float32)
    eps = np.empty((n, N), np.float32)
    for i, r in enumerate(rngs):
        ix, v = _active(cfg, sizes, r)
        idx[i, :len(ix)], val[i, :len(ix)] = ix, v
        eps[i] = cfg["noise"] * r.standard_normal(N)
    Y = _fit(design.X, jnp.asarray(idx), jnp.asarray(val), jnp.asarray(eps))
    return list(Y)


@jax.jit
def _fit(X, idx, val, eps):
    """y = X beta* + eps for each row's (index, value) pairs."""
    n, p = idx.shape[0], X.shape[1]
    B = jnp.zeros((p, n), X.dtype).at[
        idx, jnp.arange(n)[:, None]].add(val)
    return jnp.dot(X, B, precision=jax.lax.Precision.HIGHEST).T + eps
