"""The image-dictionary protocol of the paper's Table 3.

Nonnegative, strongly correlated unit-norm "images" of ``n_samples``
pixels from a low-rank model: a nonnegative basis of ``basis_rank``
images, mixed with squared-Gaussian weights, plus ``pixel_noise`` uniform
noise.  The design holds ``n_features`` such images, drawn from the
configuration's ``design_seed``; each response is one further image of the
same model, held out of the dictionary.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from bench import data


def _images(cfg, base, r, m):
    rank = base.shape[1]
    D = (base @ np.abs(r.standard_normal((rank, m))) ** 2
         + cfg["pixel_noise"] * r.random((base.shape[0], m)))
    return (D / np.linalg.norm(D, axis=0, keepdims=True)).astype(np.float32)


def design(cfg: dict) -> data.Design:
    rng = data.rng_for(cfg["design_seed"], 0)
    base = rng.random((cfg["n_samples"], cfg["basis_rank"]))
    X = _images(cfg, base, rng, cfg["n_features"])
    return data.Design(X=jnp.asarray(X), sizes=None, state={"base": base})


def responses(cfg: dict, design: data.Design, rngs) -> list:
    base = design.state["base"]
    Y = np.stack([_images(cfg, base, r, 1)[:, 0] for r in rngs])
    return list(jnp.asarray(Y))
