"""Inputs of a cell: the design, its group layout and the stream of
responses.

A configuration names its ``generator``; ``generators/<name>.py`` makes
the data, with two functions:

* ``design(cfg) -> Design`` — the deployment's design, drawn from the
  configuration's ``design_seed``: one study's SNP matrix or one image
  collection, the same in every run.
* ``responses(cfg, design, rngs) -> list`` — one response (an (N,) device
  array) per generator in ``rngs``.

Responses come from the run's seed, in two sub-streams: ``WARMUP`` feeds
set-up and ``WINDOW`` feeds the measured window, so the window never
solves a response that set-up has solved.  Response ``k`` of a stream is
drawn from ``rng_for(seed, stream, k)`` alone, so it does not depend on
how many responses are made at once.
"""
from __future__ import annotations

import dataclasses

import numpy as np

WARMUP, WINDOW = 1, 2


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` (any integer) and a sub-stream index."""
    return np.random.default_rng([int(seed < 0), abs(int(seed)), *stream])


def group_sizes(n_features: int, n_groups: int, max_size: int,
                rng: np.random.Generator) -> np.ndarray:
    """Ragged group sizes on ``1..max_size`` with exactly ``n_groups``
    groups summing to ``n_features``: uniform draws, then single steps on
    random groups until the total is exact."""
    if not n_groups <= n_features <= n_groups * max_size:
        raise ValueError(f"{n_groups} groups of 1..{max_size} cannot hold "
                         f"{n_features} features")
    sizes = rng.integers(1, max_size + 1, n_groups)
    diff = int(sizes.sum()) - n_features
    while diff:
        idx = rng.choice(n_groups, min(abs(diff), n_groups), replace=False)
        step = -1 if diff > 0 else 1
        sizes[idx] = np.clip(sizes[idx] + step, 1, max_size)
        diff = int(sizes.sum()) - n_features
    return sizes.astype(np.int32)


@dataclasses.dataclass
class Design:
    """A deployment's design, on the device."""
    X: object                    # (N, p) float32 device array
    sizes: np.ndarray | None     # (G,) group sizes, or None (no groups)
    state: dict = dataclasses.field(default_factory=dict)  # generator's own


def generator(cfg: dict):
    from bench import catalog
    try:
        return catalog.generator(cfg["generator"])
    except KeyError:
        raise ValueError(f"unknown generator {cfg.get('generator')!r}; "
                         f"known: {catalog.generator_names()}") from None


def make_design(cfg: dict) -> Design:
    return generator(cfg).design(cfg)


def make_responses(cfg: dict, design: Design, seed: int, stream: int,
                   start: int, count: int) -> list:
    """Responses ``start .. start + count - 1`` of a stream of the run."""
    rngs = [rng_for(seed, stream, k) for k in range(start, start + count)]
    return generator(cfg).responses(cfg, design, rngs)
