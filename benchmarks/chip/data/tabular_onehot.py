"""Tabular rows: quantitative features in [0, 1] plus one-hot groups.

The Covertype layout (LIBSVM ``covtype.scale``): a block of quantitative
columns scaled to [0, 1], then categorical groups written one-hot. Classes
come at fixed counts, in an order shuffled by the seed. Each class has its
own mean for every quantitative column and its own category odds in every
group; the spreads are the configuration's ``generator`` parameters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def class_counts(cfg: dict, n_rows: int) -> np.ndarray:
    """The configuration's class counts, scaled to ``n_rows`` if cut."""
    counts = np.asarray(cfg["class_counts"], np.int64)
    if counts.sum() == n_rows:
        return counts
    scaled = np.floor(counts * n_rows / counts.sum()).astype(np.int64)
    scaled[np.argsort(-counts)[: n_rows - scaled.sum()]] += 1
    return scaled


def _params(cfg, key):
    g = cfg["generator"]
    k = cfg["n_classes"]
    kq, *kg = jax.random.split(key, 1 + len(g["onehot_groups"]))
    mu = jax.random.uniform(kq, (k, g["n_quantitative"]), jnp.float32,
                            g["mean_low"], g["mean_high"])
    logits = [g["category_spread"] * jax.random.normal(kk, (k, size))
              for kk, size in zip(kg, g["onehot_groups"])]
    return mu, logits


def prototypes(cfg: dict, key) -> jax.Array:
    """(K, D) class means: quantitative means, then category odds."""
    mu, logits = _params(cfg, jax.random.fold_in(key, 0))
    return jnp.concatenate([mu] + [jax.nn.softmax(lg, -1) for lg in logits],
                           axis=1)


def stream(cfg: dict, key, n_rows: int, sharding=None):
    """(X (N, D) f32, labels (N,) int32), made on the device in one call."""
    g = cfg["generator"]
    counts = class_counts(cfg, n_rows)
    k = cfg["n_classes"]
    sizes = g["onehot_groups"]

    def gen(key):
        mu, logits = _params(cfg, jax.random.fold_in(key, 0))
        kl, kn, *kc = jax.random.split(jax.random.fold_in(key, 1),
                                       2 + len(sizes))
        labels = jax.random.permutation(kl, jnp.searchsorted(
            jnp.asarray(np.cumsum(counts), jnp.int32),
            jnp.arange(n_rows, dtype=jnp.int32), side="right").astype(jnp.int32))
        q = mu[labels] + g["noise"] * jax.random.normal(
            kn, (n_rows, g["n_quantitative"]))
        cols = [jnp.clip(q, 0.0, 1.0)]
        for kk, lg, size in zip(kc, logits, sizes):
            cat = jax.random.categorical(kk, lg[labels], axis=-1)
            cols.append(jax.nn.one_hot(cat, size, dtype=jnp.float32))
        return jnp.concatenate(cols, axis=1), labels

    out = None if sharding is None else (sharding.x, sharding.labels)
    return jax.jit(gen, out_shardings=out)(key)
