"""Dense non-negative features around class prototypes (CNN activations).

Each row is ReLU(a * p_class + sigma * noise + bias), scaled by a constant
so that rows have about unit norm: the shape of fc7 activations after the
ReLU, fed to linear one-vs-rest SVMs. The bias sets the share of zeros.
Class counts follow the configuration's published range; rows come in an
order shuffled by the seed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def class_counts(cfg: dict, n_rows: int) -> np.ndarray:
    """Published per-class counts (``generator.count_ramp`` classes rising
    evenly from ``count_min`` to ``count_max``, the rest at ``count_max``),
    scaled to ``n_rows``."""
    g = cfg["generator"]
    k, ramp = cfg["n_classes"], g["count_ramp"]
    counts = np.full(k, g["count_max"], np.float64)
    counts[:ramp] = np.linspace(g["count_min"], g["count_max"], ramp)
    scaled = np.floor(counts * n_rows / counts.sum()).astype(np.int64)
    scaled[np.argsort(-counts, kind="stable")[: n_rows - scaled.sum()]] += 1
    return scaled


def _row_scale(g: dict, d: int) -> float:
    """1 / sqrt(D E[ReLU(z)^2]) for z ~ N(bias, a^2 + sigma^2)."""
    s = math.hypot(g["prototype_scale"], g["noise"])
    b = g["bias"]
    cdf = 0.5 * (1.0 + math.erf(b / s / math.sqrt(2.0)))
    pdf = math.exp(-0.5 * (b / s) ** 2) / math.sqrt(2.0 * math.pi)
    return 1.0 / math.sqrt(d * ((b * b + s * s) * cdf + b * s * pdf))


def prototypes(cfg: dict, key) -> jax.Array:
    """(K, D) class prototypes, the directions the classes sit along."""
    return jax.random.normal(jax.random.fold_in(key, 0),
                             (cfg["n_classes"], cfg["n_features"]))


def stream(cfg: dict, key, n_rows: int, sharding=None):
    """(X (N, D) f32, labels (N,) int32), made on the device in one call."""
    g = cfg["generator"]
    k, d = cfg["n_classes"], cfg["n_features"]
    counts = class_counts(cfg, n_rows)
    scale = _row_scale(g, d)

    def gen(key):
        proto = prototypes(cfg, key)
        kl, kn = jax.random.split(jax.random.fold_in(key, 1))
        labels = jax.random.permutation(kl, jnp.searchsorted(
            jnp.asarray(np.cumsum(counts), jnp.int32),
            jnp.arange(n_rows, dtype=jnp.int32), side="right").astype(jnp.int32))
        z = (g["prototype_scale"] * proto[labels]
             + g["noise"] * jax.random.normal(kn, (n_rows, d)) + g["bias"])
        return jnp.maximum(z, 0.0) * scale, labels

    out = None if sharding is None else (sharding.x, sharding.labels)
    return jax.jit(gen, out_shardings=out)(key)
