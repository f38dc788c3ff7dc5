"""Client data of a configuration, made from the seed.

Two layouts, named by the configuration's ``layout``:

* ``rect``: equal shards {"x": (N, m, d), "y": (N, m)} split by label
  shards (``classes_per_client`` shards of m / classes_per_client
  examples each, every class cut into the same number of shards).
* ``pooled``: one client-contiguous (sum n_i + pad, d) buffer with
  per-client ``sizes`` from a Dirichlet(beta) draw of label counts; the
  pad rows after the last client let a max(n_i)-long slice start at
  any client's offset.

The partition (which labels each client holds, and how many) comes from
the configuration's ``partition_seed``, so every ``--seed`` runs the
same set of sizes and the same compiled programs.  The seed draws the
pixel values, on the device in one jitted call: class prototypes plus
Gaussian noise, squashed into the pixel range.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _input_dim(cfg: dict) -> int:
    if "input_dim" in cfg:
        return cfg["input_dim"]
    return cfg["image_hw"] ** 2 * cfg["channels"]


def label_shard_labels(cfg: dict) -> np.ndarray:
    """(N, m) int32 labels: each client holds ``classes_per_client``
    shards dealt from a shuffled deck of per-class shards."""
    n, k, classes = cfg["n_clients"], cfg["classes_per_client"], \
        cfg["num_classes"]
    per_client = cfg["n_train"] // n
    n_shards = n * k
    if n_shards % classes or per_client % k:
        raise ValueError("label shards must divide evenly: "
                         f"{n_shards} shards, {classes} classes, "
                         f"{per_client} examples a client")
    shard_class = np.arange(n_shards) // (n_shards // classes)
    deck = np.random.default_rng(cfg["partition_seed"]).permutation(
        shard_class)
    return np.repeat(deck.reshape(n, k), per_client // k,
                     axis=1).astype(np.int32)


def dirichlet_counts(cfg: dict) -> np.ndarray:
    """(N, classes) label counts of a Dirichlet(beta) split of
    n_train / classes examples per class, redrawn until every client
    holds at least ``min_points``."""
    n, classes = cfg["n_clients"], cfg["num_classes"]
    per_class = cfg["n_train"] // classes
    rng = np.random.default_rng(cfg["partition_seed"])
    while True:
        counts = np.zeros((n, classes), np.int64)
        for c in range(classes):
            p = rng.dirichlet(np.full(n, cfg["dirichlet_beta"]))
            cuts = (np.cumsum(p) * per_class).astype(int)[:-1]
            edges = np.concatenate([[0], cuts, [per_class]])
            counts[:, c] = np.diff(edges)
        if counts.sum(axis=1).min() >= cfg["min_points"]:
            return counts


def pooled_layout(cfg: dict):
    """(sizes, offsets, y_pooled) of the pooled layout; pad rows carry
    label 0 and are never addressed by a client's slice."""
    counts = dirichlet_counts(cfg)
    sizes = counts.sum(axis=1)
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    pad = int(sizes.max() - sizes[-1])
    y = np.concatenate([np.repeat(np.arange(cfg["num_classes"]), row)
                        for row in counts] + [np.zeros(pad, np.int64)])
    return sizes.astype(np.int64), offsets.astype(np.int64), \
        y.astype(np.int32)


@partial(jax.jit, static_argnames=("dim", "classes", "squash"))
def _pixels(key, y, *, dim: int, classes: int, squash: str):
    kp, kn = jax.random.split(key)
    protos = jax.random.normal(kp, (classes, dim), jnp.float32)
    noise = jax.random.normal(kn, y.shape + (dim,), jnp.float32)
    z = protos[y] + 1.5 * noise
    return jax.nn.sigmoid(z) if squash == "sigmoid" else jnp.tanh(z)


def make_data(cfg: dict, key):
    """Device data of the configuration and, for ``pooled``, the
    per-client sizes and offsets (host int arrays); None for ``rect``."""
    squash = "sigmoid" if cfg["model"] == "mlp" else "tanh"
    dim = _input_dim(cfg)
    if cfg["layout"] == "rect":
        y = label_shard_labels(cfg)
        layout = None
    elif cfg["layout"] == "pooled":
        sizes, offsets, y = pooled_layout(cfg)
        layout = (sizes, offsets)
    else:
        raise ValueError(f"unknown layout {cfg['layout']!r}")
    y = jnp.asarray(y)
    x = _pixels(key, y, dim=dim, classes=cfg["num_classes"], squash=squash)
    return {"x": x, "y": y}, layout
