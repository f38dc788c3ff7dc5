"""paper_mnist_mlp: the paper's MNIST client model, 784-200-10 MLP.

``init_params`` draws the starting weights on the device from the seed
(He-normal weights, zero biases, the program's dict layout so that the
program's ``mlp_logits`` reads them).  ``reference_logits`` is the plain
jnp forward pass the reference round runs; it imports nothing of the
program.  ``program_loss`` hands the program its own model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_params(key, cfg: dict) -> dict:
    d_in, hidden, n_out = cfg["input_dim"], cfg["hidden"], cfg["num_classes"]
    k1, k2 = jax.random.split(key)
    return {
        "fc1": {"w": jax.random.normal(k1, (d_in, hidden), jnp.float32)
                * jnp.sqrt(2.0 / d_in),
                "b": jnp.zeros((hidden,), jnp.float32)},
        "fc2": {"w": jax.random.normal(k2, (hidden, n_out), jnp.float32)
                * jnp.sqrt(2.0 / hidden),
                "b": jnp.zeros((n_out,), jnp.float32)},
    }


def reference_logits(params: dict, x, cfg: dict):
    h = jnp.maximum(x @ params["fc1"]["w"] + params["fc1"]["b"], 0.0)
    return h @ params["fc2"]["w"] + params["fc2"]["b"]


def forward_flops(cfg: dict) -> int:
    """Multiply-adds of one example's forward pass, two FLOPs each."""
    d_in, hidden, n_out = cfg["input_dim"], cfg["hidden"], cfg["num_classes"]
    return 2 * (d_in * hidden + hidden * n_out)


def program_loss(cfg: dict):
    from repro.models.mlp import make_loss_fn, mlp_logits

    return make_loss_fn(mlp_logits)
