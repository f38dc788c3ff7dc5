"""paper_cifar_cnn: the paper's CIFAR-10 client model, 3 conv + 3 fc.

Conv 3x3 SAME with ReLU and a 2x2 max-pool after each of conv 32, 64,
64, then fc 1024-128-64-10 with ReLU between (D = 196,426).
``init_params`` draws the starting weights on the device from the seed
in the program's dict layout; ``reference_logits`` is the plain jnp
forward pass of the reference round and imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CONVS = (("conv1", 32), ("conv2", 64), ("conv3", 64))
FCS = (("fc1", 128), ("fc2", 64))


def _widths(cfg: dict):
    hw, ch = cfg["image_hw"], cfg["channels"]
    convs, cin = [], ch
    for name, cout in CONVS:
        convs.append((name, cin, cout))
        cin = cout
    feat = (hw // 8) ** 2 * cin
    fcs, n_in = [], feat
    for name, n_out in FCS + (("fc3", cfg["num_classes"]),):
        fcs.append((name, n_in, n_out))
        n_in = n_out
    return convs, fcs


def init_params(key, cfg: dict) -> dict:
    convs, fcs = _widths(cfg)
    keys = jax.random.split(key, len(convs) + len(fcs))
    params = {}
    for k, (name, cin, cout) in zip(keys, convs, strict=False):
        params[name] = {"w": jax.random.normal(k, (3, 3, cin, cout),
                                               jnp.float32)
                        * jnp.sqrt(2.0 / (9 * cin)),
                        "b": jnp.zeros((cout,), jnp.float32)}
    for k, (name, n_in, n_out) in zip(keys[len(convs):], fcs, strict=True):
        params[name] = {"w": jax.random.normal(k, (n_in, n_out), jnp.float32)
                        * jnp.sqrt(2.0 / n_in),
                        "b": jnp.zeros((n_out,), jnp.float32)}
    return params


def reference_logits(params: dict, x, cfg: dict):
    hw, ch = cfg["image_hw"], cfg["channels"]
    h = x.reshape(x.shape[0], hw, hw, ch)
    for name, _ in CONVS:
        h = jax.lax.conv_general_dilated(
            h, params[name]["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = jnp.maximum(h + params[name]["b"], 0.0)
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    h = h.reshape(h.shape[0], -1)
    for name, _ in FCS:
        h = jnp.maximum(h @ params[name]["w"] + params[name]["b"], 0.0)
    return h @ params["fc3"]["w"] + params["fc3"]["b"]


def forward_flops(cfg: dict) -> int:
    """Multiply-adds of one example's forward pass, two FLOPs each:
    each conv at its input resolution (SAME, stride 1; the pools halve
    it after), then the fully connected layers."""
    convs, fcs = _widths(cfg)
    hw, macs = cfg["image_hw"], 0
    for _, cin, cout in convs:
        macs += hw * hw * 9 * cin * cout
        hw //= 2
    macs += sum(n_in * n_out for _, n_in, n_out in fcs)
    return 2 * macs


def program_loss(cfg: dict):
    from functools import partial

    from repro.models.mlp import cnn_logits, make_loss_fn

    return make_loss_fn(partial(cnn_logits, image_hw=cfg["image_hw"],
                                channels=cfg["channels"]))
