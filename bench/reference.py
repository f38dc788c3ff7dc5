"""Plain reference of the FedBack round (paper Alg. 2, compacted).

One round, written straight from the algorithm and the engine's
documented semantics, in jnp with no kernels, no flat-state codec and
nothing imported from the program:

1. trigger: d_i = ||omega - z_i|| and S_i = d_i >= delta_i (gated by
   the tick's arrivals when serving);
2. controller: L' = (1 - a) L + a S, delta' = delta + K (L - target);
3. plan: demand = S or deferred; order by (demand, age, d, index), the
   first C rows are the solve slots, the first min(demand, limit)
   commit, where limit = clip(ceil(sum qload), ceil(target N), C);
   the rest of the demand ages by one round;
4. solve: for each slot, SGD with momentum on the client's loss plus
   rho/2 ||theta - c||^2 from theta = omega, c = omega - lambda',
   lambda' = lambda + theta - omega, over ``epochs`` shuffled passes of
   ``batch_size`` (pooled clients: max(n_i)-long virtual passes, rows
   past n_i weigh 0, an all-padding step is skipped);
5. commit: theta_i, lambda_i, z_i = theta_out + lambda' for the
   committed rows; omega' = mean_i z_i.

The random draws (round keys, per-client keys, minibatch permutations)
follow the engine's documented use of ``jax.random`` so that the same
seed gives the same minibatches.  ``dtype=float32`` runs under
``default_matmul_precision("highest")``.  The control is the same round
one precision below what the configuration states: ``bfloat16`` for a
configuration run at the TPU's default float32 matmul precision,
``precision="high"`` (three bf16 passes) for one run at "highest".  Where a distance lies
within ``EVENT_BAND`` of its threshold the reference takes the decision
of the run it is compared with (``hint``): rounding decides there, and
the rows of a client that fired on one side only would differ by a
whole solve.  ``fault`` plants a fault
for the calibration of the limits: ``half_batch`` (the loss of each
minibatch taken over its first half) or ``flip_event`` (client 0's
trigger decision inverted).
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

FAULTS = (None, "half_batch", "flip_event")
#: Relative margin |d - delta| / max(|delta|, d) under which a trigger
#: decision is left to rounding (set from the chip's dist_gap readings,
#: PERF.md).
EVENT_BAND = 1e-2


def undecided(dist, delta):
    """Clients whose distance lies within ``EVENT_BAND`` of their
    threshold (numpy or jnp arrays)."""
    return abs(dist - delta) < EVENT_BAND * _maximum(abs(delta), dist)


def _maximum(a, b):
    return jnp.maximum(a, b) if isinstance(a, jax.Array) else \
        np.maximum(a, b)


def capacity(cfg: dict) -> tuple[int, int]:
    """(c_min, C): C = ceil(slack L N) solve slots, the commit limit
    never below ceil(L N)."""
    n, rate = cfg["n_clients"], cfg["participation"]
    c_max = max(1, min(math.ceil(cfg["capacity_slack"] * rate * n), n))
    c_min = max(1, min(math.ceil(rate * n), c_max))
    return c_min, c_max


def epoch_indices(key, n_points: int, batch: int, epochs: int):
    batch = min(batch, n_points)
    per_epoch = n_points // batch
    keys = jax.random.split(key, epochs)
    perms = jax.vmap(lambda k: jax.random.permutation(k, n_points)[
        :per_epoch * batch].reshape(per_epoch, batch))(keys)
    return perms.reshape(epochs * per_epoch, batch)


def init_state(cfg: dict, params0, seed: int, dtype=jnp.float32) -> dict:
    """Alg. 2's start: theta_i = z_i = omega = params0, lambda_i = 0,
    delta = delta0, L = 0; an empty queue whose demand estimate is 1."""
    n = cfg["n_clients"]
    flat, _ = ravel_pytree(params0)
    flat = flat.astype(dtype)
    return {
        "theta": jnp.tile(flat[None], (n, 1)),
        "lam": jnp.zeros((n, flat.shape[0]), dtype),
        "z": jnp.tile(flat[None], (n, 1)),
        "omega": flat,
        "delta": jnp.full((n,), cfg["delta0"], jnp.float32),
        "load": jnp.zeros((n,), jnp.float32),
        "age": jnp.zeros((n,), jnp.int32),
        "qload": jnp.ones((n,), jnp.float32),
        "rng": jax.random.PRNGKey(seed),
    }


def make_round(model, cfg: dict, data: dict, layout, params0, *,
               dtype=jnp.float32, precision: str = "highest",
               fault: str | None = None):
    """Jitted ``round(state, arrivals) -> (state, metrics)``, which
    donates the state it is given; pass
    ``arrivals=None`` for a synchronous round.  ``precision`` is the
    matmul precision of a float32 round (the control of a
    configuration run at "highest" is the same round at "high")."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    n = cfg["n_clients"]
    c_min, cap = capacity(cfg)
    alpha, gain = cfg["alpha"], cfg["K"]
    target = jnp.float32(cfg["participation"])
    rho, lr, mom = cfg["rho"], cfg["lr"], cfg["momentum"]
    batch, epochs = cfg["batch_size"], cfg["epochs"]
    p0 = jax.tree.map(lambda a: a.astype(dtype), params0)
    _, unravel = ravel_pytree(p0)
    x_in = data["x"].astype(dtype)
    y_in = data["y"]
    if layout is None:
        n_points = x_in.shape[1]
        sizes_in = offsets_in = None
    else:
        sizes_in = jnp.asarray(layout[0], jnp.int32)
        offsets_in = jnp.asarray(layout[1], jnp.int32)
        n_points = int(np.max(layout[0]))

    def batch_loss(p, xb, yb, w):
        if fault == "half_batch":
            half = xb.shape[0] // 2
            xb, yb, w = xb[:half], yb[:half], w[:half]
        logp = jax.nn.log_softmax(model.reference_logits(p, xb, cfg))
        nll = -jnp.take_along_axis(logp, yb[:, None], axis=1)[:, 0]
        return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)

    grad_fn = jax.value_and_grad(batch_loss)

    def solve(theta0, center, key, xs, ys, size):
        idx = epoch_indices(key, n_points, batch, epochs)
        c = unravel(center)

        def step(carry, idx_b):
            p, buf = carry
            if size is None:
                w = jnp.ones(idx_b.shape, dtype)
                live = jnp.bool_(True)
                rows = idx_b
            else:
                w = (idx_b < size).astype(dtype)
                live = jnp.sum(w) > 0
                rows = jnp.minimum(idx_b, size - 1)
            loss, g = grad_fn(p, xs[rows], ys[rows], w)
            g = jax.tree.map(lambda gl, pl, cl: gl + rho * (pl - cl),
                             g, p, c)
            buf_new = jax.tree.map(lambda b, gl: mom * b + gl, buf, g)
            p_new = jax.tree.map(lambda pl, b: pl - lr * b, p, buf_new)
            keep = lambda new, old: jnp.where(live, new, old)  # noqa: E731
            return ((jax.tree.map(keep, p_new, p),
                     jax.tree.map(keep, buf_new, buf)),
                    (loss, live))

        p = unravel(theta0)
        buf = jax.tree.map(jnp.zeros_like, p)
        (p, _), (losses, lives) = jax.lax.scan(step, (p, buf), idx)
        lives = lives.astype(jnp.float32)
        mean = (jnp.sum(losses.astype(jnp.float32) * lives)
                / jnp.maximum(jnp.sum(lives), 1.0))
        return ravel_pytree(p)[0].astype(dtype), mean

    def round_fn(st, arrivals, hint, x, y, sizes, offsets):
        rng, _, data_rng = jax.random.split(st["rng"], 3)
        diff = (st["z"] - st["omega"][None]).astype(dtype)
        dist = jnp.sqrt(jnp.sum(diff * diff, axis=1)).astype(jnp.float32)
        events = dist >= st["delta"]
        if hint is not None:
            # Where the distance lies within rounding of its threshold
            # the reference cannot decide: take the compared run's
            # decision there (check.EVENT_BAND).
            events = jnp.where(undecided(dist, st["delta"]), hint, events)
        if arrivals is not None:
            events = events & arrivals
        if fault == "flip_event":
            events = events.at[0].set(~events[0])
        fired = events.astype(jnp.float32)
        load = (1.0 - alpha) * st["load"] + alpha * fired
        delta = st["delta"] + gain * (st["load"] - target)

        age = st["age"]
        demand = events | (age > 0)
        order = jnp.lexsort((jnp.arange(n, dtype=jnp.int32), -dist, -age,
                             ~demand)).astype(jnp.int32)
        idx = order[:cap]
        limit = jnp.clip(jnp.ceil(jnp.sum(st["qload"])).astype(jnp.int32),
                         c_min, cap)
        num_demand = jnp.sum(demand.astype(jnp.int32))
        valid = jnp.arange(cap) < jnp.minimum(num_demand, limit)
        rank = jnp.zeros((n,), jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32))
        committed = demand & (rank < limit)
        new_age = jnp.where(demand & ~committed, age + 1, 0).astype(
            jnp.int32)
        qload = (1.0 - alpha) * st["qload"] + alpha * demand.astype(
            jnp.float32)

        keys = jax.random.split(data_rng, n)[idx]
        omega = st["omega"]
        lam_rows = st["lam"][idx] + st["theta"][idx] - omega[None]
        center = omega[None] - lam_rows
        theta0 = jnp.broadcast_to(omega, (cap,) + omega.shape)
        if sizes is None:
            out, losses = jax.vmap(
                lambda t, c, k, xs, ys: solve(t, c, k, xs, ys, None))(
                theta0, center, keys, x[idx], y[idx])
        else:
            def slot(t, c, k, off, size):
                xs = jax.lax.dynamic_slice_in_dim(x, off, n_points, 0)
                ys = jax.lax.dynamic_slice_in_dim(y, off, n_points, 0)
                return solve(t, c, k, xs, ys, size)
            out, losses = jax.vmap(slot)(theta0, center, keys,
                                         offsets[idx], sizes[idx])
        drop = jnp.where(valid, idx, n)
        theta = st["theta"].at[drop].set(out, mode="drop")
        lam = st["lam"].at[drop].set(lam_rows, mode="drop")
        z = st["z"].at[drop].set(out + lam_rows, mode="drop")
        new = dict(st, theta=theta, lam=lam, z=z,
                   omega=jnp.mean(z.astype(jnp.float32), axis=0).astype(
                       dtype),
                   delta=delta, load=load, age=new_age, qload=qload, rng=rng)
        vf = valid.astype(jnp.float32)
        metrics = {"events": events, "distances": dist, "delta": delta,
                   "committed": committed,
                   "train_loss": jnp.sum(losses * vf)
                   / jnp.maximum(jnp.sum(vf), 1.0)}
        return new, metrics

    # The data are arguments, never constants baked into the program.
    # The state is donated: a round writes its new state over the old
    # one, so a federation whose (N, D) state fills most of the chip
    # holds it once, not twice.
    jitted = jax.jit(round_fn, donate_argnums=0)

    def run(st, arrivals=None, hint=None):
        ctx = (jax.default_matmul_precision(precision)
               if dtype == jnp.float32 else contextlib.nullcontext())
        with ctx:
            return jitted(st, arrivals, hint, x_in, y_in, sizes_in,
                          offsets_in)

    return run
