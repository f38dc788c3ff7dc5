"""Host-offloaded state backend (core/hoststate.py): bit-exact parity
with the device engine, streaming-byte accounting, and device-memory
footprint.

The parity matrix is the backend's contract: with the same config the
host backend must reproduce the device engine *bit for bit* — event
decisions AND the fp32 state (ω, θ, λ, z_prev, the EF residual, the
async park buffers) — across {sync, async} × {uniform, ragged} ×
{fused, unfused} at small N.  Tiling the H2D row stream must never
change bits (tiles concatenate back to the same (C, D) working set
inside one program), and the measured per-round transfer bytes must
match the planned model exactly.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ControllerConfig,
    FLConfig,
    HostState,
    init_state,
    make_flat_spec,
    make_round_fn,
    pool_data,
    run_rounds,
)
from repro.data import make_least_squares

N = 12
POINTS = 6
DIM = 4


def _cfg(**kw):
    base = dict(algorithm="fedback", n_clients=N, participation=0.5,
                rho=1.0, lr=0.1, momentum=0.0, epochs=2, batch_size=3,
                controller=ControllerConfig(K=0.2, alpha=0.9),
                compact=True)
    base.update(kw)
    return FLConfig(**base)


def _problem(ragged_kind="none"):
    data, params0, ls = make_least_squares(N, POINTS, DIM)
    spec = make_flat_spec(params0)
    if ragged_kind == "none":
        return data, params0, ls, spec, None
    sizes = ([POINTS] * N if ragged_kind == "uniform"
             else [2 + (i % 4) for i in range(N)])
    xs = [np.asarray(data["x"][i][:s]) for i, s in enumerate(sizes)]
    ys = [np.asarray(data["y"][i][:s]) for i, s in enumerate(sizes)]
    pooled, rspec = pool_data(xs, ys)
    return pooled, params0, ls, spec, rspec


def _run(cfg, data, params0, ls, spec, rspec, rounds=5):
    state = init_state(cfg, params0, spec=spec)
    round_fn = make_round_fn(cfg, ls, data, spec=spec, ragged=rspec)
    events = []
    for _ in range(rounds):
        state, m = round_fn(state)
        events.append(np.asarray(m.events).astype(int).tolist())
    return state, events, round_fn


def _assert_bitexact(dev_st, host_st, *, compress=False, async_mode=False):
    for name in ("theta", "lam", "z_prev", "omega"):
        np.testing.assert_array_equal(
            np.asarray(getattr(dev_st, name)),
            np.asarray(getattr(host_st, name)), err_msg=name)
    if compress:
        np.testing.assert_array_equal(np.asarray(dev_st.comm),
                                      np.asarray(host_st.comm),
                                      err_msg="comm")
    if async_mode:
        for f in ("ttl", "hist", "theta", "lam", "z"):
            np.testing.assert_array_equal(
                np.asarray(getattr(dev_st.inflight, f)),
                np.asarray(getattr(host_st.inflight, f)),
                err_msg=f"inflight.{f}")


class TestHostParity:
    """Host backend ≡ device backend, bit for bit."""

    @pytest.mark.parametrize("sync", ["sync", "async"])
    @pytest.mark.parametrize("ragged_kind", ["uniform", "masked"])
    @pytest.mark.parametrize("fused", [False, True])
    def test_parity_matrix(self, sync, ragged_kind, fused):
        data, params0, ls, spec, rspec = _problem(ragged_kind)
        cfg = _cfg(max_staleness=(2 if sync == "async" else None),
                   fused_gss=fused)
        dev_st, dev_ev, _ = _run(cfg, data, params0, ls, spec, rspec)
        host_st, host_ev, _ = _run(
            dataclasses.replace(cfg, state_backend="host"),
            data, params0, ls, spec, rspec)
        assert dev_ev == host_ev
        _assert_bitexact(dev_st, host_st, async_mode=(sync == "async"))

    def test_parity_rectangular_data(self):
        """Non-ragged (N, n, ...) data path (slot gather on device)."""
        data, params0, ls, spec, _ = _problem("none")
        cfg = _cfg()
        dev_st, dev_ev, _ = _run(cfg, data, params0, ls, spec, None)
        host_st, host_ev, _ = _run(
            dataclasses.replace(cfg, state_backend="host"),
            data, params0, ls, spec, None)
        assert dev_ev == host_ev
        _assert_bitexact(dev_st, host_st)

    def test_parity_compressed_consensus(self):
        """EF residual streams through the full-width server pass."""
        data, params0, ls, spec, _ = _problem("none")
        cfg = _cfg(consensus_compress="int8")
        dev_st, dev_ev, _ = _run(cfg, data, params0, ls, spec, None)
        host_st, host_ev, _ = _run(
            dataclasses.replace(cfg, state_backend="host"),
            data, params0, ls, spec, None)
        assert dev_ev == host_ev
        _assert_bitexact(dev_st, host_st, compress=True)

    def test_parity_fedavg(self):
        """Non-ADMM family: participant mean, λ stays zero."""
        data, params0, ls, spec, _ = _problem("none")
        cfg = _cfg(algorithm="fedavg", rho=0.0)
        dev_st, dev_ev, _ = _run(cfg, data, params0, ls, spec, None)
        host_st, host_ev, _ = _run(
            dataclasses.replace(cfg, state_backend="host"),
            data, params0, ls, spec, None)
        assert dev_ev == host_ev
        _assert_bitexact(dev_st, host_st)

    def test_tiling_never_changes_bits(self):
        """stream_tiles is copy granularity only: the tiles concatenate
        back to one (C, D) working set inside the solve program."""
        data, params0, ls, spec, _ = _problem("none")
        states = []
        for tiles in (1, 4):
            cfg = _cfg(state_backend="host", stream_tiles=tiles)
            st, _, _ = _run(cfg, data, params0, ls, spec, None)
            states.append(st)
        _assert_bitexact(states[0], states[1])

    def test_metrics_match_device(self):
        """Scalar round metrics agree (the trace consumers read these).

        Everything is exact except ``train_loss``, which may differ by
        one ulp: the per-slot losses are bit-identical, but the device
        reduces their masked sum inside the fused round program and the
        host backend inside its aggregate program, and XLA may order
        that 12-term fp32 sum differently in the two (the same sum
        reduced sequentially in both programs agrees bit for bit).
        """
        data, params0, ls, spec, _ = _problem("none")
        cfg = _cfg()

        def trace(c):
            st = init_state(c, params0, spec=spec)
            fn = make_round_fn(c, ls, data, spec=spec)
            rows, losses = [], []
            for _ in range(4):
                st, m = fn(st)
                rows.append((int(m.num_events), int(m.num_deferred),
                             int(m.realized_capacity),
                             float(m.realized_slack),
                             np.asarray(m.distances).tolist(),
                             np.asarray(m.committed).tolist()))
                losses.append(np.float32(m.train_loss))
            return rows, np.asarray(losses, np.float32)

        dev_rows, dev_loss = trace(cfg)
        host_rows, host_loss = trace(
            dataclasses.replace(cfg, state_backend="host"))
        assert dev_rows == host_rows
        np.testing.assert_array_max_ulp(dev_loss, host_loss, maxulp=1)

    def test_run_rounds_compatible(self):
        """The generic trace driver works unchanged on the host backend."""
        data, params0, ls, spec, _ = _problem("none")
        cfg = _cfg(state_backend="host")
        state = init_state(cfg, params0, spec=spec)
        round_fn = make_round_fn(cfg, ls, data, spec=spec)
        state, hist = run_rounds(round_fn, state, 3)
        assert isinstance(state, HostState)
        assert np.asarray(hist.num_events).shape == (3,)


class TestHostDispatch:
    def test_init_returns_host_state(self):
        data, params0, ls, spec, _ = _problem("none")
        st = init_state(_cfg(state_backend="host"), params0, spec=spec)
        assert isinstance(st, HostState)
        assert isinstance(st.theta, np.ndarray)
        assert st.distances is None  # lazy until the first round

    def test_unknown_backend_rejected(self):
        data, params0, ls, spec, _ = _problem("none")
        with pytest.raises(ValueError, match="unknown state_backend"):
            init_state(_cfg(state_backend="tpu"), params0, spec=spec)
        with pytest.raises(ValueError, match="unknown state_backend"):
            make_round_fn(_cfg(state_backend="tpu"), ls, data, spec=spec)

    def test_host_needs_flat_and_compact(self):
        data, params0, ls, spec, _ = _problem("none")
        with pytest.raises(ValueError, match="flat"):
            init_state(_cfg(state_backend="host"), params0)
        with pytest.raises(ValueError, match="compact"):
            init_state(_cfg(state_backend="host", compact=False),
                       params0, spec=spec)
        with pytest.raises(ValueError, match="compact"):
            make_round_fn(_cfg(state_backend="host", compact=False),
                          ls, data, spec=spec)

    def test_host_rejects_mesh(self):
        data, params0, ls, spec, _ = _problem("none")
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("clients",))
        with pytest.raises(ValueError, match="single-host"):
            make_round_fn(_cfg(state_backend="host"), ls, data,
                          spec=spec, mesh=mesh)


class TestStreamingBytes:
    def test_measured_bytes_match_plan_model(self):
        data, params0, ls, spec, _ = _problem("none")
        cfg = _cfg(state_backend="host")
        st, _, fn = _run(cfg, data, params0, ls, spec, None, rounds=5)
        planned = fn.planned_bytes
        # Row-stream legs: exactly the planned C-row traffic per round.
        assert fn.stats["h2d_row_bytes"] == 5 * planned["row_stream_h2d"]
        assert fn.stats["d2h_row_bytes"] == 5 * planned["row_stream_d2h"]
        # One full-width server pass per round, plus the one-shot lazy
        # trigger seed on the first call.
        assert fn.stats["h2d_full_bytes"] == \
            (5 + 1) * planned["server_pass_h2d"]
        assert fn.stats["d2h_full_bytes"] == 5 * planned["server_pass_d2h"]
        # The streamed rows stay within the budgeted envelope.
        assert (planned["row_stream_h2d"] + planned["row_stream_d2h"]
                <= planned["row_stream_budget"])

    def test_persistent_device_bytes_are_o_n_not_o_nd(self):
        """Between rounds, no (N, D) client matrix is device-resident:
        the persistent device state is O(N) vectors + the (D,) ω."""
        data, params0, ls, spec, _ = _problem("none")
        cfg = _cfg(state_backend="host", consensus_compress="int8",
                   max_staleness=2)
        st, _, fn = _run(cfg, data, params0, ls, spec, None, rounds=3)
        n, d = N, spec.dim
        # 4 host matrices (θ, λ, z, comm) + 3 park buffers.
        assert st.host_state_bytes() == 7 * n * d * 4
        # Device: ω (D) + distances (N) + ctrl/queue/delay/ttl/hist/rng
        # vectors — all O(N) + O(D), strictly below ONE (N, D) matrix.
        assert st.device_state_bytes() < n * d * 4 + 64 * n

    def test_live_device_memory_stays_o_cd(self):
        stats_fn = getattr(jax.local_devices()[0], "memory_stats", None)
        stats = stats_fn() if stats_fn is not None else None
        if not stats or "bytes_in_use" not in stats:
            pytest.skip("allocator memory_stats unavailable (CPU)")
        data, params0, ls, spec, _ = _problem("none")
        cfg = _cfg(state_backend="host")
        baseline = stats_fn()["bytes_in_use"]
        st, _, fn = _run(cfg, data, params0, ls, spec, None, rounds=3)
        live = stats_fn()["bytes_in_use"] - baseline
        n, d = N, spec.dim
        cap = fn.static_info["capacity"]
        # Working set + persistent vectors + data + slack: far below
        # the 3·N·D·4 the device backend would keep resident.
        bound = (8 * cap * d * 4 + st.device_state_bytes()
                 + int(np.asarray(data["x"]).nbytes)
                 + int(np.asarray(data["y"]).nbytes) + (1 << 20))
        assert live <= bound, (live, bound)


class TestHostStateContainer:
    def test_checkpoint_tree_leaves_stay_numpy(self):
        """to_checkpoint_tree must hand the store host buffers directly
        — no device round-trip of the (N, D) matrices."""
        data, params0, ls, spec, _ = _problem("none")
        cfg = _cfg(state_backend="host", consensus_compress="int8")
        st = init_state(cfg, params0, spec=spec)
        tree = st.to_checkpoint_tree()
        for leaf in (tree.theta, tree.lam, tree.z_prev, tree.comm):
            assert isinstance(leaf, np.ndarray)

    def test_fused_flag_validation_mirrors_device(self):
        data, params0, ls, spec, _ = _problem("none")
        cfg = _cfg(state_backend="host", algorithm="fedavg", rho=0.0,
                   fused_gss=True)
        with pytest.raises(ValueError, match="fused_gss"):
            make_round_fn(cfg, ls, data, spec=spec)
