"""Device-resident round engine (core/engine.py): chunking equivalence and
donation safety.

The contract: a chunked ``run_rounds`` trace matches the per-round Python
loop — same per-round rows, same final state — in BOTH runtimes, including
the carried comm state and cross-round AA history. The engine's scan body
applies the round unconditionally and selects the carried state (see the
module docstring for why not lax.cond), which keeps the chunked rounds
BIT-exact with the sequential jit on this container; the tests assert the
documented rtol 1e-6 so an ulp-level fusion change in a future jax doesn't
flake them.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AAConfig,
    AlgoHParams,
    init_state,
    make_chunk_runner,
    make_round_fn,
    run_federated,
    run_rounds,
    solve_reference,
)
from repro.core.sharded import make_sharded_round_fn
from repro.data import make_binary_classification, partition
from repro.launch.mesh import make_host_mesh
from repro.models.logreg import make_logreg_problem


@pytest.fixture(scope="module")
def setup():
    X, y = make_binary_classification("synthetic_small", n=400, seed=0)
    clients = partition(X, y, num_clients=8, scheme="iid")
    prob = make_logreg_problem(clients, gamma=1e-3)
    wstar = solve_reference(prob, iters=50)
    return prob, wstar, make_host_mesh()


def _round_fn(prob, mesh, algo, hp, runtime, channel=None):
    if runtime == "sharded":
        return make_sharded_round_fn(algo, prob, hp, mesh, channel=channel)
    return make_round_fn(algo, prob, hp, channel)


def assert_tree_allclose(a, b, rtol=1e-6, atol=1e-7, what=""):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=rtol, atol=atol, err_msg=what
        )


def _history_equiv(prob, wstar, algo, hp, runtime, rounds, chunk,
                   channel=None, **kw):
    h0 = run_federated(prob, algo, hp, rounds, w_star=wstar, runtime=runtime,
                       channel=channel, **kw)
    h1 = run_federated(prob, algo, hp, rounds, w_star=wstar, runtime=runtime,
                       channel=channel, chunk=chunk, **kw)
    what = f"{algo}/{runtime}/chunk={chunk}"
    assert len(h0.rounds) == len(h1.rounds), what
    np.testing.assert_allclose(h1.loss, h0.loss, rtol=1e-6, err_msg=what)
    np.testing.assert_allclose(h1.grad_norm, h0.grad_norm, rtol=1e-6,
                               atol=1e-9, err_msg=what)
    np.testing.assert_allclose(h1.rel_error, h0.rel_error, rtol=1e-5,
                               atol=1e-9, err_msg=what)
    np.testing.assert_allclose(h1.comm_bytes, h0.comm_bytes, rtol=1e-6,
                               err_msg=what)
    tm0, tm1 = h0.theta_mean, h1.theta_mean
    np.testing.assert_array_equal(np.isnan(tm0), np.isnan(tm1), err_msg=what)
    np.testing.assert_allclose(tm1[~np.isnan(tm1)], tm0[~np.isnan(tm0)],
                               rtol=1e-4, err_msg=what)
    assert_tree_allclose(h0.final_params, h1.final_params, what=what)
    return h0, h1


class TestChunkingEquivalence:
    @pytest.mark.parametrize("runtime", ["vmap", "sharded"])
    @pytest.mark.parametrize("algo", ["fedosaa_svrg", "fedosaa_scaffold",
                                      "fedsvrg", "giant"])
    def test_trace_matches_loop(self, setup, algo, runtime):
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        _history_equiv(prob, wstar, algo, hp, runtime, rounds=7, chunk=3)

    @pytest.mark.parametrize("runtime", ["vmap", "sharded"])
    def test_comm_state_matches_loop(self, setup, runtime):
        """The carried comm state (int8 EF residuals + diff-coding refs)
        must round-trip through the donated scan identically — compared
        buffer-for-buffer after the same number of rounds."""
        prob, wstar, mesh = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = _round_fn(prob, mesh, "fedosaa_svrg", hp, runtime, channel="int8")
        jf = jax.jit(rf)
        s_loop = init_state(prob, jax.random.PRNGKey(0), hp, "int8",
                            "fedosaa_svrg")
        for _ in range(6):
            s_loop, _ = jf(s_loop)
        s_eng, trace = run_rounds(
            rf, init_state(prob, jax.random.PRNGKey(0), hp, "int8",
                           "fedosaa_svrg"), 6, chunk=4, w_star=wstar)
        assert trace.num_rounds == 6
        assert s_loop.comm is not None
        assert_tree_allclose(s_loop.comm, s_eng.comm, what="comm state")
        assert_tree_allclose(s_loop.params, s_eng.params, what="params")

    @pytest.mark.parametrize("runtime", ["vmap", "sharded"])
    def test_carry_history_matches_loop(self, setup, runtime):
        """Cross-round AA history (App. A opt. 1) rides the scan carry."""
        prob, wstar, mesh = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3, carry_history=2,
                         aa=AAConfig(tikhonov=1e-6, damping=0.7))
        rf = _round_fn(prob, mesh, "fedosaa_svrg", hp, runtime)
        jf = jax.jit(rf)
        s_loop = init_state(prob, jax.random.PRNGKey(0), hp, None,
                            "fedosaa_svrg")
        for _ in range(5):
            s_loop, _ = jf(s_loop)
        s_eng, trace = run_rounds(
            rf, init_state(prob, jax.random.PRNGKey(0), hp, None,
                           "fedosaa_svrg"), 5, chunk=2, w_star=wstar)
        assert trace.num_rounds == 5
        assert s_loop.hist_s is not None
        assert_tree_allclose(s_loop.hist_s, s_eng.hist_s, what="hist_s")
        assert_tree_allclose(s_loop.hist_y, s_eng.hist_y, what="hist_y")
        assert_tree_allclose(s_loop.params, s_eng.params, what="params")

    def test_early_stop_same_round(self, setup):
        """A stop criterion firing mid-chunk truncates the trace at the SAME
        round as the loop's break, and never advances the state past it."""
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        h0, h1 = _history_equiv(prob, wstar, "fedosaa_svrg", hp, "vmap",
                                rounds=30, chunk=7, stop_rel_error=0.09)
        # the target must actually fire mid-run for this test to bite
        assert len(h0.rounds) < 30
        assert h0.rel_error[-1] < 0.09

    def test_grad_norm_stop(self, setup):
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        h0, h1 = _history_equiv(prob, wstar, "fedsvrg", hp, "vmap",
                                rounds=30, chunk=8, stop_grad_norm=0.05)
        assert len(h0.rounds) < 30

    def test_partial_final_chunk(self, setup):
        """num_rounds not divisible by chunk: the short final chunk reuses
        the same executable via n_live and drops the padding rows."""
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        _history_equiv(prob, wstar, "fedosaa_svrg", hp, "vmap",
                       rounds=5, chunk=4)

    def test_chunk_larger_than_rounds(self, setup):
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        _history_equiv(prob, wstar, "fedsvrg", hp, "vmap", rounds=3, chunk=16)


class TestDonationSafety:
    def test_input_state_is_consumed(self, setup):
        """donate=True consumes the caller's state buffers (the documented
        engine contract): XLA reuses the K×d client buffers in place."""
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = make_round_fn("fedosaa_svrg", prob, hp)
        state = init_state(prob, jax.random.PRNGKey(0), hp, None,
                           "fedosaa_svrg")
        out_state, _ = run_rounds(rf, state, 2, chunk=2, w_star=wstar)
        assert any(leaf.is_deleted() for leaf in jax.tree.leaves(state))
        assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(out_state))

    def test_never_reads_consumed_buffer(self, setup):
        """Multi-chunk runs (state re-donated every chunk) and a second
        run_rounds on the returned state: if the engine ever re-read a
        donated buffer, jax would raise 'Array has been deleted'."""
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = make_round_fn("fedosaa_svrg", prob, hp)
        state = init_state(prob, jax.random.PRNGKey(0), hp, None,
                           "fedosaa_svrg")
        state, trace = run_rounds(rf, state, 6, chunk=2, w_star=wstar)
        assert trace.num_rounds == 6
        jax.block_until_ready(jax.tree.leaves(state.params))
        state, trace2 = run_rounds(rf, state, 4, chunk=2, w_star=wstar)
        assert trace2.num_rounds == 4
        assert np.isfinite(trace2.loss).all()

    def test_runner_second_call_after_block(self, setup):
        """The raw chunk runner: block_until_ready between calls, feed the
        returned state back — the donated executable must never alias a
        buffer the host still reads."""
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = make_round_fn("fedosaa_svrg", prob, hp)
        runner = make_chunk_runner(rf, 3, w_star=wstar)
        state = init_state(prob, jax.random.PRNGKey(0), hp, None,
                           "fedosaa_svrg")
        state, done, ms, rels, lives = runner(state, np.int32(3))
        jax.block_until_ready(jax.tree.leaves(state.params))
        loss1 = np.asarray(jax.device_get(ms.loss))
        state, done, ms, rels, lives = runner(state, np.int32(3))
        loss2 = np.asarray(jax.device_get(ms.loss))
        assert np.isfinite(loss1).all() and np.isfinite(loss2).all()
        # monotone decrease across the chunk boundary: the second chunk
        # really continued from the first chunk's final state
        assert loss2[0] < loss1[0]

    def test_w0_not_consumed_by_engine_path(self, setup):
        """run_federated(w0=..., chunk=...) must COPY the caller's w0 into
        the donated state — the same w0 arrays stay usable across calls."""
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        w0 = prob.init(jax.random.PRNGKey(7))
        h1 = run_federated(prob, "fedsvrg", hp, 3, w_star=wstar, w0=w0,
                           chunk=2)
        assert not any(l.is_deleted() for l in jax.tree.leaves(w0))
        h2 = run_federated(prob, "fedosaa_svrg", hp, 3, w_star=wstar, w0=w0,
                           chunk=2)
        assert np.isfinite(h1.loss).all() and np.isfinite(h2.loss).all()

    def test_donate_false_preserves_input(self, setup):
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = make_round_fn("fedsvrg", prob, hp)
        state = init_state(prob, jax.random.PRNGKey(0), hp, None, "fedsvrg")
        _, trace = run_rounds(rf, state, 2, chunk=2, w_star=wstar,
                              donate=False)
        assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(state))
        # the preserved input is still usable
        _, trace2 = run_rounds(rf, state, 2, chunk=2, w_star=wstar,
                               donate=False)
        np.testing.assert_allclose(trace2.loss, trace.loss, rtol=1e-6)


class TestEngineMechanics:
    def test_rejects_bad_chunk(self, setup):
        prob, _, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = make_round_fn("fedsvrg", prob, hp)
        with pytest.raises(ValueError, match="chunk"):
            make_chunk_runner(rf, 0)

    def test_run_federated_rejects_chunk_zero(self, setup):
        """The CLIs map 0 to None (per-round loop); a direct chunk=0 must
        error rather than silently picking a path."""
        prob, _, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        with pytest.raises(ValueError, match="chunk"):
            run_federated(prob, "fedsvrg", hp, 2, chunk=0)

    def test_wall_time_monotone_and_rows_cumulative(self, setup):
        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        h = run_federated(prob, "fedsvrg", hp, 6, w_star=wstar, chunk=3)
        assert (np.diff(h.wall_time) > 0).all()
        assert (np.diff(h.comm_bytes) > 0).all()
        np.testing.assert_array_equal(h.rounds, np.arange(6))

    def test_single_dispatch_per_chunk(self, setup):
        """The whole chunk lowers as ONE XLA computation containing the
        scan: B rounds = one dispatch."""
        prob, _, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = make_round_fn("fedosaa_svrg", prob, hp)
        runner = make_chunk_runner(rf, 4, donate=False)
        state = init_state(prob, jax.random.PRNGKey(0), hp, None,
                           "fedosaa_svrg")
        txt = runner.lower(state, np.int32(4)).compile().as_text()
        assert "while" in txt  # the rounds live in one compiled scan loop

    def test_computed_rounds_and_compiles_per_chunk(self, setup):
        """RoundTrace counts the scan slots dispatched (chunk × chunks, the
        slots past the stop included) and the backend compiles each chunk
        caused: the first chunk compiles the runner, later chunks and a
        second run on the same runner compile nothing; the footer carries
        the run's total."""
        from repro.obs import MemorySink

        prob, wstar, _ = setup
        hp = AlgoHParams(eta=0.5, local_epochs=3)
        rf = make_round_fn("fedosaa_svrg", prob, hp)
        runner = make_chunk_runner(rf, 5, w_star=wstar, stop_rel_error=1e-3)

        def run():
            sink = MemorySink()
            state = init_state(prob, jax.random.PRNGKey(0), hp, None,
                               "fedosaa_svrg")
            _, trace = run_rounds(rf, state, 40, chunk=5, runner=runner,
                                  sinks=[sink])
            return trace, sink.footer

        first, footer = run()
        assert first.stopped and first.num_rounds % 5 != 0
        chunks = -(-first.num_rounds // 5)
        assert len(first.chunk_compiles) == chunks
        assert first.computed_rounds == 5 * chunks > first.num_rounds
        assert first.chunk_compiles[0] >= 1
        assert not first.chunk_compiles[1:].any()
        assert footer["compiles"] == first.chunk_compiles.sum()
        again, footer = run()
        assert again.computed_rounds == first.computed_rounds
        assert not again.chunk_compiles.any() and footer["compiles"] == 0
