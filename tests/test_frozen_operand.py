"""A problem's frozen operand (FLProblem.frozen: an LM's base under low-rank
adapters, core/lm.py) enters the round and the chunk runner as an argument,
and a problem without one compiles exactly as before.

Also the adapters' flat layout and the full-batch SVRG trajectory that
reuses each client's anchor gradient.
"""
import hashlib
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import (
    AlgoHParams,
    ClientBatch,
    init_state,
    make_chunk_runner,
    make_round_fn,
    run_federated,
    run_rounds,
)
from repro.core import algorithms as A
from repro.core.lm import LORA_TARGETS, make_lm_clients, make_lm_problem, \
    make_lora
from repro.core.sharded import make_sharded_round_fn
from repro.data import make_binary_classification, make_lm_tokens, partition
from repro.launch.mesh import make_host_mesh
from repro.models.decoder import build_model
from repro.models.logreg import make_logreg_problem

#: sha256 of the chunk runner's compiled HLO below, debug locations
#: stripped, as the program compiled it before problems could carry a
#: frozen operand: a problem without one (the covtype cells: fused local
#: kernel and AA kernels) must compile to the same program
COVTYPE_RUNNER_DIGEST = (
    "f902bd539250e3ec08f704e7dd61eae1c38500ebc047b92c420c6daaa8e475f5")


def _strip_debug(text: str) -> str:
    """The HLO text without its source locations (file names, functions,
    line tables and each op's stack frame id), which move with any edit."""
    out, skip = [], False
    for line in text.splitlines():
        if re.fullmatch(r"FileNames|FunctionNames|FileLocations|StackFrames",
                        line):
            skip = True
            continue
        if skip and re.match(r"\d+ ", line):
            continue
        skip = False
        out.append(re.sub(r" stack_frame_id=\d+", "", line))
    return "\n".join(out)


def test_runner_of_a_problem_without_operand_is_unchanged():
    X, y = make_binary_classification("covtype", n=2000, seed=3)
    prob = make_logreg_problem(partition(X, y, num_clients=4, scheme="iid"),
                               gamma=1e-3)
    hp = AlgoHParams(eta=1.0, local_epochs=10, local_impl="pallas",
                     aa_impl="pallas")
    rf = make_round_fn("fedosaa_svrg", prob, hp, "identity")
    runner = make_chunk_runner(rf, 5, w_star=jnp.ones(54),
                               stop_rel_error=1e-4)
    st = init_state(prob, jax.random.PRNGKey(1), hp, "identity",
                    "fedosaa_svrg")
    text = _strip_debug(runner.lower(st, np.int32(5)).compile().as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == COVTYPE_RUNNER_DIGEST


@pytest.fixture(scope="module")
def lm():
    cfg = get_arch("granite-4.0-h-micro").reduced()
    model = build_model(cfg, remat=True)
    base = jax.jit(model.init)(jax.random.PRNGKey(0))
    lora = make_lora(base, 4, 8)
    toks = make_lm_tokens(4, 64, cfg.vocab_size)
    prob = make_lm_problem(model, make_lm_clients(toks, 4), base, lora)
    return model, base, lora, prob


def test_frozen_round_matches_the_round_over_a_closed_over_base(lm):
    _, base, _, prob = lm
    hp = AlgoHParams(eta=8.0, local_epochs=2)
    st = init_state(prob, jax.random.PRNGKey(1), hp, None, "fedosaa_svrg")
    got, m = jax.jit(make_round_fn("fedosaa_svrg", prob, hp))(st, base)
    closed = prob.bind(base)
    assert closed.frozen is None
    want, m2 = jax.jit(make_round_fn("fedosaa_svrg", closed, hp))(st)
    # a closed-over base is a constant XLA folds into the round's fusions,
    # the operand is not: the two programs part at float32 rounding (1e-7
    # absolute after a round)
    np.testing.assert_allclose(np.asarray(got.params),
                               np.asarray(want.params), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(m.loss), float(m2.loss), rtol=1e-6)


def test_engine_and_loop_pass_the_operand(lm):
    """run_federated reaches the frozen operand through the engine
    (make_chunk_runner / run_rounds) and the per-round loop alike."""
    _, _, _, prob = lm
    hp = AlgoHParams(eta=8.0, local_epochs=2)
    loop = run_federated(prob, "fedosaa_svrg", hp, 3)
    chunked = run_federated(prob, "fedosaa_svrg", hp, 3, chunk=2)
    np.testing.assert_allclose(chunked.loss, loop.loss, rtol=1e-6)
    assert chunked.loss[-1] < chunked.loss[0]


def test_runner_takes_the_operand_each_call(lm):
    _, base, _, prob = lm
    hp = AlgoHParams(eta=8.0, local_epochs=2)
    rf = make_round_fn("fedosaa_svrg", prob, hp)
    runner = make_chunk_runner(rf, 2)
    st = init_state(prob, jax.random.PRNGKey(1), hp, None, "fedosaa_svrg")
    _, trace = run_rounds(rf, st, 2, chunk=2, runner=runner, frozen=base)
    assert trace.num_rounds == 2 and np.isfinite(trace.loss).all()
    # another base of the same shapes runs on the same executable
    other = jax.tree.map(lambda x: x * 0.5, base)
    st = init_state(prob, jax.random.PRNGKey(1), hp, None, "fedosaa_svrg")
    _, trace2 = run_rounds(rf, st, 2, chunk=2, runner=runner, frozen=other)
    assert trace2.chunk_compiles.sum() == 0
    assert trace2.loss[0] != trace.loss[0]


def test_sharded_runtime_refuses_a_frozen_operand(lm):
    _, _, _, prob = lm
    with pytest.raises(ValueError, match="frozen operand"):
        make_sharded_round_fn("fedosaa_svrg", prob, AlgoHParams(),
                              make_host_mesh())


def test_lora_layout_round_trips(lm):
    model, base, lora, _ = lm
    flat = lora.init(jax.random.PRNGKey(2))
    assert flat.shape == (lora.size,) and flat.dtype == jnp.float32
    pieces = lora.split(flat)
    again = jnp.concatenate([x.ravel() for ab in pieces for x in ab])
    np.testing.assert_array_equal(np.asarray(again), np.asarray(flat))
    for slot, (a, b) in zip(lora.slots, pieces):
        assert slot.name in LORA_TARGETS[slot.path[-1]]
        assert a.shape == slot.lead + (slot.d_in, lora.rank)
        assert not np.asarray(b).any()           # B = 0 at init
    merged = lora.merge(base, flat)
    i = next(i for i, s in enumerate(lora.slots)
             if s.path == ("groups", 0, "mixer") and s.name == "wx")
    a, b = merged["groups"][0]["mixer"]["wx_lora"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(pieces[i][0]))
    # B arrives scaled by alpha / rank
    assert lora.scale == 2.0 and b.shape == pieces[i][1].shape


@pytest.mark.parametrize("impl", ["tree", A.LOCAL_IMPL_SEED])
def test_full_batch_svrg_reuses_the_anchor_gradient(impl):
    """L gradient evaluations seeded with the anchor's own gradient give the
    trajectory of the 2(L+1)-evaluation form, each step corrected with the
    anchor gradient recomputed, in the folded scan and in the seed replay."""
    X, y = make_binary_classification("synthetic_small", n=400, seed=0)
    prob = make_logreg_problem(partition(X, y, num_clients=8, scheme="iid"),
                               gamma=1e-3)
    hp = AlgoHParams(eta=0.5, local_epochs=3, local_impl=impl)
    w = prob.init(jax.random.PRNGKey(0))
    g = prob.global_grad(w)
    batch = prob.clients.client(0)
    assert isinstance(batch, ClientBatch)

    def per_step(mb):
        return jax.tree.map(lambda a, b: a - b, g, prob.grad(w, mb))

    two = A._local_trajectory(
        replace(hp, local_impl="tree"), w,
        A._make_residual_fn(prob, hp, batch, per_step), jax.random.PRNGKey(1))
    one = A._svrg_trajectory(prob, hp, w, g, batch, jax.random.PRNGKey(1),
                             prob.grad(w, batch))
    for a, b in zip(jax.tree.leaves(two), jax.tree.leaves(one)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-7)


def test_fl_train_trains_adapters_through_the_engine(monkeypatch, tmp_path):
    from repro.launch import fl_train

    # an env-set cache directory is left to jax: the test turns no cache on
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    out = fl_train.main(["--arch", "granite-4.0-h-micro", "--reduced",
                         "--lora-rank", "4", "--round-chunk", "2",
                         "--rounds", "2", "--clients", "2",
                         "--local-epochs", "2", "--eta", "8",
                         "--out", str(tmp_path / "r.json")])
    curve = out["fedosaa_svrg"]["loss_curve"]
    assert len(curve) == 2 and np.isfinite(curve).all()
