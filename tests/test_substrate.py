"""Substrate tests: optimizers, schedules, checkpointing, data pipeline,
FL-LM bridge, centralized trainer step."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; the rest of the module runs
    from _hypothesis_stub import given, settings, strategies as st

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.data import make_binary_classification, make_lm_tokens, make_mnist_like, partition
from repro.optim import adamw, clip_by_global_norm, constant, cosine, sgd, wsd


def rosenbrock_params():
    return {"a": jnp.array([1.5, -0.5]), "b": {"c": jnp.array([0.3])}}


def quad_loss(p):
    flat = jnp.concatenate([p["a"], p["b"]["c"]])
    return jnp.sum((flat - jnp.array([1.0, 2.0, 3.0])) ** 2)


class TestOptim:
    @pytest.mark.parametrize("make", [lambda: sgd(0.1), lambda: sgd(0.05, momentum=0.9),
                                      lambda: adamw(0.1)])
    def test_converges_on_quadratic(self, make):
        opt = make()
        p = rosenbrock_params()
        state = opt.init(p)
        for _ in range(200):
            g = jax.grad(quad_loss)(p)
            p, state = opt.update(g, state, p)
        assert float(quad_loss(p)) < 1e-3

    def test_adamw_weight_decay_shrinks(self):
        opt = adamw(0.1, weight_decay=0.5)
        p = {"w": jnp.ones((4,)) * 10}
        state = opt.init(p)
        zero_g = {"w": jnp.zeros((4,))}
        for _ in range(20):
            p, state = opt.update(zero_g, state, p)
        assert float(jnp.abs(p["w"]).max()) < 10.0

    def test_clip_by_global_norm(self):
        g = {"a": jnp.ones((100,)) * 10}
        clipped = clip_by_global_norm(g, 1.0)
        n = float(jnp.linalg.norm(clipped["a"]))
        assert abs(n - 1.0) < 1e-5

    def test_schedules_shapes(self):
        for fn in (constant(1.0), cosine(1.0, 100, warmup=10), wsd(1.0, 100)):
            vals = [float(fn(jnp.asarray(s))) for s in range(0, 100, 7)]
            assert all(0 <= v <= 1.0 + 1e-6 for v in vals)

    def test_wsd_phases(self):
        fn = wsd(1.0, 1000, warmup_frac=0.01, decay_frac=0.1)
        assert float(fn(jnp.asarray(0))) < 0.2          # warmup start
        assert float(fn(jnp.asarray(500))) == pytest.approx(1.0)   # stable
        assert float(fn(jnp.asarray(999))) < 0.05       # decayed


class TestCheckpoint:
    def test_roundtrip(self):
        p = {"layer": {"w": jnp.arange(12.0).reshape(3, 4),
                       "b": jnp.ones((4,), jnp.bfloat16)},
             "head": jnp.zeros((2, 2), jnp.int32)}
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "ckpt")
            save_checkpoint(path, p, step=7)
            like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), p)
            restored = restore_checkpoint(path, like)
            for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a, np.float32),
                                              np.asarray(b, np.float32))

    def test_shape_mismatch_raises(self):
        p = {"w": jnp.ones((3,))}
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "ckpt")
            save_checkpoint(path, p)
            bad = {"w": jax.ShapeDtypeStruct((4,), jnp.float32)}
            with pytest.raises(AssertionError):
                restore_checkpoint(path, bad)


class TestData:
    def test_binary_datasets_match_fingerprint(self):
        for name, d in (("covtype", 54), ("w8a", 300)):
            X, y = make_binary_classification(name, n=500)
            assert X.shape == (500, d)
            assert set(np.unique(y)) <= {-1.0, 1.0}

    def test_w8a_class_imbalance(self):
        _, y = make_binary_classification("w8a", n=5000)
        pos = float((y > 0).mean())
        assert pos < 0.15          # w8a is ~3% positive

    def test_lm_tokens_in_range(self):
        toks = make_lm_tokens(4, 64, vocab=1000)
        assert toks.shape == (4, 64)
        assert toks.min() >= 0 and toks.max() < 1000

    def test_mnist_like_labels(self):
        X, y = make_mnist_like(n=200)
        assert X.shape == (200, 784)
        assert set(np.unique(y)) <= set(range(10))

    @settings(max_examples=10, deadline=None)
    @given(k=st.integers(2, 20), scheme=st.sampled_from(["iid", "imbalance", "label_skew"]))
    def test_property_partition_conserves_weight(self, k, scheme):
        X, y = make_binary_classification("synthetic_small", n=600, seed=1)
        clients = partition(X, y, num_clients=k, scheme=scheme)
        assert clients.num_clients == k
        np.testing.assert_allclose(float(clients.weight.sum()), 1.0, rtol=1e-5)
        # masked counts == weights * total
        counts = np.asarray(clients.mask.sum(axis=1))
        np.testing.assert_allclose(
            counts / counts.sum(), np.asarray(clients.weight), rtol=1e-4
        )

    def test_imbalance_is_imbalanced(self):
        X, y = make_binary_classification("synthetic_small", n=2000, seed=0)
        clients = partition(X, y, num_clients=10, scheme="imbalance")
        w = np.asarray(clients.weight)
        assert w.max() / w.min() > 20

    def test_imbalance_adversarial_counts(self):
        """Regression: the geometric tail used to round trailing clients to
        EMPTY slices at adversarial n/num_clients (the 2-sample floor then
        overdrew the total and the last clients got nothing). Every client
        must keep >= 2 samples and the counts must exactly cover n."""
        for n, k in ((60, 20), (101, 17), (2000, 30)):
            X, y = make_binary_classification("synthetic_small", n=n, seed=0)
            clients = partition(X, y, num_clients=k, scheme="imbalance")
            counts = np.asarray(clients.mask.sum(axis=1)).astype(int)
            assert counts.min() >= 2, (n, k, counts)
            assert counts.sum() == n, (n, k, counts)
        # below the documented floor the partitioner must refuse, not emit
        # empty clients
        X, y = make_binary_classification("synthetic_small", n=30, seed=0)
        with pytest.raises(ValueError, match="2 samples per client"):
            partition(X, y, num_clients=16, scheme="imbalance")


class TestLMBridge:
    def test_fl_lm_round_decreases_loss(self):
        from repro.configs import get_arch
        from repro.core import AlgoHParams, run_federated
        from repro.core.lm import make_lm_clients, make_lm_problem
        from repro.models.decoder import build_model

        cfg = get_arch("smollm-135m").reduced()
        model = build_model(cfg)
        toks = make_lm_tokens(8, 64, cfg.vocab_size)
        clients = make_lm_clients(toks, 2)
        problem = make_lm_problem(model, clients)
        h = run_federated(problem, "fedosaa_svrg",
                          AlgoHParams(eta=0.3, local_epochs=3), 3)
        assert h.loss[-1] < h.loss[0]
        assert np.isfinite(h.loss).all()

    @pytest.mark.parametrize("aa_impl", ["tree", "pallas"])
    def test_bf16_params_keep_their_dtype(self, aa_impl):
        """Published widths train in bf16: the local step's f32 terms (the
        aggregated global gradient) must not promote the iterate, or the
        trajectory scan and the engine's round scan refuse the carry."""
        import dataclasses

        from repro.configs import get_arch
        from repro.core import (AlgoHParams, init_state, make_round_fn,
                                run_federated)
        from repro.core.lm import make_lm_clients, make_lm_problem
        from repro.models.decoder import build_model

        # one small layer: the interpret-mode AA kernels sweep every param
        cfg = dataclasses.replace(
            get_arch("smollm-135m").reduced(), dtype="bfloat16",
            num_layers=1, d_model=64, num_heads=2, num_kv_heads=1,
            head_dim=32, d_ff=128, vocab_size=256)
        model = build_model(cfg)
        toks = make_lm_tokens(4, 32, cfg.vocab_size)
        problem = make_lm_problem(model, make_lm_clients(toks, 2))
        hp = AlgoHParams(eta=0.3, local_epochs=2, aa_impl=aa_impl)
        state = init_state(problem, jax.random.PRNGKey(0), hp, None,
                           "fedosaa_svrg")
        assert {x.dtype for x in jax.tree.leaves(state.params)} == {
            jnp.dtype(jnp.bfloat16)}
        new, m = jax.jit(make_round_fn("fedosaa_svrg", problem, hp))(state)
        for a, b in zip(jax.tree.leaves(new.params),
                        jax.tree.leaves(state.params)):
            assert a.dtype == b.dtype and a.shape == b.shape
        assert np.isfinite(float(m.loss))
        h = run_federated(problem, "fedosaa_svrg", hp, 2, chunk=2)
        assert np.isfinite(h.loss).all()
