"""Per-kernel allclose tests vs the ref.py oracles (interpret mode on CPU),
with shape/dtype sweeps + hypothesis property tests (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests skip; the rest of the module runs
    from _hypothesis_stub import given, settings, strategies as st

from repro.core import AlgoHParams
from repro.kernels.anderson.ops import aa_step_flat
from repro.kernels.anderson.ref import aa_step_ref, gram_ref, update_ref
from repro.kernels.anderson.anderson import gram_pallas, update_pallas
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.quant.ops import (
    dequantize_2d,
    int8_sr_roundtrip,
    quantize_2d,
)
from repro.kernels.quant.ref import dequantize_ref, quantize_ref
from repro.kernels.ssd.ops import ssd_chunk
from repro.kernels.ssd.ref import ssd_chunk_ref


# ---------------------------------------------------------------------------
# anderson
# ---------------------------------------------------------------------------

class TestAndersonKernel:
    @pytest.mark.parametrize("d", [512, 2048, 4096, 10_000])
    @pytest.mark.parametrize("m", [1, 3, 10])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_aa_step_matches_ref(self, d, m, dtype):
        rng = np.random.default_rng(d + m)
        w = jnp.asarray(rng.standard_normal(d), dtype)
        g = jnp.asarray(rng.standard_normal(d), dtype)
        s = jnp.asarray(rng.standard_normal((m, d)) * 0.1, dtype)
        y = jnp.asarray(rng.standard_normal((m, d)) * 0.1, dtype)
        out = aa_step_flat(w, g, s, y, eta=0.5)
        ref = aa_step_ref(w, g, s, y, 0.5)
        tol = 1e-5 if dtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol * 10,
        )

    @pytest.mark.parametrize("tile", [256, 512, 2048])
    def test_gram_tile_invariance(self, tile):
        rng = np.random.default_rng(0)
        m, d = 8, 4096
        y = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
        g = jnp.asarray(rng.standard_normal(d), jnp.float32)
        gram, yg = gram_pallas(y, g, tile=tile, interpret=True)
        gram_r, yg_r = gram_ref(y, g)
        # f32 accumulation-order noise across tiles: absolute tolerance scaled
        # to the Gram magnitude (~d)
        np.testing.assert_allclose(np.asarray(gram), np.asarray(gram_r), rtol=1e-3, atol=1e-2)
        np.testing.assert_allclose(np.asarray(yg), np.asarray(yg_r), rtol=1e-3, atol=1e-2)

    def test_update_kernel_matches_ref(self):
        rng = np.random.default_rng(1)
        m, d = 8, 2048
        w = jnp.asarray(rng.standard_normal(d), jnp.float32)
        g = jnp.asarray(rng.standard_normal(d), jnp.float32)
        s = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
        y = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
        gamma = jnp.asarray(rng.standard_normal(m), jnp.float32)
        out = update_pallas(w, g, s, y, gamma, 0.3, 0.9, tile=512, interpret=True)
        ref = update_ref(w, g, s, y, gamma, 0.3, 0.9)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)

    @settings(max_examples=10, deadline=None)
    @given(d=st.integers(100, 3000), m=st.integers(1, 12), seed=st.integers(0, 99))
    def test_property_aa_step_any_shape(self, d, m, seed):
        rng = np.random.default_rng(seed)
        w = jnp.asarray(rng.standard_normal(d), jnp.float32)
        g = jnp.asarray(rng.standard_normal(d), jnp.float32)
        s = jnp.asarray(rng.standard_normal((m, d)) * 0.1, jnp.float32)
        y = jnp.asarray(rng.standard_normal((m, d)) * 0.1, jnp.float32)
        out = aa_step_flat(w, g, s, y, eta=0.5)
        ref = aa_step_ref(w, g, s, y, 0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=1e-4)

    def test_matches_pytree_multisecant(self):
        """Kernel path == core/anderson.multisecant_update on the flattened
        vector (integration with the FL core)."""
        from repro.core.anderson import AAConfig, multisecant_update
        rng = np.random.default_rng(3)
        m, d = 5, 1500
        w = jnp.asarray(rng.standard_normal(d), jnp.float32)
        g = jnp.asarray(rng.standard_normal(d), jnp.float32)
        s = jnp.asarray(rng.standard_normal((m, d)) * 0.1, jnp.float32)
        y = jnp.asarray(rng.standard_normal((m, d)) * 0.1, jnp.float32)
        out_kernel = aa_step_flat(w, g, s, y, eta=0.7, tikhonov=1e-10)
        out_core, _ = multisecant_update(w, g, s, y, 0.7, AAConfig(tikhonov=1e-10))
        np.testing.assert_allclose(
            np.asarray(out_kernel), np.asarray(out_core), rtol=2e-3, atol=2e-4
        )

    @pytest.mark.parametrize("m", [9, 10, 16, 21])
    def test_flat_passes_m_beyond_one_granule(self, m):
        """m > 8 histories (L>8 local epochs, carried cross-round columns):
        blocks span all m rows whatever m is, and the padded d lanes must
        contribute nothing."""
        from repro.kernels.anderson.ops import flat_gram, flat_update
        from repro.kernels.anderson.ref import gram_ref, update_ref
        rng = np.random.default_rng(m)
        d = 1000
        y = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
        g = jnp.asarray(rng.standard_normal(d), jnp.float32)
        gram, yg = flat_gram(y, g, interpret=True)
        gram_r, yg_r = gram_ref(y, g)
        assert gram.shape == (m, m) and yg.shape == (m,)
        np.testing.assert_allclose(np.asarray(gram), np.asarray(gram_r),
                                   rtol=1e-4, atol=1e-2)
        np.testing.assert_allclose(np.asarray(yg), np.asarray(yg_r),
                                   rtol=1e-4, atol=1e-2)
        w = jnp.asarray(rng.standard_normal(d), jnp.float32)
        s = jnp.asarray(rng.standard_normal((m, d)), jnp.float32)
        gamma = jnp.asarray(rng.standard_normal(m), jnp.float32)
        out = flat_update(w, g, s, y, gamma, 0.3, 0.9, interpret=True)
        ref = update_ref(w, g, s, y, gamma, 0.3, 0.9)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_dtype_ravel_helpers_roundtrip(self):
        """The dtype-preserving ravel helpers: grouped ravel → unravel is the
        identity, dtypes and shapes preserved, mixed-dtype trees split into
        per-dtype groups."""
        from repro.kernels.anderson.ops import (
            dtype_leaf_groups,
            ravel_group,
            ravel_stack_group,
            unravel_group_into,
        )
        rng = np.random.default_rng(0)
        tree = {
            "a": jnp.asarray(rng.standard_normal((3, 4)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal(7), jnp.bfloat16),
            "c": jnp.asarray(rng.standard_normal((2, 2)), jnp.float32),
        }
        leaves = jax.tree.leaves(tree)
        groups = dtype_leaf_groups(tree)
        assert len(groups) == 2
        assert sorted(i for _, idxs in groups for i in idxs) == [0, 1, 2]
        out = list(leaves)
        for _, idxs in groups:
            flat = ravel_group(leaves, idxs)
            assert flat.ndim == 1
            unravel_group_into(flat, leaves, idxs, out)
        for orig, rt in zip(leaves, out):
            assert orig.dtype == rt.dtype and orig.shape == rt.shape
            np.testing.assert_allclose(
                np.asarray(orig, np.float32), np.asarray(rt, np.float32))
        # stacked variant keeps the leading history axis
        stack = jax.tree.map(lambda x: jnp.stack([x, x + 1]), tree)
        sleaves = jax.tree.leaves(stack)
        for _, idxs in groups:
            flat = ravel_stack_group(sleaves, idxs)
            assert flat.shape[0] == 2


class TestAndersonRoundParity:
    """Round-level parity of aa_impl="pallas" vs "tree" (interpret mode on
    CPU): the fused kernels wired into the FULL round core — vmapped clients,
    comm channel, metrics — must reproduce the tree path. Both paths share
    the _solve_gram eigh solve; the only difference is the accumulation
    order of the one-pass tiled Gram/update, so parity is tight.

    Pinned in f64: these AA Grams reach condition numbers of 1e7-1e10, and
    the solve amplifies the paths' f32 reordering noise (1e-7) to 1e-4 in
    the state — past rtol 1e-5 even at a 1e-3 Tikhonov.  In f64 both paths
    accumulate the Gram in f64, where the same amplification stays far
    inside the bound, and a path that rounds through f32 or bf16 fails it."""

    @pytest.fixture(autouse=True)
    def x64(self):
        was = jax.config.read("jax_enable_x64")
        jax.config.update("jax_enable_x64", True)
        try:
            yield
        finally:
            jax.config.update("jax_enable_x64", was)

    @pytest.fixture
    def prob(self, x64):
        from repro.data import make_binary_classification, partition
        from repro.models.logreg import make_logreg_problem
        X, y = make_binary_classification("synthetic_small", n=200, seed=0)
        clients = partition(X, y, num_clients=4, scheme="iid")
        return make_logreg_problem(clients, gamma=1e-3, dtype=jnp.float64)

    def _roundwise(self, prob, algo, hp, rounds=3, channel=None):
        import dataclasses
        from repro.core import init_state, make_round_fn
        ft = jax.jit(make_round_fn(
            algo, prob, dataclasses.replace(hp, aa_impl="tree"), channel))
        fp = jax.jit(make_round_fn(
            algo, prob, dataclasses.replace(hp, aa_impl="pallas"), channel))
        state = init_state(prob, jax.random.PRNGKey(0), hp, channel, algo)
        for t in range(rounds):
            st, mt = ft(state)
            sp, mp = fp(state)
            for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(sp)):
                np.testing.assert_allclose(
                    np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=1e-5, atol=1e-6,
                    err_msg=f"{algo} round {t} state")
            np.testing.assert_allclose(float(mt.loss), float(mp.loss),
                                       rtol=1e-6)
            np.testing.assert_allclose(float(mt.theta_mean),
                                       float(mp.theta_mean), rtol=1e-4)
            state = st

    @pytest.mark.parametrize("algo", ["fedosaa_svrg", "fedosaa_scaffold"])
    def test_round_parity(self, prob, algo):
        self._roundwise(prob, algo,
                        AlgoHParams(eta=0.5, local_epochs=3))

    @pytest.mark.parametrize("algo", ["fedosaa_svrg", "fedosaa_scaffold"])
    def test_round_parity_l_gt_8(self, prob, algo):
        """L > 8 local epochs: the per-client history exceeds one 8-sublane
        granule, exercising a full-m block that is not 8-aligned inside the
        round."""
        self._roundwise(prob, algo,
                        AlgoHParams(eta=0.5, local_epochs=10), rounds=2)

    def test_round_parity_carry_history(self, prob):
        """carry_history columns prepend to the per-round history (m = H+L),
        and the carried columns themselves must round-trip identically."""
        from repro.core.anderson import AAConfig
        hp = AlgoHParams(eta=0.5, local_epochs=3, carry_history=2,
                         aa=AAConfig(tikhonov=1e-6, damping=0.7))
        self._roundwise(prob, "fedosaa_svrg", hp, rounds=3)

    def test_round_parity_with_codec(self, prob):
        """The fused path composes with the wire channel (per-client int8
        encode/decode happens before the AA step's ravel)."""
        self._roundwise(prob, "fedosaa_svrg",
                        AlgoHParams(eta=0.5, local_epochs=3), rounds=2,
                        channel="int8")

    def test_sharded_runtime_falls_back_to_tree(self, prob):
        """aa_impl="pallas" under the sharded runtime: automatic fallback to
        the tree path, no error, numerics identical to an explicit "tree"."""
        import dataclasses
        from repro.core import init_state
        from repro.core.sharded import make_sharded_round_fn
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh()
        hp = AlgoHParams(eta=0.5, local_epochs=3, aa_impl="pallas")
        fs = jax.jit(make_sharded_round_fn("fedosaa_svrg", prob, hp, mesh))
        ftree = jax.jit(make_sharded_round_fn(
            "fedosaa_svrg", prob,
            dataclasses.replace(hp, aa_impl="tree"), mesh))
        state = init_state(prob, jax.random.PRNGKey(0), hp, None,
                           "fedosaa_svrg")
        sa, ma = fs(state)
        sb, mb = ftree(state)
        for a, b in zip(jax.tree.leaves(sa.params), jax.tree.leaves(sb.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.isfinite(float(ma.loss))

    def test_auto_resolution(self):
        from repro.core import resolve_aa_impl
        assert resolve_aa_impl("tree") == "tree"
        assert resolve_aa_impl("pallas") == "pallas"
        assert resolve_aa_impl("pallas", "sharded") == "tree"
        assert resolve_aa_impl("auto", "sharded") == "tree"
        expected = "pallas" if jax.default_backend() == "tpu" else "tree"
        assert resolve_aa_impl("auto") == expected
        with pytest.raises(ValueError, match="aa_impl"):
            resolve_aa_impl("fused")


# ---------------------------------------------------------------------------
# quant (int8-SR wire codec, repro/comm)
# ---------------------------------------------------------------------------

class TestQuantKernel:
    @pytest.mark.parametrize("nc,C", [(1, 256), (3, 256), (8, 128), (17, 512)])
    def test_quantize_pallas_matches_ref_bit_exact(self, nc, C):
        """Same uniforms in -> the Pallas kernel (interpret mode on CPU) and
        the jnp oracle must agree EXACTLY: the int8 codes and f32 scales are
        the wire format, so parity is integer equality, not allclose."""
        rng = np.random.default_rng(nc * 1000 + C)
        x = jnp.asarray(rng.standard_normal((nc, C)), jnp.float32)
        u = jnp.asarray(rng.uniform(0, 1, (nc, C)), jnp.float32)
        qp, sp = quantize_2d(x, u, use_pallas=True)
        qr, sr = quantize_ref(x, u)
        np.testing.assert_array_equal(np.asarray(qp), np.asarray(qr))
        np.testing.assert_array_equal(np.asarray(sp), np.asarray(sr))
        dp = dequantize_2d(qp, sp, use_pallas=True)
        dr = dequantize_ref(qr, sr)
        np.testing.assert_array_equal(np.asarray(dp), np.asarray(dr))

    def test_roundtrip_error_bounded_by_chunk_scale(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal(1000) * 10.0, jnp.float32)
        out = int8_sr_roundtrip(x, jax.random.PRNGKey(1), chunk=256)
        x_np, err = np.asarray(x), np.abs(np.asarray(out) - np.asarray(x))
        for c0 in range(0, 1000, 256):
            scale = np.abs(x_np[c0:c0 + 256]).max() / 127.0
            assert err[c0:c0 + 256].max() <= scale + 1e-6

    def test_roundtrip_unbiased_over_many_draws(self):
        """Stochastic rounding is unbiased: the empirical mean over draws
        converges to x at the Monte-Carlo rate."""
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.standard_normal(512), jnp.float32)
        draws = 500
        outs = jax.vmap(lambda k: int8_sr_roundtrip(x, k))(
            jax.random.split(jax.random.PRNGKey(0), draws))
        mean = np.asarray(jnp.mean(outs, axis=0))
        scale = float(jnp.max(jnp.abs(x))) / 127.0
        assert np.max(np.abs(mean - np.asarray(x))) < 5 * scale / np.sqrt(draws)

    def test_zero_chunks_and_exact_codes(self):
        # all-zero chunks must decode to exactly zero (scale fallback = 1)
        z = jnp.zeros(300, jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(int8_sr_roundtrip(z, jax.random.PRNGKey(0))), 0.0)
        # a chunk whose values sit exactly on code points is lossless:
        # x = scale * {-127..127} with max 127 -> scale = 1
        x = jnp.asarray(np.arange(-127, 129, 2), jnp.float32)  # 128 values
        out = int8_sr_roundtrip(x, jax.random.PRNGKey(0), chunk=128)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))

    @settings(max_examples=10, deadline=None)
    @given(n=st.integers(1, 2000), chunk=st.sampled_from([64, 128, 256]),
           seed=st.integers(0, 99))
    def test_property_any_shape_bounded(self, n, chunk, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal(n), jnp.float32)
        out = int8_sr_roundtrip(x, jax.random.PRNGKey(seed), chunk=chunk)
        assert out.shape == x.shape
        scale = float(jnp.max(jnp.abs(x))) / 127.0
        assert float(jnp.max(jnp.abs(out - x))) <= scale + 1e-6


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _ref_model_layout(q, k, v, window):
    B, S, H, hd = q.shape
    KV = k.shape[2]
    kk = jnp.repeat(k, H // KV, 2)
    vv = jnp.repeat(v, H // KV, 2)
    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    ref = attention_ref(to_bh(q), to_bh(kk), to_bh(vv), window=window)
    return ref.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


class TestFlashAttention:
    @pytest.mark.parametrize("S", [64, 128, 200, 384])
    @pytest.mark.parametrize("window", [0, 64])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, S, window, dtype):
        rng = np.random.default_rng(S + window)
        B, H, KV, hd = 2, 4, 2, 64
        q = jnp.asarray(rng.standard_normal((B, S, H, hd)), dtype)
        k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), dtype)
        v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), dtype)
        out = flash_attention(q, k, v, window=window)
        ref = _ref_model_layout(q, k, v, window)
        tol = 2e-3 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol,
        )

    @pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 128)])
    def test_block_shape_invariance(self, bq, bk):
        rng = np.random.default_rng(7)
        B, S, H, hd = 1, 256, 2, 64
        q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
        out = flash_attention(q, k, v, block_q=bq, block_k=bk)
        ref = _ref_model_layout(q, k, v, 0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)

    @settings(max_examples=8, deadline=None)
    @given(
        S=st.integers(16, 300),
        hd=st.sampled_from([32, 64, 128]),
        window=st.sampled_from([0, 16, 100]),
        seed=st.integers(0, 99),
    )
    def test_property_matches_ref(self, S, hd, window, seed):
        rng = np.random.default_rng(seed)
        B, H = 1, 2
        q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
        out = flash_attention(q, k, v, window=window)
        ref = _ref_model_layout(q, k, v, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-3, atol=3e-3)

    def test_first_token_attends_itself_only(self):
        rng = np.random.default_rng(0)
        B, S, H, hd = 1, 128, 1, 64
        q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
        out = flash_attention(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out[0, 0, 0]), np.asarray(v[0, 0, 0]), rtol=1e-5, atol=1e-5
        )


# ---------------------------------------------------------------------------
# ssd
# ---------------------------------------------------------------------------

class TestSSDKernel:
    @pytest.mark.parametrize("Q", [32, 64, 128, 256])
    @pytest.mark.parametrize("st_dim", [8, 64, 128])
    def test_matches_ref(self, Q, st_dim):
        rng = np.random.default_rng(Q + st_dim)
        B, nc, nh, hd = 1, 2, 2, 32
        xc = jnp.asarray(rng.standard_normal((B, nc, Q, nh, hd)), jnp.float32)
        dtc = jnp.asarray(rng.uniform(0.01, 0.3, (B, nc, Q, nh)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.5, 4.0, (nh,)), jnp.float32)
        da = jnp.cumsum(dtc * A[None, None, None], axis=2)
        Bc = jnp.asarray(rng.standard_normal((B, nc, Q, st_dim)), jnp.float32)
        Cc = jnp.asarray(rng.standard_normal((B, nc, Q, st_dim)), jnp.float32)
        y, s = ssd_chunk(xc, dtc, da, Bc, Cc)
        yr, sr = ssd_chunk_ref(xc, dtc, da, Bc, Cc)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-4, atol=1e-4)

    def test_model_integration_ssd_fn(self):
        """build_model(ssd_fn=pallas kernel) == build_model(pure jnp) for the
        full mamba2 forward — the kernel is a drop-in replacement."""
        from repro.configs import get_arch
        from repro.models.decoder import build_model
        cfg = get_arch("mamba2-2.7b").reduced()
        m_ref = build_model(cfg)
        m_ker = build_model(cfg, ssd_fn=ssd_chunk)
        params = jax.jit(m_ref.init)(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                    cfg.vocab_size, jnp.int32)
        lr, _ = m_ref.forward(params, tokens, None)
        lk, _ = m_ker.forward(params, tokens, None)
        np.testing.assert_allclose(
            np.asarray(lk, np.float32), np.asarray(lr, np.float32),
            rtol=2e-3, atol=2e-3,
        )

    @settings(max_examples=8, deadline=None)
    @given(
        Q=st.sampled_from([16, 32, 64]),
        nh=st.integers(1, 4),
        seed=st.integers(0, 99),
    )
    def test_property_matches_ref(self, Q, nh, seed):
        rng = np.random.default_rng(seed)
        B, nc, hd, st_dim = 1, 1, 16, 16
        xc = jnp.asarray(rng.standard_normal((B, nc, Q, nh, hd)), jnp.float32)
        dtc = jnp.asarray(rng.uniform(0.01, 0.3, (B, nc, Q, nh)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.1, 2.0, (nh,)), jnp.float32)
        da = jnp.cumsum(dtc * A[None, None, None], axis=2)
        Bc = jnp.asarray(rng.standard_normal((B, nc, Q, st_dim)), jnp.float32)
        Cc = jnp.asarray(rng.standard_normal((B, nc, Q, st_dim)), jnp.float32)
        y, s = ssd_chunk(xc, dtc, da, Bc, Cc)
        yr, sr = ssd_chunk_ref(xc, dtc, da, Bc, Cc)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=2e-4, atol=2e-4)
