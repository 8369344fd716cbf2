"""Fused dual-gradient local-trajectory kernels (kernels/local_update):
kernel↔oracle parity (to f32 reordering noise on granule-aligned shapes), the
padded-row invariance property, fused↔autodiff round parity, and the
stack_client_arrays aggregation-weight regression."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # property tests degrade to corner examples
    from _hypothesis_stub import given, settings, strategies as st

from repro.core import (
    AlgoHParams,
    init_state,
    make_round_fn,
    resolve_local_impl,
    stack_client_arrays,
)
from repro.core.algorithms import _svrg_trajectory
from repro.core.sharded import make_sharded_round_fn
from repro.data import make_binary_classification, partition
from repro.kernels.local_update import fused_trajectory
from repro.launch.mesh import make_host_mesh
from repro.models.linreg import linreg_exact_solution, make_linreg_problem
from repro.models.logreg import make_logreg_problem
from repro.utils import tree_math as tm


@pytest.fixture(scope="module")
def logreg():
    X, y = make_binary_classification("synthetic_small", n=2000, seed=0)
    clients = partition(X, y, num_clients=8, scheme="iid")
    return make_logreg_problem(clients, gamma=1e-3)


@pytest.fixture
def x64():
    """Enable f64 for one test (the ext_compression pattern): the AA Gram
    solve amplifies last-ulp trajectory reorderings chaotically in f32 (the
    PR 4 lax.cond finding), so the ≤1e-6 fused↔tree ROUND contract is
    pinned where reordering noise is 1e-15, not 1e-7."""
    was = jax.config.read("jax_enable_x64")
    jax.config.update("jax_enable_x64", True)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", was)


def _rand_case(rng, n, d, link, S=1):
    x = jnp.asarray(rng.standard_normal((S, n, d)), jnp.float32)
    if link == "logistic":
        y = jnp.asarray(rng.choice([-1.0, 1.0], (S, n)), jnp.float32)
    else:
        y = jnp.asarray(rng.standard_normal((S, n)), jnp.float32)
    mask = jnp.ones((S, n), jnp.float32)
    w0 = jnp.asarray(rng.standard_normal(d) * 0.1, jnp.float32)
    u = jnp.asarray(rng.standard_normal(d) * 0.01, jnp.float32)
    return x, y, mask, w0, u


# ---------------------------------------------------------------------------
# kernel (interpret mode) vs the jnp oracle
# ---------------------------------------------------------------------------

#: kernel↔oracle parity on a single granule-aligned row tile.  The kernel
#: writes each step's row into a [steps, d] block held across the grid (the
#: layout Mosaic accepts on a TPU), and the interpreter then rounds r_traj
#: one f32 ulp away from the oracle on some entries — measured ≤ 3e-8
#: absolute on residuals of scale 0.35.  So parity is pinned at f32
#: reordering noise: rtol 1e-6 / atol 1e-7.  A kernel that rounds the design
#: to bf16 is ≥ 1e-5 off on every case below, two orders past the bound, and
#: each case asserts that it fails.
_ULP_TOL = dict(rtol=1e-6, atol=1e-7)


def _within_ulps(got, want) -> bool:
    return all(bool(np.allclose(np.asarray(g), np.asarray(w), **_ULP_TOL))
               for g, w in zip(got, want))


def _assert_kernel_parity(x, y, mask, w0, u, **kw):
    ref = fused_trajectory(x, y, mask, w0, u, impl="ref", **kw)
    got = fused_trajectory(x, y, mask, w0, u, impl="kernel", interpret=True,
                           **kw)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), **_ULP_TOL)
    # the bound is tight enough to catch a bf16-degraded design
    xb = x.astype(jnp.bfloat16).astype(x.dtype)
    degraded = fused_trajectory(xb, y, mask, w0, u, impl="kernel",
                                interpret=True, **kw)
    assert not _within_ulps(degraded, ref), "tolerance too loose for bf16"


class TestFusedKernelParity:
    @pytest.mark.parametrize("link", ["logistic", "linear"])
    @pytest.mark.parametrize("anchor", [0.0, 1.0])
    def test_bit_exact_on_granule_shapes(self, link, anchor):
        """One granule-aligned row tile: the kernel is the oracle to f32
        reordering noise (same contractions, same cast points — ref.py
        docstring; see _ULP_TOL for why not bitwise)."""
        rng = np.random.default_rng(hash((link, anchor)) % 2**31)
        x, y, mask, w0, u = _rand_case(rng, 384, 128, link)
        mask = mask.at[0, 350:].set(0.0)
        _assert_kernel_parity(x, y, mask, w0, u, link=link, reg=1e-3,
                              eta=0.5, anchor_scale=anchor, steps=11)

    def test_bit_exact_minibatch_blocks(self):
        """S == steps per-step design blocks, granule-aligned: parity to
        f32 reordering noise (_ULP_TOL)."""
        rng = np.random.default_rng(3)
        x, y, mask, w0, u = _rand_case(rng, 128, 128, "logistic", S=5)
        _assert_kernel_parity(x, y, mask, w0, u, link="logistic", reg=1e-3,
                              eta=0.5, anchor_scale=1.0, steps=5)

    @pytest.mark.parametrize("n,d,row_tile", [
        (300, 54, None),      # ragged → padded, auto tile
        (1000, 54, 128),      # multi-tile: accumulator crosses 8 row tiles
        (384, 200, 128),      # ragged d, multi-tile
    ])
    def test_padded_and_tiled_allclose(self, n, d, row_tile):
        rng = np.random.default_rng(n + d)
        x, y, mask, w0, u = _rand_case(rng, n, d, "logistic")
        mask = mask.at[0, n - n // 8:].set(0.0)
        kw = dict(link="logistic", reg=1e-3, eta=0.5, anchor_scale=1.0,
                  steps=8)
        wr, rr = fused_trajectory(x, y, mask, w0, u, impl="ref", **kw)
        wk, rk = fused_trajectory(x, y, mask, w0, u, impl="kernel",
                                  interpret=True, row_tile=row_tile, **kw)
        np.testing.assert_allclose(np.asarray(wk), np.asarray(wr),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(rk), np.asarray(rr),
                                   rtol=1e-5, atol=1e-6)

    def test_vmapped_over_clients(self):
        """The round cores vmap the per-client call — kernel must match the
        oracle under batching (scratch re-initializes per client; vmap
        changes XLA fusion, so parity is to f32 reordering noise here)."""
        rng = np.random.default_rng(9)
        K, n, d = 3, 256, 128
        x = jnp.asarray(rng.standard_normal((K, 1, n, d)), jnp.float32)
        y = jnp.asarray(rng.choice([-1.0, 1.0], (K, 1, n)), jnp.float32)
        m = jnp.ones((K, 1, n), jnp.float32)
        w0 = jnp.asarray(rng.standard_normal((K, d)) * 0.1, jnp.float32)
        u = jnp.zeros((K, d), jnp.float32)

        def call(impl):
            return jax.vmap(lambda *a: fused_trajectory(
                *a, link="logistic", reg=1e-3, eta=0.5, anchor_scale=1.0,
                steps=4, impl=impl, interpret=True))(x, y, m, w0, u)

        (wr, rr), (wk, rk) = call("ref"), call("kernel")
        np.testing.assert_allclose(np.asarray(wk), np.asarray(wr),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(rk), np.asarray(rr),
                                   rtol=1e-5, atol=1e-6)

    def test_rejects_bad_link_and_impl(self):
        rng = np.random.default_rng(0)
        x, y, mask, w0, u = _rand_case(rng, 128, 128, "linear")
        with pytest.raises(ValueError, match="unknown link"):
            fused_trajectory(x, y, mask, w0, u, link="probit", reg=0.0,
                             eta=0.1, anchor_scale=0.0, steps=2)
        with pytest.raises(ValueError, match="unknown impl"):
            fused_trajectory(x, y, mask, w0, u, link="linear", reg=0.0,
                             eta=0.1, anchor_scale=0.0, steps=2, impl="cuda")


# ---------------------------------------------------------------------------
# property: padded rows never influence fused gradients or trajectories
# ---------------------------------------------------------------------------

class TestMaskedRowInvariance:
    @settings(max_examples=8, deadline=None)
    @given(n_valid=st.integers(5, 180), d=st.integers(3, 40),
           seed=st.integers(0, 99), minibatch=st.booleans())
    def test_padded_rows_never_influence(self, n_valid, d, seed, minibatch):
        """Randomize the padded region (mask == 0) of a ragged client: every
        fused output — both executors, both batch modes — must be unchanged
        down to the bit vs the zero-padded twin."""
        rng = np.random.default_rng(seed)
        n = n_valid + int(rng.integers(1, 64))
        steps = 4
        if minibatch:
            S, B = steps, 32
            x0 = rng.standard_normal((S, B, d))
            m = np.ones((S, B), np.float32)
            m[:, B - max(1, B // 4):] = 0.0   # padded tail per block
        else:
            S, B = 1, n
            x0 = rng.standard_normal((S, n, d))
            m = np.zeros((S, n), np.float32)
            m[:, :n_valid] = 1.0
        y0 = rng.choice([-1.0, 1.0], (S, B))
        w0 = jnp.asarray(rng.standard_normal(d) * 0.1, jnp.float32)
        u = jnp.asarray(rng.standard_normal(d) * 0.01, jnp.float32)
        garbage = rng.standard_normal(x0.shape) * 1e6
        x_dirty = np.where(m[..., None] > 0, x0, garbage)
        y_dirty = np.where(m > 0, y0, 7e9)
        kw = dict(link="logistic", reg=1e-3, eta=0.5, anchor_scale=1.0,
                  steps=steps)
        for impl in ("ref", "kernel"):
            clean = fused_trajectory(
                jnp.asarray(x0 * (m[..., None] > 0), jnp.float32),
                jnp.asarray(y0 * (m > 0), jnp.float32), jnp.asarray(m),
                w0, u, impl=impl, interpret=True, **kw)
            dirty = fused_trajectory(
                jnp.asarray(x_dirty, jnp.float32),
                jnp.asarray(y_dirty, jnp.float32), jnp.asarray(m),
                w0, u, impl=impl, interpret=True, **kw)
            for a, b in zip(clean, dirty):
                assert bool(jnp.all(a == b)), (
                    f"padded rows leaked into the {impl} trajectory")
                assert bool(jnp.all(jnp.isfinite(a)))


# ---------------------------------------------------------------------------
# fused vs autodiff: trajectory- and round-level
# ---------------------------------------------------------------------------

class TestFusedVsAutodiff:
    def test_trajectory_matches_autodiff(self, logreg):
        """Ops-level contract: the fused residuals equal the double-autodiff
        residuals to f32 reordering noise, step for step (L=10)."""
        hp_t = AlgoHParams(eta=1.0, local_epochs=10, local_impl="tree")
        hp_p = dataclasses.replace(hp_t, local_impl="pallas")
        w0 = logreg.init(jax.random.PRNGKey(0))
        g = logreg.global_grad(w0)
        batch = logreg.clients.client(0)
        rng = jax.random.PRNGKey(7)
        wt, rt = _svrg_trajectory(logreg, hp_t, w0, g, batch, rng)
        wp, rp = _svrg_trajectory(logreg, hp_p, w0, g, batch, rng)
        np.testing.assert_allclose(np.asarray(wp), np.asarray(wt), atol=5e-6)
        np.testing.assert_allclose(np.asarray(rp), np.asarray(rt), atol=5e-6)

    def test_trajectory_matches_autodiff_minibatch(self, logreg):
        """Minibatch mode draws the bit-identical rows the autodiff path
        samples (sample_minibatch_indices), live+anchor on the same ζ."""
        hp_t = AlgoHParams(eta=1.0, local_epochs=6, batch_size=32,
                           local_impl="tree")
        hp_p = dataclasses.replace(hp_t, local_impl="pallas")
        w0 = logreg.init(jax.random.PRNGKey(0))
        g = logreg.global_grad(w0)
        batch = logreg.clients.client(1)
        rng = jax.random.PRNGKey(3)
        wt, rt = _svrg_trajectory(logreg, hp_t, w0, g, batch, rng)
        wp, rp = _svrg_trajectory(logreg, hp_p, w0, g, batch, rng)
        np.testing.assert_allclose(np.asarray(wp), np.asarray(wt), atol=5e-6)
        np.testing.assert_allclose(np.asarray(rp), np.asarray(rt), atol=5e-6)

    @pytest.mark.parametrize("algo", ["fedsvrg", "fedavg", "scaffold"])
    def test_round_parity_f32_non_aa(self, logreg, algo):
        """Without the AA amplifier the full f32 round agrees to ~1e-6."""
        hp_t = AlgoHParams(eta=1.0, local_epochs=10, local_impl="tree")
        hp_p = dataclasses.replace(hp_t, local_impl="pallas")
        outs = {}
        for tag, hp in (("tree", hp_t), ("pallas", hp_p)):
            rf = jax.jit(make_round_fn(algo, logreg, hp))
            st_ = init_state(logreg, jax.random.PRNGKey(0), hp, None, algo)
            for _ in range(3):
                st_, _m = rf(st_)
            outs[tag] = st_.params
        assert float(jnp.max(jnp.abs(outs["tree"] - outs["pallas"]))) <= 2e-6

    @pytest.mark.parametrize("case", ["plain", "carry", "minibatch",
                                      "scaffold"])
    def test_round_parity_f64_aa(self, x64, case):
        """The acceptance contract: fused↔tree round parity ≤ 1e-6 for the
        AA algorithms, incl. L>8 and carry-history — in f64, where float
        reordering noise (1e-16 at trajectory level, measured) stays below
        the Gram solve's amplification instead of being blown past 1e-6 as
        in f32 (see the x64 fixture). Observed on this container: 0.0 —
        bit-identical rounds — for all four cases."""
        X, y = make_binary_classification("synthetic_small", n=2000, seed=0)
        clients = partition(X, y, num_clients=8, scheme="iid")
        prob = make_logreg_problem(clients, gamma=1e-3, dtype=jnp.float64)
        algo = "fedosaa_scaffold" if case == "scaffold" else "fedosaa_svrg"
        hp = AlgoHParams(
            eta=1.0, local_epochs=10,   # L > 8: the m>8 AA granule path
            carry_history=3 if case == "carry" else 0,
            batch_size=32 if case == "minibatch" else None,
            local_impl="tree")
        outs = {}
        for impl in ("tree", "pallas"):
            h = dataclasses.replace(hp, local_impl=impl)
            rf = jax.jit(make_round_fn(algo, prob, h))
            st_ = init_state(prob, jax.random.PRNGKey(0), h, None, algo)
            for _ in range(4):
                st_, _m = rf(st_)
            outs[impl] = st_.params
        diff = float(jnp.max(jnp.abs(outs["tree"] - outs["pallas"])))
        assert diff <= 1e-6, f"{algo}/{case}: max|Δparams| {diff:.2e}"

    def test_round_through_interpret_kernel(self, logreg, monkeypatch):
        """Force the KERNEL executor (interpret mode) through a full round —
        the exact graph the TPU path compiles — and compare against the
        oracle executor the CPU path uses. fedsvrg: no AA step, so the
        comparison is not routed through the ulp-chaotic Gram solve."""
        import repro.kernels.local_update.ops as lu_ops

        hp = AlgoHParams(eta=1.0, local_epochs=4, local_impl="pallas")
        outs = {}
        for impl in ("ref", "kernel"):
            monkeypatch.setattr(lu_ops, "DEFAULT_IMPL", impl)
            rf = jax.jit(make_round_fn("fedsvrg", logreg, hp))
            st_ = init_state(logreg, jax.random.PRNGKey(0), hp, None,
                             "fedsvrg")
            st_, _m = rf(st_)
            outs[impl] = st_.params
        np.testing.assert_allclose(np.asarray(outs["kernel"]),
                                   np.asarray(outs["ref"]),
                                   rtol=1e-5, atol=1e-6)

    def test_linreg_fused_converges_to_exact_optimum(self):
        """The "linear" link end-to-end: FedOSAA-SVRG with the fused
        trajectory lands on the closed-form ridge optimum."""
        rng = np.random.default_rng(0)
        xs = [rng.standard_normal((120 + 30 * k, 12)) for k in range(4)]
        wtrue = rng.standard_normal(12)
        ys = [x @ wtrue + 0.05 * rng.standard_normal(x.shape[0]) for x in xs]
        clients = stack_client_arrays(xs, ys)
        prob = make_linreg_problem(clients, gamma=1e-2)
        wstar = linreg_exact_solution(clients, gamma=1e-2)
        hp = AlgoHParams(eta=0.3, local_epochs=8, local_impl="pallas")
        rf = jax.jit(make_round_fn("fedosaa_svrg", prob, hp))
        st_ = init_state(prob, jax.random.PRNGKey(0), hp, None,
                         "fedosaa_svrg")
        for _ in range(12):
            st_, _m = rf(st_)
        rel = float(tm.tree_norm(tm.tree_sub(st_.params, wstar))
                    / jnp.maximum(tm.tree_norm(wstar), 1e-30))
        assert rel < 1e-3, f"linreg fused rel-error {rel:.2e}"


# ---------------------------------------------------------------------------
# knob resolution / fallback
# ---------------------------------------------------------------------------

class TestLocalImplResolution:
    def test_sharded_always_tree(self):
        assert resolve_local_impl("pallas", "sharded") == "tree"
        assert resolve_local_impl("auto", "sharded") == "tree"

    def test_ineligible_falls_back(self, logreg):
        no_design = dataclasses.replace(logreg, linear_design=None)
        assert resolve_local_impl("pallas", "vmap", no_design) == "tree"
        # the Newton family has no trajectory to fuse
        assert resolve_local_impl("pallas", "vmap", logreg, "giant") == "tree"
        assert resolve_local_impl("pallas", "vmap", logreg,
                                  "fedosaa_svrg") == "pallas"
        # params must BE a flat array, not merely contain one flat leaf —
        # a container-wrapped [d] falls back instead of crashing at trace
        wrapped = dataclasses.replace(
            logreg, init=lambda rng: {"w": logreg.init(rng)})
        assert resolve_local_impl("pallas", "vmap", wrapped,
                                  "fedosaa_svrg") == "tree"

    def test_unknown_impl_raises(self):
        with pytest.raises(ValueError, match="unknown local_impl"):
            resolve_local_impl("cuda")

    def test_sharded_round_runs_with_pallas_requested(self, logreg):
        """An explicit local_impl="pallas" on the sharded runtime silently
        falls back to the autodiff path and matches the vmap tree round."""
        hp = AlgoHParams(eta=1.0, local_epochs=3, local_impl="pallas")
        mesh = make_host_mesh()
        rf_sh = jax.jit(make_sharded_round_fn("fedosaa_svrg", logreg, hp,
                                              mesh))
        rf_vm = jax.jit(make_round_fn(
            "fedosaa_svrg", logreg,
            dataclasses.replace(hp, local_impl="tree")))
        st0 = init_state(logreg, jax.random.PRNGKey(0), hp, None,
                         "fedosaa_svrg")
        st_sh, m_sh = rf_sh(st0)
        st_vm, m_vm = rf_vm(st0)
        np.testing.assert_allclose(np.asarray(st_sh.params),
                                   np.asarray(st_vm.params),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# stack_client_arrays aggregation weights (satellite regression)
# ---------------------------------------------------------------------------

class TestStackedWeights:
    def test_ragged_k100_weights_sum_to_one_ulp(self):
        """Weights normalized in f64 before the f32 cast: the f64 sum of
        the stored f32 weights stays within 1 ulp of 1.0 even for a ragged
        K=100 split (per-element drift would otherwise bias every
        delta-form aggregation by O(K·eps))."""
        rng = np.random.default_rng(0)
        sizes = rng.integers(3, 997, size=100)
        xs = [rng.standard_normal((int(s), 7)) for s in sizes]
        ys = [rng.choice([-1.0, 1.0], int(s)) for s in sizes]
        clients = stack_client_arrays(xs, ys)
        w = np.asarray(clients.weight)
        assert w.dtype == np.float32
        total = float(np.sum(w.astype(np.float64)))
        assert abs(total - 1.0) <= float(np.spacing(np.float32(1.0))), total
        # weights stay proportional to client sizes
        np.testing.assert_allclose(w, sizes / sizes.sum(), rtol=1e-6)

    def test_masks_match_sizes(self):
        xs = [np.ones((3, 2)), np.ones((5, 2))]
        ys = [np.ones(3), np.ones(5)]
        clients = stack_client_arrays(xs, ys)
        assert np.asarray(clients.mask).sum() == 8
        assert clients.x.shape == (2, 5, 2)
