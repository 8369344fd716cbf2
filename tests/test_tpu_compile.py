"""Compile the Pallas kernels of the main path for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology that
is described, not attached, and refuses what Mosaic would refuse on the chip
(misaligned blocks, VMEM overruns). Each test compiles one kernel at the
shapes the round engine runs — the paper's covtype splits for the fused
local trajectory, the AA passes at m=10 and at smollm-135m width, the int8
codec — and asserts that the compiled program holds the kernel
(``tpu_custom_call``). Nothing here runs a kernel or measures a time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

os.environ.setdefault("TPU_LOG_DIR", "disabled")

D_COVTYPE = 54
STEPS = 11          # L=10 local epochs -> L+1 trajectory rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(hlo: str):
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("K,S,n", [
    (100, 1, 5_810),      # paper covtype K=100: design block VMEM-resident
    (10, 1, 58_101),      # paper covtype K=10: streamed row tiles
    (10, STEPS, 64),      # per-step minibatch blocks (batch_size=64)
], ids=["resident", "streamed", "minibatch"])
def test_trajectory_kernel_compiles(one_chip, K, S, n):
    from repro.kernels.local_update import fused_trajectory

    def per_client(x, y, m, w0, u):
        return fused_trajectory(x, y, m, w0, u, link="logistic", reg=1e-3,
                                eta=1.0, anchor_scale=1.0, steps=STEPS,
                                impl="kernel", interpret=False)

    f32 = jnp.float32
    _assert_kernel(_compile(
        jax.vmap(per_client), one_chip,
        ((K, S, n, D_COVTYPE), f32), ((K, S, n), f32), ((K, S, n), f32),
        ((K, D_COVTYPE), f32), ((K, D_COVTYPE), f32)))


def _smollm_width() -> int:
    from repro.configs import get_arch
    from repro.models.decoder import build_model

    shapes = jax.eval_shape(build_model(get_arch("smollm-135m")).init,
                            jax.random.PRNGKey(0))
    return sum(s.size for s in jax.tree.leaves(shapes))


@pytest.mark.parametrize("m,d,dtype", [
    (10, D_COVTYPE, jnp.float32),      # covtype, L=10
    (2, None, jnp.bfloat16),           # smollm-135m full width, L=2
], ids=["covtype-m10-f32", "smollm135m-m2-bf16"])
def test_anderson_kernels_compile(one_chip, m, d, dtype):
    from repro.kernels.anderson.ops import flat_gram, flat_update

    d = _smollm_width() if d is None else d
    _assert_kernel(_compile(
        lambda y, g: flat_gram(y, g, interpret=False), one_chip,
        ((m, d), dtype), ((d,), dtype)))
    _assert_kernel(_compile(
        lambda w, g, s, y, gm: flat_update(w, g, s, y, gm, 1.0, 1.0,
                                           interpret=False), one_chip,
        ((d,), dtype), ((d,), dtype), ((m, d), dtype), ((m, d), dtype),
        ((m,), jnp.float32)))


def test_quant_kernels_compile(one_chip):
    from repro.kernels.quant.ops import chunk_rows, dequantize_2d, quantize_2d
    from repro.kernels.quant.quant import DEFAULT_CHUNK

    nc = chunk_rows(D_COVTYPE)
    _assert_kernel(_compile(
        lambda x, u: quantize_2d(x, u, use_pallas=True, interpret=False),
        one_chip, ((nc, DEFAULT_CHUNK), jnp.float32),
        ((nc, DEFAULT_CHUNK), jnp.float32)))
    _assert_kernel(_compile(
        lambda q, s: dequantize_2d(q, s, use_pallas=True, interpret=False),
        one_chip, ((nc, DEFAULT_CHUNK), jnp.int8), ((nc, 1), jnp.float32)))
