"""The float64 reference and the control that has to fail against it."""
import jax.numpy as jnp
import numpy as np

from bench import control
from bench import harness as H
from bench.data import iid_split, make_dataset
from bench.reference import gradient, newton_solve, objective, rel_error
from bench.tests import tiny


def test_newton_optimum_zeroes_the_gradient():
    X, y = make_dataset(3000, 20, 0.3, 1.0, seed=11)
    w = newton_solve(X, y, 1e-3)
    X64, y64 = X.astype(np.float64), y.astype(np.float64)
    g0 = np.linalg.norm(gradient(np.zeros(20), X64, y64, 1e-3))
    assert np.linalg.norm(gradient(w, X64, y64, 1e-3)) < 1e-13 * g0
    # and it is a minimum: any step away raises the objective
    f = objective(w, X64, y64, 1e-3)
    for v in np.eye(20)[:3]:
        assert objective(w + 1e-3 * v, X64, y64, 1e-3) > f


def test_iid_split_deals_a_permutation_and_drops_the_remainder():
    X = np.arange(103, dtype=np.float32)[:, None] * np.ones((1, 2), np.float32)
    y = np.arange(103, dtype=np.float32)
    xs, ys = iid_split(X, y, 10, seed=4)
    assert xs.shape == (10, 10, 2) and ys.shape == (10, 10)
    assert len(set(ys.ravel().tolist())) == 100
    np.testing.assert_array_equal(xs[..., 0], ys)


def test_dataset_repeats_for_its_seed():
    a = make_dataset(500, 8, 0.5, 1.0, seed=2**31 + 3)
    b = make_dataset(500, 8, 0.5, 1.0, seed=2**31 + 3)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert set(np.unique(a[1]).tolist()) == {-1.0, 1.0}


def test_rel_error():
    assert rel_error(np.array([1.0, 1.0]), np.array([1.0, 0.0])) == 1.0


def _control_run(dtype):
    cell = tiny.cell(round_budget=40)
    inputs = H.make_inputs(cell)
    init, runner = control.as_program(inputs, cell.config, cell.traffic, dtype)
    prog = H.Program(init, runner, H.model_module(cell.config))
    jobs, _ = H.run_window(prog, cell.traffic, H.job_keys(7), 0.0,
                           max_jobs=1)
    return jobs, H.check_jobs(cell, jobs, inputs)


def test_reference_algorithm_at_float32_reaches_the_target():
    jobs, checks = _control_run(jnp.float32)
    assert jobs[0].reached
    assert H.is_correct(checks), checks


def test_control_in_bfloat16_is_not_correct():
    jobs, checks = _control_run(jnp.bfloat16)
    assert not jobs[0].reached
    assert not H.is_correct(checks), checks
    # by a wide margin: the limit separates the two readings
    assert checks["rel_error_max"]["value"] > 10 * checks["rel_error_max"][
        "limit"]
