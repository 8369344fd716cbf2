"""The op and byte counts of bench/counts against hand counts at a small
shape, and the roofline arithmetic that reads them."""
import types

import pytest

from bench import harness as H
from bench.counts import logreg

CONFIG = {"model": "logreg", "n": 41, "d": 3}      # K=2: 20 rows each
TRAFFIC = {"algo": "fedosaa_svrg", "num_clients": 2,
           "hparams": {"eta": 1.0, "local_epochs": 2}}


def test_local_trajectory_hand_count():
    flops, nbytes = logreg.round_work(CONFIG, TRAFFIC)["local_trajectory"]
    # per client: 3 gradients (w0 = anchor, w1, w2), each X@w (20 rows x 3
    # mults + adds = 120) and X^T c (120)
    assert flops == 2 * 3 * (120 + 120)
    # per client: X 20x3 and y 20 read once, w and r trajectories 2 x 3x3
    assert nbytes == 4 * 2 * (20 * 3 + 20 + 2 * 3 * 3)


def test_aa_step_hand_count():
    flops, nbytes = logreg.round_work(CONFIG, TRAFFIC)["aa_step"]
    # per client, m=2 columns of d=3: Gram entries (1,1),(1,2),(2,2) at 6
    # flops each = 18; Y^T g 2 x 6 = 12; S gamma and Y gamma 2 x 2 x 6 = 24;
    # w - eta g - (SG - eta YG): 3 x 3 = 9
    assert flops == 2 * (18 + 12 + 24 + 9)
    # S, Y (2 x 2x3) read, w and g read and w+ written (3 x 3)
    assert nbytes == 4 * 2 * (12 + 9)


def test_round_is_the_sum_and_other_algorithms_are_not_counted():
    work = logreg.round_work(CONFIG, TRAFFIC)
    assert work["round"] == tuple(a + b for a, b in zip(
        work["local_trajectory"], work["aa_step"]))
    assert logreg.round_work(CONFIG, {**TRAFFIC, "algo": "giant"}) == {}
    minibatch = {**TRAFFIC, "hparams": {"batch_size": 8}}
    assert logreg.round_work(CONFIG, minibatch) == {}


def _ctx(phase_s, chips=1, slots=10):
    cell = H.Cell("c", chips, CONFIG, TRAFFIC, [], [])
    jobs = [H.Job(rounds=slots - 1, slots=slots, reached=True, answer=None)]
    trace = types.SimpleNamespace(phase_s=phase_s)
    peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}
    return H.Context(cell, jobs, 1.0, 0.0, peaks, trace)


def test_roofline_is_least_time_over_device_time_per_computed_round():
    flops, nbytes = logreg.round_work(CONFIG, TRAFFIC)["local_trajectory"]
    least = max(flops, nbytes) / 1e9          # both peaks 1e9 per second
    ctx = _ctx({"fl.local_trajectory": [10 * 2 * least]})
    share, bound = ctx.roofline("fl.local_trajectory", "local_trajectory")
    assert share == pytest.approx(50.0)
    assert bound == ("compute" if flops >= nbytes else "bytes")


def test_roofline_splits_the_work_over_chips_and_phase_max_picks_slowest():
    ctx = _ctx({"fl.psum": [1.0, 3.0, 2.0, 2.0]}, chips=4)
    assert ctx.phase_s_per_slot("fl.psum", reduce=max) == pytest.approx(0.3)
    assert ctx.phase_s_per_slot("fl.psum") == pytest.approx(0.2)
    assert ctx.phase_s_per_slot("fl.scatter") is None
    assert ctx.roofline("fl.aa_step", "aa_step") is None
