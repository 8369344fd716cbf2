"""The one-device runtime of a model with a frozen operand (an LM's base
under low-rank adapters): ``make_chunk_runner`` over ``make_round_fn`` as in
``vmap.py``, with the problem's frozen operand passed to the runner on every
call instead of compiled in. The job loop calls ``runner(state, n_live)``;
the operand rides along. The model's ``Recorder`` keeps each job's answer
and the window's last adapter, and ``init`` a way to rebuild that job's
first adapter, for the check."""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.harness import Program


@dataclasses.dataclass
class LMProgram(Program):
    """``Program`` with the frozen operand: ``runner`` is the job loop's
    two-argument call, ``compiled`` the engine's runner, ``first`` a job's
    first state from its key without the note ``init`` keeps for the
    check."""

    compiled: object = None
    frozen: object = None
    first: object = None

    def hlo_texts(self, chunk: int) -> list:
        """The compiled HLO of the runner as the window calls it, with the
        operand (already compiled: nothing compiles)."""
        first = self.first(0)
        return [self.compiled.lower(first, np.int32(chunk), self.frozen)
                .compile().as_text()]


def build(cell, inputs, devices):
    import jax

    from bench import harness as H
    from repro.core import (AlgoHParams, init_state, make_chunk_runner,
                            make_round_fn)

    tr = cell.traffic
    model = H.model_module(cell.config)
    problem = model.problem(cell.config, inputs)
    hp = AlgoHParams(**tr["hparams"])
    round_fn = make_round_fn(tr["algo"], problem, hp, tr["channel"])
    compiled = make_chunk_runner(round_fn, tr["chunk"],
                                 **model.stop(cell.config, tr, inputs))
    frozen = problem.frozen
    d = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(problem.init, jax.random.PRNGKey(0))))
    H.log(f"lm: adapter dimension d {d}, frozen operand "
          f"{sum(x.nbytes for x in jax.tree.leaves(frozen))} bytes")

    def runner(state, n_live):
        return compiled(state, n_live, frozen)

    def init(key: int):
        return init_state(problem, jax.random.PRNGKey(key), hp, tr["channel"],
                          tr["algo"])

    def job_init(key: int):
        # the check rebuilds the last job's first adapter from its key: the
        # runner donates the state it is given
        kept["first"] = lambda: init(key).params
        return init(key)

    kept = inputs.data.kept
    return LMProgram(job_init, runner, model.Recorder(kept), round_fn,
                     compiled, frozen, init)
