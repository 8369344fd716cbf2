"""The four-chip runtime: ``make_sharded_round_fn`` over a ``("data",
"model")`` mesh of the cell's chips (``data`` = the chips, ``model`` = 1),
the clients split evenly over ``data``, each chip vmapping its share and the
cross-client sums finishing in a psum (``fl.psum``), under
``make_chunk_runner`` as the one-device runtime builds it. The runtime
takes the tree paths (``core/sharded.py``).

A job's first state is replicated over the mesh. The runner returns the
state as the partitioner lays it out, which may differ from the first
state's, so a job's later chunks may run a second executable of the same
runner: the warm-up job compiles both, and ``hlo_texts`` lowers both.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.harness import Program


@dataclasses.dataclass
class ShardedProgram(Program):

    def hlo_texts(self, chunk: int) -> list:
        """The compiled HLO of the runner for a job's first state and for
        the state a chunk returns (both compiled by the warm-up job)."""
        import jax

        first = self.init(0)
        head = self.runner.lower(first, np.int32(chunk)).compile()
        later = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            first, head.output_shardings[0])
        texts = [head.as_text(),
                 self.runner.lower(later, np.int32(chunk)).compile().as_text()]
        return texts[:1] if texts[1] == texts[0] else texts


def build(cell, inputs, devices):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from bench.harness import model_module
    from repro.core import (AlgoHParams, init_state, make_chunk_runner,
                            make_sharded_round_fn)

    tr = cell.traffic
    model = model_module(cell.config)
    problem = model.problem(cell.config, inputs)
    hp = AlgoHParams(**tr["hparams"])
    mesh = Mesh(np.asarray(devices).reshape(len(devices), 1),
                ("data", "model"))
    round_fn = make_sharded_round_fn(tr["algo"], problem, hp, mesh,
                                     channel=tr["channel"])
    runner = make_chunk_runner(round_fn, tr["chunk"],
                               **model.stop(cell.config, tr, inputs))
    replicated = NamedSharding(mesh, PartitionSpec())

    def init(key: int):
        state = init_state(problem, jax.random.PRNGKey(key), hp,
                           tr["channel"], tr["algo"])
        return jax.device_put(state, replicated)

    return ShardedProgram(init, runner, model, round_fn)
