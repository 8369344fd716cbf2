"""A tiny cell for the CPU tests: the harness's job loop at a size a test
run holds."""
from bench import harness as H

CONFIG = {"name": "tiny", "model": "logreg", "dtype": "float32", "n": 2000,
          "d": 54, "gamma": 1e-3, "pos_frac": 0.49, "scale": 1.0,
          "data_seed": 3}
TRAFFIC = {"num_clients": 4, "split_seed": 5,
           "algo": "fedosaa_svrg", "hparams": {"eta": 1.0, "local_epochs": 10},
           "channel": "identity", "runtime": "vmap", "chunk": 5,
           "round_budget": 60, "target_rel_error": 1e-4,
           "rel_error_limit": 1e-3, "trace_jobs": 2}


def cell(**traffic) -> H.Cell:
    return H.Cell("tiny", 1, dict(CONFIG), {**TRAFFIC, **traffic}, [], [])
