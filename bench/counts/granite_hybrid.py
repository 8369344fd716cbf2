"""Work one round of FedOSAA-SVRG requires of the Granite 4.0-H scan.

Counted from the shapes alone: the algorithm's work, not any
implementation's. A round evaluates, per client, the anchor gradient and L
local gradients (K·(L+1) gradient evaluations, each a forward and a
backward pass of one sequence) and the anchor loss (K forward passes). A
backward pass over the frozen base computes the activations' gradients
only; the recompute of a rematerialized layer is not required work and is
not counted. Returns ``{phase: (flops, bytes)}``; an algorithm this module
does not count gives ``{}``.

``lm_ssd``, the Mamba-2 layers' chunked scan (chunk Q, nc = S/Q chunks, H
heads of P, state N), per sequence and layer:

* forward FLOPs: C·Bᵀ within chunks 2·nc·Q²·N; the diagonal blocks
  (L∘CBᵀ)(dt·x) 2·nc·Q²·H·P; the chunks' final states and the carried-in
  states' outputs 2·2·nc·Q·H·P·N; the decay matrices (difference, exp,
  product) 3·nc·Q²·H;
* forward bytes: x [S, H, P], dt [S, H], B and C [S, N] read and y [S, H, P]
  written, float32;
* backward: twice the forward's FLOPs (each product's two input
  gradients) and its bytes (the same inputs and dy read, their gradients
  written).
"""
from __future__ import annotations

F32 = 4


def _ssd_forward(S: int, Q: int, H: int, P: int, N: int) -> tuple:
    nc = S // Q
    flops = (2 * nc * Q * Q * N + 2 * nc * Q * Q * H * P
             + 4 * nc * Q * H * P * N + 3 * nc * Q * Q * H)
    nbytes = F32 * (2 * S * H * P + S * H + 2 * S * N)
    return flops, nbytes


def round_work(config: dict, traffic: dict) -> dict:
    hp = traffic["hparams"]
    if traffic["algo"] != "fedosaa_svrg" or hp.get("batch_size"):
        return {}
    K, S, L = traffic["num_clients"], traffic["seq_len"], hp["local_epochs"]
    grads, losses = K * (L + 1), K
    H, P, N = (config["mamba_n_heads"], config["mamba_d_head"],
               config["mamba_d_state"])
    n_mamba = config["layer_types"].count("mamba")

    ssd_f, ssd_b = _ssd_forward(S, config["mamba_chunk_size"], H, P, N)
    passes = 3 * grads + losses          # forward + 2x backward, per grad
    return {"lm_ssd": (passes * n_mamba * ssd_f, passes * n_mamba * ssd_b)}
