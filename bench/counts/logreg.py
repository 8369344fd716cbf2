"""Work one round of FedOSAA-SVRG requires on ℓ2 logistic regression.

Counted from the shapes alone, at the unpadded d: the algorithm's work, not
any implementation's, so a phase reads the same share of its roofline
whether a Pallas kernel or XLA computes it. One gradient evaluation of a
client's f_k is X w (2·n_k·d) and Xᵀc (2·n_k·d). Returns
``{phase: (flops, bytes)}``; an algorithm or mode this module does not count
gives ``{}`` and the metrics that need it stay silent.
"""
from __future__ import annotations

F32 = 4


def round_work(config: dict, traffic: dict) -> dict:
    hp = traffic["hparams"]
    if traffic["algo"] != "fedosaa_svrg" or hp.get("batch_size"):
        return {}
    K = traffic["num_clients"]
    n_k = config["n"] // K          # the IID split's block (remainder dropped)
    d = config["d"]
    L = hp.get("local_epochs", 10)
    m = L                            # AA history columns: L secant pairs
    # local trajectory: the gradients at w_0 = w^t (the anchor) and at the
    # L iterates w_1..w_L; the design block and labels read once, the
    # [L+1, d] iterate and residual trajectories written once
    local = (K * (L + 1) * 4 * n_k * d,
             F32 * K * (n_k * (d + 1) + 2 * (L + 1) * d))
    # AA step per client: Gram YᵀY (upper triangle, m(m+1)/2 dot products of
    # length d), Yᵀg, and the update w − ηg − (SΓ − ηYΓ); S and Y read once,
    # w and g read and w⁺ written
    aa = (K * (m * (m + 1) * d + 2 * m * d + 4 * m * d + 3 * d),
          F32 * K * (2 * m * d + 3 * d))
    return {"local_trajectory": local, "aa_step": aa,
            "round": (local[0] + aa[0], local[1] + aa[1])}
