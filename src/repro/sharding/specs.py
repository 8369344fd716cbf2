"""PartitionSpec generation for every model/optimizer/cache pytree.

Two parameter regimes (DESIGN.md §6):

* replica  — params TP-sharded over "model", replicated over data/pod axes.
  FL semantics: every client group holds a full (tensor-sharded) replica, so
  per-client divergent local models are representable.
* fsdp     — additionally shards the non-TP dim of every ≥2D weight over
  "data" (ZeRO/FSDP style, gathered per-layer inside the scan). Used for the
  archs whose replica-regime working set exceeds HBM (internvl2-76b,
  llama4-scout); there the FL runtime time-multiplexes clients over the whole
  mesh (sequential-client cross-silo execution).

Specs are derived from leaf PATHS (naming conventions in models/layers.py) —
one place to audit the entire sharding story.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import Sharder, default_axes

Pytree = Any

# archs whose train working set (params+grads+correction, bf16) exceeds a
# single v5e's HBM share under pure TP — see DESIGN.md memory math
FSDP_ARCHS = ("internvl2-76b", "llama4-scout-17b-a16e")


@dataclasses.dataclass(frozen=True)
class ShardingPlan:
    cfg: ArchConfig
    mesh: Any
    multi_pod: bool
    regime: str                  # "replica" | "fsdp"
    axes: dict

    @property
    def model_shards(self) -> int:
        return self.mesh.shape["model"]

    def sharder(self) -> Sharder:
        return Sharder(mesh=self.mesh, axes=self.axes)


def make_plan(cfg: ArchConfig, mesh, multi_pod: bool = False,
              regime: str | None = None) -> ShardingPlan:
    shards = mesh.shape["model"]
    cfg = cfg.padded(shards)
    axes = default_axes(multi_pod)
    # divisibility overrides
    if cfg.num_heads and cfg.eff_kv_heads % shards != 0:
        axes["kv_heads"] = None                       # MQA: replicate kv
    if cfg.num_experts:
        if cfg.eff_experts % shards == 0:
            axes["experts"], axes["expert_ff"] = "model", None
        else:
            axes["experts"], axes["expert_ff"] = None, "model"
    if cfg.family in ("ssm", "hybrid") and cfg.d_inner % shards != 0:
        axes["ssm_inner"] = None
    regime = regime or ("fsdp" if cfg.name in FSDP_ARCHS else "replica")
    return ShardingPlan(cfg=cfg, mesh=mesh, multi_pod=multi_pod,
                        regime=regime, axes=axes)


# ---------------------------------------------------------------------------
# param specs by leaf path
# ---------------------------------------------------------------------------

def _fsdp_axis(plan: ShardingPlan):
    if plan.regime != "fsdp":
        return None
    return ("pod", "data") if plan.multi_pod else "data"


def param_spec_for_path(path: str, ndim: int, plan: ShardingPlan) -> P:
    """path: '/'-joined dict keys, e.g. 'blocks/attn/wq'."""
    ax = plan.axes
    fa = _fsdp_axis(plan)
    name = path.split("/")[-1]
    stacked = path.startswith(("blocks", "mamba_groups", "mamba_tail", "groups"))
    L = (None,) if stacked else ()

    def spec(*dims):
        return P(*L, *dims)

    # --- embeddings / head ---
    if name == "embed":
        return P(ax["vocab"], fa)
    if name == "lm_head":
        return P(fa, ax["vocab"])
    # --- norms & small vectors: replicated ---
    if name in ("final_norm", "attn_norm", "mlp_norm", "norm", "q_norm",
                "k_norm", "A_log", "D", "dt_bias", "conv_x_b", "conv_bc_b",
                "conv_bc_w"):
        return spec(*([None] * (ndim - len(L))))
    # --- attention ---
    if name == "wq":
        return spec(fa, ax["heads"])
    if name in ("wk", "wv"):
        return spec(fa, ax["kv_heads"])
    if name == "wo" and "attn" in path:
        return spec(ax["heads"], fa)
    # --- dense mlp ---
    if name in ("wi_gate", "wi_up") and "moe" not in path:
        return spec(fa, ax["d_ff"])
    if name == "wo" and "mlp" in path:
        return spec(ax["d_ff"], fa)
    # --- moe ---
    if name == "router":
        return spec(fa, None)
    if name in ("wi_gate", "wi_up") and "moe" in path:
        return spec(ax["experts"], fa, ax["expert_ff"])
    if name == "wo" and "moe" in path:
        return spec(ax["experts"], ax["expert_ff"], fa)
    # --- mamba ---
    if name in ("wx", "wz"):
        return spec(fa, ax["ssm_inner"])
    if name in ("wB", "wC", "wdt"):
        return spec(fa, None)
    if name == "conv_x_w":
        return spec(None, ax["ssm_inner"])
    if name == "out_proj":
        return spec(ax["ssm_inner"], fa)
    if name == "norm":
        return spec(None)
    raise ValueError(f"no sharding rule for param path {path!r} (ndim={ndim})")


def tree_specs(tree: Pytree, plan: ShardingPlan, spec_fn) -> Pytree:
    """Map spec_fn(path_str, ndim) over a pytree of ShapeDtypeStruct/arrays."""
    def visit(kp, leaf):
        path = "/".join(
            k.key if hasattr(k, "key") else str(k) for k in kp
        )
        return spec_fn(path, getattr(leaf, "ndim", len(leaf.shape)))
    return jax.tree_util.tree_map_with_path(visit, tree)


def param_specs(params_shape: Pytree, plan: ShardingPlan) -> Pytree:
    return tree_specs(params_shape, plan,
                      lambda p, nd: param_spec_for_path(p, nd, plan))


# ---------------------------------------------------------------------------
# data / cache specs
# ---------------------------------------------------------------------------

def batch_axis(plan: ShardingPlan, batch_size: int):
    """Shard the batch over as many of (pod, data) as divide it; B=1 decodes
    are model-parallel-only (reported in the roofline)."""
    pod = plan.mesh.shape.get("pod", 1) if plan.multi_pod else 1
    data = plan.mesh.shape["data"]
    if plan.multi_pod and batch_size % (pod * data) == 0:
        return ("pod", "data")
    if batch_size % data == 0:
        return "data"
    return None


def batch_specs(batch_shape: Pytree, plan: ShardingPlan, batch_size: int) -> Pytree:
    ba = batch_axis(plan, batch_size)

    def visit(path, leaf):
        nd = len(leaf.shape)
        return P(ba, *([None] * (nd - 1)))

    return jax.tree_util.tree_map_with_path(visit, batch_shape)


def cache_spec_for_path(path: str, ndim: int, plan: ShardingPlan,
                        batch_size: int) -> P:
    ax, ba = plan.axes, batch_axis(plan, batch_size)
    name = path.split("/")[-1]
    # all cache leaves are layer-stacked: leading L axis
    if name in ("k", "v", "k_scale", "v_scale"):   # [L, B, C, KV, hd|1]
        return P(None, ba, None, ax["kv_heads"], None)
    if name == "pos":             # [L, B, C]
        return P(None, ba, None)
    if name == "idx":             # [L]
        return P(None)
    if name == "conv":            # [L, B, W-1, conv_dim]
        return P(None, ba, None, None)
    if name == "ssm":             # [L, B, nh, hd, st]
        return P(None, ba, ax["ssm_inner"] if plan.cfg.ssm_heads % plan.model_shards == 0 else None, None, None)
    raise ValueError(f"no cache rule for {path!r}")


def cache_specs(cache_shape: Pytree, plan: ShardingPlan, batch_size: int) -> Pytree:
    return tree_specs(cache_shape, plan,
                      lambda p, nd: cache_spec_for_path(p, nd, plan, batch_size))
