"""Transformer / MoE / Mamba2 building blocks, sharding-annotated.

Conventions:
* params are nested dicts of jnp arrays; layer stacks carry a leading
  [num_layers] axis and are consumed by ``jax.lax.scan`` so HLO size is
  O(1 layer) regardless of depth (required: 80-layer dry-runs at 512 logical
  devices on a 1-core CPU host).
* every block takes ``sh``: a ``Sharder`` that applies
  with_sharding_constraint when a mesh is active and no-ops otherwise, so the
  same code path serves smoke tests (1 CPU device) and the production mesh.
* GQA head padding: configs' ``eff_heads``/``eff_kv_heads`` may exceed the
  true counts for tensor-parallel divisibility. Padded q-heads have zero
  o_proj rows => exact no-ops at init (documented in DESIGN.md §5).
* dtype policy: params & activations in cfg.dtype (bf16 for the big archs),
  softmax/normalization/SSM state math in f32.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

Pytree = Any


# ---------------------------------------------------------------------------
# sharding helper
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Sharder:
    """Applies logical-axis sharding constraints when a mesh is present.

    axes maps logical names -> mesh axis (or None). The FL mapping puts
    clients on ("pod","data") — ``batch`` is sharded over both — and tensor
    parallelism on "model".
    """

    mesh: Any = None
    axes: dict | None = None

    def spec(self, *logical: str | None) -> P:
        ax = self.axes or {}
        return P(*(ax.get(l) if l else None for l in logical))

    def __call__(self, x: jax.Array, *logical: str | None) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.spec(*logical))
        )


def default_axes(multi_pod: bool = False) -> dict:
    batch = ("pod", "data") if multi_pod else "data"
    return {
        "batch": batch,
        "seq": None,
        "heads": "model",
        "kv_heads": "model",     # overridden to None when kv % shards != 0
        "d_model": None,
        "d_ff": "model",
        "experts": "model",      # overridden to None when E % shards != 0
        "expert_ff": None,       # flipped to "model" when experts replicated
        "vocab": "model",
        "ssm_inner": "model",
        "ssm_state": None,
    }


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dt)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, hd]; positions: [B, S] (int). Rotates pairs (even, odd)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs   # [B, S, hd/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def linear(p: dict, name: str, x: jax.Array) -> jax.Array:
    """``x @ p[name]``, plus the low-rank adapter ``(x @ A) @ B`` when the
    params carry one under ``name + "_lora"`` (core/lm.py merges an adapter
    into a frozen base that way; B arrives already scaled by alpha/rank)."""
    y = x @ p[name]
    ab = p.get(name + "_lora")
    if ab is not None:
        a, b = ab
        y = y + (x @ a) @ b
    return y


def dense_init(rng, shape, in_axis_size, dtype):
    scale = 1.0 / jnp.sqrt(jnp.asarray(in_axis_size, jnp.float32))
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# attention (GQA, optional qk-norm, full-causal or sliding-window, KV cache)
# ---------------------------------------------------------------------------

def gqa_mode(cfg) -> str:
    """'grouped' when attention can use the block-GQA einsum (q reshaped to
    [.., KV_eff, G, hd] with NO kv materialization — §Perf H2); requires the
    uniform slot map i -> i//G to reproduce the TRUE mapping i -> i·KV//H
    through the replicated-kv weight layout. Otherwise 'gather'."""
    H, KVe = cfg.eff_heads, cfg.eff_kv_heads
    KV, Ht = cfg.num_kv_heads, cfg.num_heads
    if not H or H % KVe != 0 or KVe % KV != 0:
        return "gather"
    G, r = H // KVe, KVe // KV
    for i in range(Ht):
        if (i // G) // r != (i * KV) // Ht:
            return "gather"
    return "grouped"


def attn_init(rng, cfg, dtype) -> dict:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    H, KV = cfg.eff_heads, cfg.eff_kv_heads
    KV_true = cfg.num_kv_heads
    ks = jax.random.split(rng, 4)

    def kv_proj(rng_):
        if gqa_mode(cfg) == "grouped" and KV != KV_true and KV % KV_true == 0:
            # replicated-kv layout: padded slots repeat true kv heads so the
            # uniform grouped mapping stays exact (DESIGN.md §5)
            w = dense_init(rng_, (d, KV_true, hd), d, dtype)
            return jnp.repeat(w, KV // KV_true, axis=1).reshape(d, KV * hd)
        return dense_init(rng_, (d, KV * hd), d, dtype)

    p = {
        "wq": dense_init(ks[0], (d, H * hd), d, dtype),
        "wk": kv_proj(ks[1]),
        "wv": kv_proj(ks[2]),
        "wo": dense_init(ks[3], (H * hd, d), H * hd, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    if cfg.eff_heads != cfg.num_heads:
        # zero the padded heads' output rows => padded heads are no-ops
        mask = (jnp.arange(H * hd) < cfg.num_heads * hd).astype(dtype)
        p["wo"] = p["wo"] * mask[:, None]
    return p


def _attn_scores_mask(q_pos, k_pos, window: int):
    """[.., Sq, Sk] boolean mask: causal, optionally sliding-window."""
    m = k_pos[..., None, :] <= q_pos[..., :, None]
    if window:
        m = jnp.logical_and(m, k_pos[..., None, :] > q_pos[..., :, None] - window)
    return m


def attention_scale(cfg):
    """The softmax scale: the config's ``attention_multiplier`` (granite's
    muP scale, 1/head_dim there) or the usual 1/sqrt(head_dim)."""
    if cfg.attention_multiplier:
        return cfg.attention_multiplier
    return 1.0 / jnp.sqrt(jnp.asarray(cfg.resolved_head_dim, jnp.float32))


def _attention_blocked(q5, k, v, positions, window: int, scale,
                       block: int = 512):
    """Flash-style blocked attention in pure XLA (§Perf H3 iter 2).

    q5: [B, Sq, KV, G, hd] (grouped layout); k, v: [B, Sk, KV, hd].
    Scans over key blocks with an online softmax so the [Sq, Sk] score
    matrix is NEVER materialized in HBM — on the TPU target the Pallas
    kernel (kernels/flash_attention) does the same thing intra-core; this
    version is the GSPMD-shardable train/prefill path. The scan body is
    rematerialized so backward recomputes per-block scores instead of
    saving them.
    """
    B, Sq, KV, G, hd = q5.shape
    Sk = k.shape[1]
    block = min(block, Sk)
    nb = Sk // block
    assert Sk % block == 0
    q32 = q5.astype(jnp.float32)

    def body(carry, kb):
        acc, m, l = carry
        k_b, v_b, pos_b = kb                              # [B, bk, KV, hd]
        s = jnp.einsum("bqkgd,bskd->bkgqs", q32, k_b.astype(jnp.float32))
        s = s * scale
        mask = _attn_scores_mask(positions, pos_b, window)  # [B, Sq, bk]
        s = jnp.where(mask[:, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, v_b.astype(jnp.float32)
        )
        return (acc, m_new, l), None

    acc0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    m0 = jnp.full((B, KV, G, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    kb = (
        k.reshape(B, nb, block, KV, hd).transpose(1, 0, 2, 3, 4),
        v.reshape(B, nb, block, KV, hd).transpose(1, 0, 2, 3, 4),
        positions.reshape(B, nb, block).transpose(1, 0, 2),
    )
    (acc, m, l), _ = jax.lax.scan(jax.checkpoint(body), (acc0, m0, l0), kb)
    out = acc / jnp.maximum(l[..., None], 1e-30)          # [B, KV, G, Sq, hd]
    return out.transpose(0, 3, 1, 2, 4).astype(q5.dtype)  # [B, Sq, KV, G, hd]


def attention(
    p: dict, x: jax.Array, cfg, sh: Sharder,
    positions: jax.Array,
    cache: dict | None = None,
    window: int = 0,
) -> tuple[jax.Array, dict | None]:
    """x: [B, S, d]. With ``cache`` (decode): S==1, reads/writes the KV ring
    buffer. Returns (out [B,S,d], new_cache)."""
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.eff_heads, cfg.eff_kv_heads
    q = linear(p, "wq", x).reshape(B, S, H, hd)
    k = linear(p, "wk", x).reshape(B, S, KV, hd)
    v = linear(p, "wv", x).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"])
        k = rms_norm(k, p["k_norm"])
    if cfg.position_embedding == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    with jax.named_scope("fl.lm_attention"):
        out, new_cache = _attention_core(q, k, v, x.dtype, cfg, sh, positions,
                                         cache, window)
    return linear(p, "wo", out), new_cache


def _attention_core(q, k, v, dtype, cfg, sh, positions, cache, window):
    """softmax(q kᵀ · scale) v over the causal (or windowed) keys, with the
    decode path's KV ring buffer. Returns ([B, S, H·hd] before o_proj,
    new_cache)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    q = sh(q, "batch", None, "heads", None)
    k = sh(k, "batch", None, "kv_heads", None)
    v = sh(v, "batch", None, "kv_heads", None)

    if cache is not None:
        # decode: write this step's k/v at slot idx (ring buffer when the
        # cache is shorter than the sequence, i.e. sliding-window archs)
        C = cache["k"].shape[1]
        slot = cache["idx"] % C
        quant = "k_scale" in cache
        if quant:
            kq, ks = _quantize_kv(k[:, 0])
            vq, vs = _quantize_kv(v[:, 0])
            k_all = cache["k"].at[:, slot].set(kq)
            v_all = cache["v"].at[:, slot].set(vq)
            k_sc = cache["k_scale"].at[:, slot].set(ks)
            v_sc = cache["v_scale"].at[:, slot].set(vs)
            k_pos = cache["pos"].at[:, slot].set(positions[:, 0])
            new_cache = {"k": k_all, "v": v_all, "k_scale": k_sc,
                         "v_scale": v_sc, "pos": k_pos, "idx": cache["idx"] + 1}
            k_use = k_all.astype(jnp.float32) * k_sc
            v_use = (v_all.astype(jnp.float32) * v_sc).astype(dtype)
            k_use = k_use.astype(dtype)
        else:
            k_all = cache["k"].at[:, slot].set(k[:, 0])
            v_all = cache["v"].at[:, slot].set(v[:, 0])
            k_pos = cache["pos"].at[:, slot].set(positions[:, 0])
            new_cache = {"k": k_all, "v": v_all, "pos": k_pos, "idx": cache["idx"] + 1}
            k_use, v_use = k_all, v_all
        k_positions = k_pos
    else:
        new_cache = None
        k_use, v_use, k_positions = k, v, positions

    scale = attention_scale(cfg)
    mask = _attn_scores_mask(positions, k_positions, window)   # [B,Sq,Sk]
    if cache is not None:
        # never-written slots carry pos = -1; exclude them
        valid = (k_positions >= 0)[:, None]
        mask = jnp.logical_and(mask, valid)

    if gqa_mode(cfg) == "grouped":
        # §Perf H2: block-GQA einsum — kv heads are NEVER materialized at q
        # multiplicity (a 4× KV-cache re-read per layer at 76B/decode scale)
        G = H // KV
        q5 = q.reshape(B, S, KV, G, hd)
        if cache is None and S >= 1024 and S % 512 == 0:
            # §Perf H3 iter 2: flash-style blocked path for long train/
            # prefill sequences — scores never hit HBM
            out = _attention_blocked(q5, k_use, v_use, positions, window,
                                     scale)
            return out.reshape(B, S, H * hd), new_cache
        logits = jnp.einsum("bqkgd,bskd->bkgqs", q5, k_use).astype(jnp.float32)
        logits = jnp.where(mask[:, None, None], logits * scale, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
        out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v_use)
        return out.reshape(B, S, H * hd), new_cache

    # gather fallback: map each q head to its kv head via the TRUE counts so
    # padded q/kv heads never change the mapping — padded kv heads are never
    # referenced, padded q heads are killed by their zero o_proj rows.
    Ht, KVt = cfg.num_heads, cfg.num_kv_heads
    kv_map = jnp.asarray(
        [(i * KVt) // Ht if i < Ht else i % KV for i in range(H)], jnp.int32
    )
    k_use = jnp.take(k_use, kv_map, axis=2)
    v_use = jnp.take(v_use, kv_map, axis=2)

    if cache is None and S >= 1024 and S % 512 == 0:
        # §Perf H3 iter 2 (gather-mode variant): blocked attention with the
        # gathered kv treated as MHA (KV=H, G=1)
        out = _attention_blocked(
            q.reshape(B, S, H, 1, hd), k_use, v_use, positions, window, scale
        )
        return out.reshape(B, S, H * hd), new_cache

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_use).astype(jnp.float32) * scale
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_use)
    return out.reshape(B, S, H * hd), new_cache


def init_kv_cache(cfg, batch: int, cache_len: int, dtype) -> dict:
    KV, hd = cfg.eff_kv_heads, cfg.resolved_head_dim
    if getattr(cfg, "kv_quant", False):
        # int8 cache with per-(slot, head) scales — halves HBM traffic of the
        # dominant decode stream (PerfH2 iter 2)
        return {
            "k": jnp.zeros((batch, cache_len, KV, hd), jnp.int8),
            "v": jnp.zeros((batch, cache_len, KV, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, cache_len, KV, 1), jnp.float32),
            "v_scale": jnp.zeros((batch, cache_len, KV, 1), jnp.float32),
            "pos": jnp.full((batch, cache_len), -1, jnp.int32),
            "idx": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, cache_len, KV, hd), dtype),
        "v": jnp.zeros((batch, cache_len, KV, hd), dtype),
        "pos": jnp.full((batch, cache_len), -1, jnp.int32),
        "idx": jnp.zeros((), jnp.int32),
    }


def _quantize_kv(x):
    """x: [B, KV, hd] -> (int8 values, [B, KV, 1] scales)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_init(rng, d: int, f: int, dtype) -> dict:
    ks = jax.random.split(rng, 3)
    return {
        "wi_gate": dense_init(ks[0], (d, f), d, dtype),
        "wi_up": dense_init(ks[1], (d, f), d, dtype),
        "wo": dense_init(ks[2], (f, d), f, dtype),
    }


def mlp(p: dict, x: jax.Array, sh: Sharder) -> jax.Array:
    h = jax.nn.silu(linear(p, "wi_gate", x)) * linear(p, "wi_up", x)
    h = sh(h, "batch", None, "d_ff")
    return linear(p, "wo", h)


# ---------------------------------------------------------------------------
# MoE (capacity-based scatter/gather dispatch — no dense one-hot einsum, so
# cost_analysis FLOPs stay honest and XLA emits a real all-to-all when experts
# are sharded on "model")
# ---------------------------------------------------------------------------

def moe_sharded(p: dict, x: jax.Array, cfg, sh: Sharder,
                dropless: bool = False) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE via explicit shard_map (§Perf H1, iteration 2).

    GSPMD lowers the data-dependent scatter dispatch of ``moe`` to an
    all-reduce of the whole [E, C, d] buffer per layer. The communication-
    optimal pattern needs no dispatch collective at all: activations are
    replicated over the 'model' axis (they are sharded over 'data'), so each
    model rank can locally select the tokens routed to ITS experts, run the
    expert FFNs, and contribute a partial [T_loc, d] output — one psum over
    'model' per layer is the entire collective footprint.
    """
    mesh = sh.mesh
    E = cfg.eff_experts
    model_size = mesh.shape["model"]
    if E % model_size != 0:
        return moe(p, x, cfg, sh, dropless=dropless)
    E_loc = E // model_size
    k = cfg.experts_per_token
    batch_ax = sh.axes.get("batch") or None
    batch_tuple = batch_ax if isinstance(batch_ax, tuple) else (
        (batch_ax,) if batch_ax else ())
    # B=1 decodes (long_500k) can't shard the batch — replicate it instead
    n_batch_shards = 1
    for a in batch_tuple:
        n_batch_shards *= mesh.shape[a]
    if x.shape[0] % max(n_batch_shards, 1) != 0:
        batch_ax, batch_tuple = None, ()

    from jax.sharding import PartitionSpec as P

    def local_fn(router_w, wig, wiu, wo, xl):
        Bl, Sl, d = xl.shape
        T = Bl * Sl
        xt = xl.reshape(T, d)
        logits = xt.astype(jnp.float32) @ router_w
        if E != cfg.num_experts:
            dummy = jnp.arange(E) >= cfg.num_experts
            logits = jnp.where(dummy[None, :], -1e30, logits)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_i = jax.lax.top_k(probs, k)
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
        frac = jnp.mean(jax.nn.one_hot(gate_i[:, 0], E, dtype=jnp.float32), 0)
        aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))
        if batch_tuple:
            aux = jax.lax.pmean(aux, batch_tuple)

        cap = T * k if dropless else max(int(cfg.capacity_factor * T * k / E), 1)
        flat_e = gate_i.reshape(-1)                       # [T*k] global ids
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(T * k), flat_e]
        e0 = jax.lax.axis_index("model") * E_loc
        local_e = flat_e - e0                             # [T*k]
        mine = jnp.logical_and(local_e >= 0, local_e < E_loc)
        keep = jnp.logical_and(mine, pos < cap)
        safe_e = jnp.where(keep, local_e, 0)
        safe_p = jnp.where(keep, pos, cap - 1)

        # index-based dispatch (§Perf H1 iter 4): scatter 4-byte token ids
        # instead of the [T·k, d] repeated activations, then gather rows —
        # cuts dispatch HBM traffic by ~d·dtype/4 per assignment.
        tok_id = jnp.arange(T * k, dtype=jnp.int32) // k
        idx_buf = jnp.full((E_loc, cap), T, jnp.int32)       # T = sentinel
        idx_buf = idx_buf.at[safe_e, safe_p].set(jnp.where(keep, tok_id, T))
        xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
        buf = xt_pad[idx_buf]                                # [E_loc, cap, d]
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wig))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wiu)
        yb = jnp.einsum("ecf,efd->ecd", h, wo)
        y_tok = jnp.where(keep[:, None], yb[safe_e, safe_p], 0)
        w_flat = gate_w.reshape(-1, 1).astype(xl.dtype)
        y = jnp.sum((y_tok * w_flat).reshape(T, k, d), axis=1)
        y = jax.lax.psum(y, "model")                      # THE collective
        return y.reshape(Bl, Sl, d), aux

    ba = batch_ax
    out, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(), P("model", None, None), P("model", None, None),
                  P("model", None, None), P(ba, None, None)),
        out_specs=(P(ba, None, None), P()),
        check_vma=False,
    )(p["router"], p["wi_gate"], p["wi_up"], p["wo"], x)
    return out, aux


def moe_init(rng, cfg, dtype) -> dict:
    d, E, f = cfg.d_model, cfg.eff_experts, cfg.moe_d_ff
    ks = jax.random.split(rng, 4)
    return {
        "router": dense_init(ks[0], (d, E), d, jnp.float32),
        "wi_gate": dense_init(ks[1], (E, d, f), d, dtype),
        "wi_up": dense_init(ks[2], (E, d, f), d, dtype),
        "wo": dense_init(ks[3], (E, f, d), f, dtype),
    }


def moe(p: dict, x: jax.Array, cfg, sh: Sharder,
        dropless: bool = False) -> tuple[jax.Array, jax.Array]:
    """Returns (out [B,S,d], aux_loss scalar: load-balance, Switch-style).

    dropless=True sets capacity = T*k so no token can ever be dropped —
    required for decode (capacity routing is non-causal across the batch, so
    teacher-forced decode would diverge from a capacity-based forward).
    Training/prefill keep GShard capacity semantics (cfg.capacity_factor)."""
    B, S, d = x.shape
    E, k = cfg.eff_experts, cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, d)
    logits = (xt.astype(jnp.float32) @ p["router"])            # [T, E]
    if E != cfg.num_experts:
        # padded (dummy) experts are masked out of routing entirely
        dummy = jnp.arange(E) >= cfg.num_experts
        logits = jnp.where(dummy[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_i = jax.lax.top_k(probs, k)                    # [T, k]
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * Σ_e fraction_e * prob_e
    frac = jnp.mean(jax.nn.one_hot(gate_i[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))

    if dropless:
        capacity = T * k          # worst case: every assignment to one expert
    else:
        capacity = max(int(cfg.capacity_factor * T * k / E), 1)

    # position of each (token, slot) within its expert, via cumsum of one-hot
    flat_e = gate_i.reshape(-1)                                 # [T*k]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)         # [T*k, E]
    pos_in_e = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(T * k), flat_e]
    keep = pos_in_e < capacity
    safe_pos = jnp.where(keep, pos_in_e, capacity - 1)

    # scatter tokens into [E, C, d] expert buffers
    xt_rep = jnp.repeat(xt, k, axis=0)                          # [T*k, d]
    buf = jnp.zeros((E, capacity, d), x.dtype)
    buf = buf.at[flat_e, safe_pos].add(jnp.where(keep[:, None], xt_rep, 0))
    buf = sh(buf, "experts", None, None)

    # per-expert FFN (batched over E)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["wi_up"])
    h = sh(h, "experts", None, "expert_ff")
    yb = jnp.einsum("ecf,efd->ecd", h, p["wo"])                 # [E, C, d]
    yb = sh(yb, "experts", None, None)

    # gather back + weight
    y_tok = yb[flat_e, safe_pos]                                # [T*k, d]
    y_tok = jnp.where(keep[:, None], y_tok, 0)
    w_flat = gate_w.reshape(-1, 1).astype(x.dtype)
    y = jnp.sum((y_tok * w_flat).reshape(T, k, d), axis=1)
    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Mamba2 (SSD) mixer
# ---------------------------------------------------------------------------

def mamba_init(rng, cfg, dtype) -> dict:
    """Projections kept SEPARATE (not fused) so tensor-parallel sharding is
    clean: wx/wz/out_proj shard on d_inner ('model'); B/C/dt projections are
    small and replicated. The causal conv is split accordingly (conv_x over
    the sharded inner channels, conv_bc over the replicated state channels)."""
    d, di, st = cfg.d_model, cfg.d_inner, cfg.ssm_state
    nh = cfg.ssm_heads
    ks = jax.random.split(rng, 8)
    return {
        "wx": dense_init(ks[0], (d, di), d, dtype),
        "wz": dense_init(ks[1], (d, di), d, dtype),
        "wB": dense_init(ks[2], (d, st), d, dtype),
        "wC": dense_init(ks[3], (d, st), d, dtype),
        "wdt": dense_init(ks[4], (d, nh), d, dtype),
        "conv_x_w": dense_init(ks[5], (cfg.ssm_conv_width, di), cfg.ssm_conv_width, dtype),
        "conv_x_b": jnp.zeros((di,), dtype),
        "conv_bc_w": dense_init(ks[6], (cfg.ssm_conv_width, 2 * st), cfg.ssm_conv_width, dtype),
        "conv_bc_b": jnp.zeros((2 * st,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm": jnp.ones((di,), dtype),
        "out_proj": dense_init(ks[7], (di, d), di, dtype),
    }


def _ssd_chunked_scan(xh, dt, A, Bm, Cm, chunk: int, ssd_fn=None):
    """SSD forward (Mamba2, arXiv:2405.21060 §6): chunked dual form.

    xh: [B, S, nh, hd]; dt: [B, S, nh] (softplus'd); A: [nh] (negative);
    Bm/Cm: [B, S, st]. Returns y [B, S, nh, hd] and final state
    [B, nh, hd, st].

    ``ssd_fn`` optionally overrides the intra-chunk compute with the Pallas
    kernel (kernels/ssd); default is the pure-jnp reference path.
    """
    B, S, nh, hd = xh.shape
    st = Bm.shape[-1]
    nc = S // chunk
    Q = chunk

    xc = xh.reshape(B, nc, Q, nh, hd)
    dtc = dt.reshape(B, nc, Q, nh)
    Bc = Bm.reshape(B, nc, Q, st)
    Cc = Cm.reshape(B, nc, Q, st)

    dA = dtc * A[None, None, None, :]              # [B,nc,Q,nh]  (negative)
    dA_cumsum = jnp.cumsum(dA, axis=2)             # within-chunk cumsum

    if ssd_fn is not None:
        y_diag, chunk_state = ssd_fn(xc, dtc, dA_cumsum, Bc, Cc)
    else:
        # intra-chunk (diagonal block): quadratic attention-like form
        # L[i,j] = exp(dA_cum[i] - dA_cum[j]) for i >= j
        seg = dA_cumsum[:, :, :, None, :] - dA_cumsum[:, :, None, :, :]
        causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
        # mask BEFORE exp: the non-causal region has seg > 0 and would
        # overflow, poisoning gradients through the where (NaN-grad trap)
        decay = jnp.exp(jnp.where(causal, seg, -1e30))
        cb = jnp.einsum("bcqs,bcks->bcqk", Cc, Bc)  # [B,nc,Q,Q]
        att = cb[..., None] * decay                  # [B,nc,Q,Q,nh]
        xdt = xc * dtc[..., None]                    # dt-weighted inputs
        y_diag = jnp.einsum("bcqkh,bckhd->bcqhd", att, xdt)
        # chunk final states: sum_j exp(dA_cum[Q-1]-dA_cum[j]) dt_j B_j x_j
        decay_last = jnp.exp(dA_cumsum[:, :, -1:, :] - dA_cumsum)   # [B,nc,Q,nh]
        chunk_state = jnp.einsum(
            "bcqs,bcqh,bcqhd->bchds", Bc, dtc * decay_last, xc
        )                                            # [B,nc,nh,hd,st]

    # inter-chunk recurrence over nc (associative scan on (decay, state))
    chunk_decay = jnp.exp(dA_cumsum[:, :, -1, :])    # [B,nc,nh]

    def combine(a, b):
        d_a, s_a = a
        d_b, s_b = b
        return d_a * d_b, s_a * d_b[..., None, None] + s_b

    decays, states = jax.lax.associative_scan(
        combine, (chunk_decay, chunk_state), axis=1
    )
    # state entering chunk c = states[c-1]; shift right with zero init
    init_state = jnp.zeros_like(states[:, :1])
    prev_states = jnp.concatenate([init_state, states[:, :-1]], axis=1)

    # contribution of carried-in state to each position in the chunk
    state_decay = jnp.exp(dA_cumsum)                 # [B,nc,Q,nh]
    y_off = jnp.einsum(
        "bcqs,bchds,bcqh->bcqhd", Cc, prev_states, state_decay
    )
    y = (y_diag + y_off).reshape(B, S, nh, hd)
    final_state = states[:, -1]                      # [B,nh,hd,st]
    return y, final_state


def mamba_forward(
    p: dict, x: jax.Array, cfg, sh: Sharder,
    state: dict | None = None, ssd_fn=None,
) -> tuple[jax.Array, dict | None]:
    """Mamba2 block. Training/prefill when state is None (uses chunked SSD);
    single-token decode when state given (O(1) recurrent update).

    state = {"conv": [B, W-1, conv_dim], "ssm": [B, nh, hd, st]}.
    """
    B, S, d = x.shape
    di, st, nh, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    xz = sh(linear(p, "wx", x), "batch", None, "ssm_inner")
    z = sh(linear(p, "wz", x), "batch", None, "ssm_inner")
    Bm = linear(p, "wB", x)
    Cm = linear(p, "wC", x)
    dt_raw = linear(p, "wdt", x)

    conv_in = jnp.concatenate([xz, Bm, Cm], axis=-1)     # [B,S,di+2st]
    W = cfg.ssm_conv_width
    if state is None:
        pad = jnp.zeros((B, W - 1, conv_in.shape[-1]), conv_in.dtype)
        cseq = jnp.concatenate([pad, conv_in], axis=1)
        new_conv_state = cseq[:, -(W - 1):] if W > 1 else None
    else:
        cseq = jnp.concatenate([state["conv"], conv_in], axis=1)
        new_conv_state = cseq[:, -(W - 1):]
    # depthwise causal conv, split into sharded-x and replicated-B/C parts
    idx = jnp.arange(S)[:, None] + jnp.arange(W)[None, :]
    windows = cseq[:, idx]                                # [B,S,W,di+2st]
    wx_full = jnp.concatenate([p["conv_x_w"], p["conv_bc_w"]], axis=-1)
    bx_full = jnp.concatenate([p["conv_x_b"], p["conv_bc_b"]], axis=-1)
    conv_out = jnp.einsum("bswc,wc->bsc", windows, wx_full) + bx_full
    conv_out = jax.nn.silu(conv_out)
    xc, Bc, Cc = jnp.split(conv_out, [di, di + st], axis=-1)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])   # [B,S,nh]
    A = -jnp.exp(p["A_log"])                                          # [nh], < 0
    xh = xc.reshape(B, S, nh, hd).astype(jnp.float32)
    Bc32, Cc32 = Bc.astype(jnp.float32), Cc.astype(jnp.float32)

    if state is None:
        with jax.named_scope("fl.lm_ssd"):
            y, final_state = _ssd_chunked_scan(
                xh, dt, A, Bc32, Cc32, min(cfg.ssm_chunk, S), ssd_fn=ssd_fn
            )
        new_state = (
            {"conv": new_conv_state, "ssm": final_state} if new_conv_state is not None
            else {"ssm": final_state}
        )
    else:
        # recurrent step: h ← exp(dtA) h + dt·B⊗x ;  y = C·h + D·x
        dA = jnp.exp(dt[:, 0] * A[None])                 # [B,nh]
        h = state["ssm"] * dA[..., None, None]
        h = h + jnp.einsum("bh,bhd,bs->bhds", dt[:, 0], xh[:, 0], Bc32[:, 0])
        y = jnp.einsum("bs,bhds->bhd", Cc32[:, 0], h)[:, None]  # [B,1,nh,hd]
        final_state = h
        new_state = {"conv": new_conv_state, "ssm": final_state}

    y = y + xh * p["D"][None, None, :, None]
    y = y.reshape(B, S, di).astype(x.dtype)
    y = y * jax.nn.silu(z)                                # gated
    y = rms_norm(y, p["norm"], cfg.norm_eps)
    y = sh(y, "batch", None, "ssm_inner")
    return linear(p, "out_proj", y), new_state


def init_ssm_state(cfg, batch: int, dtype) -> dict:
    conv_dim = cfg.d_inner + 2 * cfg.ssm_state
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
    }
