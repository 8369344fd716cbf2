"""Unified decoder model over all assigned families.

Layer stacks are scanned (``jax.lax.scan``) so HLO is depth-independent; the
hybrid (Zamba2) family uses a group-scan: scan over groups of
(period−1 mamba layers + one weight-TIED shared attention/MLP block). A
config with ``layer_types`` (granite-4.0-h) is a stack driven by that
pattern: one scan per run of layers of the same kind, each layer a mixer
(Mamba-2 or attention) and its own SwiGLU MLP, with the residual stream in
float32 whatever the weights' dtype.

API (all pure functions, built by ``build_model(cfg, sh)``):
  init(rng)                        -> params
  forward(params, tokens, embeds)  -> logits            (train/prefill path)
  loss(params, batch)              -> scalar
  prefill(params, tokens, embeds)  -> (logits_last, caches)
  decode_step(params, caches, tok, pos) -> (logits, caches)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as Lyr
from repro.models.layers import Sharder

Pytree = Any


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    sh: Sharder
    init: Callable
    forward: Callable
    loss: Callable
    prefill: Callable
    decode_step: Callable
    init_caches: Callable


# ---------------------------------------------------------------------------
# per-family layer bodies
# ---------------------------------------------------------------------------

def _dense_block(p, h, cfg, sh, positions, window, cache=None):
    a, new_cache = Lyr.attention(
        p["attn"], Lyr.rms_norm(h, p["attn_norm"]), cfg, sh, positions,
        cache=cache, window=window,
    )
    h = h + a
    h = h + Lyr.mlp(p["mlp"], Lyr.rms_norm(h, p["mlp_norm"]), sh)
    return h, new_cache


def _moe_block(p, h, cfg, sh, positions, window, cache=None):
    a, new_cache = Lyr.attention(
        p["attn"], Lyr.rms_norm(h, p["attn_norm"]), cfg, sh, positions,
        cache=cache, window=window,
    )
    h = h + a
    # decode (cache given) uses dropless routing: capacity dispatch is
    # non-causal, so drops would make decode diverge from teacher forcing
    # Under a mesh, the expert-parallel shard_map path is used (see Perf H1).
    moe_fn = Lyr.moe_sharded if sh.mesh is not None else Lyr.moe
    y, aux = moe_fn(p["moe"], Lyr.rms_norm(h, p["mlp_norm"]), cfg, sh,
                    dropless=cache is not None)
    return h + y, new_cache, aux


def _ssm_block(p, h, cfg, sh, state=None, ssd_fn=None):
    y, new_state = Lyr.mamba_forward(
        p["mixer"], Lyr.rms_norm(h, p["norm"]), cfg, sh, state=state, ssd_fn=ssd_fn
    )
    return h + y, new_state


def _pattern_block(kind, p, h, cfg, sh, positions, window, cache=None,
                   ssd_fn=None):
    """One layer of a ``layer_types`` stack (granite-4.0-h):
    h += m·mixer(norm(h)); h += m·mlp(norm(h)), m the residual multiplier.
    Returns (h, the mixer's new cache or final state)."""
    m = cfg.residual_multiplier
    x = Lyr.rms_norm(h, p["norm"], cfg.norm_eps)
    if kind == "mamba":
        y, new_cache = Lyr.mamba_forward(p["mixer"], x, cfg, sh, state=cache,
                                         ssd_fn=ssd_fn)
    else:
        y, new_cache = Lyr.attention(p["attn"], x, cfg, sh, positions,
                                     cache=cache, window=window)
    h = h + m * y
    h = h + m * Lyr.mlp(p["mlp"], Lyr.rms_norm(h, p["mlp_norm"], cfg.norm_eps),
                        sh)
    return h, new_cache


def pattern_groups(cfg) -> list:
    """The runs of one kind in ``cfg.layer_types``: [(kind, layers), ...];
    the params hold one layer-stacked group per run (``params["groups"]``)."""
    groups = []
    for kind in cfg.layer_types:
        if groups and groups[-1][0] == kind:
            groups[-1] = (kind, groups[-1][1] + 1)
        else:
            groups.append((kind, 1))
    return groups


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stacked_init(layer_init: Callable, rng: jax.Array, n: int) -> Pytree:
    return jax.vmap(layer_init)(jax.random.split(rng, n))


def _dense_layer_init(cfg, dtype):
    def one(rng):
        k1, k2 = jax.random.split(rng)
        return {
            "attn_norm": jnp.ones((cfg.d_model,), dtype),
            "attn": Lyr.attn_init(k1, cfg, dtype),
            "mlp_norm": jnp.ones((cfg.d_model,), dtype),
            "mlp": Lyr.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype),
        }
    return one


def _moe_layer_init(cfg, dtype):
    def one(rng):
        k1, k2 = jax.random.split(rng)
        return {
            "attn_norm": jnp.ones((cfg.d_model,), dtype),
            "attn": Lyr.attn_init(k1, cfg, dtype),
            "mlp_norm": jnp.ones((cfg.d_model,), dtype),
            "moe": Lyr.moe_init(k2, cfg, dtype),
        }
    return one


def _ssm_layer_init(cfg, dtype):
    def one(rng):
        return {
            "norm": jnp.ones((cfg.d_model,), dtype),
            "mixer": Lyr.mamba_init(rng, cfg, dtype),
        }
    return one


def _pattern_layer_init(cfg, kind, dtype):
    def one(rng):
        k1, k2 = jax.random.split(rng)
        mixer = ({"mixer": Lyr.mamba_init(k1, cfg, dtype)} if kind == "mamba"
                 else {"attn": Lyr.attn_init(k1, cfg, dtype)})
        return {
            "norm": jnp.ones((cfg.d_model,), dtype),
            **mixer,
            "mlp_norm": jnp.ones((cfg.d_model,), dtype),
            "mlp": Lyr.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype),
        }
    return one


def _hybrid_counts(cfg):
    """Zamba2 pattern: every ``period``-th block is the shared attn block.
    total = num_layers; n_shared = L // period; mamba fills the rest."""
    p = cfg.shared_attn_period
    n_shared = cfg.num_layers // p
    n_mamba = cfg.num_layers - n_shared
    group = p - 1                       # mamba layers per group
    n_groups = n_shared
    trailing = n_mamba - n_groups * group
    assert trailing >= 0
    return n_groups, group, trailing


# ---------------------------------------------------------------------------
# model builder
# ---------------------------------------------------------------------------

def build_model(cfg: ArchConfig, sh: Sharder | None = None, ssd_fn=None,
                remat: bool = False) -> Model:
    cfg.validate()
    sh = sh or Sharder()
    dtype = jnp.dtype(cfg.dtype)
    V, d, L = cfg.eff_vocab, cfg.d_model, cfg.num_layers
    fam = "pattern" if cfg.layer_types else cfg.family
    window = cfg.sliding_window
    # the residual stream's dtype: the weights' for every family but the
    # pattern stack, which keeps it in float32
    act_dtype = jnp.float32 if fam == "pattern" else dtype
    groups = pattern_groups(cfg)

    # ------------------------------ init ------------------------------
    def init(rng: jax.Array) -> Pytree:
        ks = jax.random.split(rng, 4)
        params = {
            "embed": Lyr.dense_init(ks[0], (V, d), d, dtype),
            "final_norm": jnp.ones((d,), dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = Lyr.dense_init(ks[1], (d, V), d, dtype)
        if fam in ("dense", "vlm", "audio"):
            params["blocks"] = _stacked_init(_dense_layer_init(cfg, dtype), ks[2], L)
        elif fam == "moe":
            params["blocks"] = _stacked_init(_moe_layer_init(cfg, dtype), ks[2], L)
        elif fam == "ssm":
            params["blocks"] = _stacked_init(_ssm_layer_init(cfg, dtype), ks[2], L)
        elif fam == "pattern":
            params["groups"] = [
                _stacked_init(_pattern_layer_init(cfg, kind, dtype), k, n)
                for (kind, n), k in zip(
                    groups, jax.random.split(ks[2], len(groups)))
            ]
        else:  # hybrid
            n_groups, group, trailing = _hybrid_counts(cfg)
            k_m, k_t, k_s = jax.random.split(ks[2], 3)
            params["mamba_groups"] = _stacked_init(
                _ssm_layer_init(cfg, dtype), k_m, n_groups * group
            )
            if trailing:
                params["mamba_tail"] = _stacked_init(
                    _ssm_layer_init(cfg, dtype), k_t, trailing
                )
            params["shared"] = _dense_layer_init(cfg, dtype)(k_s)  # weight-tied
        return params

    # --------------------------- embedding ---------------------------
    def embed_tokens(params, tokens, embeds):
        mult = cfg.embedding_multiplier or jnp.sqrt(d)
        h = (params["embed"][tokens].astype(act_dtype)
             * jnp.asarray(mult, act_dtype))
        if embeds is not None:
            # modality frontend stub: precomputed embeddings overwrite the
            # first `frontend_tokens` positions (vlm patches / audio frames)
            Pn = embeds.shape[1]
            h = jnp.concatenate([embeds.astype(h.dtype), h[:, Pn:]], axis=1)
        return sh(h, "batch", None, None)

    def scale_logits(logits):
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits

    def unembed(params, h):
        h = Lyr.rms_norm(h, params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = scale_logits(h @ head)
        return sh(logits, "batch", None, "vocab")

    def unembed_last(params, h):
        """Logits for the LAST position only — prefill must never
        materialize [B, S, V] (a 76B/32k prefill would be 269 GB)."""
        return unembed(params, h[:, -1:])[:, -1]

    # --------------------------- forward ------------------------------
    # Remat policy (§Perf H1 iter 3 — REFUTED): dots_saveable measured WORSE
    # (memory term 0.31s -> 0.67s on granite-moe/train_4k): saving every dot
    # output streams more residual bytes through HBM than the elementwise
    # recompute it avoids. Full remat stays the default.
    _remat = jax.checkpoint

    def _scan_blocks(body, params_stack, h, *extra):
        def f(carry, xs):
            out = body(xs, carry, *extra)
            if isinstance(out, tuple):
                return out[0], out[2] if len(out) > 2 else None
            return out, None
        if remat:
            f = _remat(f)
        h, aux = jax.lax.scan(f, h, params_stack)
        return h, aux

    def forward_hidden(params, tokens, embeds=None):
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        h = embed_tokens(params, tokens, embeds)
        aux_total = jnp.zeros((), jnp.float32)

        if fam in ("dense", "vlm", "audio"):
            h, _ = _scan_blocks(
                lambda p, hh: _dense_block(p, hh, cfg, sh, positions, window)[0],
                params["blocks"], h,
            )
        elif fam == "moe":
            def body(carry, p):
                hh, aux = carry
                hh, _, a = _moe_block(p, hh, cfg, sh, positions, window)
                return (hh, aux + a), None
            bodyf = _remat(body) if remat else body
            (h, aux_total), _ = jax.lax.scan(bodyf, (h, aux_total), params["blocks"])
        elif fam == "ssm":
            h, _ = _scan_blocks(
                lambda p, hh: _ssm_block(p, hh, cfg, sh, ssd_fn=ssd_fn)[0],
                params["blocks"], h,
            )
        elif fam == "pattern":
            for (kind, _), gp in zip(groups, params["groups"]):
                h, _ = _scan_blocks(
                    lambda p, hh, kind=kind: _pattern_block(
                        kind, p, hh, cfg, sh, positions, window,
                        ssd_fn=ssd_fn)[0],
                    gp, h,
                )
        else:  # hybrid group scan
            n_groups, group, trailing = _hybrid_counts(cfg)
            gshape = jax.tree.map(
                lambda x: x.reshape((n_groups, group) + x.shape[1:]),
                params["mamba_groups"],
            )

            def group_body(hh, gp):
                hh, _ = _scan_blocks(
                    lambda p, inner_h: _ssm_block(p, inner_h, cfg, sh, ssd_fn=ssd_fn)[0],
                    gp, hh,
                )
                hh, _ = _dense_block(params["shared"], hh, cfg, sh, positions, window)
                return hh, None

            gb = _remat(group_body) if remat else group_body
            h, _ = jax.lax.scan(gb, h, gshape)
            if trailing:
                h, _ = _scan_blocks(
                    lambda p, hh: _ssm_block(p, hh, cfg, sh, ssd_fn=ssd_fn)[0],
                    params["mamba_tail"], h,
                )
        return h, aux_total

    def forward(params, tokens, embeds=None):
        h, aux = forward_hidden(params, tokens, embeds)
        return unembed(params, h), aux

    # ----------------------------- loss -------------------------------
    XENT_CHUNK = 512

    def _chunked_xent(params, h, tgt, mask):
        """PerfH3 iter 3: scan the unembed+softmax-xent over sequence
        chunks so the [B, S, V] logits never hit HBM (the lm head is the
        single largest activation for big-vocab archs); the chunk body is
        rematerialized, so backward recomputes chunk logits too."""
        B, Sm1, d_ = h.shape
        pad_mask = None
        if cfg.eff_vocab != cfg.vocab_size:
            pad_mask = jnp.arange(cfg.eff_vocab) >= cfg.vocab_size
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]

        c = min(XENT_CHUNK, Sm1)
        if Sm1 % c != 0:
            pad = c - Sm1 % c
            h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
            tgt = jnp.pad(tgt, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        nchunk = h.shape[1] // c

        def body(carry, xs):
            hb, tb, mb = xs                      # [B, c, ...]
            lg = scale_logits((hb @ head).astype(jnp.float32))
            lg = sh(lg, "batch", None, "vocab")
            if pad_mask is not None:
                lg = jnp.where(pad_mask[None, None, :], -1e30, lg)
            logp = jax.nn.log_softmax(lg, axis=-1)
            nll = -jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0]
            return carry + jnp.sum(nll * mb), None

        xs = (
            h.reshape(B, nchunk, c, d_).transpose(1, 0, 2, 3),
            tgt.reshape(B, nchunk, c).transpose(1, 0, 2),
            mask.reshape(B, nchunk, c).transpose(1, 0, 2),
        )
        total, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32), xs)
        return total

    def loss(params, batch: dict) -> jax.Array:
        """batch: tokens [B,S] int32, loss_mask [B,S] (optional),
        embeds [B,P,d] (vlm/audio). Next-token cross entropy, computed
        chunked over the sequence (logits never fully materialized)."""
        tokens = batch["tokens"]
        h, aux = forward_hidden(params, tokens, batch.get("embeds"))
        h = Lyr.rms_norm(h, params["final_norm"], cfg.norm_eps)[:, :-1]
        tgt = tokens[:, 1:]
        mask = batch.get("loss_mask")
        mask = (jnp.ones(tgt.shape, jnp.float32) if mask is None
                else mask[:, 1:].astype(jnp.float32))
        if cfg.frontend_tokens and batch.get("embeds") is not None:
            Pn = batch["embeds"].shape[1]
            pos_ok = jnp.arange(tgt.shape[1]) >= Pn    # only text positions
            mask = mask * pos_ok[None, :]
        total = _chunked_xent(params, h, tgt, mask)
        l = total / jnp.maximum(jnp.sum(mask), 1.0)
        if cfg.num_experts:
            l = l + 0.01 * aux
        return l

    # --------------------------- caches -------------------------------
    def init_caches(batch: int, cache_len: int) -> Pytree:
        if fam in ("dense", "vlm", "audio", "moe"):
            def one(_):
                return Lyr.init_kv_cache(cfg, batch, cache_len, dtype)
            return jax.vmap(one)(jnp.arange(L))
        if fam == "ssm":
            def one(_):
                return Lyr.init_ssm_state(cfg, batch, dtype)
            return jax.vmap(one)(jnp.arange(L))
        if fam == "pattern":
            def one(kind):
                if kind == "mamba":
                    return lambda _: Lyr.init_ssm_state(cfg, batch, act_dtype)
                return lambda _: Lyr.init_kv_cache(cfg, batch, cache_len,
                                                   act_dtype)
            return [jax.vmap(one(kind))(jnp.arange(n)) for kind, n in groups]
        # hybrid: mamba states + shared-block KV caches (one per application)
        n_groups, group, trailing = _hybrid_counts(cfg)
        m_states = jax.vmap(lambda _: Lyr.init_ssm_state(cfg, batch, dtype))(
            jnp.arange(n_groups * group)
        )
        t_states = (
            jax.vmap(lambda _: Lyr.init_ssm_state(cfg, batch, dtype))(
                jnp.arange(trailing)
            ) if trailing else None
        )
        kv = jax.vmap(lambda _: Lyr.init_kv_cache(cfg, batch, cache_len, dtype))(
            jnp.arange(n_groups)
        )
        out = {"mamba": m_states, "shared_kv": kv}
        if t_states is not None:
            out["tail"] = t_states
        return out

    # --------------------------- decode -------------------------------
    def decode_step(params, caches, tokens, pos):
        """tokens: [B,1] int32; pos: [B,1] int32 absolute positions."""
        h = embed_tokens(params, tokens, None)

        if fam in ("dense", "vlm", "audio", "moe"):
            def body(hh, xs):
                p, cache = xs
                if fam == "moe":
                    hh, nc, _ = _moe_block(p, hh, cfg, sh, pos, window, cache=cache)
                else:
                    hh, nc = _dense_block(p, hh, cfg, sh, pos, window, cache=cache)
                return hh, nc
            h, new_caches = jax.lax.scan(body, h, (params["blocks"], caches))
        elif fam == "ssm":
            def body(hh, xs):
                p, st = xs
                hh, ns = _ssm_block(p, hh, cfg, sh, state=st)
                return hh, ns
            h, new_caches = jax.lax.scan(body, h, (params["blocks"], caches))
        elif fam == "pattern":
            new_caches = []
            for (kind, _), gp, gc in zip(groups, params["groups"], caches):
                def body(hh, xs, kind=kind):
                    p, c = xs
                    return _pattern_block(kind, p, hh, cfg, sh, pos, window,
                                          cache=c)
                h, nc = jax.lax.scan(body, h, (gp, gc))
                new_caches.append(nc)
        else:  # hybrid
            n_groups, group, trailing = _hybrid_counts(cfg)
            gparams = jax.tree.map(
                lambda x: x.reshape((n_groups, group) + x.shape[1:]),
                params["mamba_groups"],
            )
            gstates = jax.tree.map(
                lambda x: x.reshape((n_groups, group) + x.shape[1:]),
                caches["mamba"],
            )

            def group_body(hh, xs):
                gp, gs, kvc = xs
                def inner(ih, ixs):
                    p, st = ixs
                    ih, ns = _ssm_block(p, ih, cfg, sh, state=st)
                    return ih, ns
                hh, new_gs = jax.lax.scan(inner, hh, (gp, gs))
                hh, new_kv = _dense_block(
                    params["shared"], hh, cfg, sh, pos, window, cache=kvc
                )
                return hh, (new_gs, new_kv)

            h, (new_gstates, new_kv) = jax.lax.scan(
                group_body, h, (gparams, gstates, caches["shared_kv"])
            )
            new_caches = {
                "mamba": jax.tree.map(
                    lambda x: x.reshape((n_groups * group,) + x.shape[2:]), new_gstates
                ),
                "shared_kv": new_kv,
            }
            if trailing:
                def body(hh, xs):
                    p, st = xs
                    hh, ns = _ssm_block(p, hh, cfg, sh, state=st)
                    return hh, ns
                h, new_tail = jax.lax.scan(body, h, (params["mamba_tail"], caches["tail"]))
                new_caches["tail"] = new_tail

        logits = unembed(params, h)
        return logits[:, -1], new_caches

    # --------------------------- prefill ------------------------------
    def prefill(params, tokens, embeds=None, cache_len: int | None = None):
        """Full forward that also builds decode caches (training-free path).

        ``cache_len`` reserves headroom for subsequent decode steps (defaults
        to S — i.e. ring-buffer wrap on the first decoded token; serving
        passes S + max_new_tokens, or the sliding window for windowed archs).

        For attention families the per-layer K/V sequences are recomputed into
        cache layout via a scan that emits them as ys; SSM families emit final
        states directly."""
        B, S = tokens.shape
        C = cache_len or S
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        h = embed_tokens(params, tokens, embeds)

        def pad_kv(kv_stacked, n_stack):
            """[n,B,S,KV,hd] k/v -> cache layout of length C with pos padding."""
            k, v = kv_stacked["k"], kv_stacked["v"]
            if C > S:
                padder = lambda x: jnp.pad(
                    x, ((0, 0), (0, 0), (0, C - S), (0, 0), (0, 0))
                )
                k, v = padder(k), padder(v)
            pos = jnp.pad(
                jnp.broadcast_to(positions, (n_stack, B, S)),
                ((0, 0), (0, 0), (0, C - S)), constant_values=-1,
            )
            return {"k": k, "v": v, "pos": pos,
                    "idx": jnp.full((n_stack,), S, jnp.int32)}

        def attn_with_cache_emit(p, hh):
            hn = Lyr.rms_norm(hh, p["attn_norm"])
            hd = cfg.resolved_head_dim
            H, KV = cfg.eff_heads, cfg.eff_kv_heads
            k = (hn @ p["attn"]["wk"]).reshape(B, S, KV, hd)
            v = (hn @ p["attn"]["wv"]).reshape(B, S, KV, hd)
            if cfg.qk_norm:
                k = Lyr.rms_norm(k, p["attn"]["k_norm"])
            k = Lyr.apply_rope(k, positions, cfg.rope_theta)
            a, _ = Lyr.attention(p["attn"], hn, cfg, sh, positions, window=window)
            hh = hh + a
            return hh, {"k": k, "v": v}

        if fam in ("dense", "vlm", "audio", "moe"):
            def body(hh, p):
                hh, kv = attn_with_cache_emit(p, hh)
                if fam == "moe":
                    moe_fn = Lyr.moe_sharded if sh.mesh is not None else Lyr.moe
                    y, _ = moe_fn(p["moe"], Lyr.rms_norm(hh, p["mlp_norm"]), cfg, sh)
                else:
                    y = Lyr.mlp(p["mlp"], Lyr.rms_norm(hh, p["mlp_norm"]), sh)
                return hh + y, kv
            h, kvs = jax.lax.scan(body, h, params["blocks"])
            caches = pad_kv(kvs, L)
        elif fam == "ssm":
            def body(hh, p):
                hh2, st = _ssm_block(p, hh, cfg, sh, ssd_fn=ssd_fn)
                return hh2, st
            h, caches = jax.lax.scan(body, h, params["blocks"])
        elif fam == "pattern":
            caches = []
            for (kind, n), gp in zip(groups, params["groups"]):
                if kind == "mamba":
                    def body(hh, p):
                        return _pattern_block("mamba", p, hh, cfg, sh,
                                              positions, window, ssd_fn=ssd_fn)
                    h, states = jax.lax.scan(body, h, gp)
                    caches.append(states)
                    continue

                def body(hh, p):
                    hn = Lyr.rms_norm(hh, p["norm"], cfg.norm_eps)
                    hd = cfg.resolved_head_dim
                    k = Lyr.linear(p["attn"], "wk", hn).reshape(
                        B, S, cfg.eff_kv_heads, hd)
                    v = Lyr.linear(p["attn"], "wv", hn).reshape(
                        B, S, cfg.eff_kv_heads, hd)
                    if cfg.position_embedding == "rope":
                        k = Lyr.apply_rope(k, positions, cfg.rope_theta)
                    hh, _ = _pattern_block("attention", p, hh, cfg, sh,
                                           positions, window)
                    return hh, {"k": k, "v": v}
                h, kvs = jax.lax.scan(body, h, gp)
                caches.append(pad_kv(kvs, n))
        else:  # hybrid
            n_groups, group, trailing = _hybrid_counts(cfg)
            gshape = jax.tree.map(
                lambda x: x.reshape((n_groups, group) + x.shape[1:]),
                params["mamba_groups"],
            )

            def group_body(hh, gp):
                def inner(ih, p):
                    ih2, st = _ssm_block(p, ih, cfg, sh, ssd_fn=ssd_fn)
                    return ih2, st
                hh, gstates = jax.lax.scan(inner, hh, gp)
                hh, kv = attn_with_cache_emit(params["shared"], hh)
                y = Lyr.mlp(params["shared"]["mlp"],
                            Lyr.rms_norm(hh, params["shared"]["mlp_norm"]), sh)
                return hh + y, (gstates, kv)

            h, (gstates, kvs) = jax.lax.scan(group_body, h, gshape)
            caches = {
                "mamba": jax.tree.map(
                    lambda x: x.reshape((n_groups * group,) + x.shape[2:]), gstates
                ),
                "shared_kv": pad_kv(kvs, n_groups),
            }
            if trailing:
                def body(hh, p):
                    hh2, st = _ssm_block(p, hh, cfg, sh, ssd_fn=ssd_fn)
                    return hh2, st
                h, tstates = jax.lax.scan(body, h, params["mamba_tail"])
                caches["tail"] = tstates

        return unembed_last(params, h), caches

    return Model(cfg=cfg, sh=sh, init=init, forward=forward, loss=loss,
                 prefill=prefill, decode_step=decode_step, init_caches=init_caches)
