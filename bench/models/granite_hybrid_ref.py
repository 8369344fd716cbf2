"""A plain reference of IBM Granite 4.0-H (``granitemoehybrid`` with no
experts), written from the published description and independent of the
program: it imports nothing of ``repro``.

Layers (hf:ibm-granite/granite-4.0-h-micro config.json; the Mamba-2 paper,
arXiv:2405.21060, for the mixer):

    h_0 = E[tokens] · embedding_multiplier
    h  += residual_multiplier · mixer(rms_norm(h))      mixer: Mamba-2 or GQA
    h  += residual_multiplier · mlp(rms_norm(h))        SwiGLU
    logits = rms_norm(h) · Eᵀ / logits_scaling          tied embeddings

* Mamba-2: x, z, B, C, dt projected from the normed input; a causal
  depthwise conv (width 4, with bias) and SiLU over [x, B, C]; dt =
  softplus(dt + dt_bias), A = −exp(A_log); the selective scan
  h_t = exp(dt_t A) h_{t−1} + dt_t x_t B_tᵀ, y_t = h_t C_t + D x_t, one state
  group; then rms_norm(y · silu(z)) (the gated norm) and out_proj.
* Attention: q, k, v projected, no positional encoding, scores scaled by
  ``attention_multiplier``, causal softmax, each key-value head shared by
  num_heads / num_kv_heads query heads, o_proj.
* Low-rank adapters: a targeted map ``x W`` becomes ``x W + s · (x A) B``.

The training rounds (``fedosaa_svrg``) follow FedOSAA-SVRG
(arXiv:2503.10961, Algorithm 1) on the flat adapter vector, with full
batches, a lossless wire and equal client weights: each round every client
takes L SVRG-corrected gradient steps from the server's w^t, and its upload
is one Anderson step over the L secant pairs of its trajectory.

Departures from a line-by-line transcription, each for memory, none for the
mathematics:

* the scan is computed in its quadratic (attention-like) form over the whole
  sequence, y = (L ∘ C Bᵀ)(dt · x) with L_ij = exp(Σ_{j<k≤i} dt_k A), one
  head at a time; the segment sums are summed directly (masked cumulative
  sums), not as differences of running sums, so no cancellation enters. It
  is not the program's chunked algorithm;
* attention is computed one query head at a time, and the loss over blocks
  of positions, each block rematerialized;
* each layer is rematerialized in the backward pass (``jax.checkpoint``),
  and the layers of one run are a loop (``lax.scan``), to compile once.

The caller gives the weights as plain arrays in ``dtype`` (the reference's
float32 widens the base's bfloat16 values; the control computes wholly in
bfloat16) and sets the matmul precision: ``jax.default_matmul_precision(
"highest")`` for the reference.

Weights: ``{"embed": [V, d], "final_norm": [d], "groups": [group, ...]}``,
one group per run of layers of one kind, in order: ``{"kind": "mamba" |
"attention", "layers": {...}}`` with every array stacked over the run's
layers: ``norm, mlp_norm, mlp: {wi_gate, wi_up, wo}``, and ``mixer: {wx,
wz, wB, wC, wdt [d, ·], conv_x_w [W, d_inner], conv_x_b, conv_bc_w
[W, 2·state], conv_bc_b, A_log, D, dt_bias [heads], norm [d_inner],
out_proj}`` or ``attn: {wq, wk, wv, wo}``. Adapters: per group
``{module: {name: (A, B)}}``, stacked the same way.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LOSS_BLOCK = 512      # positions per block of the loss


@dataclasses.dataclass(frozen=True)
class Shape:
    """The published sizes the reference reads."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    ssm_state: int
    ssm_head_dim: int
    conv_width: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    eps: float


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _linear(p, name, x, adapters, scale):
    y = x @ p[name]
    if adapters is not None and name in adapters:
        a, b = adapters[name]
        y = y + scale * ((x @ a) @ b)
    return y


def _segment_sums(da):
    """[S, S]: Σ_{j<k≤i} da_k on and below the diagonal, −inf above."""
    S = da.shape[0]
    below = jnp.tril(jnp.ones((S, S), bool), -1)
    rows = jnp.where(below, jnp.broadcast_to(da[:, None], (S, S)), 0.0)
    sums = jnp.cumsum(rows, axis=0)                      # [i, j]: Σ_{j<k≤i}
    return jnp.where(jnp.tril(jnp.ones((S, S), bool)), sums, -jnp.inf)


def ssm_scan(x, dt, A, Bm, Cm, D):
    """The selective scan of one sequence. x [S, H, P], dt [S, H], A [H],
    Bm, Cm [S, N], D [H] -> y [S, H, P]."""
    cb = Cm @ Bm.T                                       # [S, S]

    @jax.checkpoint
    def head(args):
        xh, dth, ah = args                               # [S, P], [S], []
        decay = jnp.exp(_segment_sums(dth * ah))
        return (decay * cb) @ (xh * dth[:, None])

    y = jax.lax.map(head, (jnp.moveaxis(x, 1, 0), dt.T, A))
    return jnp.moveaxis(y, 0, 1) + x * D[None, :, None]


def mamba(p, x, sh: Shape, adapters, scale):
    S = x.shape[0]
    xs = _linear(p, "wx", x, adapters, scale)
    z = _linear(p, "wz", x, adapters, scale)
    Bm = _linear(p, "wB", x, adapters, scale)
    Cm = _linear(p, "wC", x, adapters, scale)
    dt = _linear(p, "wdt", x, adapters, scale)
    seq = jnp.concatenate([xs, Bm, Cm], axis=-1)
    w = jnp.concatenate([p["conv_x_w"], p["conv_bc_w"]], axis=-1)
    b = jnp.concatenate([p["conv_x_b"], p["conv_bc_b"]], axis=-1)
    W = sh.conv_width
    padded = jnp.concatenate([jnp.zeros((W - 1, seq.shape[1]), seq.dtype),
                              seq])
    conv = b + sum(padded[k:k + S] * w[k] for k in range(W))
    conv = jax.nn.silu(conv)
    di = xs.shape[1]
    xs, Bm, Cm = conv[:, :di], conv[:, di:di + sh.ssm_state], \
        conv[:, di + sh.ssm_state:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    heads = di // sh.ssm_head_dim
    y = ssm_scan(xs.reshape(S, heads, sh.ssm_head_dim), dt, A, Bm, Cm,
                 p["D"]).reshape(S, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], sh.eps)
    return _linear(p, "out_proj", y, adapters, scale)


def attention(p, x, sh: Shape, adapters, scale):
    S = x.shape[0]
    H, KV, hd = sh.num_heads, sh.num_kv_heads, sh.head_dim
    q = _linear(p, "wq", x, adapters, scale).reshape(S, H, hd)
    k = _linear(p, "wk", x, adapters, scale).reshape(S, KV, hd)
    v = _linear(p, "wv", x, adapters, scale).reshape(S, KV, hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    group = H // KV

    @jax.checkpoint
    def head(args):
        qh, i = args
        kh, vh = k[:, i // group], v[:, i // group]
        s = (qh @ kh.T) * sh.attention_multiplier
        s = jnp.where(causal, s, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    out = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.arange(H)))
    return _linear(p, "wo", jnp.moveaxis(out, 0, 1).reshape(S, H * hd),
                   adapters, scale)


def mlp(p, x, adapters, scale):
    g = _linear(p, "wi_gate", x, adapters, scale)
    u = _linear(p, "wi_up", x, adapters, scale)
    return _linear(p, "wo", jax.nn.silu(g) * u, adapters, scale)


def _layer(kind, sh: Shape, h, p, ad):
    x = rms_norm(h, p["norm"], sh.eps)
    if kind == "mamba":
        y = mamba(p["mixer"], x, sh, ad.get("mixer"), ad.get("scale"))
    else:
        y = attention(p["attn"], x, sh, ad.get("attn"), ad.get("scale"))
    h = h + sh.residual_multiplier * y
    x = rms_norm(h, p["mlp_norm"], sh.eps)
    return h + sh.residual_multiplier * mlp(p["mlp"], x, ad.get("mlp"),
                                            ad.get("scale"))


def hidden(weights, tokens, sh: Shape, adapters=None, scale=1.0):
    """The final normed hidden states [S, d] of one sequence: the layers in
    order, a loop over each run of layers of one kind."""
    h = weights["embed"][tokens] * sh.embedding_multiplier
    for i, group in enumerate(weights["groups"]):
        ad = {} if adapters is None else adapters[i]
        layer = jax.checkpoint(partial(_layer, group["kind"], sh))

        def step(h, xs, layer=layer):
            p, a = xs
            return layer(h, p, {**a, "scale": scale}), None

        h, _ = jax.lax.scan(step, h, (group["layers"], ad))
    return rms_norm(h, weights["final_norm"], sh.eps)


def logits(weights, h, sh: Shape):
    """[S, V] next-token logits from the final hidden states ``h``."""
    return (h @ weights["embed"].T) / sh.logits_scaling


def nll(weights, h, tokens, sh: Shape):
    """Mean next-token negative log-likelihood of a sequence from its final
    hidden states ``h``, over blocks of ``LOSS_BLOCK`` positions."""
    h, tgt = h[:-1], tokens[1:]
    n = h.shape[0]
    pad = -n % LOSS_BLOCK
    h = jnp.pad(h, ((0, pad), (0, 0)))
    tgt = jnp.pad(tgt, (0, pad))
    valid = jnp.arange(n + pad) < n

    @jax.checkpoint
    def block(args):
        hb, tb, vb = args
        z = logits(weights, hb, sh)
        out = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
            z, tb[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(vb, out, 0.0))

    k = (n + pad) // LOSS_BLOCK
    parts = jax.lax.map(block, (h.reshape(k, LOSS_BLOCK, -1),
                                tgt.reshape(k, LOSS_BLOCK),
                                valid.reshape(k, LOSS_BLOCK)))
    return jnp.sum(parts) / n


def adapter_grad(weights, tokens, sh: Shape, adapters, scale):
    """The gradient of a sequence's ``nll`` with respect to every (A, B) of
    ``adapters`` (the same structure)."""
    return adapter_value_and_grad(weights, tokens, sh, adapters, scale)[1]


def adapter_value_and_grad(weights, tokens, sh: Shape, adapters, scale):
    """(a sequence's ``nll``, its gradient with respect to every (A, B) of
    ``adapters``)."""
    def loss(ad):
        return nll(weights, hidden(weights, tokens, sh, ad, scale), tokens,
                   sh)

    return jax.value_and_grad(loss)(adapters)


def anderson_step(w, g, ws, rs, eta):
    """One Anderson step over a client's trajectory: the secant pairs
    S = [w_{l+1} − w_l], Y = [r_{l+1} − r_l] (L columns), the least-squares
    Γ = argmin ‖Y Γ − g‖ (its L×L normal equations solved in float64), and
    w − η g − (S − η Y) Γ."""
    S = jnp.stack([b - a for a, b in zip(ws[:-1], ws[1:])])
    Y = jnp.stack([b - a for a, b in zip(rs[:-1], rs[1:])])
    gram = np.asarray(Y @ Y.T, np.float64)
    gamma = np.linalg.solve(gram, np.asarray(Y @ g, np.float64))
    gamma = jnp.asarray(gamma, w.dtype)
    return w - eta * g - (gamma @ S - eta * (gamma @ Y))


def fedosaa_svrg(value_and_grad, w0, num_clients: int, rounds: int,
                 local_steps: int, eta: float):
    """FedOSAA-SVRG from ``w0`` for ``rounds`` rounds. ``value_and_grad(w,
    k)`` is client k's (loss, gradient) at the flat vector ``w``. Returns
    (each round's mean client loss at its w^t, the last round's result).

    Per round: the clients' gradients at w^t and their mean g; each client
    k runs w_{l+1} = w_l − η r_l with r_l = ∇f_k(w_l) − ∇f_k(w^t) + g
    (so r_0 = g) for l < L, evaluates r_L, and uploads its Anderson step;
    the server averages the uploads."""
    w, losses = w0, []
    for _ in range(rounds):
        anchor = [value_and_grad(w, k) for k in range(num_clients)]
        losses.append(float(np.mean([float(v) for v, _ in anchor])))
        g = sum(gk for _, gk in anchor) / num_clients
        uploads = []
        for k in range(num_clients):
            gk = anchor[k][1]
            ws, rs = [w], [g]
            for _ in range(local_steps):
                ws.append(ws[-1] - eta * rs[-1])
                rs.append(value_and_grad(ws[-1], k)[1] - gk + g)
            uploads.append(anderson_step(w, g, ws, rs, eta))
        w = sum(uploads) / num_clients
    return losses, w
