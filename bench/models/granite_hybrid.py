"""IBM Granite 4.0-H (Mamba-2 layers with NoPE GQA attention, a SwiGLU MLP
after each mixer) under federated low-rank adapters, as a model of the
harness.

The configuration (``bench/configs/granite-4.0-h-micro-10l.json``) holds the
published ``config.json`` keys as they are run. The frozen base is drawn
from its ``data_seed`` on the device (no weights are in the repository), the
clients' token corpus and a held-out probe from the same seed. The program
trains one flat float32 adapter vector over that base through the round
engine; every job starts at B = 0, so its first row's loss is the base
model's.

No reference is made before the window: it needs the chip, so ``check``
computes it after the window from the plain float32 reference
(``granite_hybrid_ref.py``, ``jax.default_matmul_precision("highest")``) and
compares, each number beside its limit:

* ``logit_err_max``: the program's logits on the probe at the window's last
  adapter against the reference's, max |Δ| over the reference's max |z|;
* ``grad_rel_err``: the program's adapter gradient on client 0's sequence at
  that adapter against the reference's, ‖Δ‖ / ‖g_ref‖;
* ``base_loss_rel_err``: every job's first-row loss (the base model) against
  the reference's loss of the base over the clients, the worst |Δ| / loss;
* ``second_loss_rel_err`` and ``adapter_rel_err``: the last job trained
  again by the reference's FedOSAA-SVRG from the same first adapter (the
  anchor gradients, L corrected steps and the Anderson step of every
  client, the server's mean), against the timed runner's job: its
  second-row loss, |Δ| / loss, and its final adapter, ‖Δw‖ / ‖w_ref − w0‖
  (1 for a job that did not move);
* ``jobs_without_progress``: jobs whose second row's loss is not below the
  first's (limit 0).

What each job keeps (``answer``/``reached``, through ``Recorder``): its rows'
loss from the engine's trace and its adapter's norm; the window's last
adapter stays on the device, unfetched, until ``check``, and the ``lm``
runtime keeps how to rebuild that job's first adapter from its key.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import NamedTuple

import numpy as np

from bench.harness import Inputs


# --------------------------------------------------------------------------
# the configuration
# --------------------------------------------------------------------------

def arch_config(config: dict):
    """The program's ArchConfig from the configuration's published keys."""
    from repro.configs.base import ArchConfig

    layer_types = tuple(config["layer_types"])
    assert len(layer_types) == config["num_hidden_layers"]
    assert config["position_embedding_type"] == "nope"
    assert config["mamba_n_groups"] == 1 and config["num_local_experts"] == 0
    assert (config["mamba_n_heads"] * config["mamba_d_head"]
            == config["mamba_expand"] * config["hidden_size"])
    return ArchConfig(
        name=config["name"], family="hybrid",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        tie_embeddings=config["tie_word_embeddings"],
        ssm_state=config["mamba_d_state"], ssm_head_dim=config["mamba_d_head"],
        ssm_expand=config["mamba_expand"],
        ssm_chunk=config["mamba_chunk_size"],
        ssm_conv_width=config["mamba_d_conv"],
        layer_types=layer_types,
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        norm_eps=float(config["rms_norm_eps"]),
        position_embedding="nope", dtype=config["base_dtype"],
        source=config["source"])


def ref_shape(config: dict):
    """The sizes the plain reference reads."""
    from bench.models.granite_hybrid_ref import Shape

    return Shape(
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["hidden_size"] // config["num_attention_heads"],
        ssm_state=config["mamba_d_state"], ssm_head_dim=config["mamba_d_head"],
        conv_width=config["mamba_d_conv"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        eps=float(config["rms_norm_eps"]))


def groups(config: dict) -> list:
    """Runs of one kind in the layer pattern: [(kind, layers)]."""
    out = []
    for kind in config["layer_types"]:
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1] + 1)
        else:
            out.append((kind, 1))
    return out


# --------------------------------------------------------------------------
# data: the base and the corpus, from data_seed
# --------------------------------------------------------------------------

def _layer_shapes(config: dict, kind: str) -> dict:
    """{module: {name: (shape, init)}} of one layer (a module's own array
    under the name ""); init is "matrix", "conv", "ones", "A_log" or
    "dt_bias"."""
    d, f = config["hidden_size"], config["intermediate_size"]
    out = {"norm": {"": ((d,), "ones")}, "mlp_norm": {"": ((d,), "ones")},
           "mlp": {"wi_gate": ((d, f), "matrix"), "wi_up": ((d, f), "matrix"),
                   "wo": ((f, d), "matrix")}}
    if kind == "attention":
        H, KV = config["num_attention_heads"], config["num_key_value_heads"]
        hd = d // H
        out["attn"] = {"wq": ((d, H * hd), "matrix"),
                       "wk": ((d, KV * hd), "matrix"),
                       "wv": ((d, KV * hd), "matrix"),
                       "wo": ((H * hd, d), "matrix")}
        return out
    di = config["mamba_n_heads"] * config["mamba_d_head"]
    st, nh, W = (config["mamba_d_state"], config["mamba_n_heads"],
                 config["mamba_d_conv"])
    out["mixer"] = {
        "wx": ((d, di), "matrix"), "wz": ((d, di), "matrix"),
        "wB": ((d, st), "matrix"), "wC": ((d, st), "matrix"),
        "wdt": ((d, nh), "matrix"),
        "conv_x_w": ((W, di), "conv"), "conv_x_b": ((di,), "conv"),
        "conv_bc_w": ((W, 2 * st), "conv"), "conv_bc_b": ((2 * st,), "conv"),
        "A_log": ((nh,), "A_log"), "D": ((nh,), "ones"),
        "dt_bias": ((nh,), "dt_bias"), "norm": ((di,), "ones"),
        "out_proj": ((di, d), "matrix"),
    }
    return out


def _draw(key, n: int, shape: tuple, init: str, W: int, base_dtype):
    """n layers' [n, *shape] of one parameter, drawn as the configuration's
    ``assumed.base`` says."""
    import jax
    import jax.numpy as jnp

    full = (n,) + shape
    if init == "matrix":
        b = 1.0 / np.sqrt(shape[0])
        return jax.random.uniform(key, full, jnp.float32, -b, b).astype(
            base_dtype)
    if init == "conv":
        b = 1.0 / np.sqrt(W)
        return jax.random.uniform(key, full, jnp.float32, -b, b).astype(
            base_dtype)
    if init == "ones":
        return jnp.ones(full, base_dtype)
    if init == "A_log":          # A = -(1..heads), in float32
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)), full)
    assert init == "dt_bias"     # inverse softplus of a log-uniform dt
    lo, hi = np.log(1e-3), np.log(1e-1)
    dt = jnp.exp(jax.random.uniform(key, full, jnp.float32, lo, hi))
    return dt + jnp.log(-jnp.expm1(-dt))


# f32 in the base: the scan's own parameters
_F32_PARAMS = ("A_log", "D", "dt_bias")


def make_base(config: dict):
    """The frozen base in the program's layout (models/decoder.py: one
    layer-stacked group per run of the pattern), drawn on the device from
    ``data_seed``."""
    import jax
    import jax.numpy as jnp

    base_dtype = jnp.dtype(config["base_dtype"])
    W = config["mamba_d_conv"]
    key = jax.random.PRNGKey(config["data_seed"])
    k_embed, k_layers = jax.random.split(key)
    V, d = config["vocab_size"], config["hidden_size"]
    embed = (jax.random.normal(k_embed, (V, d), jnp.float32)
             / np.sqrt(d)).astype(base_dtype)
    out = {"embed": embed, "final_norm": jnp.ones((d,), base_dtype),
           "groups": []}
    gkeys = jax.random.split(k_layers, len(groups(config)))
    for (kind, n), gk in zip(groups(config), gkeys):
        shapes = _layer_shapes(config, kind)
        g = {}
        names = [(m, p) for m in shapes for p in shapes[m]]
        for (m, p), k in zip(names, jax.random.split(gk, len(names))):
            shape, init = shapes[m][p]
            dt = jnp.float32 if p in _F32_PARAMS else base_dtype
            leaf = _draw(k, n, shape, init, W, dt)
            if p == "":
                g[m] = leaf
            else:
                g.setdefault(m, {})[p] = leaf
        out["groups"].append(g)
    return out


def make_corpus(config: dict, num_seqs: int, seq_len: int) -> np.ndarray:
    """[num_seqs, seq_len] int32: Zipf ids with repeated earlier spans."""
    c = config["corpus"]
    rng = np.random.default_rng(config["data_seed"])
    V = config["vocab_size"]
    ranks = np.arange(1, V + 1, dtype=np.float64)
    p = ranks ** -c["zipf_exponent"]
    p /= p.sum()
    perm = rng.permutation(V)          # frequent ids spread over the vocab
    out = np.empty((num_seqs, seq_len), np.int64)
    for s in range(num_seqs):
        seq = perm[rng.choice(V, size=seq_len, p=p)]
        i = c["span_max"]
        while i < seq_len:
            n = int(rng.integers(c["span_min"], c["span_max"] + 1))
            if rng.random() < c["span_repeat_prob"]:
                src = int(rng.integers(0, i - n + 1))
                m = min(n, seq_len - i)
                seq[i:i + m] = seq[src:src + m]
            i += n
        out[s] = seq
    return out.astype(np.int32)


@dataclasses.dataclass
class Data:
    config: dict
    tokens: np.ndarray        # [K, S] int32: one sequence per client
    probe: np.ndarray         # [S] int32: held out
    base: object              # the frozen base on the device
    kept: dict                # the window's last adapter (Recorder)


def make_inputs(config: dict, traffic: dict) -> Inputs:
    """The base and the corpus; the reference comes after the window."""
    import jax

    t0 = time.perf_counter()
    K, S = traffic["num_clients"], traffic["seq_len"]
    corpus = make_corpus(config, K + 1, S)
    base = jax.block_until_ready(make_base(config))
    return Inputs(Data(config, corpus[:K], corpus[K], base, {}), None,
                  time.perf_counter() - t0, 0.0)


# --------------------------------------------------------------------------
# the program's side
# --------------------------------------------------------------------------

def build(config: dict, base):
    """(model, adapter layout) of the program."""
    from repro.core.lm import make_lora
    from repro.models.decoder import build_model

    model = build_model(arch_config(config), remat=True)
    lora = make_lora(base, config["lora"]["rank"], config["lora"]["alpha"])
    return model, lora


def problem(config: dict, inputs: Inputs):
    """The program's FLProblem: the adapter vector over the frozen base."""
    from repro.core.lm import make_lm_clients, make_lm_problem

    d = inputs.data
    model, lora = build(config, d.base)
    return make_lm_problem(model, make_lm_clients(d.tokens, len(d.tokens)),
                           d.base, lora)


def stop(config: dict, traffic: dict, inputs: Inputs) -> dict:
    """No target: every job runs the round budget."""
    return {}


class Recorder:
    """The harness's ``answer``/``reached`` for one run: each job's answer is
    a dict of its adapter's norm and (filled in by ``reached``) its rows'
    loss; the last job's adapter stays on the device in ``kept``."""

    def __init__(self, kept: dict):
        self.kept = kept
        self.last = None

    def answer(self, state) -> dict:
        self.kept["params"] = state.params
        self.last = answer(state)
        return self.last

    def reached(self, trace) -> bool:
        self.last["loss"] = [float(v) for v in trace.loss]
        return reached(trace)


def answer(state) -> dict:
    """A job's adapter norm (the runtime's Recorder adds its rows' loss)."""
    import jax.numpy as jnp

    return {"adapter_norm": float(jnp.linalg.norm(state.params))}


def reached(trace) -> bool:
    """Every round ran: the engine stops a job early only on a non-finite
    loss."""
    return not trace.stopped


# --------------------------------------------------------------------------
# the check
# --------------------------------------------------------------------------

def reference_weights(config: dict, base, dtype):
    """The base in the reference's layout (its runs of layers stacked, as
    the program's groups are), in ``dtype``."""
    import jax

    return {"embed": base["embed"].astype(dtype),
            "final_norm": base["final_norm"].astype(dtype),
            "groups": [{"kind": kind,
                        "layers": jax.tree.map(lambda x: x.astype(dtype), g)}
                       for (kind, _), g in zip(groups(config),
                                               base["groups"])]}


def reference_adapters(lora, flat, dtype) -> list:
    """The adapter vector as the reference takes it: per group
    ``{module: {name: (A, B)}}``, B unscaled."""
    out = [dict() for _ in {slot.path[1] for slot in lora.slots}]
    for slot, (a, b) in zip(lora.slots, lora.split(flat)):
        g, module = slot.path[1], slot.path[2]
        out[g].setdefault(module, {})[slot.name] = (a.astype(dtype),
                                                    b.astype(dtype))
    return out


def flatten_like(lora, grads: list):
    """The reference's per-group (A, B) gradients in the program's flat
    layout."""
    import jax.numpy as jnp

    pieces = []
    for slot in lora.slots:
        a, b = grads[slot.path[1]][slot.path[2]][slot.name]
        pieces += [a.ravel(), b.ravel()]
    return jnp.concatenate(pieces).astype(jnp.float32)


#: the jitted sides, built once per configuration, adapter layout (which
#: follows the order of the base's keys) and dtype, so that a script
#: checking many adapters traces each once
_SIDES: dict = {}


def _memo(key: tuple, build):
    if key not in _SIDES:
        _SIDES[key] = build()
    return _SIDES[key]


def reference_logits_fn(config: dict, lora, dtype):
    """The jitted reference logits in ``dtype``: (base, adapter, tokens) ->
    [S, V] float32."""
    return _memo(("reference_logits", json.dumps(config, sort_keys=True),
                  lora, str(dtype)),
                 lambda: _reference_logits_fn(config, lora, dtype))


def _reference_logits_fn(config: dict, lora, dtype):
    import jax
    import jax.numpy as jnp

    from bench.models import granite_hybrid_ref as ref

    sh = ref_shape(config)

    @jax.jit
    def logits(base, flat, tokens):
        w = reference_weights(config, base, dtype)
        h = ref.hidden(w, tokens, sh, reference_adapters(lora, flat, dtype),
                       lora.scale)
        return ref.logits(w, h, sh).astype(jnp.float32)

    return logits


def reference_side(config: dict, data: Data, flat, dtype, precision):
    """(logits on the probe at ``flat``, the adapter gradient on client 0's
    sequence there) from the plain reference computed in ``dtype`` at
    ``precision``."""
    import jax
    import jax.numpy as jnp

    _, lora = build(config, data.base)
    with jax.default_matmul_precision(precision):
        z = reference_logits_fn(config, lora, dtype)(
            data.base, flat, jnp.asarray(data.probe))
        _, g = reference_vg_fn(config, lora, dtype)(
            data.base, flat.astype(dtype), jnp.asarray(data.tokens[0]))
    return z, g


def reference_vg_fn(config: dict, lora, dtype):
    """The jitted reference (loss, adapter gradient) of one sequence in
    ``dtype``: (base, adapter, tokens) -> (nll, flat gradient)."""
    return _memo(("reference_vg", json.dumps(config, sort_keys=True), lora,
                  str(dtype)), lambda: _reference_vg_fn(config, lora, dtype))


def _reference_vg_fn(config: dict, lora, dtype):
    import jax

    from bench.models import granite_hybrid_ref as ref

    sh = ref_shape(config)

    @jax.jit
    def vg(base, flat, tokens):
        w = reference_weights(config, base, dtype)
        v, g = ref.adapter_value_and_grad(
            w, tokens, sh, reference_adapters(lora, flat, dtype), lora.scale)
        return v, flatten_like(lora, g).astype(flat.dtype)

    return vg


def reference_rounds(config: dict, data: Data, w0, traffic: dict, dtype,
                     precision):
    """The job from the adapter ``w0`` as the plain reference trains it
    (``granite_hybrid_ref.fedosaa_svrg``, in ``dtype`` at ``precision``):
    (each round's mean client loss at its start, the final adapter)."""
    import jax
    import jax.numpy as jnp

    from bench.models import granite_hybrid_ref as ref

    _, lora = build(config, data.base)
    vg = reference_vg_fn(config, lora, dtype)
    tokens = [jnp.asarray(t) for t in data.tokens]
    hp = traffic["hparams"]
    assert traffic["algo"] == "fedosaa_svrg" and traffic["channel"] == \
        "identity" and set(hp) == {"eta", "local_epochs"}
    with jax.default_matmul_precision(precision):
        return ref.fedosaa_svrg(
            lambda w, k: vg(data.base, w, tokens[k]), w0.astype(dtype),
            len(tokens), traffic["round_budget"], hp["local_epochs"],
            hp["eta"])


class Readings(NamedTuple):
    """What ``compare`` compares: the logits on the probe and the adapter
    gradient on client 0's sequence at the last job's final adapter, every
    job's first-row loss (the base), that job's second-row loss and its
    final adapter."""

    logits: object
    grad: object
    first_losses: list
    second_loss: float
    adapter: object


def program_fn(config: dict, base, tokens):
    """The jitted program side: (base, adapter, probe, tokens) -> (the
    program's logits on the probe, its adapter gradient on tokens[0])."""
    return _memo(("program", json.dumps(config, sort_keys=True),
                  build(config, base)[1]),
                 lambda: _program_fn(config, base, tokens))


def _program_fn(config: dict, base, tokens):
    import jax
    import jax.numpy as jnp

    from repro.core import ClientBatch
    from repro.core.lm import make_lm_clients, make_lm_problem

    model, lora = build(config, base)
    prob = make_lm_problem(model, make_lm_clients(tokens, len(tokens)), base,
                           lora)

    @jax.jit
    def side(base, flat, probe, tokens):
        z, _ = model.forward(lora.merge(base, flat), probe[None])
        batch = ClientBatch(tokens[:1], jnp.zeros((1,), jnp.float32),
                            jnp.ones((1,), jnp.float32))
        return (z[0].astype(jnp.float32),
                jax.grad(prob.bind(base).loss)(flat, batch))

    return side


def program_side(config: dict, data: Data, flat):
    """(logits on the probe, the adapter gradient on client 0's sequence)
    of the program at the adapter ``flat``."""
    import jax.numpy as jnp

    return program_fn(config, data.base, data.tokens)(
        data.base, flat, jnp.asarray(data.probe), jnp.asarray(data.tokens))


def reference_readings(config: dict, data: Data, w0, flat, traffic: dict,
                       dtype, precision) -> Readings:
    """The plain reference's ``Readings`` in ``dtype`` at ``precision``:
    its logits and gradient at the program's final adapter ``flat``, and
    its own job from ``w0``."""
    z, g = reference_side(config, data, flat, dtype, precision)
    losses, w = reference_rounds(config, data, w0, traffic, dtype, precision)
    return Readings(z, g, [losses[0]], losses[1], w)


def compare(got: Readings, ref: Readings, w0, traffic: dict) -> dict:
    """The checks, each beside its limit, of ``got`` against the float32
    reference's ``ref``, both jobs from the adapter ``w0``."""
    import jax.numpy as jnp

    lim = traffic["limits"]

    def rel(a, b, scale):
        a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
        return float(jnp.linalg.norm(a - b) / scale)

    z_ref = jnp.asarray(ref.logits, jnp.float32)
    g_ref = jnp.asarray(ref.grad, jnp.float32)
    w_ref = jnp.asarray(ref.adapter, jnp.float32)
    z = jnp.asarray(got.logits, jnp.float32)
    logit_err = float(jnp.max(jnp.abs(z - z_ref)) / jnp.max(jnp.abs(z_ref)))
    loss_ref = ref.first_losses[0]
    # np.max, not max: a non-finite loss must reach the check, not be
    # skipped by a comparison
    loss_err = float(np.max(np.abs(np.asarray(got.first_losses) - loss_ref))
                     / abs(loss_ref))
    values = {
        "logit_err_max": logit_err,
        "grad_rel_err": rel(got.grad, g_ref, jnp.linalg.norm(g_ref)),
        "base_loss_rel_err": loss_err,
        "second_loss_rel_err": abs(got.second_loss - ref.second_loss)
        / abs(ref.second_loss),
        "adapter_rel_err": rel(got.adapter, w_ref,
                               jnp.linalg.norm(w_ref - w0)),
    }
    return {name: {"value": v, "limit": lim[name]}
            for name, v in values.items()}


def progress(first_and_second: list) -> dict:
    """``jobs_without_progress``: the jobs whose second row's loss is not
    below their first's (limit 0)."""
    stalled = sum(not (len(rows) > 1 and rows[1] < rows[0])
                  for rows in first_and_second)
    return {"jobs_without_progress": {"value": stalled, "limit": 0}}


def program_readings(config: dict, data: Data, jobs: list) -> Readings:
    """The program's ``Readings`` of a window whose last job left its final
    adapter in ``data.kept``."""
    z, g = program_side(config, data, data.kept["params"])
    loss = jobs[-1].answer["loss"]
    return Readings(z, g, [j.answer["loss"][0] for j in jobs],
                    loss[1] if len(loss) > 1 else float("nan"),
                    data.kept["params"])


def check(jobs: list, inputs: Inputs, traffic: dict) -> dict:
    """The window's answers against the float32 reference (module
    docstring), computed after the window for the last job: from its first
    adapter, rebuilt from its key, the reference trains its own job."""
    import jax.numpy as jnp

    data = inputs.data
    config = data.config
    w0 = data.kept["first"]()
    got = program_readings(config, data, jobs)
    ref = reference_readings(config, data, w0, got.adapter, traffic,
                             jnp.float32, "highest")
    checks = compare(got, ref, w0, traffic)
    checks.update(progress([j.answer["loss"] for j in jobs]))
    return checks
