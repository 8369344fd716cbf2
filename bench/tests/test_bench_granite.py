"""The Granite 4.0-H program against the plain reference, at a tiny size on
the CPU: two layers (``[mamba, attention]``), d_model 256, the published
widths' ratios, seeded random weights (the configuration's draw, with the
norms, D and biases perturbed so that every parameter is exercised).

Tolerances: both sides compute in float32 on the CPU, where a float32
matrix product is exact to rounding, so they part only by summation order
(the program's chunked scan against the reference's quadratic form, its
blocked softmax against a plain one): readings are 1e-7 to 3e-7. Each limit
is 1e-5, 30-100 times that, and a bfloat16 rounding anywhere in the
program's path reads 1e-3 or more (the control at this size: 7e-3 on the
logits, 1.3e-2 on the gradient, 1e-3 on the loss).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench.models import granite_hybrid as G

TOL = 1e-5


def tiny_config(**over) -> dict:
    cfg = H.load_json(H.BENCH / "configs" / "granite-4.0-h-micro-10l.json")
    cfg.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=1024, mamba_d_state=32,
               mamba_d_head=32, mamba_n_heads=16, mamba_chunk_size=64,
               layer_types=["mamba", "attention"], num_hidden_layers=2,
               **over)
    return cfg


def perturbed_base(cfg: dict, seed: int = 7):
    """The configuration's base with every leaf drawn as 1 (norms, D) or 0
    moved off it."""
    base = G.make_base(cfg)
    leaves, tree = jax.tree.flatten(base)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = [leaf + (0.1 * jax.random.normal(k, leaf.shape)).astype(leaf.dtype)
           for leaf, k in zip(leaves, keys)]
    return jax.tree.unflatten(tree, out)


@pytest.fixture(scope="module", params=[256, 1024], ids=["plain", "blocked"])
def sides(request):
    """Program and reference at one adapter with B != 0, on sequences of
    256 tokens (plain attention) and 1024 (the blocked path the cell's
    4,096 tokens take)."""
    S = request.param
    cfg = tiny_config()
    base = perturbed_base(cfg)
    corpus = G.make_corpus(cfg, 5, S)
    data = G.Data(cfg, corpus[:4], corpus[4], base, {})
    _, lora = G.build(cfg, base)
    flat = lora.init(jax.random.PRNGKey(3))
    flat = flat + 0.05 * jax.random.normal(jax.random.PRNGKey(4), flat.shape)
    prog = G.program_side(cfg, data, flat)
    z, g = G.reference_side(cfg, data, flat, jnp.float32, "highest")
    vg = G.reference_vg_fn(cfg, lora, jnp.float32)
    with jax.default_matmul_precision("highest"):
        base_flat = lora.init(jax.random.PRNGKey(3))       # B = 0
        loss0 = np.mean([float(vg(base, base_flat, jnp.asarray(t))[0])
                         for t in data.tokens])
    return cfg, data, lora, flat, prog, (z, g, loss0)


def test_logits_match_the_reference(sides):
    *_, (z, _), (z_ref, _, _) = sides
    assert z.shape == z_ref.shape
    err = float(jnp.max(jnp.abs(z - z_ref)) / jnp.max(jnp.abs(z_ref)))
    assert err < TOL


def test_adapter_gradient_matches_the_reference(sides):
    *_, (_, g), (_, g_ref, _) = sides
    assert g.shape == g_ref.shape and float(jnp.linalg.norm(g_ref)) > 0
    assert float(jnp.linalg.norm(g - g_ref) / jnp.linalg.norm(g_ref)) < TOL


def test_base_loss_matches_the_reference(sides):
    cfg, data, lora, *_, (_, _, loss_ref) = sides
    model, _ = G.build(cfg, data.base)
    losses = [float(model.loss(data.base, {"tokens": jnp.asarray(t[None])}))
              for t in data.tokens]
    assert abs(np.mean(losses) - loss_ref) / loss_ref < TOL


def test_zero_B_is_the_base_model_exactly():
    """The adapter's init (B = 0) leaves the base model: its logits are
    bit-identical to the base's own."""
    cfg = tiny_config()
    base = perturbed_base(cfg)
    model, lora = G.build(cfg, base)
    tokens = jnp.asarray(G.make_corpus(cfg, 1, 128))
    flat = lora.init(jax.random.PRNGKey(11))
    adapted, _ = jax.jit(model.forward)(lora.merge(base, flat), tokens)
    plain, _ = jax.jit(model.forward)(base, tokens)
    np.testing.assert_array_equal(np.asarray(adapted), np.asarray(plain))


def test_adapter_covers_every_linear_map_of_every_layer():
    cfg = H.load_json(H.BENCH / "configs" / "granite-4.0-h-micro-10l.json")
    base = jax.eval_shape(lambda: G.make_base(cfg))
    _, lora = G.build(cfg, base)
    names = {(s.path[2], s.name) for s in lora.slots}
    assert names == {("mixer", n) for n in ("wx", "wz", "wB", "wC", "wdt",
                                           "out_proj")} | {
        ("attn", n) for n in ("wq", "wk", "wv", "wo")} | {
        ("mlp", n) for n in ("wi_gate", "wi_up", "wo")}
    assert all(int(np.prod(s.lead)) == n for s in lora.slots
               for g, (_, n) in enumerate(G.groups(cfg)) if s.path[1] == g)
    # rank 16 on the published widths: the configuration's 8,713,216
    assert lora.size == 8_713_216
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(base)) \
        == 951_991_232


def test_runner_takes_the_base_as_an_operand():
    """The compiled chunk holds no copy of the frozen base: its leaves
    arrive as parameters, and no constant is as large as its smallest
    projection from the hidden state (wdt, [d, heads])."""
    import re

    cfg = tiny_config()
    traffic = dict(H.load_json(H.BENCH / "traffic" / "lora-k4-s4096.json"),
                   seq_len=128)
    cell = H.Cell("tiny-lm", 1, cfg, traffic, [], [])
    inputs = G.make_inputs(cfg, traffic)
    prog = H.build_program(cell, inputs, jax.devices()[:1])
    text = prog.hlo_texts(traffic["chunk"])[0]
    smallest = min(int(np.prod(x.shape)) for x in jax.tree.leaves(
        inputs.data.base) if x.ndim == 3 and x.shape[1] == cfg["hidden_size"])
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* constant\(", line)
        if m and m.group(1):
            assert int(np.prod([int(v) for v in m.group(1).split(",")])) \
                < smallest, line[:120]
    entry = text[text.index("ENTRY"):]
    assert len(re.findall(r"parameter\(\d+\)", entry.split("\n}")[0])) >= \
        len(jax.tree.leaves(inputs.data.base))


def test_reference_rounds_match_the_program_job():
    """The reference's FedOSAA-SVRG (the check's rounds) and the program's
    round engine train the same job from the same first adapter: float32
    on both sides, so they part by summation order only, which the 3×3
    Anderson solves amplify: 1.3e-4 of the update ‖w − w0‖ here, against a
    tolerance of 1e-3; the losses, 2e-7 against 1e-5. A round without its
    Anderson step, or over half of the clients, reads 0.5 or more
    (``test_bench_lm_cell``)."""
    from repro.core import run_rounds

    cfg = tiny_config()
    traffic = dict(H.load_json(H.BENCH / "traffic" / "lora-k4-s4096.json"),
                   seq_len=128)
    cell = H.Cell("tiny-lm", 1, cfg, traffic, [], [])
    inputs = G.make_inputs(cfg, traffic)
    prog = H.build_program(cell, inputs, jax.devices()[:1])
    state = prog.init(5)
    w0 = inputs.data.kept["first"]()
    state, trace = run_rounds(prog.round_fn, state, traffic["round_budget"],
                              chunk=traffic["chunk"], runner=prog.runner)
    losses, w_ref = G.reference_rounds(cfg, inputs.data, w0, traffic,
                                       jnp.float32, "highest")
    err = float(jnp.linalg.norm(state.params - w_ref)
                / jnp.linalg.norm(w_ref - w0))
    assert err < 1e-3
    np.testing.assert_allclose(trace.loss, losses, rtol=TOL)
