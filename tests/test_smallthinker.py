"""The SmallThinker stack (``build_smallthinker``: periods of one rotary-less
full-attention layer and three rotated sliding-window layers at 7 q heads a
k/v head, every feed-forward top-k routed ReGLU experts under a softmax over
the chosen logits with no shared expert, **the router reading the block's
un-normed input ahead of the mixer**) at ``smallthinker-test-tiny`` on the CPU,
in float32, against the plain reference ``perf/reference/smallthinker.py``
from the same seeded weights; the two halves of ``ops/moe.py``'s routed layer
against the one-call form; and ``flash_attention`` at this model's group and
windows. (The techniques and search -> orchestrate are
``tests/test_smallthinker_techniques.py``, so that ``--dist loadfile`` spreads
the compiles.)

Tolerances as ``tests/test_laguna.py``: program and reference are both float32
here and differ by the order of their roundings only: logits to 2e-5 absolute,
gradients to 2e-4 of each leaf's norm.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import smallthinker as st
from saturn_tpu.models import gpt2
from saturn_tpu.models.gpt2 import build_gpt2, build_smallthinker
from saturn_tpu.ops import moe
from saturn_tpu.ops.flash import flash_attention

FULL, SLIDING = "full_attention", "sliding_attention"
KINDS = {FULL: 1, SLIDING: 3}
ARCH = st.Arch(vocab_size=256, d_model=64, kinds=(FULL, SLIDING, SLIDING, SLIDING),
               rotated=(False, True, True, True), heads=(14,) * 4, n_kv_heads=2,
               head_dim=16, window=32, experts=16, held=4, first_expert=0, top_k=4,
               d_expert=32, rope_theta=1.5e6, norm_eps=1e-6)
SEQ, SEED = 64, 2_147_483_693
VARIANTS = {"dense": {"attention": "dense"},    # the plain twins: masked einsums, ragged_dot
            # window, full and gmm kernels, interpret mode, each layer rematerialised
            "flash-remat": {"attention": "flash", "remat": True},
            # a row buffer a quarter of the mean: every step takes the second path
            "flash-second-path": {"attention": "flash", "routed_buffer": 0.25}}
LOGITS_ATOL, GRAD_RTOL = 2e-5, 2e-4


def _tokens(seed, batch=2, seq=SEQ):
    return np.random.default_rng(seed).integers(0, 256, size=(batch, seq), dtype=np.int32)


def _spec(**kw):
    return build_smallthinker("smallthinker-test-tiny", dtype=jnp.float32, **kw)


def _weights(arch=ARCH):
    return st.program_params(arch, st.seed_key(SEED))


def _reference(fault=None):
    """(logits, loss, gradients in the program's layout) of the reference on
    one batch, in one jitted call."""
    tokens = jnp.asarray(_tokens(1))

    @jax.jit
    def all_of(key):
        params = st.seeded_params(ARCH, key)
        loss, grads = jax.value_and_grad(
            lambda p: st.loss_fn(ARCH, p, tokens, fault=fault))(params)
        return (st.forward(ARCH, params, tokens, fault=fault), loss,
                st.program_layout(ARCH, grads))

    with jax.default_matmul_precision("highest"):
        return all_of(st.seed_key(SEED))


@pytest.fixture(scope="module")
def reference():
    return _reference()


@pytest.fixture(scope="module")
def program():
    """(logits, loss, gradients, counters) of the program's plain twins."""
    spec, weights, tokens = _spec(attention="dense"), _weights(), jnp.asarray(_tokens(1))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(spec.apply_fn)(weights, tokens)
        (loss, counters), grads = jax.jit(jax.value_and_grad(
            spec.fused_loss_stats_fn, has_aux=True))(weights, tokens)
    return got, loss, grads, counters


def _off(want, got):
    """(the largest logit difference, the worst leaf's gradient difference
    over its norm and its path)."""
    (want_logits, _, want_grads), (logits, _, grads) = want, got[:3]
    want_g, got_g = st.flat(want_grads), st.flat(grads)
    assert set(want_g) == set(got_g)
    worst = max(want_g, key=lambda k: np.linalg.norm(got_g[k] - want_g[k])
                / np.linalg.norm(want_g[k]))
    return (float(jnp.abs(logits - want_logits).max()),
            float(np.linalg.norm(got_g[worst] - want_g[worst]) / np.linalg.norm(want_g[worst])),
            worst)


# ------------------------------------------------------------ the model
def test_preset_is_the_published_model_and_the_tree_is_the_references():
    cfg = build_smallthinker("smallthinker-21b").config
    assert (cfg.d_model, cfg.head_dim, cfg.heads_held, cfg.n_kv_heads, cfg.vocab_size,
            cfg.window, cfg.routed_experts, cfg.top_k, cfg.expert_ff, cfg.shared_ff,
            cfg.routed_scale) == (2560, 128, 28, 4, 151936, 4096, 64, 6, 768, 0, 1.0)
    assert cfg.layer_types == (FULL,) + (SLIDING,) * 3 and cfg.stack_kinds == KINDS
    assert (cfg.lead_layers, cfg.n_layers, cfg.n_periods, cfg.experts_held) == (0, 52, 13, 64)
    assert (cfg.rotary, cfg.rotary_kinds, cfg.window_rope_theta, cfg.yarn) == (
        True, (SLIDING,), 1.5e6, None)
    assert (cfg.route_from, cfg.router_score, cfg.expert_act, cfg.router_bias) == (
        "block_input", "softmax", "reglu", False)
    assert (cfg.norm, cfg.use_bias, cfg.tie_head, cfg.attn_gate, cfg.norm_eps) == (
        "rmsnorm", False, False, False, None)       # (flax's RMSNorm eps is 1e-6)
    spec = _spec()
    want = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    got = jax.eval_shape(_weights)
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    assert jax.tree_util.tree_leaves(want) == jax.tree_util.tree_leaves(got)
    assert set(got) == {"wte", "lm_head", "ln_f", "blocks"} and "'bias'" not in str(got)
    layer = got["blocks"]["l0"]
    # q wider than the stream (14 heads of 16 over 64 lanes), k/v at 2 heads;
    # no gate a head, no shared expert, no dense feed-forward
    assert layer["qkv"]["kernel"].shape == (1, 64, 14 * 16 + 2 * 2 * 16)
    assert layer["router"].shape == (1, 64, 16) and layer["we_gate"].shape == (1, 4, 64, 32)
    assert set(layer) == {"ln_1", "ln_2", "qkv", "attn_out", "router",
                          "we_gate", "we_up", "we_down"}
    assert (spec.stack_layers, spec.stack_kinds, spec.stack_lead, spec.stack_passes) == (
        4, KINDS, None, 1)
    assert spec.hints["routed"]["held"] == 4 and spec.hints["seq_parallel"] is False
    assert build_gpt2("test-tiny").hints["seq_parallel"] is True


def test_config_refuses_what_the_layers_cannot_be():
    tiny = "smallthinker-test-tiny"
    with pytest.raises(ValueError, match="route_from"):
        gpt2.config_for(tiny, route_from="mixer_output")
    with pytest.raises(ValueError, match="route_from"):     # no latent under a carried route
        gpt2.config_for(tiny, latent_dim=32)
    with pytest.raises(ValueError, match="router_score"):
        gpt2.config_for(tiny, router_score="tanh")
    with pytest.raises(ValueError, match="rotary_kinds"):
        gpt2.config_for(tiny, rotary_kinds=("mla",))
    with pytest.raises(ValueError, match="rotary_kinds"):
        gpt2.config_for(tiny, rotary=False)
    with pytest.raises(ValueError, match="shared expert is not built"):
        gpt2.config_for(tiny, shared_ff=32)
    with pytest.raises(ValueError, match="expert_act"):
        gpt2.config_for(tiny, expert_act="geglu")
    with pytest.raises(ValueError, match="act must be"):
        moe.routed_plan(64, 16, 4, 4, act="geglu")
    with pytest.raises(ValueError, match="score must be"):
        moe.routed_plan(64, 16, 4, 4, score="tanh")
    with pytest.raises(ValueError, match="route_from must be"):
        moe.routed_plan(64, 16, 4, 4, route_from="mixer_output")
    # the one-call form is for a router that reads the experts' own rows
    plan = moe.routed_plan(8, 4, 4, 2, route_from="block_input", row_tile=8)
    zeros = jnp.zeros
    with pytest.raises(ValueError, match="reads the experts' own rows"):
        moe.routed_experts(zeros((8, 4)), zeros((4, 4)), zeros((4, 4, 2)), zeros((4, 4, 2)),
                           zeros((4, 2, 4)), plan=plan)


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_logits_loss_and_gradients_are_the_references(variant, reference):
    spec, weights, tokens = _spec(**VARIANTS[variant]), _weights(), jnp.asarray(_tokens(1))
    want, want_loss, want_grads = reference
    with jax.default_matmul_precision("highest"):
        got = jax.jit(spec.apply_fn)(weights, tokens)
        (loss, counters), grads = jax.jit(jax.value_and_grad(
            spec.fused_loss_stats_fn, has_aux=True))(weights, tokens)
    np.testing.assert_allclose(got, want, atol=LOGITS_ATOL)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    want_g, got_g = st.flat(want_grads), st.flat(grads)
    assert set(want_g) == set(got_g)
    for leaf, g in want_g.items():
        assert np.linalg.norm(got_g[leaf] - g) <= GRAD_RTOL * np.linalg.norm(g), (variant, leaf)
    # the router's above all: its cotangent reaches the stream ahead of the
    # mixer, and it is no rounding noise that the comparison holds
    for i in range(4):
        assert np.linalg.norm(want_g[f"blocks/l{i}/router"]) > 1e-6
    second = variant == "flash-second-path"
    assert float(counters["moe_second_path"]) == (1.0 if second else 0.0)
    assert 0 < float(counters["moe_pairs_held"]) and float(counters["moe_rows_max"]) >= \
        float(counters["moe_rows_mean"])


@pytest.mark.parametrize("fault", st.FAULTS)
def test_a_planted_fault_is_outside_the_comparisons_tolerances(fault, reference, program):
    """The program against a reference with one thing wrong: the router on
    N2(h) (the usual place) or on N1(x), a rotated full layer, an un-rotated
    sliding layer, a window off by one either way, silu for relu,
    sigmoid-normalised weights for the softmax. Each is outside the tolerance
    the sound comparison is held to, and the sound one is inside by a wide
    margin: the comparison tells them apart."""
    sound_logits, sound_grad, _ = _off(reference, program)
    assert sound_logits <= LOGITS_ATOL / 4 and sound_grad <= GRAD_RTOL / 4
    logits_off, grad_off, leaf = _off(_reference(fault), program)
    assert logits_off > 5 * LOGITS_ATOL or grad_off > 10 * GRAD_RTOL, (
        fault, logits_off, grad_off, leaf)
    if fault.startswith("router_on") or fault == "sigmoid_weights":
        assert "router" in leaf       # the router's gradient says it first


def test_a_rematerialised_block_chooses_and_sorts_once_a_routed_layer(monkeypatch):
    """An attention layer lies between the route and its use inside one
    rematerialised block; the route's choice and integer tables are kept
    (``LAYOUT_NAME``), so the whole gradient holds one ``top_k`` and one
    ``sort`` a routed layer. The planted fault (nothing kept: a route chosen
    again in the backward) doubles both, and this count catches it."""
    def text(remat):
        spec = build_smallthinker("smallthinker-test-tiny", attention="dense", remat=remat)
        params = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
        tokens = jax.ShapeDtypeStruct((2, SEQ), jnp.int32)
        return str(jax.make_jaxpr(jax.grad(spec.fused_loss_fn))(params, tokens))

    for remat in (False, True):
        got = text(remat)
        assert (got.count("top_k["), got.count("sort[")) == (4, 4), remat
    monkeypatch.setattr(moe, "checkpoint_name", lambda x, name: x)
    got = text(True)
    assert (got.count("top_k["), got.count("sort[")) == (8, 8)


# ------------------------------------------------ the share ties to the model
def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares of a layer (the program's two
    halves, each share holding 4 of the 16 experts, under a route made from
    *other* rows than the experts read) are the uncut reference's
    feed-forward: nothing stands in for an absent share, nothing is counted
    twice, and there is no shared expert to count once."""
    uncut = st.Arch(**{**ARCH.__dict__, "held": 16})
    params = st.seeded_params(uncut, st.seed_key(SEED))
    p = st._layer_weights(uncut, params, 2)
    key = jax.random.PRNGKey(3)
    x = params["wte"][jnp.asarray(_tokens(4))]                    # the router's rows
    u = 8.0 * jax.random.normal(key, x.shape, jnp.float32)        # the experts'
    with jax.default_matmul_precision("highest"):
        routing = st.routing_of(uncut, p["router"], x)
        whole = st.routed_part(uncut, st._plain_mm, p, u, routing)
        total = jnp.zeros_like(whole)
        for share in range(4):
            tables = [p[n][share * 4:(share + 1) * 4] for n in ("we_gate", "we_up", "we_down")]
            for impl in ("xla", "kernel"):
                plan = moe.routed_plan(2 * SEQ, 16, 4, 4, row_tile=8, impl=impl, act="reglu",
                                       score="softmax", route_from="block_input")
                made = moe.route(x.reshape(-1, 64), p["router"], plan=plan,
                                 first_expert=share * 4)
                part, counters = moe.experts_under(made, u.reshape(-1, 64), *tables,
                                                   plan=plan, dtype=jnp.float32)
                np.testing.assert_allclose(
                    part.reshape(u.shape),
                    st.routed_part(ARCH, st._plain_mm, {**p, **dict(zip(
                        ("we_gate", "we_up", "we_down"), tables))}, u, routing,
                        first_expert=share * 4),
                    atol=2e-6)
                np.testing.assert_array_equal(counters["chosen"],
                                              routing[0].reshape(-1, 4))
            total = total + part.reshape(u.shape)
    np.testing.assert_allclose(total, whole, atol=5e-6)
    assert float(jnp.abs(whole).mean()) > 3e-2
    # a token's weights over all the shares are one softmax: they sum to 1
    np.testing.assert_allclose(jnp.sum(routing[1], -1), 1.0, atol=1e-6)


def test_the_logits_over_eight_vocabulary_slices_are_the_uncut_heads(reference):
    """The held rows of the head give the held columns of the logits: eight
    slices of 32 rows side by side are the uncut head's logits."""
    params = st.seeded_params(ARCH, st.seed_key(SEED))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = st._head(ARCH, st._plain_mm, params, x)
        parts = [st._head(ARCH, st._plain_mm,
                          {**params, "lm_head": params["lm_head"][s * 32:(s + 1) * 32]}, x)
                 for s in range(8)]
    np.testing.assert_allclose(jnp.concatenate(parts, axis=-1), whole, atol=1e-6)


# ---------------------------------- the two halves against the one-call form
ONE_CALL = {     # the routed layers of the three presets that call the op whole
    "laguna-test-tiny": dict(scale=2.5),
    "nemotron-test-tiny": dict(scale=5.0, latent=32, bias=True),
    "ling-test-tiny": dict(scale=2.5, bias=True),
}


@pytest.mark.parametrize("impl", ["xla", "kernel"])
@pytest.mark.parametrize("preset", list(ONE_CALL))
def test_the_two_halves_are_the_one_call_op_bit_for_bit(preset, impl):
    cfg = gpt2.config_for(preset)
    kw = ONE_CALL[preset]
    T, D, E, held, k, F = 128, cfg.d_model, cfg.routed_experts, cfg.experts_held, \
        cfg.top_k, cfg.expert_ff
    L = kw.get("latent", 0) or D
    key = jax.random.PRNGKey(11)
    y, router, w_up, w_down, w_gate, latent, bias = (
        0.3 * jax.random.normal(jax.random.fold_in(key, i), s, jnp.float32) for i, s in
        enumerate([(T, D), (D, E), (held, L, F), (held, F, L), (held, L, F), (T, L), (E,)]))
    if cfg.expert_act == "relu2":
        w_gate = None
    latent = latent if kw.get("latent") else None
    bias = 0.01 * bias if kw.get("bias") else None
    plan = moe.routed_plan(T, E, held, k, row_tile=8, impl=impl, act=cfg.expert_act,
                           latent=cfg.latent_dim, bias=cfg.router_bias,
                           groups=cfg.route_groups, groups_kept=cfg.route_groups_kept)
    assert (plan.score, plan.route_from) == ("sigmoid", "ff_input")

    def whole(y, router, w_gate, w_up, w_down):
        out, stats = moe.routed_experts(y, router, w_gate, w_up, w_down, plan=plan,
                                        scale=kw["scale"], dtype=jnp.float32, bias=bias,
                                        latent=latent)
        return out.sum(), (out, stats)

    def halves(y, router, w_gate, w_up, w_down):
        made = moe.route(y, router, plan=plan, scale=kw["scale"], bias=bias)
        out, stats = moe.experts_under(made, y if latent is None else latent, w_gate, w_up,
                                       w_down, plan=plan, dtype=jnp.float32)
        return out.sum(), (out, stats)

    args = (y, router, w_gate, w_up, w_down)
    argnums = tuple(i for i, a in enumerate(args) if a is not None)
    (_, (a_out, a_stats)), a_grads = jax.value_and_grad(whole, argnums, has_aux=True)(*args)
    (_, (b_out, b_stats)), b_grads = jax.value_and_grad(halves, argnums, has_aux=True)(*args)
    np.testing.assert_array_equal(a_out, b_out)
    for name in a_stats:
        np.testing.assert_array_equal(a_stats[name], b_stats[name])
    for a, b in zip(a_grads, b_grads):
        np.testing.assert_array_equal(a, b)
    assert float(jnp.abs(a_out).mean()) > 0 and int(a_stats["pairs_held"]) > 0


def test_a_softmax_over_the_chosen_is_the_full_softmax_renormalised():
    """``moe_primary_router_apply_softmax`` with ``norm_topk_prob``: the two
    readings of the switches are one function."""
    z = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (64, 16), jnp.float32)
    plan = moe.routed_plan(64, 16, 16, 4, row_tile=8, score="softmax", act="reglu")
    made = moe.route(z, jnp.eye(16, dtype=jnp.float32), plan=plan)
    full = jax.nn.softmax(z, axis=-1)
    top, chosen = jax.lax.top_k(full, 4)
    np.testing.assert_array_equal(made["chosen"], chosen)
    np.testing.assert_allclose(made["weights"], top / top.sum(-1, keepdims=True), rtol=1e-5)


# ------------------------------------- flash attention at this model's shapes
def _masked_dense(q, k, v, window):
    B, H, T, D = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(D)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
    s = jnp.where((j <= i) & (i - j < window), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("T,block,window,heads,kv", [
    (64, 16, 32, 14, 2),      # 7 q heads a k/v head, a window of half the sequence
    (64, 16, 64, 14, 2),      # ... of all the sequence: causal attention
    (64, 16, None, 14, 2),    # the full layer's kernels at the same group
    (128, 32, 64, 7, 1),      # one group of seven, half the sequence, two blocks a window
    (64, 16, 33, 28, 4),      # the published heads, a window one past a block boundary
])
def test_flash_attention_at_seven_q_heads_a_kv_head(T, block, window, heads, kv):
    key = jax.random.PRNGKey(7)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, h, T, 8), jnp.float32)
               for i, h in enumerate((heads, kv, kv)))
    do = jax.random.normal(jax.random.fold_in(key, 9), q.shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(lambda *a: flash_attention(*a, window=window, block_q=block,
                                                      block_k=block), q, k, v)
        want, want_vjp = jax.vjp(lambda *a: _masked_dense(*a, window or T), q, k, v)
        np.testing.assert_allclose(out, want, atol=2e-6)
        for got_g, want_g, name in zip(vjp(do), want_vjp(do), ("dq", "dk", "dv")):
            np.testing.assert_allclose(got_g, want_g, atol=1e-5, err_msg=name)
