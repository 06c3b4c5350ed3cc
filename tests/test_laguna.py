"""The Laguna stack (``build_laguna``: a leading dense layer outside the scan,
then periods of three sliding-window layers and one full-attention layer at
their own q-head counts over shared k/v heads, a gate a head, per-kind rotary,
and a shared expert beside top-k routed experts of which a share is held) at
``laguna-test-tiny`` on the CPU, in float32, against the plain reference
``perf/reference/laguna.py`` (every held expert over every token under a mask,
attention as masked einsums) from the same seeded weights; and its two ops:
the window kernels of ``ops/flash.py`` and the routed layer of ``ops/moe.py``.
(The techniques and search -> orchestrate are ``tests/test_laguna_techniques.py``,
so that ``--dist loadfile`` spreads the compiles.)

Tolerances as ``tests/test_olmo_hybrid.py``: program and reference are both
float32 here and differ by the order of their roundings only: logits to 2e-5
absolute, gradients to 2e-4 of each leaf's norm.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import laguna as lg
from saturn_tpu.models import gpt2
from saturn_tpu.models.gpt2 import build_gpt2, build_laguna
from saturn_tpu.ops import moe
from saturn_tpu.ops.flash import flash_attention, window_plan

FULL, SLIDING = "full_attention", "sliding_attention"
KINDS = {SLIDING: 3, FULL: 1}
ARCH = lg.Arch(vocab_size=256, d_model=64, kinds=(FULL, SLIDING, SLIDING, SLIDING, FULL),
               ffs=("dense",) + ("sparse",) * 4, heads=(6, 8, 8, 8, 6), n_kv_heads=2,
               head_dim=16, window=24, d_dense=128, experts=16, held=4, first_expert=0,
               top_k=4, d_expert=32, d_shared=32, routed_scale=2.5,
               full_rope=(500000.0, 0.5, 64.0, 16, 8.0, 1.0, 1.4158883083359672),
               sliding_theta=10000.0, norm_eps=1e-6)
SEQ, SEED = 64, 2_147_483_693
VARIANTS = {"dense": {"attention": "dense"},    # the plain twins: masked einsums, ragged_dot
            # window, full and gmm kernels, interpret mode, each layer rematerialised
            "flash-remat": {"attention": "flash", "remat": True},
            # a row buffer a quarter of the mean: every step takes the second path
            "flash-second-path": {"attention": "flash", "BUFFER": 0.25}}


def _tokens(seed, batch=2, seq=SEQ):
    return np.random.default_rng(seed).integers(0, 256, size=(batch, seq), dtype=np.int32)


def _spec(**kw):
    return build_laguna("laguna-test-tiny", dtype=jnp.float32, **kw)


def _weights(arch=ARCH):
    return lg.program_params(arch, lg.seed_key(SEED))


@pytest.fixture(scope="module")
def reference():
    """(logits, loss, gradients in the program's layout) of the reference on
    one batch, in one jitted call."""
    tokens = jnp.asarray(_tokens(1))

    @jax.jit
    def all_of(key):
        params = lg.seeded_params(ARCH, key)
        loss, grads = jax.value_and_grad(lambda p: lg.loss_fn(ARCH, p, tokens))(params)
        return lg.forward(ARCH, params, tokens), loss, lg.program_layout(ARCH, grads)

    with jax.default_matmul_precision("highest"):
        return all_of(lg.seed_key(SEED))


# ------------------------------------------------------------ the model
def test_preset_is_the_published_model_and_the_tree_is_the_references():
    cfg = build_laguna("laguna-xs2").config
    assert (cfg.d_model, cfg.head_dim, cfg.n_kv_heads, cfg.ff_dim, cfg.vocab_size, cfg.window,
            cfg.routed_experts, cfg.top_k, cfg.expert_ff, cfg.shared_ff, cfg.routed_scale) == (
        2048, 128, 8, 8192, 100352, 512, 256, 8, 512, 512, 2.5)
    assert (cfg.heads_of(FULL), cfg.heads_of(SLIDING)) == (48, 64)
    assert cfg.layer_types == (SLIDING,) * 3 + (FULL,) and cfg.stack_kinds == KINDS
    assert (cfg.lead_layers, cfg.n_layers, cfg.n_periods, cfg.experts_held) == (1, 37, 9, 256)
    assert (cfg.rotary_dim, cfg.rope_theta, cfg.window_rope_theta, cfg.yarn[:4]) == (
        64, 500000.0, 10000.0, (64.0, 4096, 64.0, 1.0))
    assert (cfg.norm, cfg.mlp_act, cfg.use_bias, cfg.tie_head, cfg.attn_gate) == (
        "rmsnorm", "swiglu", False, False, True)
    spec = _spec()
    want = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    got = jax.eval_shape(_weights)
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    assert jax.tree_util.tree_leaves(want) == jax.tree_util.tree_leaves(got)
    assert set(got) == {"wte", "lm_head", "ln_f", "lead", "blocks"} and "'bias'" not in str(got)
    # q wider than the stream, k/v at the shared heads, a gate a head
    assert got["blocks"]["l0"]["qkv"]["kernel"].shape == (1, 64, 8 * 16 + 2 * 2 * 16)
    assert got["blocks"]["l3"]["qkv"]["kernel"].shape == (1, 64, 6 * 16 + 2 * 2 * 16)
    assert got["blocks"]["l0"]["attn_gate"]["kernel"].shape == (1, 64, 8)
    assert got["blocks"]["l0"]["router"].shape == (1, 64, 16)         # all the experts
    assert got["blocks"]["l0"]["we_gate"].shape == (1, 4, 64, 32)     # the held ones
    assert "mlp_in" in got["lead"]["l0"] and "router" not in got["lead"]["l0"]
    assert (spec.stack_layers, spec.stack_kinds, spec.stack_lead, spec.stack_passes) == (
        5, KINDS, {"full_attention_dense": 1}, 1)
    assert build_gpt2("test-tiny").stack_lead is None
    assert spec.hints["routed"]["held"] == 4 and spec.hints["moe"] is None


def test_config_refuses_what_the_layers_cannot_be():
    with pytest.raises(ValueError, match="whole periods"):
        gpt2.config_for("laguna-test-tiny", n_layers=6)
    with pytest.raises(ValueError, match="window"):
        gpt2.config_for("laguna-test-tiny", window=None)
    with pytest.raises(ValueError, match="whole share"):
        gpt2.config_for("laguna-test-tiny", held_experts=5)
    with pytest.raises(ValueError, match="kind_heads"):
        gpt2.config_for("laguna-test-tiny", kind_heads=((FULL, 5), (SLIDING, 8)))


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_logits_loss_and_gradients_are_the_references(variant, reference, monkeypatch):
    overrides = dict(VARIANTS[variant])
    if "BUFFER" in overrides:       # the row buffer is the op's constant, not the model's
        monkeypatch.setattr(moe, "BUFFER", overrides.pop("BUFFER"))
    spec, weights, tokens = _spec(**overrides), _weights(), jnp.asarray(_tokens(1))
    want, want_loss, want_grads = reference
    with jax.default_matmul_precision("highest"):
        got = jax.jit(spec.apply_fn)(weights, tokens)
        (loss, counters), grads = jax.jit(jax.value_and_grad(
            spec.fused_loss_stats_fn, has_aux=True))(weights, tokens)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    want_g, got_g = lg.flat(want_grads), lg.flat(grads)
    assert set(want_g) == set(got_g)
    for leaf, g in want_g.items():
        assert np.linalg.norm(got_g[leaf] - g) <= 2e-4 * np.linalg.norm(g), (variant, leaf)
    second = variant == "flash-second-path"
    assert float(counters["moe_second_path"]) == (1.0 if second else 0.0)
    assert 0 < float(counters["moe_pairs_held"]) and float(counters["moe_rows_max"]) >= \
        float(counters["moe_rows_mean"])


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts of all the shares of a layer (the program's, each
    holding 4 of the 16 experts) plus the shared expert once are the uncut
    reference's feed-forward: nothing stands in for an absent share, and
    nothing is counted twice."""
    uncut = lg.Arch(**{**ARCH.__dict__, "held": 16})
    params = lg.seeded_params(uncut, lg.seed_key(SEED))
    p = lg._layer_weights(uncut, params, 2)
    y = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = lg.routed_part(uncut, lg._plain_mm, p, y)
        total = jnp.zeros_like(whole)
        for share in range(4):
            tables = [p[n][share * 4:(share + 1) * 4] for n in ("we_gate", "we_up", "we_down")]
            for impl in ("xla", "kernel"):
                plan = moe.routed_plan(2 * SEQ, 16, 4, 4, row_tile=8, impl=impl)
                part, counters = moe.routed_experts(
                    y.reshape(-1, 64), p["router"], *tables, plan=plan,
                    first_expert=share * 4, scale=2.5, dtype=jnp.float32)
                np.testing.assert_allclose(
                    part.reshape(y.shape),
                    lg.routed_part(ARCH, lg._plain_mm, {**p, **dict(zip(
                        ("we_gate", "we_up", "we_down"), tables))}, y, first_expert=share * 4),
                    atol=2e-6)
            total = total + part.reshape(y.shape)
    np.testing.assert_allclose(total, whole, atol=5e-6)
    assert float(jnp.abs(whole).mean()) > 1e-3


@pytest.mark.parametrize("impl", ["xla", "kernel"])
@pytest.mark.parametrize("buffer", ["worst", "small"])
def test_top_k_drops_nothing_when_every_token_goes_to_one_expert(impl, buffer):
    """A router that sends every token to expert 0 first (and to three more
    held ones): the fullest expert holds T rows and every held pair is
    computed, through the worst-case buffer or, past a small one, through the
    exact second path."""
    T, D, E, held, k, F = 96, 32, 8, 4, 4, 16
    key = jax.random.PRNGKey(5)
    y = jnp.abs(jax.random.normal(key, (T, D), jnp.float32)) + 0.1
    router = jnp.zeros((D, E)).at[:, 0].set(1.0).at[:, 1:4].set(0.5).at[:, 4:].set(-1.0)
    tables = [0.1 * jax.random.normal(jax.random.fold_in(key, i), s) for i, s in
              enumerate([(held, D, F), (held, D, F), (held, F, D)])]
    plan = moe.routed_plan(T, E, held, k, row_tile=8, impl=impl,
                           buffer=100.0 if buffer == "worst" else 0.5)
    assert plan.second_path == (buffer == "small")
    with jax.default_matmul_precision("highest"):
        out, counters = moe.routed_experts(y, router, *tables, plan=plan, scale=2.5,
                                           dtype=jnp.float32)
        scores = jax.nn.sigmoid(y @ router)[:, :4]
        w = 2.5 * scores / scores.sum(-1, keepdims=True)
        want = sum(w[:, e:e + 1] * ((jax.nn.silu(y @ tables[0][e]) * (y @ tables[1][e]))
                                    @ tables[2][e]) for e in range(4))
    np.testing.assert_allclose(out, want, atol=2e-6)
    assert (int(counters["rows_max"]), int(counters["pairs_held"])) == (T, 4 * T)
    assert int(counters["second_path"]) == (1 if buffer == "small" else 0)


def test_a_rematerialised_routed_layer_keeps_its_choice_with_its_tables():
    """Under the policy the model's remat gives a routed layer (keep
    ``LAYOUT_NAME``) the backward neither sorts nor chooses again: the kept
    tables were built from the forward's choice, and a second ``top_k`` over
    scores recomputed to another last bit orders two near-equal scores the
    other way, which puts one expert's weight gradient on another's router
    column (the chip read router gradients 2-3 % off: PERF.md, PR 36)."""
    T, D, E, held, k, F = 64, 32, 8, 4, 4, 16
    key = jax.random.PRNGKey(7)
    y = jax.random.normal(key, (T, D), jnp.float32)
    router, *tables = [0.1 * jax.random.normal(jax.random.fold_in(key, i), s) for i, s in
                       enumerate([(D, E), (held, D, F), (held, D, F), (held, F, D)])]
    plan = moe.routed_plan(T, E, held, k, row_tile=8)

    def layer(y, router, *tables):
        return moe.routed_experts(y, router, *tables, plan=plan, dtype=jnp.float32)[0].sum()

    kept = jax.checkpoint(layer, policy=jax.checkpoint_policies.save_only_these_names(
        moe.LAYOUT_NAME))
    text = str(jax.make_jaxpr(jax.grad(kept, argnums=(0, 1)))(y, router, *tables))
    assert (text.count("top_k["), text.count("sort[")) == (1, 1)
    plain, again = (jax.grad(f, argnums=(0, 1))(y, router, *tables) for f in (layer, kept))
    for a, b in zip(plain, again):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_routed_plan_sizes_the_buffer_and_says_when_it_can_overflow():
    plan = moe.routed_plan(16384, 256, 32, 8, buffer=2.0, impl="kernel")
    assert (plan.rows, plan.worst_rows) == (2 * 16384 + 32 * 128, 8 * 16384 + 32 * 128)
    assert plan.second_path and plan.as_event()["second_path"] is True
    assert not moe.routed_plan(128, 16, 4, 4, buffer=100.0, row_tile=8).second_path
    assert moe.routed_plan(128, 16, 4, 4, buffer=100.0, row_tile=8).rows == 128 * 4 + 32


# ------------------------------------------------------ the window kernels
def _masked_dense(q, k, v, window):
    B, H, T, D = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(D)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
    s = jnp.where((j <= i) & (i - j < window), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("T,block,window,heads,kv", [
    (64, 16, 32, 12, 2),      # a window of two blocks, 6 q heads a k/v head
    (64, 16, 24, 16, 2),      # no multiple of the block, 8 q heads a k/v head
    (64, 16, 5, 4, 4),        # inside one block
    (128, 32, 100, 4, 1),     # all q heads on one k/v head
    (64, 16, 64, 2, 2),       # the whole sequence: causal attention
    (64, 16, 1, 2, 1),        # the token itself
])
def test_window_kernels_are_masked_dense_attention_fwd_dq_dkv(T, block, window, heads, kv):
    key = jax.random.PRNGKey(7)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, h, T, 8), jnp.float32)
               for i, h in enumerate((heads, kv, kv)))
    do = jax.random.normal(jax.random.fold_in(key, 9), q.shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(lambda *a: flash_attention(*a, window=window, block_q=block), q, k, v)
        want, want_vjp = jax.vjp(lambda *a: _masked_dense(*a, window), q, k, v)
        np.testing.assert_allclose(out, want, atol=2e-6)
        for got_g, want_g, name in zip(vjp(do), want_vjp(do), ("dq", "dk", "dv")):
            np.testing.assert_allclose(got_g, want_g, atol=1e-5, err_msg=name)


def test_window_kernels_have_names_of_their_own_and_skip_blocks():
    q = jnp.zeros((1, 2, 64, 8))
    text = str(jax.make_jaxpr(jax.grad(lambda q: flash_attention(
        q, q, q, window=24, block_q=16).sum()))(q))
    assert "saturn_swa_fwd" in text and "saturn_swa_dq" in text and "saturn_swa_dkv" in text
    assert "saturn_flash_" not in text
    plain = str(jax.make_jaxpr(jax.grad(lambda q: flash_attention(q, q, q).sum()))(q))
    assert "saturn_flash_dq" in plain and "saturn_swa_" not in plain
    # 8192 positions, a window of 512, blocks of 512: a row block reaches 2 of
    # a row's up to 16 blocks, 1 + 15 x 2 of a causal walk's 16 x 17 / 2; fwd
    # and dq walk them by the loop inside one chunk of all of T, dkv as a grid
    # axis over chunks of one block (PR 50)
    plan = window_plan(8192, 128, 512)
    assert (plan["window"], plan["seq"], plan["head_dim"]) == (512, 8192, 128)
    walk = {"block_q": 512, "block_k": 512, "visited": 1 + 15 * 2, "masked": 31,
            "computed_over_needed": 2.0}
    assert plan["fwd"] == plan["dq"] == {**walk, "chunk": 8192, "steps": 1,
                                         "blocks_a_step": 2}
    assert plan["dkv"] == {**walk, "chunk": 512, "steps": 2, "blocks_a_step": 1}
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, window=8, causal=False)


# ---------------------------------------------------------------- rotary
def test_the_yarn_table_against_one_written_by_hand():
    """rotary_dim 64, theta 500000, factor 64, 4096 original positions,
    beta_fast 64, beta_slow 1: dimension j turns 4096 / (2 pi theta^(2j/64))
    times; the ramp runs from the dimension that turns 64 times (64 ln(4096 /
    128 pi) / 2 ln 500000 = 5.66 -> 5) to the one that turns once (15.8 ->
    16)."""
    inv = np.asarray(gpt2.yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0))
    by_hand = []
    for j in range(32):
        plain = 500000.0 ** (-2 * j / 64)
        ramp = min(max((j - 5) / (16 - 5), 0.0), 1.0)
        by_hand.append(plain * (1 - ramp) + plain / 64 * ramp)
    np.testing.assert_allclose(inv, by_hand, rtol=1e-6)
    assert inv[0] == 1.0 and inv[5] == pytest.approx(500000.0 ** (-10 / 64))      # kept
    np.testing.assert_allclose(inv[8], 500000.0 ** (-16 / 64) * (1 - 3 / 11 * 63 / 64),
                               rtol=1e-6)                                        # on the ramp
    np.testing.assert_allclose(inv[16:], [500000.0 ** (-2 * j / 64) / 64 for j in range(16, 32)],
                               rtol=1e-6)                                        # / 64
    np.testing.assert_allclose(np.asarray(lg.yarn_inv_freq(64, 500000.0, 64.0, 4096, 64.0, 1.0)),
                               inv, rtol=1e-6)
    cfg = gpt2.config_for("laguna-xs2")
    sin, cos, rd = gpt2.rotary_tables(cfg, FULL, jnp.asarray([0, 1, 4097]))
    assert rd == 64 and sin.shape == (3, 32)
    for row, position in enumerate((0, 1, 4097)):          # three positions, by hand
        np.testing.assert_allclose(sin[row], 1.4158883083359672 * np.sin(position * inv),
                                   atol=2e-4 if position > 1 else 1e-6)
        np.testing.assert_allclose(cos[row], 1.4158883083359672 * np.cos(position * inv),
                                   atol=2e-4 if position > 1 else 1e-6)
    sin, cos, rd = gpt2.rotary_tables(cfg, SLIDING, jnp.asarray([3]))
    assert rd == 128 and float(sin[0, 0]) == pytest.approx(math.sin(3.0))        # plain, theta 1e4
    np.testing.assert_allclose(sin[0, 63], math.sin(3.0 * 10000.0 ** (-126 / 128)), rtol=1e-5)
