"""The LFM2 stack (``build_lfm2``: a leading dense layer whose mixer is a doubly
gated short convolution, then periods of one grouped-query attention layer
with an RMSNorm a head on q and k before the rotation and three
short-convolution layers, each before top-k routed SwiGLU experts chosen by
sigmoid scores under a selection bias, the head tied to the embedding) at
``lfm2-test-tiny`` on the CPU, in float32, against the plain reference
``perf/reference/lfm2.py`` from the same seeded weights; the grouped product
at a table over ``ops/moe.py``'s threshold; and ``flash_attention`` at this
model's group and head. (The techniques and search -> orchestrate are
``tests/test_lfm2_techniques.py``, so that ``--dist loadfile`` spreads the
compiles.)

Program and reference are both float32 here and differ by the order of their
roundings only: logits to 2e-5 of their largest (the tied head's logits are
small under the seeded final gain: ``perf/reference/lfm2.py::HEAD_GAIN``),
gradients to 2e-4 of each leaf's norm.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import lfm2 as ref
from saturn_tpu.models import gpt2
from saturn_tpu.models.gpt2 import build_gpt2, build_lfm2
from saturn_tpu.ops import moe, plans
from saturn_tpu.ops.flash import flash_attention

CONV, FULL = "conv", "full_attention"
KINDS, LEAD = {FULL: 1, CONV: 3}, {"conv_dense": 1}
ARCH = ref.Arch(vocab_size=256, d_model=64, kinds=(CONV, FULL, CONV, CONV, CONV),
                ffs=("dense",) + ("sparse",) * 4, n_heads=8, n_kv_heads=2, head_dim=8,
                taps=3, rope_theta=1e6, d_ff=128, experts=16, held=4, first_expert=0,
                top_k=4, d_expert=32, routed_scale=1.0, route_eps=1e-6, norm_eps=1e-5)
SEQ, SEED = 64, 2_147_483_693
VARIANTS = {"dense": {"attention": "dense"},    # the plain twins: masked einsums, ragged_dot
            # flash and gmm kernels, interpret mode, each layer rematerialised
            "flash-remat": {"attention": "flash", "remat": True},
            # a row buffer a quarter of the mean: every step takes the second path
            "flash-second-path": {"attention": "flash", "routed_buffer": 0.25}}
LOGITS_RTOL, GRAD_RTOL = 2e-5, 2e-4


def _tokens(seed, batch=2, seq=SEQ):
    return np.random.default_rng(seed).integers(0, 256, size=(batch, seq), dtype=np.int32)


def _spec(**kw):
    return build_lfm2("lfm2-test-tiny", dtype=jnp.float32, **kw)


def _weights(arch=ARCH):
    return ref.program_params(arch, ref.seed_key(SEED))


def _reference(fault=None):
    """(logits, loss, gradients in the program's layout) of the reference on
    one batch, in one jitted call."""
    tokens = jnp.asarray(_tokens(1))

    @jax.jit
    def all_of(key):
        params = ref.seeded_params(ARCH, key)
        loss, grads = jax.value_and_grad(
            lambda p: ref.loss_fn(ARCH, p, tokens, fault=fault))(params)
        return (ref.forward(ARCH, params, tokens, fault=fault), loss,
                ref.program_layout(ARCH, grads))

    with jax.default_matmul_precision("highest"):
        return all_of(ref.seed_key(SEED))


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
    """(the largest logit difference over the largest logit, the worst leaf's
    gradient difference over its norm and its path)."""
    (want_logits, _, want_grads), (logits, _, grads) = want, got[:3]
    want_g, got_g = ref.flat(want_grads), ref.flat(grads)
    assert set(want_g) == set(got_g)
    # (a selection bias's gradient is exactly zero on a sound side: its
    # difference is held to the largest norm of either side)
    rel = {k: np.linalg.norm(got_g[k] - want_g[k])
           / max(np.linalg.norm(want_g[k]), np.linalg.norm(got_g[k]), 1e-30) for k in want_g}
    worst = max(rel, key=rel.get)
    return (float(jnp.abs(logits - want_logits).max() / jnp.abs(want_logits).max()),
            float(rel[worst]), worst)


# ------------------------------------------------------------ the model
def test_preset_is_the_published_model_and_the_tree_is_the_references():
    cfg = build_lfm2("lfm2-8b-a1b").config
    assert (cfg.d_model, cfg.head_dim, cfg.heads_held, cfg.n_kv_heads, cfg.vocab_size,
            cfg.ff_dim, cfg.routed_experts, cfg.top_k, cfg.expert_ff, cfg.shared_ff,
            cfg.routed_scale, cfg.conv_taps) == (
                2048, 64, 32, 8, 65536, 7168, 32, 4, 1792, 0, 1.0, 3)
    assert cfg.layer_types == (FULL,) + (CONV,) * 3 and cfg.stack_kinds == KINDS
    assert (cfg.lead_layers, cfg.lead_kind, cfg.stack_lead) == (2, CONV, {"conv_dense": 2})
    # the published 24 less the tail of two periods of three
    assert (cfg.n_layers, cfg.n_periods, cfg.experts_held) == (18, 4, 32)
    assert (cfg.rotary, cfg.rotary_dim, cfg.rope_theta, cfg.head_qk_norm, cfg.qk_norm) == (
        True, None, 1e6, True, False)
    assert (cfg.router_score, cfg.router_bias, cfg.route_eps, cfg.expert_act,
            cfg.route_from) == ("sigmoid", True, 1e-6, "swiglu", "ff_input")
    assert (cfg.norm, cfg.norm_eps, cfg.use_bias, cfg.tie_head, cfg.attn_gate) == (
        "rmsnorm", 1e-5, False, True, False)
    spec = _spec()
    want = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    got = jax.eval_shape(_weights)
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    assert jax.tree_util.tree_leaves(want) == jax.tree_util.tree_leaves(got)
    # the head is the embedding: no lm_head; no bias anywhere
    assert set(got) == {"wte", "ln_f", "lead", "blocks"} and "'bias'" not in str(got)
    conv, full = got["blocks"]["l1"], got["blocks"]["l0"]
    assert set(got["lead"]["l0"]) == {"ln_1", "ln_2", "conv_b", "conv_c", "conv_x", "conv_w",
                                      "attn_out", "mlp_gate", "mlp_in", "mlp_out"}
    assert set(conv) == {"ln_1", "ln_2", "conv_b", "conv_c", "conv_x", "conv_w", "attn_out",
                         "router", "router_bias", "we_gate", "we_up", "we_down"}
    assert set(full) == {"ln_1", "ln_2", "qkv", "q_norm", "k_norm", "attn_out",
                         "router", "router_bias", "we_gate", "we_up", "we_down"}
    assert conv["conv_w"].shape == (1, 3, 64) and conv["conv_x"]["kernel"].shape == (1, 64, 64)
    # 8 q heads over 2 k/v heads of 8 lanes; one gain of a head's lanes
    assert full["qkv"]["kernel"].shape == (1, 64, 64 + 2 * 16)
    assert full["q_norm"].shape == full["k_norm"].shape == (1, 8)
    assert got["lead"]["l0"]["mlp_in"]["kernel"].shape == (64, 128)
    assert full["we_gate"].shape == (1, 4, 64, 32) and full["router_bias"].shape == (1, 16)
    assert (spec.stack_layers, spec.stack_kinds, spec.stack_lead, spec.stack_passes) == (
        5, KINDS, LEAD, 1)
    assert spec.hints["routed"]["held"] == 4 and spec.hints["seq_parallel"] is False
    # a dense stack of convolutions is not sequence-parallel either
    assert build_gpt2("lfm2-test-tiny", routed_experts=0,
                      n_layers=5).hints["seq_parallel"] is False


def test_config_refuses_what_the_layers_cannot_be():
    tiny = "lfm2-test-tiny"
    with pytest.raises(ValueError, match="two periods of three"):
        build_lfm2("lfm2-8b-a1b", n_layers=22)      # whole periods, past the last whole one
    with pytest.raises(ValueError, match="whole periods"):
        build_lfm2("lfm2-8b-a1b", n_layers=24)
    with pytest.raises(ValueError, match="lead_kind"):
        gpt2.config_for(tiny, lead_kind="mamba2")
    with pytest.raises(ValueError, match="holds all its channels"):
        gpt2.config_for(tiny, held_heads=4)
    with pytest.raises(ValueError, match="single-program"):
        gpt2.config_for(tiny, seq_axis="seq", seq_axis_size=2)
    with pytest.raises(ValueError, match="one or the other"):
        gpt2.config_for(tiny, qk_norm=True)
    with pytest.raises(ValueError, match="normalising sum"):
        moe.routed_plan(64, 16, 4, 4, score="softmax", eps=1e-6)
    with pytest.raises(ValueError, match="normalising sum"):
        moe.routed_plan(64, 16, 4, 4, eps=-1.0)


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_logits_loss_and_gradients_are_the_references(variant, reference):
    spec, weights, tokens = _spec(**VARIANTS[variant]), _weights(), jnp.asarray(_tokens(1))
    want, want_loss, want_grads = reference
    with jax.default_matmul_precision("highest"):
        got = jax.jit(spec.apply_fn)(weights, tokens)
        (loss, counters), grads = jax.jit(jax.value_and_grad(
            spec.fused_loss_stats_fn, has_aux=True))(weights, tokens)
    np.testing.assert_allclose(got, want, atol=LOGITS_RTOL * float(jnp.abs(want).max()))
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=2e-6)
    want_g, got_g = ref.flat(want_grads), ref.flat(grads)
    assert set(want_g) == set(got_g)
    for leaf, g in want_g.items():
        if leaf.endswith("router_bias"):    # enters the choice only: exactly zero
            assert not np.any(g) and not np.any(got_g[leaf]), leaf
            continue
        assert np.linalg.norm(got_g[leaf] - g) <= GRAD_RTOL * np.linalg.norm(g), (variant, leaf)
    # the taps', the head norms' and the routers' are no rounding noise
    for leaf in ("lead/l0/conv_w", "blocks/l1/conv_w", "blocks/l0/q_norm",
                 "blocks/l0/k_norm", "blocks/l2/router"):
        assert np.linalg.norm(want_g[leaf]) > 1e-7, leaf
    second = variant == "flash-second-path"
    assert float(counters["moe_second_path"]) == (1.0 if second else 0.0)
    assert 0 < float(counters["moe_pairs_held"]) and float(counters["moe_rows_max"]) >= \
        float(counters["moe_rows_mean"])


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_is_outside_the_comparisons_tolerances(fault, reference, program):
    """The program against a reference with one thing wrong: the taps in
    reverse order, ``silu`` on the convolution (``_conv_heads``' habit), the B
    gate or the C gate dropped, q and k normed over all the heads' lanes
    together (``qk_norm``) or after the rotation, the selection bias in the
    weights, weights not renormalised, the period one place on (conv, conv,
    conv, full), the dense layer at an expert's width. Each is outside the
    tolerance the sound comparison is held to, and the sound one is inside by
    a wide margin: the comparison tells them apart."""
    sound_logits, sound_grad, _ = _off(reference, program)
    assert sound_logits <= LOGITS_RTOL / 2 and sound_grad <= GRAD_RTOL / 4
    logits_off, grad_off, leaf = _off(_reference(fault), program)
    # (the layers write little to the stream beside the embedding's rows --
    # ``perf/reference/lfm2.py::OUT`` -- so two layers nearly commute and the
    # period's order shows in the gradients at a part in a thousand: five
    # times the tolerance, twenty times the sound reading's bound)
    margin = 5 if fault == "period_rotated" else 10
    assert logits_off > 5 * LOGITS_RTOL or grad_off > margin * GRAD_RTOL, (
        fault, logits_off, grad_off, leaf)


def test_the_route_adds_its_eps_to_the_sum_and_the_bias_to_the_choice_only():
    z = 2.0 * jax.random.normal(jax.random.PRNGKey(2), (64, 16), jnp.float32)
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (16,), jnp.float32)
    eye = jnp.eye(16, dtype=jnp.float32)
    s = jax.nn.sigmoid(z)
    _, chosen = jax.lax.top_k(s + bias, 4)
    top = jnp.take_along_axis(s, chosen, axis=-1)
    for eps in (0.0, 1e-6, 0.5):
        plan = moe.routed_plan(64, 16, 16, 4, row_tile=8, bias=True, eps=eps)
        assert plan.as_event()["eps"] == eps
        made = moe.route(z, eye, plan=plan, bias=bias)
        np.testing.assert_array_equal(made["chosen"], chosen)
        np.testing.assert_allclose(made["weights"],
                                   top / (top.sum(-1, keepdims=True) + eps), rtol=1e-6)
    assert float(jnp.abs(jax.lax.top_k(s, 4)[1] != chosen).sum()) > 0   # the bias chose


# ------------------------------------------------ the share ties to the model
def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares of a layer (the program's op, each
    share holding 4 of the 16 experts) are the uncut reference's feed-forward:
    nothing stands in for an absent share, nothing is counted twice, and
    there is no shared expert to count once."""
    uncut = ref.Arch(**{**ARCH.__dict__, "held": 16})
    params = ref.seeded_params(uncut, ref.seed_key(SEED))
    p = ref._layer_weights(uncut, params, 2)
    u = ref._rms_norm(params["wte"][jnp.asarray(_tokens(4))], p["ln_2"]["scale"], 1e-5)
    with jax.default_matmul_precision("highest"):
        whole = ref.routed_part(uncut, ref._plain_mm, p, u)
        chosen, weights = ref.routing_of(uncut, p, u)
        total, scale = jnp.zeros_like(whole), float(jnp.abs(whole).max())
        for share in range(4):
            tables = [p[n][share * 4:(share + 1) * 4] for n in ("we_gate", "we_up", "we_down")]
            for impl in ("xla", "kernel"):
                plan = moe.routed_plan(2 * SEQ, 16, 4, 4, row_tile=8, impl=impl, bias=True,
                                       eps=1e-6)
                part, counters = moe.routed_experts(
                    u.reshape(-1, 64), p["router"], *tables, plan=plan,
                    first_expert=share * 4, dtype=jnp.float32, bias=p["router_bias"])
                np.testing.assert_allclose(
                    part.reshape(u.shape),
                    ref.routed_part(ARCH, ref._plain_mm, {**p, **dict(zip(
                        ("we_gate", "we_up", "we_down"), tables))}, u,
                        first_expert=share * 4),
                    atol=1e-4 * scale)
                np.testing.assert_array_equal(counters["chosen"], chosen.reshape(-1, 4))
            total = total + part.reshape(u.shape)
    # (the seeded down tables are small: ``perf/reference/lfm2.py::OUT``)
    np.testing.assert_allclose(total, whole, atol=1e-4 * scale)
    assert scale > 5e-4
    # a token's weights over all the shares are its scores over their sum + eps
    np.testing.assert_allclose(jnp.sum(weights, -1), 1.0, atol=2e-6)


def test_the_logits_over_four_vocabulary_slices_are_the_uncut_tied_heads():
    """The held rows of the tied embedding give the held columns of the
    logits: four slices of 64 rows side by side are the uncut head's logits."""
    params = ref.seeded_params(ARCH, ref.seed_key(SEED))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref._head(ARCH, ref._plain_mm, params, x)
        parts = [ref._head(ARCH, ref._plain_mm,
                           {**params, "wte": params["wte"][s * 64:(s + 1) * 64]}, x)
                 for s in range(4)]
    np.testing.assert_allclose(jnp.concatenate(parts, axis=-1), whole, atol=1e-6)


# ------------------------------- the grouped product at a table over the limit
def _gmm_case(key, held=3, P=32, Q=48, tile=8):
    """Rows of a buffer whose experts have 2, 1 and 3 tiles, the last tile
    past the active ones."""
    tile_expert = jnp.asarray([0, 0, 1, 2, 2, 2, 2], jnp.int32)
    n_active = jnp.asarray([6], jnp.int32)
    x = jax.random.normal(jax.random.fold_in(key, 0), (7 * tile, P), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (held, P, Q), jnp.float32)
    dy = jax.random.normal(jax.random.fold_in(key, 2), (7 * tile, Q), jnp.float32)
    return x, w, dy, tile_expert, n_active, tile


def _gmm_all(x, w, dy, tile_expert, n_active, tile):
    out, vjp = jax.vjp(lambda x, w: moe._gmm_kernels(x, w, tile_expert, n_active, tile), x, w)
    return (out, *vjp(dy))


@pytest.mark.parametrize("which", ["fwd", "dx", "dw"])
def test_the_grouped_product_over_the_table_limit_is_the_whole_matrix_forms(
        which, monkeypatch):
    """A table over ``_GMM_TABLE_MAX`` stays one block an expert and asks the
    compiler for its VMEM (``gmm_plan``); under it the call is the one the
    cells before ran. Both against a plain einsum over each tile's expert, and
    against each other bit for bit."""
    x, w, dy, tile_expert, n_active, tile = _gmm_case(jax.random.PRNGKey(1))
    index = {"fwd": 0, "dx": 1, "dw": 2}[which]
    with jax.default_matmul_precision("highest"):
        with plans.traced() as under:
            whole = _gmm_all(x, w, dy, tile_expert, n_active, tile)[index]
        monkeypatch.setattr(moe, "_GMM_TABLE_MAX", 32 * 48 * 4 - 1)
        with plans.traced() as over:
            asked = _gmm_all(x, w, dy, tile_expert, n_active, tile)[index]
        live = (jnp.arange(7 * tile) < 6 * tile)[:, None]
        rows_w = w[jnp.repeat(tile_expert, tile)]                  # (R, P, Q)
        want = {"fwd": jnp.where(live, jnp.einsum("rp,rpq->rq", x, rows_w), 0.0),
                "dx": jnp.where(live, jnp.einsum("rq,rpq->rp", dy, rows_w), 0.0),
                "dw": jnp.einsum("rp,rq,re->epq", jnp.where(live, x, 0.0), dy,
                                 jax.nn.one_hot(jnp.repeat(tile_expert, tile), 3))}[which]
    np.testing.assert_array_equal(asked, whole)
    np.testing.assert_allclose(asked, want, rtol=1e-5, atol=1e-5)
    assert all(p.vmem_limit is None for p in under["gmm"])
    first = over["gmm"][0]
    assert first.table_bytes == 32 * 48 * 4 and first.vmem_limit >= first.vmem > 2 * 32 * 48 * 4
    assert first.vmem_limit % (1 << 20) == 0


@pytest.mark.parametrize("shape,asks", [
    ((2048, 512), False), ((2560, 768), False), ((1024, 2688), False),   # the cells before
    ((2048, 1792), True),                                                 # 7 MiB
])
def test_gmm_plan_is_a_pure_function_of_the_tables_bytes(shape, asks):
    P, Q = shape
    for lanes_in, lanes_out in ((P, Q), (Q, P)):      # fwd; dx
        plan = moe.gmm_plan(128, lanes_in, lanes_out, P * Q, 2)
        assert plan.table_bytes == 2 * P * Q and (plan.vmem_limit is not None) == asks
        assert plan.vmem == 2 * (2 * P * Q + 128 * (P + Q) * 2) + 128 * lanes_out * 4
    if asks:
        assert plan.vmem > 16 << 20 and plan.vmem_limit == 22 << 20   # (dx: 2048 lanes out)
        assert moe.gmm_plan(128, P, Q, P * Q, 2).vmem_limit == 21 << 20
    assert set(plans.as_event(plan)) == {"table_bytes", "vmem", "vmem_limit"}


# ------------------------------------- flash attention at this model's shapes
def _masked_dense(q, k, v):
    B, H, T, D = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(D)
    s = jnp.where(jnp.arange(T)[None] <= jnp.arange(T)[:, None], s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("T,block,heads,kv", [
    (128, 32, 8, 2),       # 4 q heads a k/v head of 64 lanes, four blocks
    (64, 16, 32, 8),       # the published heads
    (128, 64, 4, 1),       # one group of four
])
def test_flash_attention_at_four_q_heads_a_kv_head_of_64_lanes(T, block, heads, kv):
    key = jax.random.PRNGKey(7)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, h, T, 64), jnp.float32)
               for i, h in enumerate((heads, kv, kv)))
    do = jax.random.normal(jax.random.fold_in(key, 9), q.shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(lambda *a: flash_attention(*a, block_q=block, block_k=block),
                           q, k, v)
        want, want_vjp = jax.vjp(_masked_dense, q, k, v)
        np.testing.assert_allclose(out, want, atol=2e-6)
        for got_g, want_g, name in zip(vjp(do), want_vjp(do), ("dq", "dk", "dv")):
            np.testing.assert_allclose(got_g, want_g, atol=2e-5, err_msg=name)
