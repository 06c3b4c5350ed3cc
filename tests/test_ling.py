"""The Ling stack (``build_ling``) at ``ling-test-tiny`` on the CPU, in
float32, against the plain reference ``perf/reference/ling.py`` from the same
seeded weights: logits, loss and gradients for the twin and the kernel grid
point (kernels in interpret mode); the head shares and the expert shares add
up to the uncut layers; the group limit binds where it should; latent
attention through the flash kernels at 192 / 128 lanes.
(``tests/test_ling_techniques.py`` has ``search`` -> ``orchestrate`` and
every technique; ``tests/test_kda.py`` the delta rule itself.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import ling
from saturn_tpu.models.gpt2 import GPT2Config, build_ling, config_for
from saturn_tpu.ops import flash, kda, moe
from saturn_tpu.ops import plans as op_plans

SEED, SEQ = 3, 128
KINDS = {"kda": 5, "mla": 1}
LEAD = {"kda_dense": 1}
#: the uncut tiny model (every expert held), and the tiny cell's share
FULL = ling.Arch(
    vocab_size=256, d_model=64, kinds=(ling.KDA,) * 4 + (ling.MLA,) + (ling.KDA,) * 2,
    ffs=(ling.DENSE,) + (ling.SPARSE,) * 6, n_heads=4, head_dim=16, conv_taps=4,
    gate_floor=-5.0, kv_latent=32, qk_nope=16, qk_rope=8, v_head=16, rope_theta=6e6,
    d_dense=128, experts=16, held=16, first_expert=0, top_k=4, groups=4, groups_kept=2,
    d_expert=32, d_shared=32, routed_scale=2.5, norm_eps=1e-6)
ARCH = dataclasses.replace(FULL, held=4)


def _tokens(batch=2, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ), 0, 256)


def _rel(a, b):
    return float(np.linalg.norm(np.asarray(a) - np.asarray(b))
                 / max(float(np.linalg.norm(np.asarray(b))), 1e-30))


# --------------------------------------------------- program and reference
@pytest.fixture(scope="module")
def reference_side():
    key = ling.seed_key(SEED)
    tokens = _tokens()
    with jax.default_matmul_precision("highest"):
        params = ling.seeded_params(ARCH, key)
        logits = ling.forward(ARCH, params, tokens)
        loss, grads = jax.value_and_grad(lambda p: ling.loss_fn(ARCH, p, tokens))(params)
    return tokens, logits, float(loss), ling.flat(ling.program_layout(ARCH, grads))


@pytest.mark.parametrize("attention", ["dense", "flash"], ids=["twin", "kernels"])
def test_logits_loss_and_gradients_are_the_references(reference_side, attention):
    tokens, ref_logits, ref_loss, ref_grads = reference_side
    spec = build_ling("ling-test-tiny", dtype=jnp.float32, attention=attention)
    assert (spec.stack_kinds, spec.stack_lead, spec.stack_layers) == (KINDS, LEAD, 7)
    params = ling.program_params(ARCH, ling.seed_key(SEED))
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        spec.init_fn(jax.random.PRNGKey(0)))
    with jax.default_matmul_precision("highest"):
        logits = spec.apply_fn(params, tokens)
        loss, grads = jax.value_and_grad(spec.fused_loss_fn)(params, tokens)
    assert _rel(logits, ref_logits) < 2e-6
    assert abs(float(loss) - ref_loss) < 2e-6 * ref_loss
    grads = ling.flat(grads)
    assert set(grads) == set(ref_grads)
    for leaf, want in ref_grads.items():
        if leaf.endswith("router_bias"):     # in the choice only: exactly zero
            assert not np.any(np.asarray(grads[leaf])) and not np.any(np.asarray(want))
            continue
        assert _rel(grads[leaf], want) < 3e-5, leaf


def test_the_kernel_grid_point_traces_the_kernels_and_says_its_plans():
    spec = build_ling("ling-test-tiny", attention="flash", remat=True)
    shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    with op_plans.traced() as got:
        text = str(jax.make_jaxpr(jax.grad(spec.fused_loss_fn))(shapes, _tokens()))
    kda_plans, moe_plans, flash_plans = got["kda"], got["moe"], got["flash"]
    for kernel in ("saturn_mla_fwd", "saturn_mla_dq", "saturn_mla_dkv",
                   "saturn_gmm_fwd", "saturn_gmm_dw"):
        assert kernel in text, kernel
    assert "saturn_flash_" not in text      # no equal-width attention in this stack
    assert "saturn_kda_" not in text        # the delta rule is the plain scan
    assert kda_plans[0] == kda.KDAPlan("xla", 64, 16, 8, 2, 16, 16, 2 * 8 * 16 * 16 * 4)
    assert (flash_plans[0]["d_qk"], flash_plans[0]["d_v"], flash_plans[0]["head_dim"]) == (
        24, 16, 24)
    event = moe_plans[0].as_event()
    assert (event["groups"], event["groups_kept"], event["bias"], event["top_k"],
            event["experts"], event["held"], event["act"]) == (4, 2, True, 4, 16, 4, "swiglu")


# ------------------------------------------------------- the shares add up
def _normed_rows(seed=5):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, SEQ, FULL.d_model))


def _layer(arch, n, key=SEED):
    return ling._layer_weights(arch, ling.seeded_params(arch, ling.seed_key(key)), n)


def _columns(kernel, heads, width):
    lanes = (np.asarray(heads)[:, None] * width + np.arange(width)).ravel()
    return {"kernel": kernel["kernel"][:, lanes]}, lanes


def test_the_head_shares_of_a_kda_layer_add_up_to_the_uncut_layer():
    """Everything in the mixer is a head's own (convolutions lane by lane, l2
    and the output norm over a head's lanes, beta and the gates a head), so a
    share of whole heads computes exactly its heads' part."""
    y, p = _normed_rows(), _layer(FULL, 1)
    d = FULL.head_dim
    with jax.default_matmul_precision("highest"):
        whole = ling.kda_mixer(FULL, ling._plain_mm, p, y)
        total = 0.0
        for hs in ([0], [1, 2], [3]):
            share = {"o_norm": p["o_norm"], "A_log": p["A_log"][np.asarray(hs)]}
            for name in ("lin_q", "lin_k", "lin_v", "lin_a"):
                share[name], lanes = _columns(p[name], hs, d)
            for name in ("conv_q", "conv_k", "conv_v"):
                share[name] = p[name][:, lanes]
            share["dt_bias"] = p["dt_bias"][lanes]
            for name in ("lin_b", "attn_gate"):
                share[name] = {"kernel": p[name]["kernel"][:, np.asarray(hs)]}
            share["attn_out"] = {"kernel": p["attn_out"]["kernel"][lanes]}
            total = total + ling.kda_mixer(FULL, ling._plain_mm, share, y)
    assert _rel(total, whole) < 1e-5


def test_the_head_shares_of_the_mla_layer_add_up_to_the_uncut_layer():
    """The latent down-projection, its norm and the shared rotary key are on
    every share alike; q, the latent's up-projection, the gate and the
    output projection by head; the q / k norms are over a head's lanes."""
    y, p = _normed_rows(), _layer(FULL, 4)
    qk, kv = FULL.qk_nope + FULL.qk_rope, FULL.qk_nope + FULL.v_head
    with jax.default_matmul_precision("highest"):
        whole = ling.mla_mixer(FULL, ling._plain_mm, p, y)
        total = 0.0
        for hs in ([0, 1], [2], [3]):
            share = {k: p[k] for k in ("mla_kv_a", "kv_norm", "q_norm", "k_norm")}
            share["mla_q"], _ = _columns(p["mla_q"], hs, qk)
            share["mla_kv_b"], _ = _columns(p["mla_kv_b"], hs, kv)
            share["attn_gate"] = {"kernel": p["attn_gate"]["kernel"][:, np.asarray(hs)]}
            rows = (np.asarray(hs)[:, None] * FULL.v_head + np.arange(FULL.v_head)).ravel()
            share["attn_out"] = {"kernel": p["attn_out"]["kernel"][rows]}
            total = total + ling.mla_mixer(FULL, ling._plain_mm, share, y)
    assert _rel(total, whole) < 1e-5


def test_the_four_expert_shares_and_the_shared_expert_once_add_up():
    y, p = _normed_rows(), _layer(FULL, 2)
    with jax.default_matmul_precision("highest"):
        whole = ling.routed_ff(FULL, ling._plain_mm, p, y)
        total = 0.0
        for n in range(FULL.experts // ARCH.held):
            es = slice(n * ARCH.held, (n + 1) * ARCH.held)
            share = dict(p, we_gate=p["we_gate"][es], we_up=p["we_up"][es],
                         we_down=p["we_down"][es])
            total = total + ling.routed_ff(ARCH, ling._plain_mm, share, y,
                                           first_expert=n * ARCH.held, shared=n == 0)
        chosen, weights = ling.routing_of(FULL, p, y)
    assert _rel(total, whole) < 1e-5
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), FULL.routed_scale, rtol=1e-5)
    assert chosen.shape[-1] == FULL.top_k


@pytest.mark.parametrize("held", [1, 2, 4])
def test_held_heads_is_the_references_layer_at_the_held_count(held):
    arch = dataclasses.replace(ARCH, n_heads=held)
    params = ling.program_params(arch, ling.seed_key(SEED))
    spec = build_ling("ling-test-tiny", dtype=jnp.float32, attention="dense",
                      held_heads=held)
    tokens = _tokens(batch=1)
    with jax.default_matmul_precision("highest"):
        got = spec.apply_fn(params, tokens)
        want = ling.forward(arch, ling.seeded_params(arch, ling.seed_key(SEED)), tokens)
    assert params["blocks"]["l3"]["mla_q"]["kernel"].shape == (1, 64, held * 24)
    assert params["blocks"]["l3"]["mla_kv_a"]["kernel"].shape == (1, 64, 32 + 8)   # whole
    assert params["lead"]["l0"]["lin_a"]["kernel"].shape == (64, held * 16)
    assert _rel(got, want) < 2e-6


# ------------------------------------------------------- the group limit
def _planted(experts=32, groups=8, d=16):
    """One token whose 8 largest scores lie in 5 groups (2 + 2 + 2 + 1 + 1),
    with a ninth in the fourth group: an identity router reads the row."""
    logits = np.full((2, experts), -2.0, np.float32)
    strong = [0, 1, 4, 5, 8, 9, 12, 16]           # groups 0, 1, 2 twice, 3 and 4 once
    logits[0, strong] = 3.0
    logits[0, 13] = 2.0                            # the stand-in, in group 3
    logits[1, [0, 1, 2, 3, 4, 5, 6, 7]] = 3.0      # a token in two groups: unmoved
    return jnp.asarray(logits), strong


def test_a_token_whose_best_eight_lie_in_five_groups_chooses_otherwise_under_the_limit():
    logits, strong = _planted()
    scores = jax.nn.sigmoid(logits)
    free = np.sort(np.asarray(jax.lax.top_k(scores, 8)[1]), -1)
    kept = np.sort(np.asarray(jax.lax.top_k(moe.limited_choice(scores, 8, 4), 8)[1]), -1)
    assert list(free[0]) == sorted(strong)
    assert list(kept[0]) == sorted(set(strong) - {16} | {13})     # group 4 is not to be had
    assert list(kept[1]) == list(free[1]) == list(range(8))
    # the reference's own routing says the same, and so does the routed layer
    a = dataclasses.replace(FULL, experts=32, groups=8, groups_kept=4, top_k=8)
    p = {"router": jnp.eye(32), "router_bias": jnp.zeros((32,))}
    chosen, weights = ling.routing_of(a, p, logits)
    unlimited, _ = ling.routing_of(a, p, logits, limit=False)
    assert list(np.sort(np.asarray(chosen[0]))) == list(kept[0])
    assert list(np.sort(np.asarray(unlimited[0]))) == list(free[0])
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-6)
    plan = moe.routed_plan(2, 32, 32, 8, groups=8, groups_kept=4, bias=True, buffer=100.0)
    tables = [0.1 * jax.random.normal(jax.random.PRNGKey(i), s)
              for i, s in enumerate([(32, 32, 8), (32, 32, 8), (32, 8, 32)])]
    _, stats = moe.routed_experts(logits, jnp.eye(32), *tables, plan=plan, scale=2.5,
                                  dtype=jnp.float32, bias=jnp.zeros((32,)))
    assert list(np.sort(np.asarray(stats["chosen"][0]))) == list(kept[0])
    assert (plan.as_event()["groups"], plan.as_event()["groups_kept"]) == (8, 4)
    with pytest.raises(ValueError, match="group limit"):
        moe.routed_plan(2, 32, 32, 8, groups=5, groups_kept=4)
    with pytest.raises(ValueError, match="group limit"):
        moe.routed_plan(2, 32, 32, 8, groups=8, groups_kept=1)    # 8 of 4 experts


def test_the_seeded_draw_is_made_in_groups_and_one_id_in_sixteen_binds_the_limit():
    a = dataclasses.replace(FULL, vocab_size=4096, experts=128, groups=8, groups_kept=4,
                            top_k=8)
    own, stand_in, five = (np.asarray(x) for x in ling.token_columns(a, ling.seed_key(0)))
    groups = own // 16
    n_groups = np.array([len(set(g)) for g in groups])
    assert 0.04 < five.mean() < 0.09                      # one id in sixteen
    assert set(n_groups[five]) == {5} and set(n_groups[~five]) <= {1, 2, 3, 4}
    assert np.mean(n_groups[~five] >= 3) > 0.9
    assert all(len(set(row)) == 8 for row in own)
    # a five-group id's stand-in lies in its fourth group, beside that group's one
    for row, extra in zip(own[five][:50], stand_in[five][:50]):
        assert extra not in row and extra // 16 == row[6] // 16
    perm = np.asarray(ling.expert_permutations(a, ling.seed_key(1), 3))[2]
    assert sorted(perm) == list(range(128))
    assert all(len(set(perm[g * 16:(g + 1) * 16] // 16)) == 1 for g in range(8))


# ------------------------------------------- latent attention through flash
def _dense(q, k, v):
    t = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v, precision="highest")


@pytest.mark.parametrize("blocks", [(None, None), (128, 128), (128, 64)],
                         ids=["plan", "128x128", "128x64"])
def test_flash_at_192_score_lanes_over_128_value_lanes_is_masked_dense_attention(blocks):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k = (jax.random.normal(ks[i], (1, 2, 256, 192)) for i in range(2))
    v, w = (jax.random.normal(ks[i], (1, 2, 256, 128)) for i in (2, 3))
    fn = lambda q, k, v: flash.flash_attention(q, k, v, block_q=blocks[0], block_k=blocks[1])
    got, want = fn(q, k, v), _dense(q, k, v)
    assert got.shape == (1, 2, 256, 128)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    g_got = jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: jnp.sum(_dense(*a) * w), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_got, g_want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5, err_msg=name)


def test_two_widths_go_under_their_own_names_and_a_window_is_refused():
    sds = lambda d: jax.ShapeDtypeStruct((1, 2, 256, d), jnp.bfloat16)
    fn = lambda q, k, v: jnp.sum(flash.flash_attention(q, k, v).astype(jnp.float32))
    text = str(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1, 2)))(sds(192), sds(192), sds(128)))
    for name in ("saturn_mla_fwd", "saturn_mla_dq", "saturn_mla_dkv"):
        assert name in text
    assert "saturn_flash_" not in text
    plan = flash.flash_plan(8192, 192, d_v=128)
    assert (plan["d_qk"], plan["d_v"]) == (192, 128)
    assert "d_v" not in flash.flash_plan(8192, 128, d_v=128)        # equal widths: as before
    q = jnp.zeros((1, 2, 256, 192))
    with pytest.raises(ValueError, match="window"):
        flash.flash_attention(q, q, jnp.zeros((1, 2, 256, 128)), window=64)
    # under 128 score lanes the forward is the transposed one, at two widths too
    narrow = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 256, 64))
    np.testing.assert_allclose(
        flash.flash_attention(narrow, narrow, narrow[..., :32]),
        _dense(narrow, narrow, narrow[..., :32]), rtol=0, atol=2e-6)
    with pytest.raises(ValueError, match="lanes"):
        flash.flash_attention(q, q[..., :128], q[..., :128])


# ------------------------------------------------------------ the builder
def test_a_swiglu_limit_is_refused_at_build_and_the_kinds_keep_their_rules():
    with pytest.raises(ValueError, match="swiglu_limit"):
        build_ling("ling-test-tiny", swiglu_limit=4.0)
    with pytest.raises(ValueError, match="kda / mla"):
        config_for("ling-test-tiny", rotary=True)
    with pytest.raises(ValueError, match="kda / mla"):
        config_for("ling-test-tiny", kv_latent=0)
    with pytest.raises(ValueError, match="lead_kind"):
        config_for("ling-test-tiny", lead_kind="mla")
    with pytest.raises(ValueError, match="whole periods"):
        config_for("ling-test-tiny", n_layers=8)
    cfg = config_for("ling3-flash")
    assert (cfg.n_layers, cfg.lead_layers, cfg.n_periods, cfg.layer_types.index("mla")) == (
        38, 2, 6, 3)        # published layers 2..7: the MLA layer is the published 5
    assert (cfg.route_groups, cfg.route_groups_kept, cfg.top_k, cfg.routed_experts) == (
        8, 4, 8, 512)
    assert GPT2Config().lead_kind == "full_attention" and GPT2Config().kv_latent == 0
    assert build_ling("ling-test-tiny").hints["seq_parallel"] is False


def test_the_references_programs_compiled_ahead_are_the_ones_it_then_calls():
    """``ling._compile_ahead`` compiles the reference's programs
    side by side, on threads of its own, at the setting the calls run under
    (``highest`` is a thread's own): the forward and the training step that
    follow compile none of them again."""
    from perf.lib.clock import CompileClock

    tokens = np.random.default_rng(7).integers(0, 256, (1, 64), dtype=np.int32)
    arch = dataclasses.replace(ARCH, norm_eps=ARCH.norm_eps * 1.0001)   # programs no test has made
    fns = ling._jitted(arch, None)
    clock = CompileClock()
    ling.seed_key(0)        # (the key's own little programs)
    before = clock.snapshot()
    ling._compile_ahead(arch, None, fns, tokens)
    ahead = clock.since(before)["backend_compiles"]
    assert ahead == 4 + 2 * len(set(arch.kinds)) + 2 * len(set(arch.ffs))    # 12
    with jax.default_matmul_precision("highest"):
        params = ling._unstack(arch, fns["params"](ling.seed_key(SEED)))
        before = clock.snapshot()
        x = fns["embed"](params["top"]["wte"], jnp.asarray(tokens))
        for n in range(arch.n_layers):
            h, out, _ = ling._layer_forward(arch, fns, n, params["layers"][n], x)
            kind, which = ling._sig(arch, n)
            mine, theirs = ling._halves(params["layers"][n])
            fns["ff_back", which](theirs, h, out)
            fns["mixer_back", kind](mine, x, h)
            x = out
        head = {k: params["top"][k] for k in ("ln_f", "lm_head")}
        fns["head"](head, x)
        fns["head_back"](head, x, jnp.asarray(tokens))
    assert clock.since(before)["backend_compiles"] == 1     # the embedding's gather alone
    ling._compile_ahead(arch, None, fns, tokens)             # once a shape
    assert clock.since(before)["backend_compiles"] == 1
