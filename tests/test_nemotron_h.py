"""The Nemotron-H stack (``build_nemotron_h``) at ``nemotron-test-tiny`` on
the CPU, in float32, against the plain reference
``perf/reference/nemotron_h.py`` from the same seeded weights: logits, loss
and gradients for the twin and the kernel grid point (kernels in interpret
mode); the head shares and the expert shares add up to the uncut layers; the
routed layer drops nothing, and its selection bias enters the choice only.
(``tests/test_nemotron_h_techniques.py`` has ``search`` -> ``orchestrate``
and every technique; ``tests/test_ssd.py`` the recurrence itself.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import nemotron_h as nh
from saturn_tpu.models.gpt2 import GPT2Config, build_nemotron_h, config_for
from saturn_tpu.ops import moe

SEED, SEQ = 3, 64
KINDS = {"latent_moe": 5, "mamba2": 5, "attention_only": 1}
#: the uncut tiny model, and the quarter of its heads the tiny cell holds
FULL = nh.Arch(
    vocab_size=256, d_model=64, kinds=(nh.MOE, nh.MAMBA) * 5 + (nh.ATTENTION,),
    n_heads=8, n_kv_heads=2, head_dim=16, ssm_heads=16, ssm_groups=8,
    ssm_head_dim=8, ssm_state=16, conv_taps=4, chunk=16, experts=12, held=12,
    first_expert=0, top_k=3, d_latent=32, d_expert=48, d_shared=96,
    routed_scale=5.0, norm_eps=1e-5)
ARCH = dataclasses.replace(FULL, n_heads=2, n_kv_heads=1, ssm_heads=4, ssm_groups=2,
                           held=4)
HELD = {"held_heads": 2}


def _tokens(batch=2, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ), 0, 256)


def _rel(a, b):
    return float(np.linalg.norm(np.asarray(a) - np.asarray(b))
                 / max(float(np.linalg.norm(np.asarray(b))), 1e-30))


# --------------------------------------------------- program and reference
@pytest.fixture(scope="module")
def reference_side():
    key = nh.seed_key(SEED)
    tokens = _tokens()
    with jax.default_matmul_precision("highest"):
        params = nh.seeded_params(ARCH, key)
        logits = nh.forward(ARCH, params, tokens)
        loss, grads = jax.value_and_grad(lambda p: nh.loss_fn(ARCH, p, tokens))(params)
    return tokens, logits, float(loss), nh.flat(nh.program_layout(ARCH, grads))


@pytest.mark.parametrize("attention", ["dense", "flash"], ids=["twin", "kernels"])
def test_logits_loss_and_gradients_are_the_references(reference_side, attention):
    tokens, ref_logits, ref_loss, ref_grads = reference_side
    spec = build_nemotron_h("nemotron-test-tiny", dtype=jnp.float32,
                            attention=attention, **HELD)
    assert spec.stack_kinds == KINDS and spec.stack_layers == 11
    params = nh.program_params(ARCH, nh.seed_key(SEED))
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(
        spec.init_fn(jax.random.PRNGKey(0)))
    with jax.default_matmul_precision("highest"):
        logits = spec.apply_fn(params, tokens)
        loss, grads = jax.value_and_grad(spec.fused_loss_fn)(params, tokens)
    assert _rel(logits, ref_logits) < 2e-6
    assert abs(float(loss) - ref_loss) < 2e-6 * ref_loss
    grads = nh.flat(grads)
    assert set(grads) == set(ref_grads)
    for leaf, want in ref_grads.items():
        if leaf.endswith("router_bias"):     # in the choice only: exactly zero
            assert not np.any(np.asarray(grads[leaf])) and not np.any(np.asarray(want))
            continue
        assert _rel(grads[leaf], want) < 2e-5, leaf


def test_the_kernel_grid_point_traces_the_kernels_and_says_its_plans():
    from saturn_tpu.ops import plans as op_plans

    spec = build_nemotron_h("nemotron-test-tiny", attention="flash", remat=True, **HELD)
    shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    with op_plans.traced() as got:
        text = str(jax.make_jaxpr(jax.grad(spec.fused_loss_fn))(shapes, _tokens()))
    ssd_plans, moe_plans = got["ssd"], got["moe"]
    for kernel in ("saturn_ssd_fwd", "saturn_gmm_fwd", "saturn_gmm_dw", "saturn_flash_fwd"):
        assert kernel in text, kernel
    plan = ssd_plans[0]
    assert (plan.impl, plan.chunk, plan.heads, plan.groups, plan.heads_published,
            plan.groups_published, plan.head_dim, plan.state) == (
                "kernel", 16, 4, 2, 16, 8, 8, 16)
    assert plan.state_bytes_kept == (SEQ // 16) * 2 * 4 * 8 * 16 * 4
    event = moe_plans[0].as_event()
    assert (event["act"], event["latent"], event["bias"], event["top_k"],
            event["experts"], event["held"]) == ("relu2", 32, True, 3, 12, 4)


# ------------------------------------------------------- the shares add up
def _normed_rows(seed=5):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, SEQ, FULL.d_model))


def _layer(arch, n, key=SEED):
    return nh._layer_weights(arch, nh.seeded_params(arch, nh.seed_key(key)), n)


def test_the_four_head_shares_of_a_mamba_layer_add_up_to_the_uncut_layer():
    """Two groups a share: the gated norm is over a group's lanes, so a share
    on group boundaries computes exactly its heads' part."""
    y, p = _normed_rows(), _layer(FULL, 1)
    H, G, P, N = FULL.ssm_heads, FULL.ssm_groups, FULL.ssm_head_dim, FULL.ssm_state
    inner, bc = H * P, G * N
    with jax.default_matmul_precision("highest"):
        whole = nh.mamba_mixer(FULL, nh._plain_mm, p, y)
        total = 0.0
        for n in range(4):
            hs = np.arange(n * H // 4, (n + 1) * H // 4)
            gs = np.arange(n * G // 4, (n + 1) * G // 4)
            lanes = (hs[:, None] * P + np.arange(P)).ravel()
            group_lanes = (gs[:, None] * N + np.arange(N)).ravel()
            conv = np.concatenate([lanes, inner + group_lanes, inner + bc + group_lanes])
            cols = np.concatenate([lanes, inner + conv, 2 * inner + 2 * bc + hs])
            share = {"in_proj": {"kernel": p["in_proj"]["kernel"][:, cols]},
                     "conv_w": p["conv_w"][:, conv], "conv_b": p["conv_b"][conv],
                     "A_log": p["A_log"][hs], "dt_bias": p["dt_bias"][hs], "D": p["D"][hs],
                     "o_norm": p["o_norm"][lanes],
                     "out_proj": {"kernel": p["out_proj"]["kernel"][lanes]}}
            total = total + nh.mamba_mixer(ARCH, nh._plain_mm, share, y)
    assert _rel(total, whole) < 1e-5
    # a share that cuts a group in two would not: the norm's statistic differs
    with pytest.raises(ValueError, match="group boundaries"):
        config_for("nemotron-test-tiny", held_heads=1, ssm_groups=4)


def test_the_four_head_shares_of_the_attention_layer_add_up_to_the_uncut_layer():
    """Share n holds q heads 2n, 2n + 1 and k/v head n // 2."""
    y, p = _normed_rows(), _layer(FULL, 10)
    hd = FULL.head_dim
    with jax.default_matmul_precision("highest"):
        whole = nh.attention_mixer(FULL, nh._plain_mm, p, y)
        total = 0.0
        for n in range(4):
            q = slice(2 * n * hd, (2 * n + 2) * hd)
            kv = slice((n // 2) * hd, (n // 2 + 1) * hd)
            share = {"q": {"kernel": p["q"]["kernel"][:, q]},
                     "k": {"kernel": p["k"]["kernel"][:, kv]},
                     "v": {"kernel": p["v"]["kernel"][:, kv]},
                     "attn_out": {"kernel": p["attn_out"]["kernel"][q]}}
            total = total + nh.attention_mixer(ARCH, nh._plain_mm, share, y)
    assert _rel(total, whole) < 1e-5


def test_the_expert_shares_through_the_up_projection_and_the_shared_expert_once_add_up():
    y, p = _normed_rows(), _layer(FULL, 0)
    with jax.default_matmul_precision("highest"):
        whole = nh.latent_moe_mixer(FULL, nh._plain_mm, p, y)
        total = 0.0
        for n in range(FULL.experts // ARCH.held):
            es = slice(n * ARCH.held, (n + 1) * ARCH.held)
            share = dict(p, we_up=p["we_up"][es], we_down=p["we_down"][es])
            total = total + nh.latent_moe_mixer(ARCH, nh._plain_mm, share, y,
                                                first_expert=n * ARCH.held, shared=n == 0)
        chosen, weights = nh.routing_of(FULL, p, y)
    assert _rel(total, whole) < 1e-5
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), FULL.routed_scale, rtol=1e-5)
    assert chosen.shape[-1] == FULL.top_k


@pytest.mark.parametrize("n_heads, n_kv, held, kv_held", [
    (32, 2, 16, 1), (32, 2, 8, 1), (16, 2, 8, 1), (16, 2, 16, 2), (8, 2, 2, 1)])
def test_held_heads_with_grouped_kv(n_heads, n_kv, held, kv_held):
    """16 and 8 q heads a k/v head: the held q heads read the k/v heads of
    their groups; the program's layer is the reference's at the held counts."""
    shape = dict(n_heads=n_heads, n_kv_heads=n_kv, held_heads=held, head_width=8,
                 ssm_heads=n_heads, ssm_groups=n_heads // 2,
                 layer_types=("attention_only",), n_layers=1, routed_experts=0)
    cfg = config_for("nemotron-test-tiny", **shape)
    assert (cfg.heads_held, cfg.kv_heads_held) == (held, kv_held)
    arch = dataclasses.replace(FULL, kinds=(nh.ATTENTION,), n_heads=held,
                               n_kv_heads=kv_held, head_dim=8)
    params = nh.program_params(arch, nh.seed_key(SEED))
    tokens = _tokens(batch=1)
    spec = build_nemotron_h("nemotron-test-tiny", dtype=jnp.float32, attention="dense",
                            **shape)
    with jax.default_matmul_precision("highest"):
        got = spec.apply_fn(params, tokens)
        want = nh.forward(arch, nh.seeded_params(arch, nh.seed_key(SEED)), tokens)
    assert params["blocks"]["l0"]["qkv"]["kernel"].shape == (1, 64, (held + 2 * kv_held) * 8)
    assert _rel(got, want) < 2e-6


def test_a_share_that_is_neither_whole_groups_nor_a_part_of_one_is_refused():
    with pytest.raises(ValueError, match="whole"):
        GPT2Config(n_heads=32, n_kv_heads=2, held_heads=24, d_model=64)   # 1.5 groups
    with pytest.raises(ValueError, match="whole"):
        GPT2Config(n_heads=32, n_kv_heads=2, held_heads=6, d_model=64)    # 16 % 6


# ------------------------------------------------------- the routed layer
def _routed_inputs(tokens=96, d=32, experts=16, held=4, latent=16, ff=24, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {"y": jax.random.normal(ks[0], (tokens, d)),
            "router": 0.3 * jax.random.normal(ks[1], (d, experts)),
            "bias": 0.01 * jax.random.normal(ks[2], (experts,)),
            "w_down": 0.3 * jax.random.normal(ks[3], (d, latent)),
            "we_up": 0.3 * jax.random.normal(ks[4], (held, latent, ff)),
            "we_down": 0.3 * jax.random.normal(ks[5], (held, ff, latent))}


def _routed_reference(x, top_k, scale=5.0, first=0, on_weights=False):
    """Every held expert over every token's latent row, under the mask."""
    scores = jax.nn.sigmoid(x["y"] @ x["router"])
    _, chosen = jax.lax.top_k(scores + x["bias"], top_k)
    top = jnp.take_along_axis(scores + x["bias"] if on_weights else scores, chosen, axis=-1)
    weights = scale * top / jnp.sum(top, axis=-1, keepdims=True)
    u = x["y"] @ x["w_down"]
    out = 0.0
    for e in range(x["we_up"].shape[0]):
        m = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        out = out + (jnp.square(jax.nn.relu(u @ x["we_up"][e])) @ x["we_down"][e]) * m[:, None]
    return out, chosen


def _routed(x, top_k, impl="xla", buffer=None, bias=True):
    held, latent = x["we_up"].shape[0], x["w_down"].shape[1]
    plan = moe.routed_plan(x["y"].shape[0], x["router"].shape[1], held, top_k, impl=impl,
                           buffer=buffer, act="relu2", latent=latent, bias=bias)
    return moe.routed_experts(
        x["y"], x["router"], None, x["we_up"], x["we_down"], plan=plan, scale=5.0,
        dtype=jnp.float32, bias=x["bias"] if bias else None, latent=x["y"] @ x["w_down"])


@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_relu2_experts_on_latent_rows_under_a_selection_bias(impl):
    x = _routed_inputs()
    with jax.default_matmul_precision("highest"):
        want, chosen = _routed_reference(x, 6)
        got, stats = _routed(x, 6, impl=impl, buffer=100.0)
        g_got = jax.grad(lambda x: jnp.sum(jnp.sin(_routed(x, 6, impl=impl, buffer=100.0)[0])))(x)
        g_want = jax.grad(lambda x: jnp.sum(jnp.sin(_routed_reference(x, 6)[0])))(x)
    assert got.shape == (96, 16) and _rel(got, want) < 1e-5
    np.testing.assert_array_equal(np.sort(stats["chosen"], -1), np.sort(chosen, -1))
    assert int(stats["second_path"]) == 0
    for leaf in ("y", "router", "w_down", "we_up", "we_down"):
        assert _rel(g_got[leaf], g_want[leaf]) < 2e-5, leaf
    assert not np.any(np.asarray(g_got["bias"]))        # exactly zero


def test_top_k_drops_nothing_when_every_token_goes_to_one_held_expert():
    """A router made to send every token to held expert 0 overflows the row
    buffer (twice the mean): the exact second path computes every pair."""
    x = _routed_inputs(tokens=128, experts=64)
    x["router"] = x["router"].at[:, 0].set(0.0)
    x["bias"] = jnp.zeros_like(x["bias"]).at[0].set(10.0)     # the choice only
    with jax.default_matmul_precision("highest"):
        want, chosen = _routed_reference(x, 6)
        got, stats = _routed(x, 6)
    assert bool(jnp.all(jnp.any(chosen == 0, axis=-1)))
    assert int(stats["second_path"]) == 1 and int(stats["rows_max"]) == 128
    assert int(stats["pairs_held"]) == int(jnp.sum(chosen < 4)) >= 128
    assert _rel(got, want) < 1e-5


def test_the_selection_bias_changes_the_choice_and_not_the_weights():
    x = _routed_inputs()
    tilted = dict(x, bias=x["bias"].at[3].set(5.0).at[12].set(5.0))
    with jax.default_matmul_precision("highest"):
        _, plain = _routed(x, 6, buffer=100.0)
        out, stats = _routed(tilted, 6, buffer=100.0)
        want, _ = _routed_reference(tilted, 6)
        wrong, _ = _routed_reference(tilted, 6, on_weights=True)
    chosen = np.asarray(stats["chosen"])
    assert np.all((chosen == 3).any(-1)) and np.all((chosen == 12).any(-1))
    assert not np.array_equal(np.sort(chosen, -1), np.sort(plain["chosen"], -1))
    assert _rel(out, want) < 1e-5          # weights from the scores alone
    assert _rel(out, wrong) > 0.05         # a bias in the weights is another result


def test_a_plan_for_another_layer_is_refused():
    x = _routed_inputs()
    plan = moe.routed_plan(96, 16, 4, 6)            # SwiGLU, no latent, no bias
    with pytest.raises(ValueError, match="plan"):
        moe.routed_experts(x["y"], x["router"], None, x["we_up"], x["we_down"],
                           plan=plan, latent=x["y"] @ x["w_down"])
    with pytest.raises(ValueError, match="act"):
        moe.routed_plan(96, 16, 4, 6, act="gelu")


@pytest.mark.parametrize("p, q, want", [(2688, 1024, 384), (1024, 2688, 128),
                                        (2048, 512, 1024), (512, 2048, 256)])
def test_the_table_gradients_blocks_keep_to_whole_lanes(p, q, want, monkeypatch):
    """2688 = 21 x 128 does not halve to a multiple of 128; Laguna's widths
    keep the blocks they had."""
    seen = {}

    def fake_call(kernel, grid_spec, **kw):
        seen["block"] = grid_spec.in_specs[0].block_shape
        raise StopIteration

    monkeypatch.setattr(moe.pl, "pallas_call", fake_call)
    with pytest.raises(StopIteration):
        moe._gmm_dw_call(jnp.zeros((256, p)), jnp.zeros((256, q)),
                         jnp.zeros((2,), jnp.int32), jnp.ones((1,), jnp.int32), 2,
                         row_tile=128)
    assert seen["block"] == (128, want)
