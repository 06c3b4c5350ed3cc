"""The hybrid stack (``build_olmo_hybrid``: periods of three gated-delta-rule
layers and one full-attention layer, one ``nn.scan`` over a period block) at
``olmo-hybrid-test-tiny`` (two periods deep) on the CPU, in float32, against
the plain reference ``perf/reference/olmo_hybrid.py`` (the rule token by
token) from the same seeded weights.

Tolerances. Program and reference are both float32 here and differ by the
order of their roundings only (the chunked form against the token scan, a
fused qkv against three products, flax's norm against the written-out one):
logits to 2e-5 absolute of values around 0.5, gradients to 2e-4 of each leaf's
norm. Through AdamW a rounding difference in a gradient element near zero
becomes a difference of a whole step in that element, so weights after
training are held to 3e-3 of the distance training moved them and losses to
2e-5 relative (``tests/test_ouro.py``'s figures).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import olmo_hybrid as oh
from saturn_tpu.models.gpt2 import build_gpt2, build_olmo_hybrid

PERIOD = ("linear_attention",) * 3 + ("full_attention",)
KINDS = {"linear_attention": 3, "full_attention": 1}
ARCH = oh.Arch(vocab_size=256, d_model=64, kinds=PERIOD * 2, period=4, n_heads=4,
               head_dim=16, key_dim=12, value_dim=24, conv_taps=4, neg_eigval=True,
               d_inner=176, norm_eps=1e-6)
SEQ, SEED, LR = 64, 2_147_483_659, 1e-3
VARIANTS = {"dense": {"attention": "dense"},
            "dense-remat": {"attention": "dense", "remat": True},
            "flash": {"attention": "flash"},          # both Pallas kernels, interpret mode
            "flash-remat": {"attention": "flash", "remat": True}}


def _tokens(seed, batch=2, seq=SEQ):
    return np.random.default_rng(seed).integers(0, 256, size=(batch, seq), dtype=np.int32)


def _spec(**kw):
    return build_olmo_hybrid("olmo-hybrid-test-tiny", dtype=jnp.float32, **kw)


def _weights(arch=ARCH):
    return oh.program_params(arch, oh.seed_key(SEED))


@pytest.fixture(scope="module")
def reference_grads():
    tokens = jnp.asarray(_tokens(1))
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p: oh.loss_fn(ARCH, p, tokens))(
            oh.seeded_params(ARCH, oh.seed_key(SEED)))
    return oh.program_layout(ARCH, grads)


# ------------------------------------------------------------ the model
def test_preset_is_the_published_model_and_the_tree_is_the_references():
    cfg = build_olmo_hybrid("olmo-hybrid-7b").config
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.ff_dim, cfg.vocab_size, cfg.n_layers,
            cfg.lin_key_dim, cfg.lin_value_dim, cfg.lin_conv, cfg.lin_neg_eigval) == (
        3840, 30, 128, 11008, 100352, 32, 96, 192, 4, True)
    assert cfg.layer_types == PERIOD and cfg.n_periods == 8 and cfg.stack_kinds == KINDS
    assert (cfg.norm, cfg.mlp_act, cfg.pre_norm, cfg.sandwich_norm, cfg.use_bias, cfg.tie_head,
            cfg.rotary, cfg.learned_positions, cfg.qk_norm) == (
        "rmsnorm", "swiglu", False, True, False, False, False, False, True)
    spec = _spec()
    want = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    got = jax.eval_shape(_weights)
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    assert jax.tree_util.tree_leaves(want) == jax.tree_util.tree_leaves(got)
    assert "lm_head" in got and "wpe" not in got and "'bias'" not in str(got)
    # what the spec says of its stack: layers of every kind, kinds a period
    assert (spec.stack_layers, spec.stack_kinds, spec.stack_passes) == (8, KINDS, 1)
    assert build_gpt2("test-tiny").stack_kinds is None
    assert spec.hints["seq_parallel"] is False and spec.hints["embed_param_keys"] == ("wte",)


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_logits_agree_with_the_reference(variant):
    tokens = _tokens(1)
    with jax.default_matmul_precision("highest"):
        got = _spec(**VARIANTS[variant]).apply_fn(_weights(), tokens)
    want = oh.logits_of(ARCH, SEED, tokens)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    assert float(jnp.std(want)) > 0.1


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_gradients_agree_with_the_reference(variant, reference_grads):
    spec = _spec(**VARIANTS[variant])
    with jax.default_matmul_precision("highest"):
        got = jax.grad(spec.fused_loss_fn)(_weights(), jnp.asarray(_tokens(1)))
    got, want = oh.flat(got), oh.flat(reference_grads)
    assert set(got) == set(want)
    for leaf in want:
        rel = float(jnp.linalg.norm(got[leaf] - want[leaf]) / jnp.linalg.norm(want[leaf]))
        assert rel < 2e-4, (leaf, rel)


def test_sequence_lengths_that_are_no_multiple_of_the_chunk():
    tokens = _tokens(3, seq=40)          # the tiny preset's chunk is 16
    for variant in ("dense", "flash"):
        kw = dict(VARIANTS[variant], seq_len=40)
        if variant == "flash":
            # flash attention wants whole blocks; the rule's kernel pads
            kw["layer_types"], kw["n_layers"] = ("linear_attention",) * 2, 2
        spec = _spec(**kw)
        arch = ARCH if variant == "dense" else dataclasses.replace(
            ARCH, kinds=("linear_attention",) * 2, period=2)
        with jax.default_matmul_precision("highest"):
            got = spec.apply_fn(_weights(arch), tokens)
        np.testing.assert_allclose(got, oh.logits_of(arch, SEED, tokens), rtol=0, atol=2e-5)


# ------------------------------------------------------------ the share
def _half(arch, params, which):
    """The weights of one half of the heads: the held heads' columns of every
    mixer's input projections (and of what follows them head by head), their
    rows of ``attn_out``; everything outside the mixers whole."""
    H = arch.n_heads // 2

    def cols(x, width):         # (..., n_heads * width) -> the half's lanes
        return x[..., which * H * width:(which + 1) * H * width]

    def layer(kind, p):
        p = jax.tree_util.tree_map(lambda x: x, p)
        if kind == oh.FULL:
            for n in ("q", "k", "v"):
                p[n] = {"kernel": cols(p[n]["kernel"], arch.head_dim)}
            for n in ("q_norm", "k_norm"):
                p[n] = {"scale": cols(p[n]["scale"], arch.head_dim)}
            rows = cols(jnp.swapaxes(p["attn_out"]["kernel"], -1, -2), arch.head_dim)
        else:
            for n, w in (("q", arch.key_dim), ("k", arch.key_dim), ("v", arch.value_dim)):
                p[f"lin_{n}"] = {"kernel": cols(p[f"lin_{n}"]["kernel"], w)}
                p[f"conv_{n}"] = cols(p[f"conv_{n}"], w)
            p["lin_gate"] = {"kernel": cols(p["lin_gate"]["kernel"], arch.value_dim)}
            for n in ("lin_a", "lin_b"):
                p[n] = {"kernel": cols(p[n]["kernel"], 1)}
            p["A_log"], p["dt_bias"] = cols(p["A_log"], 1), cols(p["dt_bias"], 1)
            rows = cols(jnp.swapaxes(p["attn_out"]["kernel"], -1, -2), arch.value_dim)
        p["attn_out"] = {"kernel": jnp.swapaxes(rows, -1, -2)}
        return p

    blocks = {f"l{i}": layer(kind, params["blocks"][f"l{i}"])
              for i, kind in enumerate(arch.kinds[:arch.period])}
    return dict(params, blocks=blocks)


def test_two_halves_of_the_heads_add_up_to_the_uncut_layer():
    """What a share of the heads computes is the held heads' part of each
    mixer's output and nothing else: summed over the two halves -- with what
    both compute alike (the norm on the mixer's output, the SwiGLU branch)
    counted once, and the full layer's q/k-norm statistic handed in whole --
    it is the uncut reference's layer."""
    half = dataclasses.replace(ARCH, n_heads=2)
    params = oh.seeded_params(ARCH, oh.seed_key(SEED))
    halves = [_half(ARCH, params, w) for w in (0, 1)]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, ARCH.d_model))
    mm = lambda a, b: a @ b
    with jax.default_matmul_precision("highest"):
        for n, kind in enumerate(ARCH.kinds[:ARCH.period]):
            whole = oh._layer_weights(ARCH, params["blocks"], n)
            rms = None
            if kind == oh.FULL:
                rms = [jnp.sqrt(jnp.mean(jnp.square(x @ whole[m]["kernel"]), -1, keepdims=True)
                                + ARCH.norm_eps) for m in ("q", "k")]
            parts = [oh.mixer_parts(half, mm, kind, oh._layer_weights(half, h["blocks"], n),
                                    x, qk_rms=rms) for h in halves]
            want = oh.mixer_parts(ARCH, mm, kind, whole, x)
            np.testing.assert_allclose(parts[0] + parts[1], want, rtol=0, atol=2e-6)
            assert float(jnp.std(want)) > 1e-3 and float(jnp.std(parts[0])) > 1e-4
            # and the layer: the shared norm and SwiGLU once, on the sum
            h = x + oh._rms_norm(parts[0] + parts[1], whole["ln_1_post"]["scale"], ARCH.norm_eps)
            f = (jax.nn.silu(h @ whole["mlp_gate"]["kernel"]) * (h @ whole["mlp_in"]["kernel"])) \
                @ whole["mlp_out"]["kernel"]
            got = h + oh._rms_norm(f, whole["ln_2_post"]["scale"], ARCH.norm_eps)
            np.testing.assert_allclose(got, oh._layer(ARCH, mm, kind, whole, x), rtol=0, atol=2e-6)


def test_the_program_told_its_share_computes_that_half():
    """``held_heads`` ties the share to the model: the program holding 2 of
    the 4 heads, handed one half's weights, is the reference of that half
    (whose q/k-norm statistic is over the held lanes, as the program's)."""
    half = dataclasses.replace(ARCH, n_heads=2)
    params = _half(ARCH, oh.seeded_params(ARCH, oh.seed_key(SEED)), 1)
    tokens = _tokens(2)
    spec = _spec(held_heads=2, attention="dense")
    shapes = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    handed = oh.program_layout(half, params)
    assert jax.tree_util.tree_leaves(shapes) == jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: handed))
    assert shapes["blocks"]["l3"]["qkv"]["kernel"].shape == (2, 64, 3 * 2 * 16)
    assert shapes["blocks"]["l0"]["attn_out"]["kernel"].shape == (2, 2 * 24, 64)
    with jax.default_matmul_precision("highest"):
        got = spec.apply_fn(handed, tokens)
        want = oh.forward(half, params, jnp.asarray(tokens))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_new_options_are_validated():
    with pytest.raises(ValueError, match="layer_types"):
        _spec(layer_types=("latent_attention",))      # (a sliding layer is one, since PR 36)
    with pytest.raises(ValueError, match="whole periods"):
        _spec(n_layers=6)
    with pytest.raises(ValueError, match="held_heads"):
        _spec(held_heads=5)
    with pytest.raises(ValueError, match="pre_norm"):
        build_gpt2("test-tiny", pre_norm=False)
    with pytest.raises(ValueError, match="single-program"):
        _spec(seq_axis="seq", seq_axis_size=2)
    with pytest.raises(NotImplementedError):
        _spec(pretrained={})


# ----------------------------------------------------- static analyses
def _chunk_scan_in_layer_scan(layers, tokens, chunk=4, width=32, keep=False):
    """The rule's chunk scan (``ops/gdn.py``'s plain twin) inside a scan over
    layers, as the model has it; ``keep`` also returns every chunk's starting
    state of every layer (what the differentiated forward keeps)."""
    from saturn_tpu.ops import gdn

    def model(q, k, v, g, beta):
        def layer(h, _):
            o, starts = gdn._fwd_xla(q, k, h, g, beta, chunk)
            return o, (starts if keep else None)
        return jax.lax.scan(layer, v, None, length=layers)

    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    args = (sds(2, tokens, width), sds(2, tokens, width), sds(2, tokens, width),
            sds(2, tokens), sds(2, tokens))
    return jax.make_jaxpr(model)(*args), [((), (), ())] * 3 + [((), ())] * 2


def test_shardflow_multiplies_the_chunk_scans_trip_count_by_the_layers():
    from saturn_tpu.analysis.shardflow.interp import Interpreter

    def flops(layers, tokens):
        closed, specs = _chunk_scan_in_layer_scan(layers, tokens)
        interp = Interpreter({"data": 1})
        interp.run(closed, specs)
        return interp.ledger.flops

    one = flops(1, 4)                   # one layer, one chunk
    assert one > 0
    assert flops(1, 64) == 16 * one and flops(3, 4) == 3 * one and flops(3, 64) == 48 * one


def test_memlens_keeps_the_chunk_states_of_both_trip_counts():
    from saturn_tpu.analysis.memlens.liveness import analyze_closed

    def kept(layers, tokens):
        """Bytes the kept chunk states add to the peak."""
        peaks = [analyze_closed(*_chunk_scan_in_layer_scan(layers, tokens, keep=k),
                                {"data": 1}).peak_bytes for k in (False, True)]
        return peaks[1] - peaks[0]

    state = 2 * 32 * 32 * 4             # (N, dk, dv) float32: one chunk's start
    assert kept(6, 64) - kept(3, 64) == 3 * 16 * state    # three more layers of 16 chunks
    assert kept(6, 32) - kept(3, 32) == 3 * 8 * state     # ... of 8 chunks
