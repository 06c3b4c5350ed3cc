"""``perf/lib/flops_lfm2.py`` against counts written out by hand, at the tiny
preset and at the published widths of the cell (1.30 GFLOP a token at seq
8192); and the kernels' counts (``flops.flash_call``, ``flops_laguna.attn_call``
/ ``gmm_call``, read off this model's ``Arch``) at these rows: no call's
operations exceed what the products hold."""

import pytest

from perf.lib import bench, flops, flops_laguna, flops_lfm2
from perf.reference import lfm2 as ref

CELL = "lfm2-8b-a1b-1chip.steady-8k"
CONV, FULL = ref.CONV, ref.FULL
TINY = ref.Arch(vocab_size=256, d_model=64, kinds=(CONV, FULL, CONV, CONV, CONV),
                ffs=("dense",) + ("sparse",) * 4, n_heads=8, n_kv_heads=2, head_dim=8,
                taps=3, rope_theta=1e6, d_ff=128, experts=16, held=4, first_expert=0,
                top_k=4, d_expert=32, routed_scale=1.0, route_eps=1e-6, norm_eps=1e-5)


def test_the_tiny_presets_parts_by_hand():
    parts = flops_lfm2.matmul_params(TINY)
    assert parts == {"conv_mixers": 4 * (64 * 192 + 64 * 64),       # W_in | W_out
                     "attention_mixers": 64 * 64 + 2 * 64 * 16 + 64 * 64,   # q | k v | o
                     "dense_ff": 3 * 64 * 128,
                     "router": 4 * 64 * 16,
                     "routed": 4 * 3 * 64 * 32 * 4 * 4 / 16,        # one expert a token held
                     "head": 64 * 256}
    attention = 12 * 8 * 8 * (64 + 1) / 2
    assert flops_lfm2.required_flops_per_token(TINY, 64) == pytest.approx(
        6 * sum(parts.values()) + attention)


def test_the_cells_parts_at_the_published_widths_by_hand():
    a = ref.arch_from_config(bench.load_cell(CELL).config, 8192)
    parts = flops_lfm2.matmul_params(a)
    assert parts["conv_mixers"] == 4 * 4 * 2048 * 2048               # 16.78 M a layer
    assert parts["attention_mixers"] == 2 * 2048 * 2048 + 2 * 2048 * 512 == 10_485_760
    assert parts["dense_ff"] == 3 * 2048 * 7168 == 44_040_192
    assert parts["router"] == 4 * 2048 * 32
    # 4 of 32 chosen, 8 held: one expert a token and layer
    assert parts["routed"] == pytest.approx(4 * 3 * 2048 * 1792) and parts["routed"] == 44_040_192
    assert parts["head"] == 2048 * 16384
    assert sum(parts.values()) == pytest.approx(199.49e6, rel=1e-4)
    attention = 12 * 32 * 64 * (8192 + 1) / 2                        # 4096.5 mean keys
    assert attention == pytest.approx(100.68e6, rel=1e-4)
    per_token = flops_lfm2.required_flops_per_token(a, 8192)
    assert per_token == pytest.approx(6 * sum(parts.values()) + attention)
    # the issue's 1.30 GFLOP a token and its shares: the held experts 20.4 %,
    # the four conv mixers 31.0, the dense SwiGLU 20.4, the head 15.5, the one
    # attention layer (projections and scores) 12.6
    assert per_token / 1e9 == pytest.approx(1.2976, abs=0.0005)
    share = lambda x: 100 * x / per_token      # noqa: E731
    assert share(6 * parts["routed"]) == pytest.approx(20.4, abs=0.05)
    assert share(6 * parts["conv_mixers"]) == pytest.approx(31.0, abs=0.05)
    assert share(6 * parts["dense_ff"]) == pytest.approx(20.4, abs=0.05)
    assert share(6 * parts["head"]) == pytest.approx(15.5, abs=0.05)
    assert share(6 * parts["attention_mixers"] + attention) == pytest.approx(12.6, abs=0.05)
    # a step of 32768 tokens: 42.5 TFLOP required
    assert per_token * 32768 / 1e12 == pytest.approx(42.5, abs=0.05)


def test_a_call_of_each_kernel_at_this_models_shapes_by_hand():
    a = ref.arch_from_config(bench.load_cell(CELL).config, 8192)
    seq, batch = 8192, 4
    # what ``flash_roofline`` reads (``readers.kernel_roofline``): the q heads,
    # the causal half; what ``flops_laguna.attn_call`` reads off the same Arch
    # (k/v-side tensors at the 8 k/v heads) has the same operations
    plain = flops.flash_call("saturn_flash_fwd", batch, a.n_heads, seq, a.head_dim)
    grouped = flops_laguna.attn_call("saturn_flash_fwd", a, batch, seq)
    assert plain["flops"] == 2 * 2 * batch * 32 * 64 * seq * seq / 2
    assert grouped["flops"] == 2 * 2 * batch * 32 * 64 * seq * (seq + 1) / 2
    assert grouped["kind"] == FULL and grouped["bytes"] == (2 * 32 + 2 * 8) * batch * seq * 64 * 2
    assert plain["flops"] / 197e12 > 5 * plain["bytes"] / 819e9      # bound by compute
    # a grouped product over the rows really routed: no more operations than
    # the rows hold, at any rows (a share over 100 % would be a count too high)
    for rows in (4096.0 * 8, 46000.0, 50176.0):
        g = flops_laguna.gmm_call("saturn_gmm_fwd", a, rows)
        assert g["flops"] == 2 * rows * 2048 * 1792
        assert g["bytes"] == rows * (2048 + 1792) * 2 + 8 * 2048 * 1792 * 2
    assert flops_laguna.gmm_call("saturn_gmm_dw", a, 32768.0)["bytes"] == \
        32768 * (2048 + 1792) * 2 + 8 * 2048 * 1792 * 4
    ce = flops.ce_call("saturn_ce_fwd", batch * seq, a.d_model, a.vocab_size)
    assert ce["flops"] == 2 * 32768 * 2048 * 16384
