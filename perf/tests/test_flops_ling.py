"""``perf/lib/flops_ling.py`` against counts written out by hand, at the tiny
preset and at the published widths of the cell."""

import dataclasses

import pytest

from perf.lib import bench, flops_laguna, flops_ling
from perf.reference import ling

CELL = "ling3-flash-1chip.steady-8k"
KDA, MLA, DENSE, SPARSE = ling.KDA, ling.MLA, ling.DENSE, ling.SPARSE
TINY = ling.Arch(
    vocab_size=256, d_model=64, kinds=(KDA,) * 4 + (MLA,) + (KDA,) * 2,
    ffs=(DENSE,) + (SPARSE,) * 6, n_heads=4, head_dim=16, conv_taps=4, gate_floor=-5.0,
    kv_latent=32, qk_nope=16, qk_rope=8, v_head=16, rope_theta=6e6, d_dense=128,
    experts=16, held=4, first_expert=0, top_k=4, groups=4, groups_kept=2, d_expert=32,
    d_shared=32, routed_scale=2.5, norm_eps=1e-6)


def test_the_tiny_presets_parts_by_hand():
    parts = flops_ling.matmul_params(TINY)
    kda = 4 * 64 * 64 + 2 * 64 * 4 + 64 * 64 + 3 * 4 * 64     # q k v a | beta gate | o | taps
    assert parts["kda_mixers"] == 6 * kda == 6 * 21760
    mla = 64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 64 * 4 + 4 * 16 * 64
    assert parts["mla_mixers"] == mla == 17152
    assert parts["dense_ff"] == 3 * 64 * 128 and parts["router"] == 6 * 64 * 16
    assert parts["shared"] == 6 * 3 * 64 * 32
    assert parts["routed"] == 6 * 3 * 64 * 32 * 4 * 4 / 16      # one expert a token held
    assert parts["head"] == 64 * 256
    rule = flops_ling.rule_flops_per_token_head(16, 16)
    assert rule == 2 * (64 * (3 * 16 + 2 * 16) + 3 * 16 * 16)
    seq = 64
    want = 6 * sum(parts.values()) + 6 * 4 * (24 + 16) * (seq + 1) / 2 + 3 * 4 * rule * 6
    assert flops_ling.required_flops_per_token(TINY, seq) == pytest.approx(want)


def test_the_cells_parts_at_the_published_widths_by_hand():
    a = ling.arch_from_config(bench.load_cell(CELL).config, 8192)
    parts = flops_ling.matmul_params(a)
    kda = 4 * 2560 * 4096 + 2 * 2560 * 32 + 4096 * 2560 + 3 * 4 * 4096      # 52.64 M
    assert kda == 52_641_792 and parts["kda_mixers"] == 6 * kda
    mla = 2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32 + 4096 * 2560
    assert mla == 31_965_184 and parts["mla_mixers"] == mla
    assert parts["dense_ff"] == 3 * 2560 * 6144                             # 47.19 M
    assert parts["router"] == 6 * 2560 * 512 and parts["shared"] == 6 * 3 * 2560 * 768
    # 8 of 512 chosen, 8 held: an eighth of an expert a token and layer
    assert parts["routed"] == pytest.approx(6 * 3 * 2560 * 768 / 8)
    assert parts["head"] == 2560 * 19712
    seq = 8192
    rule = flops_ling.rule_flops_per_token_head(128, 128)
    assert rule == 2 * (64 * 5 * 128 + 3 * 128 * 128) == 180224
    attention = 6 * 32 * 320 * (seq + 1) / 2                                # 0.25 GFLOP
    per_token = flops_ling.required_flops_per_token(a, seq)
    assert per_token == pytest.approx(6 * sum(parts.values()) + attention + 3 * 32 * rule * 6)
    # GFLOP a token: the KDA mixers 1.90 + the rule 0.10, the MLA layer 0.19 + 0.25,
    # the dense layer 0.28, router + shared 0.26, routed 0.03, the head 0.30
    assert 6 * parts["kda_mixers"] / 1e9 == pytest.approx(1.895, abs=0.005)
    assert 3 * 32 * rule * 6 / 1e9 == pytest.approx(0.104, abs=0.002)
    assert attention / 1e9 == pytest.approx(0.252, abs=0.002)
    assert 3.2e9 < per_token < 3.5e9
    # the mixers are most of a KDA block: 6 x 52.6 M + the rule against the
    # routed feed-forward's router, shared expert and eighth of a routed one
    block_ff = 6 * (2560 * 512 + 3 * 2560 * 768 + 3 * 2560 * 768 / 8)
    assert (6 * kda + 3 * 32 * rule) / (6 * kda + 3 * 32 * rule + block_ff) > 0.85


def test_a_call_of_each_latent_attention_kernel_by_hand():
    a = ling.arch_from_config(bench.load_cell(CELL).config, 8192)
    seq = 8192
    assert flops_ling.rule_flops_per_token_head(128, 128) == 180224
    pairs = 32 * seq * (seq + 1) / 2
    fwd = flops_ling.mla_flash_call("saturn_mla_fwd", a, 1, seq)
    assert fwd["flops"] == 2 * pairs * (192 + 128)
    assert fwd["bytes"] == 32 * seq * (2 * 192 + 2 * 128) * 2
    dq = flops_ling.mla_flash_call("saturn_mla_dq", a, 1, seq)
    dkv = flops_ling.mla_flash_call("saturn_mla_dkv", a, 1, seq)
    assert dq["flops"] == 2 * pairs * (2 * 192 + 128)
    assert dkv["flops"] == 2 * pairs * (2 * 192 + 2 * 128)
    assert dq["bytes"] == dkv["bytes"] == 32 * seq * (3 * 192 + 3 * 128) * 2
    # what a kernel that padded v to 192 lanes would do is not what is counted
    assert fwd["flops"] < 2 * pairs * 2 * 192
    with pytest.raises(KeyError):
        flops_ling.mla_flash_call("saturn_flash_fwd", a, 1, seq)
    # ``gmm_roofline.ling`` is read by Laguna's reader off the same names
    g = flops_laguna.gmm_call("saturn_gmm_fwd", a, 1024.0)
    assert g["flops"] == 2 * 1024 * 2560 * 768
    assert g["bytes"] == 1024 * (2560 + 768) * 2 + 8 * 2560 * 768 * 2
    half = dataclasses.replace(a, n_heads=16)
    assert flops_ling.mla_flash_call("saturn_mla_fwd", half, 1, seq)["flops"] == fwd["flops"] / 2
