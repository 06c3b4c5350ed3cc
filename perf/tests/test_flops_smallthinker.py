"""``perf/lib/flops_smallthinker.py`` against counts written out by hand, at
the tiny preset and at the published widths of the cell; and the kernels'
counts (``flops_laguna.attn_call`` / ``gmm_call``, read off this model's
``Arch``) at a window of half the sequence and at these rows: no call's
operations exceed what the products hold."""

import pytest

from perf.lib import bench, flops_laguna, flops_smallthinker
from perf.reference import smallthinker as st

CELL = "smallthinker-21b-1chip.steady-8k"
FULL, SLIDING = st.FULL, st.SLIDING
TINY = st.Arch(vocab_size=256, d_model=64, kinds=(FULL, SLIDING, SLIDING, SLIDING),
               rotated=(False, True, True, True), heads=(14,) * 4, n_kv_heads=2,
               head_dim=16, window=32, experts=16, held=4, first_expert=0, top_k=4,
               d_expert=32, rope_theta=1.5e6, norm_eps=1e-6)


def test_the_tiny_presets_parts_by_hand():
    parts = flops_smallthinker.matmul_params(TINY)
    mixer = 64 * 224 + 2 * 64 * 32 + 224 * 64            # q | k v | o
    assert parts == {"mixers": 4 * mixer, "router": 4 * 64 * 16,
                     "routed": 4 * 3 * 64 * 32 * 4 * 4 / 16,      # one expert a token held
                     "head": 64 * 256}
    seq = 64
    window_keys = (32 * 33 / 2 + (seq - 32) * 32) / seq          # mean_i min(i + 1, 32)
    attention = 12 * 14 * 16 * ((seq + 1) / 2 + 3 * window_keys)
    assert flops_smallthinker.required_flops_per_token(TINY, seq) == pytest.approx(
        6 * sum(parts.values()) + attention)


def test_the_cells_parts_at_the_published_widths_by_hand():
    a = st.arch_from_config(bench.load_cell(CELL).config, 8192)
    parts = flops_smallthinker.matmul_params(a)
    mixer = 2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560           # 20.97 M
    assert mixer == 20_971_520 and parts["mixers"] == 4 * mixer
    assert parts["router"] == 4 * 2560 * 64                       # 0.16 M a layer
    # 6 of 64 chosen, 16 held: one and a half experts a token and layer
    assert parts["routed"] == pytest.approx(4 * 1.5 * 3 * 2560 * 768)
    assert parts["head"] == 2560 * 19072
    seq = 8192
    window_keys = (4096 * 4097 / 2 + (seq - 4096) * 4096) / seq  # 3072.25
    assert window_keys == flops_laguna.reach(seq, 4096) / seq == 3072.25
    attention = 12 * 28 * 128 * ((seq + 1) / 2 + 3 * window_keys)
    per_token = flops_smallthinker.required_flops_per_token(a, seq)
    assert per_token == pytest.approx(6 * sum(parts.values()) + attention)
    # GFLOP a token: the projections 0.503, the router 0.004, the held experts
    # 0.212, the head 0.293, attention 0.573 (the full layer 0.176, the three
    # sliding ones 0.396): the issue's 1.585
    assert 6 * parts["mixers"] / 1e9 == pytest.approx(0.503, abs=0.001)
    assert 6 * parts["routed"] / 1e9 == pytest.approx(0.212, abs=0.001)
    assert 6 * parts["head"] / 1e9 == pytest.approx(0.293, abs=0.001)
    assert attention / 1e9 == pytest.approx(0.573, abs=0.001)
    assert per_token / 1e9 == pytest.approx(1.585, abs=0.002)
    assert 0.35 < attention / per_token < 0.37 and 0.13 < 6 * parts["routed"] / per_token < 0.14


def test_a_call_of_each_kernel_at_this_models_shapes_by_hand():
    a = st.arch_from_config(bench.load_cell(CELL).config, 8192)
    seq, batch = 8192, 4
    full = flops_laguna.attn_call("saturn_flash_fwd", a, batch, seq)
    window = flops_laguna.attn_call("saturn_swa_fwd", a, batch, seq)
    assert (full["kind"], window["kind"]) == (FULL, SLIDING)
    assert full["flops"] == 2 * 2 * batch * 28 * 128 * seq * (seq + 1) / 2
    # a window of half the sequence: the first 4096 queries see no cut, the
    # rest 4096 keys each: three quarters of the causal half, not a half
    assert window["flops"] == 2 * 2 * batch * 28 * 128 * (4096 * 4097 / 2 + 4096 * 4096)
    assert window["flops"] / full["flops"] == pytest.approx(0.75, abs=1e-3)
    assert full["bytes"] == window["bytes"] == (2 * 28 + 2 * 4) * batch * seq * 128 * 2
    dkv = flops_laguna.attn_call("saturn_swa_dkv", a, batch, seq)
    assert dkv["flops"] == 2 * window["flops"]
    assert dkv["bytes"] == (2 * 28 + 4 * 4) * batch * seq * 128 * 2
    # a grouped product over the rows really routed: no more operations than
    # the rows hold, at any rows (a share over 100 % would be a count too high)
    for rows in (1536.0 * 16, 3072.0 * 16, 62720.0):
        g = flops_laguna.gmm_call("saturn_gmm_fwd", a, rows)
        assert g["flops"] == 2 * rows * 2560 * 768
        assert g["bytes"] == rows * (2560 + 768) * 2 + 16 * 2560 * 768 * 2
    assert flops_laguna.gmm_call("saturn_gmm_dw", a, 49152.0)["bytes"] == \
        49152 * (2560 + 768) * 2 + 16 * 2560 * 768 * 4
