"""``perf/lib/flops.py`` against counts worked by hand for both
configurations."""

import json
import os

import pytest

from perf.lib import bench, flops


def test_gptj_two_layers_by_hand():
    # per layer: q k v o 4 x 4096^2 = 67,108,864; mlp 2 x 4096 x 16384 = 134,217,728
    # head 4096 x 50400 = 206,438,400
    matmul = 2 * (67_108_864 + 134_217_728) + 206_438_400
    assert flops.matmul_params(4096, 2, 16384, 50400) == matmul == 609_091_584
    # 6 N + 12 L S d at S 2048: 3,654,549,504 + 201,326,592
    assert flops.required_flops_per_token(4096, 2, 16384, 50400, 2048) == 3_855_876_096


def test_gpt2_medium_by_hand():
    matmul = 24 * (4 * 1024 * 1024 + 2 * 1024 * 4096) + 1024 * 50304
    assert matmul == 353_501_184
    assert flops.required_flops_per_token(1024, 24, 4096, 50304, 1024) == 2_422_996_992
    assert flops.required_flops_per_token(1024, 24, 4096, 50304, 512) == 2_272_002_048
    # the published 354,823,168 parameters plus the 47 padded vocabulary rows
    assert flops.total_params(1024, 24, 4096, 50304, n_positions=1024) == 354_823_168 + 47 * 1024


def test_flash_and_ce_calls_by_hand():
    # GPT-J cell: batch 4, 16 heads, seq 2048, head 256; one S x S x hd matmul,
    # causal half: 2 * 4 * 16 * 2048^2 * 256 / 2 = 68,719,476,736
    one = 68_719_476_736
    assert flops.flash_call("saturn_flash_fwd", 4, 16, 2048, 256)["flops"] == 2 * one
    assert flops.flash_call("saturn_flash_dq", 4, 16, 2048, 256)["flops"] == 3 * one
    assert flops.flash_call("saturn_flash_dkv", 4, 16, 2048, 256)["flops"] == 4 * one
    tensor = 4 * 16 * 2048 * 256 * 2
    assert flops.flash_call("saturn_flash_fwd", 4, 16, 2048, 256)["bytes"] == 4 * tensor
    # head: 8192 tokens x 4096 x 50400, one matmul a kernel
    mm = 2 * 8192 * 4096 * 50400
    for k in ("saturn_ce_fwd", "saturn_ce_dx", "saturn_ce_dw"):
        assert flops.ce_call(k, 8192, 4096, 50400)["flops"] == mm
    assert flops.ce_call("saturn_ce_fwd", 8192, 4096, 50400)["bytes"] == (
        8192 * 4096 * 2 + 50400 * 4096 * 2)


def test_roofline_share_names_its_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    r = flops.roofline_share(197e12, 1.0, 2.0, peaks)
    assert r["bound"] == "compute" and r["share_pct"] == pytest.approx(50.0)
    r = flops.roofline_share(1.0, 819e9, 4.0, peaks)
    assert r["bound"] == "memory" and r["share_pct"] == pytest.approx(25.0)


def test_configuration_files_give_these_sizes():
    from perf.reference import gpt

    for name, seq, want in (("gptj-6b-1chip", 2048, (4096, 2, 16384, 50400)),
                            ("gpt2-medium", 1024, (1024, 24, 4096, 50304))):
        with open(os.path.join(bench.PERF_DIR, "configs", name + ".json")) as f:
            a = gpt.arch_from_config(json.load(f), seq)
        assert (a.d_model, a.n_layers, a.d_ff, a.vocab_size) == want


def test_unknown_device_kind_is_an_error():
    assert bench.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(bench.BenchmarkError):
        bench.load_peaks("TPU v9 imaginary")
    with pytest.raises(bench.BenchmarkError):
        bench.load_peaks("_source")
