"""The trace reducer on a small trace recorded on the chip
(``data/small_trace.xplane.pb``, written by ``record_trace.py`` on a TPU v5
lite, PR 25): three steps of a tiny program that calls the six Pallas kernels,
30 ms of host sleep after each, inside a ``perf.window`` annotation."""

import os

import pytest

from perf.lib import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_trace(TRACE)


def test_window_and_device_planes(reduced):
    assert reduced["n_devices"] == 1
    assert list(reduced["devices"]) == ["/device:TPU:0"]
    # the annotation lasted 96.47 ms (3 x (step + 30 ms sleep))
    assert reduced["window_s"] == pytest.approx(0.09647, rel=1e-3)
    assert ("perf.window",) == tuple(n for n, _, _ in reduced["spans"])


def test_busy_time_is_a_union_not_a_sum(reduced):
    dev = reduced["devices"]["/device:TPU:0"]
    # the first step ran 0.56 ms before the host's annotation opened (device
    # and host clocks differ by that much), so two steps of ~12.2 us lie inside
    assert dev["n_ops"] == 46
    assert reduced["busy_s"] == pytest.approx(2 * 12.2e-6, rel=0.05)
    assert 100 * (1 - reduced["busy_s"] / reduced["window_s"]) > 99.9


def test_kernels_are_found_by_their_own_names(reduced):
    kernels = reduced["devices"]["/device:TPU:0"]["kernels"]
    assert sorted(kernels) == [
        "saturn_ce_dw", "saturn_ce_dx", "saturn_ce_fwd",
        "saturn_flash_dkv", "saturn_flash_dq", "saturn_flash_fwd"]
    assert all(len(calls) == 3 for calls in kernels.values())
    fwd = kernels["saturn_flash_fwd"][0]
    assert fwd[1] == pytest.approx(2463.0, abs=2.0)   # ns, as recorded


def test_ops_are_named_by_instruction_and_sorted(reduced):
    names = [n for n, _ in reduced["ops"]]
    assert "saturn_flash_fwd" in names and not any(" = " in n for n in names)
    seconds = [s for _, s in reduced["ops"]]
    assert seconds == sorted(seconds, reverse=True)


def test_longest_gaps_are_the_sleeps(reduced):
    gaps = reduced["devices"]["/device:TPU:0"]["gaps"]
    longest = sorted((e - s) / 1e9 for s, e in gaps)[-3:]
    assert all(0.030 < g < 0.034 for g in longest)


def test_union_and_gaps_arithmetic():
    assert trace_reduce.union_seconds([(0, 10e9), (5e9, 12e9), (20e9, 21e9)]) == 13.0
    assert trace_reduce.gaps([(2, 4), (6, 8)], 0, 10, keep=2) in (
        [(0, 2), (4, 6)], [(0, 2), (8, 10)], [(4, 6), (8, 10)], [(4, 6), (0, 2)],
        [(8, 10), (0, 2)], [(8, 10), (4, 6)])
    assert trace_reduce._kernel_name("%transpose_jvp_saturn_ce_dw__.3 = f32[8]") == "saturn_ce_dw"
    assert trace_reduce._kernel_name("%fusion.3 = f32[8] fusion()") is None
