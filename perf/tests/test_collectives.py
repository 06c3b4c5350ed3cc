"""The two metrics of what exists only across chips, ``collective_share`` and
``collective_exposed``: on hand-made op lines with known answers, and on the
four-plane trace recorded on four chips (``data/small_trace4.xplane.pb``,
written by ``record_trace4.py`` on a TPU v5 lite 2x2, PR 32)."""

import os

import pytest

from perf.lib import bench, trace_reduce

TRACE4 = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "small_trace4.xplane.pb")


def reader(name):
    return bench.load_reader(bench.load_cell("gptj-6b-4chip.fsdp"), name)


class FakeRun:
    def __init__(self, trace):
        self.trace = trace


def test_collectives_are_known_by_their_instruction():
    kind = trace_reduce.collective_kind
    assert kind("%all-gather-start.3 = (f32[8], f32[32]) all-gather-start(%p)") == "all-gather"
    assert kind("%reduce-scatter.7 = f32[8] reduce-scatter(%x)") == "reduce-scatter"
    assert kind("%all-reduce-done.1 = f32[8] all-reduce-done(%all-reduce-start.1)") == "all-reduce"
    assert kind("%collective-permute-done = bf16[4] collective-permute-done(%s)") == "collective-permute"
    # an operand that is a collective does not make a fusion one
    assert kind("%fusion.386 = bf16[16] fusion(%bitcast.402, %collective-permute-done.5)") is None
    assert kind("%convolution_add_fusion.12 = bf16[8] fusion(%a)") is None
    assert trace_reduce._done_operand(
        "%all-gather-done.3 = f32[32] all-gather-done(%all-gather-start.3), metadata={}"
    ) == "all-gather-start.3"
    assert trace_reduce._done_operand("all-gather-done.3") is None


def test_start_and_done_are_one_stretch_in_flight():
    flight = trace_reduce.collectives_in_flight
    # by operand: the second gather starts before the first is done (prefetch)
    events = [
        ("%all-gather-start.1 = f32[8] all-gather-start(%a)", 0, 1),
        ("%all-gather-start.2 = f32[8] all-gather-start(%b)", 2, 3),
        ("%fusion.1 = f32[8] fusion(%x)", 3, 10),
        ("%all-gather-done.1 = f32[8] all-gather-done(%all-gather-start.1)", 10, 12),
        ("%all-gather-done.2 = f32[8] all-gather-done(%all-gather-start.2)", 20, 21),
        ("%all-reduce.5 = f32[8] all-reduce(%g)", 30, 34),
    ]
    assert sorted(flight(events)) == [(0, 12), (2, 21), (30, 34)]
    # named by the instruction alone: first started, first done, per kind
    bare = [("all-gather-start.1", 0, 1), ("collective-permute-start.9", 1, 2),
            ("all-gather-start.2", 2, 3), ("all-gather-done.1", 10, 12),
            ("collective-permute-done.9", 12, 13), ("all-gather-done.2", 20, 21)]
    assert sorted(flight(bare)) == [(0, 12), (1, 13), (2, 21)]
    # a done whose start fell before the trace stands for itself
    assert flight([("all-reduce-done.4", 5, 9)]) == [(5, 9)]


def _trace(devices, window_s):
    n = len(devices)
    out = {"window_s": window_s, "devices": devices, "n_devices": n,
           "n_collectives": sum(d["n_collectives"] for d in devices.values())}
    for key in ("busy_s", "collective_s", "collective_exposed_s"):
        out[key] = sum(d[key] for d in devices.values()) / n
    return out


def test_share_and_exposed_are_means_over_the_chips(capsys):
    chips = {
        "/device:TPU:0": {"busy_s": 8.0, "collective_s": 2.0,
                          "collective_exposed_s": 1.0, "n_collectives": 5},
        "/device:TPU:1": {"busy_s": 4.0, "collective_s": 3.0,
                          "collective_exposed_s": 2.0, "n_collectives": 5},
    }
    run = FakeRun(_trace(chips, 10.0))
    assert reader("collective_share")(run) == pytest.approx((25.0 + 75.0) / 2)
    assert reader("collective_exposed")(run) == pytest.approx((10.0 + 20.0) / 2)
    assert "/device:TPU:1 (the most)" in capsys.readouterr().out
    # one chip, no collective: nothing to read, the metric is left out
    alone = {"/device:TPU:0": {"busy_s": 8.0, "collective_s": 0.0,
                               "collective_exposed_s": 0.0, "n_collectives": 0}}
    for name in ("collective_share", "collective_exposed"):
        assert reader(name)(FakeRun(_trace(alone, 10.0))) is None
        assert reader(name)(FakeRun(None)) is None


def test_the_one_chip_trace_holds_no_collective():
    one = trace_reduce.reduce_trace(os.path.join(os.path.dirname(TRACE4),
                                                 "small_trace.xplane.pb"))
    assert one["n_collectives"] == 0 and one["collective_s"] == 0.0
    assert one["worst"] == "/device:TPU:0"
    for name in ("collective_share", "collective_exposed"):
        assert reader(name)(FakeRun(one)) is None


@pytest.fixture(scope="module")
def four():
    return trace_reduce.reduce_trace(TRACE4)


def test_the_recorded_trace_has_four_planes_each_reduced_alone(four):
    assert four["n_devices"] == 4
    assert sorted(four["devices"]) == [f"/device:TPU:{i}" for i in range(4)]
    # three steps with 30 ms of sleep after each; two and a half lie inside
    # the annotation (the clocks of host and device differ by half a step)
    assert four["window_s"] == pytest.approx(0.09718, rel=1e-3)
    busy = [d["busy_s"] for d in four["devices"].values()]
    assert all(b == pytest.approx(290e-6, rel=0.03) for b in busy)
    assert four["busy_s"] == pytest.approx(sum(busy) / 4)
    assert four["worst"] == min(four["devices"], key=lambda k: four["devices"][k]["busy_s"])


def test_collectives_of_the_recorded_trace(four, capsys):
    # a step holds one all-gather (a synchronous event of ~37 us) and one
    # collective-permute (start and done joined: ~13 us): 5 stretches a chip
    assert all(d["n_collectives"] == 5 for d in four["devices"].values())
    names = [n for n, _ in four["ops"]]
    assert "all-gather.6" in names and "collective-permute-done" in names
    for d in four["devices"].values():
        assert d["collective_s"] == pytest.approx(114e-6, rel=0.05)
        # nothing else runs on the chip under either: all of it is exposed
        assert d["collective_exposed_s"] == pytest.approx(d["collective_s"], rel=1e-3)
    run = FakeRun(four)
    assert reader("collective_share")(run) == pytest.approx(39.4, abs=1.0)
    assert reader("collective_exposed")(run) == pytest.approx(
        100 * four["collective_exposed_s"] / four["window_s"], rel=1e-6)
    assert "the most" in capsys.readouterr().out
