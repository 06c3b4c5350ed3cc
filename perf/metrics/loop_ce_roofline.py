"""Kernels: the fused head + cross-entropy kernels' share of their roofline
in the looped model's cell: d 2048, an untied 49152-row head, 8192 tokens,
recompute mode (see ``perf/lib/readers.kernel_roofline``)."""

from perf.lib import readers


def read(run):
    return readers.kernel_roofline(run, "saturn_ce_")
