"""Kernels: the flash-attention kernels' share of their roofline over the
traced window (see ``perf/lib/readers.kernel_roofline``)."""

from perf.lib import readers


def read(run):
    return readers.kernel_roofline(run, "saturn_flash_")
