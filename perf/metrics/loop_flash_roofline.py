"""Kernels: the flash-attention kernels' share of their roofline in the looped
model's cell: head 128 at seq 4096, called passes x layers times a step (see
``perf/lib/readers.kernel_roofline``)."""

from perf.lib import readers


def read(run):
    return readers.kernel_roofline(run, "saturn_flash_")
