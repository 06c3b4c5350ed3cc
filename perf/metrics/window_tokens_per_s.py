"""Engine, per layer: the window's rate where it is no end-to-end metric.
``train_tokens_per_s``'s quantity and arithmetic under another name, for a
cell whose runs spread too widely for a bound of 0.1 (PR 32: the four-chip
cell, whose window is 3/4 the four-rank checkpoint's writer). Read in a traced
run, so the profiler is on around it."""

from perf.metrics import train_tokens_per_s

read = train_tokens_per_s.read
