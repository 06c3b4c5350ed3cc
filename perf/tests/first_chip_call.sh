#!/bin/sh
# One chip call for a new cell's first readings (not part of the benchmark's
# runs): a traced run of the cell (with its priming child, whose phases print
# the host's memory), then an untraced one. Usage:
#   chiprun --timeout 3300 -- sh perf/tests/first_chip_call.sh <cell> <seed>
cell=$1; seed=$2
t0=$(date +%s)
python3 perf/run.py --workload "$cell" --seed "$seed" --seconds 30 --trace 1
echo "first_chip_call: the traced run (with its priming child) ended with $? after $(( $(date +%s) - t0 )) s"
t0=$(date +%s)
python3 perf/run.py --workload "$cell" --seed $((seed + 7919)) --seconds 30 --trace 0
echo "first_chip_call: the untraced run ended with $? after $(( $(date +%s) - t0 )) s"
