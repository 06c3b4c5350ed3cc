#!/bin/sh
# Run chip_smoke.py twice in one chip call: the second run shows what the
# persistent compile cache saves (its "compile" seconds against the first's).
#   chiprun --timeout 3000 -- sh tools/chip_smoke_twice.sh
#   chiprun --chips 4 --timeout 3000 -- env RUNS=1 sh tools/chip_smoke_twice.sh --chips 4
# Output and metrics events of each run land under chiprun_out/.
mkdir -p chiprun_out
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"
for run in $(seq 1 "${RUNS:-2}"); do
  python chip_smoke.py "$@" > chiprun_out/run$run.out 2> chiprun_out/run$run.err
  rc=$?
  mkdir -p chiprun_out/run$run.events
  cp chip_smoke_out/*.jsonl chiprun_out/run$run.events/ 2>/dev/null
  echo "== run $run rc=$rc"
  cat chiprun_out/run$run.out
  if [ $rc -ne 0 ]; then tail -c 8000 chiprun_out/run$run.err; exit $rc; fi
done
du -sh .jax_compile_cache 2>/dev/null
exit 0
