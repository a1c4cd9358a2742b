#!/bin/bash
# Every cell of BENCHMARK.json end to end on the CPU at gpt2-test size
# (cell 4 on four virtual devices). Counts are real; no device metric is.
set -e
cd "$(dirname "$0")/.."
for w in $(python3 -c "import json; print(' '.join(w['name'] for w in json.load(open('BENCHMARK.json'))['workloads']))"); do
  for trace in 0 1; do
    echo "== $w --trace $trace"
    python3 chipbench/run.py --workload "$w" --seed 2147483659 --seconds 4 \
      --trace $trace --rehearse | tail -n 1
  done
done
