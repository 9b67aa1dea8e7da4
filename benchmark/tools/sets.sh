#!/bin/bash
# The runs a bound is set from: two sets of 6 untraced runs of one cell,
# the same six seeds in both sets, then (with a fourth argument) one
# traced run. Run it in ONE chip call, from the root of the checkout:
#
#   chiprun --timeout 3000 -- bash benchmark/tools/sets.sh <cell> <seconds> <outdir> [trace]
#
# Each run's stdout goes to chiprun_out/<outdir>/<set>.<seed>.out; read
# the spreads with `python3 benchmark/tools/spread.py chiprun_out/<outdir>`.
W=$1; S=$2; OUT=chiprun_out/$3; mkdir -p "$OUT"
SEEDS="2147483659 3000000019 17 4294967311 123456789 2718281828"
for set in A B; do
  for seed in $SEEDS; do
    python3 benchmark/run.py --workload "$W" --seed $seed --seconds "$S" --trace 0 \
      > "$OUT/$set.$seed.out" 2> "$OUT/$set.$seed.err"
    echo "$set $seed rc=$? $(tail -1 "$OUT/$set.$seed.out" | cut -c1-400)"
  done
done
if [ -n "$4" ]; then
  python3 benchmark/run.py --workload "$W" --seed 99991 --seconds "$S" --trace 1 \
    > "$OUT/T.out" 2> "$OUT/T.err"
  echo "T rc=$? $(tail -1 "$OUT/T.out" | cut -c1-3000)"
  tail -3 "$OUT/T.err"
fi
