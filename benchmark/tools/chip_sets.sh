#!/bin/sh
# How PR 25's numbers for one cell were taken, in one chip call:
#   chiprun --chips 1 --timeout 3500 -- sh benchmark/tools/chip_sets.sh <cell> <out dir> [seeds-base]
# (optionally the limits' readings over a dozen seeds,) two sets of six runs
# with the same six seeds, then three traced runs on other seeds.
CELL=$1; O=$2; BASE=$3
SECONDS_=$(python3 -c "import json; print(json.load(open('BENCHMARK.json'))['run_seconds'])")
mkdir -p $O
if [ -n "$BASE" ]; then
  S=$BASE
  python3 benchmark/tools/seeds.py --workload $CELL --seconds 12 --controls 6 --control fp8,int8 \
    --seeds $((S+1)),$((S+2)),$((S+3)),$((S+4)),$((S+5)),$((S+6)),$((S+7)),$((S+8)),$((S+9)),$((S+10)),$((S+11)),$((3000000000+S)) \
    --out $O/seeds.$CELL.json 2> $O/seeds.$CELL.err | cut -c1-300
fi
for set in setA setB; do
  for s in 21 22 23 24 25 3000000026; do
    python3 benchmark/run.py --workload $CELL --seed $s --seconds $SECONDS_ --trace 0 \
      > $O/$set.$CELL.$s.json 2> $O/$set.$CELL.$s.err; echo "$set $s rc=$?"
  done
done
for s in 31 32 3000000033; do
  python3 benchmark/run.py --workload $CELL --seed $s --seconds $SECONDS_ --trace 1 \
    > $O/traced.$CELL.$s.json 2> $O/traced.$CELL.$s.err; echo "traced $s rc=$?"
done
tail -c 600 $O/traced.$CELL.3000000033.err
