#!/bin/bash
# The builder's chip runs for the hybrid token-model cell, in ONE call (not run by a check).
#
#   bash benchmark/tests/chip_runs_hybrid.sh prepare          # here, before the call
#   chiprun --timeout 3500 -- bash benchmark/tests/chip_runs_hybrid.sh run \
#       <hows of the readings, or -> <seed of the readings> <seed:trace> ...
#
# prepare: .bench_archive/parent = the parent commit with THIS tree's benchmark files laid over it
# (what the driver does), .smoke_archive/tree = `git archive $(git write-tree)` (the committed files
# alone). Both directories are git-ignored and go to the chip with the copy of the repo.
# run: the parent on the cell (it must fail at once, naming the family); the limits' readings on the
# first seed (tests/read_limits_hybrid.py: sound, the float8 control and the planted faults, traced;
# "-" skips them); one benchmark/run.py a "seed:trace".
set -u
CELL=granite-4.0-h-small-s4-tune.doc32k-steps
if [ "$1" = prepare ]; then
  set -e
  cd "$(dirname "$0")/../.."
  rm -rf .bench_archive/parent .smoke_archive/tree
  mkdir -p .bench_archive/parent .smoke_archive/tree
  git archive HEAD | tar -x -C .bench_archive/parent
  cp BENCHMARK.json .bench_archive/parent/BENCHMARK.json
  cp -r benchmark/. .bench_archive/parent/benchmark/
  git add -A
  git archive "$(git write-tree)" | tar -x -C .smoke_archive/tree
  find .bench_archive/parent .smoke_archive/tree -name __pycache__ -prune -exec rm -rf {} +
  du -sh .bench_archive/parent .smoke_archive/tree
  exit 0
fi
shift
ROOT=$(pwd)
OUT="$ROOT/chiprun_out/hybrid"
mkdir -p "$OUT"
t0=$(date +%s)
( cd .bench_archive/parent && python3 benchmark/run.py --workload $CELL --seed 3200000400 --seconds 20 --trace 0 \
    > "$OUT/parent.out" 2> "$OUT/parent.err"; echo "PARENT rc=$? after $(( $(date +%s) - t0 )) s" )
tail -n 2 "$OUT/parent.err" | cut -c1-300
show() { grep -h "\"phase\": \"first_call\"\|window_closed\|\"phase\": \"scopes\"\|\"phase\": \"reference\"\|\"phase\": \"gaps\"" "$1" | cut -c1-1800; }
HOWS=$1; seed=$2; shift 2
if [ "$HOWS" != - ]; then
  t1=$(date +%s)
  ( cd .smoke_archive/tree && python3 benchmark/tests/read_limits_hybrid.py --workload $CELL --seed "$seed" --seconds 20 --trace 1 \
      --hows "$HOWS" > "$OUT/readings.out" 2> "$OUT/readings.err" )
  echo "READINGS seed $seed rc=$? wall $(( $(date +%s) - t1 )) s"
  cat "$OUT/readings.out"
  show "$OUT/readings.err"
  tail -n 5 "$OUT/readings.err" | cut -c1-600
fi
i=0
for spec in "$@"; do
  i=$((i+1)); t1=$(date +%s)
  seed=${spec%%:*}; tr=${spec##*:}
  ( cd .smoke_archive/tree && python3 benchmark/run.py --workload $CELL --seed "$seed" --seconds 20 --trace "$tr" \
      > "$OUT/run$i.out" 2> "$OUT/run$i.err" )
  echo "RUN $i seed $seed trace $tr rc=$? wall $(( $(date +%s) - t1 )) s"
  tail -c 3500 "$OUT/run$i.out"
  show "$OUT/run$i.err"
  tail -n 3 "$OUT/run$i.err" | cut -c1-600
done
