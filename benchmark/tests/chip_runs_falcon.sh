#!/bin/bash
# Chip runs for Falcon-H1-34B's token-model cell, in one process tree (not run by the tests).
#
#   bash benchmark/tests/chip_runs_falcon.sh prepare          # in the git checkout
#   bash benchmark/tests/chip_runs_falcon.sh run <spec> ...   # on a one-chip v5e host
#
# prepare: .bench_archive/plain = the parent commit as it is; .bench_archive/parent = the parent
# with THIS tree's benchmark files laid over it (a new cell's harness on the old program);
# .smoke_archive/tree = `git archive $(git write-tree)` (the committed files alone). All are
# git-ignored; copy them to the chip host with the rest of the repo.
# run: each <spec> in turn, from the checkout it names:
#   <plain|parent|tree>,<cell>,<seed>,<trace>   one benchmark/run.py (the window's result line);
#                                               with STOP_ON_FAIL=1 a tree run that errs ends the call
#   readings,<seed>,<how:how:...>               tests/read_limits_falcon.py from the tree: sound,
#                                               the float8 control and the planted faults
set -u
if [ "$1" = prepare ]; then
  set -e
  cd "$(dirname "$0")/../.."
  rm -rf .bench_archive .smoke_archive/tree
  mkdir -p .bench_archive/plain .bench_archive/parent .smoke_archive/tree
  git archive HEAD | tar -x -C .bench_archive/plain
  git archive HEAD | tar -x -C .bench_archive/parent
  cp BENCHMARK.json .bench_archive/parent/BENCHMARK.json
  cp -r benchmark/. .bench_archive/parent/benchmark/
  git add -A
  git archive "$(git write-tree)" | tar -x -C .smoke_archive/tree
  find .bench_archive .smoke_archive/tree -name __pycache__ -prune -exec rm -rf {} +
  du -sh .bench_archive/plain .bench_archive/parent .smoke_archive/tree
  exit 0
fi
shift
ROOT=$(pwd)
OUT="$ROOT/chiprun_out/falcon"
mkdir -p "$OUT"
show() { grep -h "\"phase\": \"first_call\"\|window_closed\|\"phase\": \"scopes\"\|\"phase\": \"reference\"\|\"phase\": \"gaps\"" "$1" | cut -c1-2500; }
i=0
for spec in "$@"; do
  i=$((i+1)); t1=$(date +%s)
  IFS=, read -r where a b c <<< "$spec"
  if [ "$where" = readings ]; then
    ( cd .smoke_archive/tree && python3 benchmark/tests/read_limits_falcon.py \
        --workload falcon-h1-34b-s1-tune.doc32k-steps --seed "$a" --seconds 20 --trace 0 \
        --hows "${b//:/,}" > "$OUT/$i.readings.out" 2> "$OUT/$i.readings.err" )
    echo "== $i READINGS seed $a rc=$? wall $(( $(date +%s) - t1 )) s"
    cat "$OUT/$i.readings.out"
    show "$OUT/$i.readings.err"
    tail -n 4 "$OUT/$i.readings.err" | cut -c1-800
    continue
  fi
  dir=.smoke_archive/tree
  [ "$where" = tree ] || dir=.bench_archive/$where
  ( cd "$dir" && python3 benchmark/run.py --workload "$a" --seed "$b" --seconds 20 --trace "$c" \
      > "$OUT/$i.$where.out" 2> "$OUT/$i.$where.err" )
  rc=$?
  echo "== $i $where $a seed $b trace $c rc=$rc wall $(( $(date +%s) - t1 )) s"
  tail -c 4000 "$OUT/$i.$where.out"
  show "$OUT/$i.$where.err"
  tail -n 3 "$OUT/$i.$where.err" | cut -c1-800
  # STOP_ON_FAIL=1: a run of the committed tree that ends in an error stops the rest
  if [ -n "${STOP_ON_FAIL:-}" ] && [ "$where" = tree ] && [ "$rc" != 0 ]; then
    echo "== stopping after run $i"; exit 1
  fi
done
