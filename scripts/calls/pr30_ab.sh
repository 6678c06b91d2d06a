# PR 30: one cell, parent against change, in one call on one machine and one
# cache directory. tmp/parent is `git archive` of the parent commit with this
# PR's BENCHMARK.json, benchmark/ and tests/benchmark_harness/ laid over it,
# as the driver lays them. The change runs from CHANGE_DIR (default: the
# working tree; tmp/final is `git archive $(git write-tree)`, the files git
# would commit). Each side's first run compiles or loads; pairs share a seed
# and the sides alternate.
#   bash scripts/calls/pr30_ab.sh <cell> <seed base> "<side:seed-offset:trace> ..."
set -x
cell=$1; base=$2; out=chiprun_out/pr30_$cell; mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"
for spec in $3; do
  IFS=: read side off tr <<< "$spec"
  seed=$((base+off)); dir=${CHANGE_DIR:-.}; [ $side = parent ] && dir=tmp/parent
  ( cd $dir && python3 benchmark/run.py --workload $cell --seed $seed --seconds 20 --trace $tr ) \
    > $out/$side.$seed.t$tr.out 2> $out/$side.$seed.t$tr.err
  echo "rc=$? $side $seed trace=$tr: $(tail -n 1 $out/$side.$seed.t$tr.out | cut -c1-1500)"
  grep '^{"workload"' $out/$side.$seed.t$tr.err | cut -c1-800
done
