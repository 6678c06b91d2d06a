# PR 29: one cell, parent against change, in one call on one machine and one
# cache directory. tmp/parent is `git archive` of the parent commit with this
# PR's BENCHMARK.json, benchmark/ and tests/benchmark_harness/ laid over it,
# as the driver lays them. Untraced: 3 pairs on shared seeds, sides
# alternating (setup_s, rounds_per_s, seg_p95_ms, memory peak). Traced: the
# parent first (its new metrics must be left out, nothing may fail), then the
# change straight after it (stage_us_unscoped must stay far under half:
# the parent's executable was not loaded), 3 runs of the change in all.
#   bash benchmark/tools/calls/call16_parent_and_change.sh <cell> <seed base>
set -x
cell=$1; base=$2; out=chiprun_out/c16_$cell; mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"
one() {  # side seed trace
  dir=.; [ $1 = parent ] && dir=tmp/parent
  ( cd $dir && python3 benchmark/run.py --workload $cell --seed $2 --seconds 20 --trace $3 ) \
    > $out/$1.$2.t$3.out 2> $out/$1.$2.t$3.err
  echo "rc=$? $1 $2 trace=$3: $(tail -n 1 $out/$1.$2.t$3.out | cut -c1-1100)"
  grep '^{"workload"' $out/$1.$2.t$3.err | cut -c1-700
}
one parent $((base+1)) 0; one change $((base+1)) 0
one change $((base+2)) 0; one parent $((base+2)) 0
one parent $((base+3)) 0; one change $((base+3)) 0
one parent $((base+11)) 1; one change $((base+11)) 1
one change $((base+12)) 1; one parent $((base+12)) 1
one change $((base+13)) 1
