# PR 41, call 3 (call 2 was cut at its time limit before the sets): timed runs
# on seeds of their own and one more traced run of each cell, the cells with
# the fewest sound seeds so far first; random-100k.stepped, the cell whose runs
# spread widest, gets its two sets of 6 on the same seeds.
#   bash benchmark/tools/calls/call41_3_six_seeds.sh
out=chiprun_out/c41_six; mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"
runs() {  # cell seed-base seeds sets traces
  b=$2; seeds=$((b+1))
  for k in $(seq 2 $3); do seeds=$seeds,$((b+k)); done
  python3 benchmark/tools/sets.py --workload $1 --seeds $seeds --sets $4 --traces $5 \
    > $out/$1.out 2> $out/$1.err
  echo "rc=$? $1: $(grep -c '"correct": true' $out/$1.out) correct"
  grep '"correct": false' $out/$1.out | cut -c1-600
  grep '"metric"' $out/$1.out | cut -c1-300
}
runs eth2-100k.stepped 4100002100 4 1 1
runs sybil-50k.stepped 4100002200 4 1 1
runs random-100k.stepped 4100002600 6 2 2
runs lattice-100k.steady 4100002300 3 1 1
runs random-10k-t8.watched 4100002400 3 1 0
runs churn-100k.stepped 4100002500 3 1 1
