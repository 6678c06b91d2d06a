# PR 41, on a four-chip host (chiprun --chips 4): `lattice-100k.steady-x4` from a
# scratch copy of the tree: tmp/x4 = `git archive $(git write-tree) | tar -x -C
# tmp/x4`, then ONE entry appended to tmp/x4/BENCHMARK.json `workloads` (data
# only; NOT committed; tmp/ is git-ignored and the chip tool copies it):
#   {"name": "lattice-100k.steady-x4", "config": "lattice-100k",
#    "traffic": "steady", "chips": 4, "why": "..."}
# No four-chip host was free in PR 41 (two asks of 720 s): this has NOT run.
# Does it build, is it `correct`, its rounds_per_s, `window_compiles`, and what
# the stage readers return. Two timed runs (the first compiles) and one traced.
#   bash benchmark/tools/calls/call41_x4_rehearsal.sh
out=$PWD/chiprun_out/c41_x4; mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"
cd tmp/x4 || exit 1
one() {  # seed trace
  python3 benchmark/run.py --workload lattice-100k.steady-x4 --seed $1 --seconds 20 --trace $2 \
    > $out/x4.$1.t$2.out 2> $out/x4.$1.t$2.err
  echo "rc=$? x4 $1 trace=$2: $(tail -n 1 $out/x4.$1.t$2.out | cut -c1-3000)"
  grep '^{"workload"' $out/x4.$1.t$2.err | cut -c1-1200
  tail -n 3 $out/x4.$1.t$2.err | cut -c1-400
}
one 4100000501 0
one 4100000502 0
one 4100000503 1
