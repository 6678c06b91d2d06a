# PR 41, call 1: (a) the twelve per-layer metrics that came into BENCHMARK.json,
# read by the command itself in the quickest cell, twice (the first may
# compile); (b) mesh_degree_out as repaired, on the seed and length that read 1
# on both commits of PR 38, and one segment on; (c) the D = 3 control at size
# in churn-100k.stepped, 3 seeds; (d) parent, change, change, parent in
# random-100k.stepped and churn-100k.stepped: the result line's end-to-end
# numbers must not move (tmp/p41 = `git archive` of the parent commit).
#   bash benchmark/tools/calls/call41_1_smoke_pairs_controls.sh
set -x
out=chiprun_out/c41_1; mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"
one() {  # side cell seed trace
  dir=.; [ $1 = parent ] && dir=tmp/p41
  ( cd $dir && python3 benchmark/run.py --workload $2 --seed $3 --seconds 20 --trace $4 ) \
    > $out/$1.$2.$3.t$4.out 2> $out/$1.$2.$3.t$4.err
  echo "rc=$? $1 $2 $3 trace=$4: $(tail -n 1 $out/$1.$2.$3.t$4.out | cut -c1-2600)"
  grep '^{"workload"' $out/$1.$2.$3.t$4.err | cut -c1-900
}
one change random-10k-t8.watched 4100000101 1
one change random-10k-t8.watched 4100000102 1
python3 benchmark/tools/churn_readings.py --workload churn-100k.stepped \
  --seeds 3800000214 --segments 102,103 2> $out/b.err | tee $out/b.out | cut -c1-1500
python3 benchmark/tools/churn_readings.py --workload churn-100k.stepped \
  --seeds 4100000201,4100000202,4100000203 --segments 40 \
  --control '{"program_mesh_params": {"D": 3, "D_lo": 2, "D_hi": 4, "D_score": 1, "D_out": 1}}' \
  2> $out/c.err | tee $out/c.out | cut -c1-1500
for cell in random-100k.stepped churn-100k.stepped; do
  one parent $cell 4100000301 0; one change $cell 4100000301 0
  one change $cell 4100000302 0; one parent $cell 4100000302 0
done
grep -il "traceback" $out/*.err | head
