# PR 35: every chip call of the PR, one phase a call.
#   chiprun --timeout 3400 -- bash scripts/calls/pr35.sh <phase>
# explore   the working tree (chips were scarce: one call for all of it):
#           the new cell traced with both parts and the dearest ops, the
#           parent (tmp/parent, the PR's benchmark files laid over it) once,
#           which has to refuse soon, 6 timed runs on distinct seeds, the
#           readings the file states (8 seeds at 12 and 80 segments), the
#           controls at size, 3 seeds each, and every cell the benchmark
#           had, parent against change, one timed pair each
# final     the files git would commit (tmp/final = git archive of the
#           index) against the parent, with the limits the first call's
#           readings set: the refusal, 6 timed runs + 2 traced of the new
#           cell, 4 seeds at 12 and 80 segments, the two controls the first
#           call lacked (D = 3 with D_score under it; a validation queue of
#           8), one timed pair of every old cell and the setup_s the new
#           cell reads after them
out=chiprun_out/pr35; mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"
W=sybil-50k.stepped

run() {  # run <dir> <tag> <cell> <seed> <trace>
  ( cd $1 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 20 --trace $5 ) \
    > $out/$2.$3.$4.t$5.out 2> $out/$2.$3.$4.t$5.err
  echo "rc=$? $2 $3 $4 trace=$5: $(tail -n 1 $out/$2.$3.$4.t$5.out | cut -c1-2600)"
  grep '^{"workload"' $out/$2.$3.$4.t$5.err | cut -c1-900
}
traced() {  # traced <dir> <tag> <cell> <seed>
  ( cd $1 && python3 benchmark/tools/traced.py --workload $3 --seed $4 \
      --readers part_us_attrib,part_us_gater,edge_rows_per_round --top 24 ) \
    > $out/$2.$3.$4.traced.out 2> $out/$2.$3.$4.traced.err
  echo "rc=$? $2 $3 $4 traced: $(tail -n 1 $out/$2.$3.$4.traced.out | cut -c1-3000)"
  grep '^{"us_per_round_by\|^{"op"' $out/$2.$3.$4.traced.err | cut -c1-330
  grep '^{"workload"' $out/$2.$3.$4.traced.err | cut -c1-900
}
readings() {  # readings <dir> <tag> <seeds> <segments> [control]
  ( cd $1 && python3 benchmark/tools/sybil_readings.py --workload $W \
      --seeds $3 --segments $4 ${5:+--control "$5"} ) \
    > $out/$2.readings.out 2> $out/$2.readings.err
  echo "rc=$? readings $2"; tail -n 2 $out/$2.readings.err | cut -c1-300
  cut -c1-1500 $out/$2.readings.out
}
sweep() {  # sweep <dir> <tag> <cell> <seeds> <seconds> <control>
  ( cd $1 && python3 benchmark/tools/sweep.py --workload $3 --seeds $4 \
      --seconds $5 --control "$6" ) > $out/$2.sweep.out 2> $out/$2.sweep.err
  echo "rc=$? sweep $2 $6"; tail -n 2 $out/$2.sweep.err | cut -c1-300
  cut -c1-900 $out/$2.sweep.out
}

parent_refuses() {
  t0=$(date +%s)
  ( cd tmp/parent && timeout 600 python3 benchmark/run.py --workload $W --seed 3500000200 --seconds 20 --trace 0 ) \
    > $out/parent.$W.out 2> $out/parent.$W.err
  echo "rc=$? parent $W after $(( $(date +%s) - t0 )) s: $(tail -n 3 $out/parent.$W.err | cut -c1-700)"
}
old_cells() {  # old_cells <dir of the change> <seed base>
  i=0
  for c in random-100k.stepped random-10k-t8.watched lattice-100k.steady eth2-100k.stepped; do
    i=$(( i + 1 ))
    run tmp/parent parent $c $(( $2 + i )) 0
    run $1 final $c $(( $2 + i )) 0
  done
}

case $1 in
explore)
  traced . change $W 3500000001
  parent_refuses
  for s in 1 2 3 4 5 6; do run . change $W $(( 3500000010 + s )) 0; done
  readings . first 3500000101,3500000102,3500000103,3500000104,3500000105,3500000106,3500000107,3500000108 12,20,80
  readings . w3off 3500000341,3500000342,3500000343 100 '{"program_score": {"mesh_message_deliveries_weight": 0}}'
  sweep . lossy $W 3500000301,3500000302,3500000303 3 '{"chaos_loss_rate": 0.02}'
  sweep . bf16 $W 3500000311,3500000312,3500000313 3 '{"score_dtype": "bfloat16"}'
  sweep . d3 $W 3500000321,3500000322,3500000323 3 '{"program_mesh_params": {"D": 3, "D_lo": 2}}'
  sweep . nogossip $W 3500000331,3500000332,3500000333 3 '{"program_mesh_params": {"D_lazy": 0, "gossip_factor": 0.0}}'
  old_cells . 3500000400
  ;;
final)
  parent_refuses
  for s in 1 2 3 4 5 6; do run tmp/final final $W $(( 3500000200 + s )) 0; done
  traced tmp/final final $W 3500000250
  run tmp/final final $W 3500000251 1
  readings tmp/final final 3500000261,3500000262,3500000263,3500000264 12,80
  sweep tmp/final d3 $W 3500000321,3500000322,3500000323 3 '{"program_mesh_params": {"D": 3, "D_lo": 2, "D_score": 2, "D_out": 1}}'
  sweep tmp/final queue8 $W 3500000351,3500000352,3500000353 3 '{"program_validation_capacity": 8}'
  old_cells tmp/final 3500000500
  run tmp/final final $W 3500000510 0
  ;;
esac
