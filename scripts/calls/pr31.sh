# PR 31: every chip call of the PR, one phase a call (one script, ROADMAP
# queue 3 item 12).
#   chiprun --timeout 3400 -- bash scripts/calls/pr31.sh <phase>
# first   the new cell's first runs: one traced (with the readers that have
#         no line in BENCHMARK.json), one timed
# main    traced with the dearest ops; 8 seeds by birth phase
#         (delivery_by_birth) and 8 in short windows (sweep.py): the
#         readings full_delivery_rounds / mesh_build_rounds are set from;
#         the four controls, 3 seeds each, each must come out not correct;
#         6 timed runs + 2 traced. (Fanout peers as the bool plane.)
# second  after the fanout peers were packed in words and the reference's
#         score membership was repaired: traced, 8 seeds for exactly 17
#         segments (round 144, where 3 of 8 had read score_gap 0.029), 3
#         for 32 (round 264), 6 timed, 1 traced by run.py
# last    the files git would commit (tmp/final = git archive of the
#         index) against the parent (tmp/parent = git archive of the
#         parent commit with this PR's benchmark files laid over it, as
#         the driver lays them): the three old cells, 2 timed pairs and 1
#         traced pair each; the new cell timed and traced, and on the
#         parent (must fail at once); the controls again, 1 seed each;
#         8 seeds' births of rounds 8-55
# confirm mesh_build_rounds set to 24 from those births: 8 seeds for exactly
#         6 segments (56 rounds, a traced run's age) and one traced run
cell=eth2-100k.stepped; out=chiprun_out/pr31; mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"

run() {  # run <dir> <tag> <cell> <seed> <trace>
  ( cd $1 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 20 --trace $5 ) \
    > $out/$2.$3.$4.t$5.out 2> $out/$2.$3.$4.t$5.err
  echo "rc=$? $2 $3 $4 trace=$5: $(tail -n 1 $out/$2.$3.$4.t$5.out | cut -c1-2500)"
  grep '^{"workload"' $out/$2.$3.$4.t$5.err | cut -c1-900
  grep '^compared' $out/$2.$3.$4.t$5.err | tr '\n' ';' | cut -c1-1500; echo
}
traced() {  # traced <dir> <tag> <cell> <seed>: with the unlisted readers
  ( cd $1 && python3 benchmark/tools/traced.py --workload $3 --seed $4 \
      --readers part_us_fanout,edge_rows_per_round --top 60 ) \
    > $out/$2.$3.$4.traced.out 2> $out/$2.$3.$4.traced.err
  echo "rc=$? $2 $3 $4 traced: $(tail -n 1 $out/$2.$3.$4.traced.out | cut -c1-1700)"
  grep -o '"unlisted".*' $out/$2.$3.$4.traced.out | cut -c1-300
  grep '^{"us_per_round_by\|^{"op"' $out/$2.$3.$4.traced.err | cut -c1-330
  grep '^{"workload"\|^compared' $out/$2.$3.$4.traced.err | tr '\n' ';' | cut -c1-2500; echo
}
sweep() {  # sweep <tag> <seeds> <seconds> [control json]
  python3 benchmark/tools/sweep.py --workload $cell --seeds $2 --seconds $3 \
    ${4:+--control "$4"} > $out/sweep.$1.out 2> $out/sweep.$1.err
  echo "rc=$? sweep $1"; cut -c1-1800 $out/sweep.$1.out
}

births() {  # births <segments> <seeds>: slowest first receipt by birth phase
  python3 benchmark/tools/delivery_by_birth.py --workload $cell --segments $1 \
    --seeds $2 > $out/births.$1.out 2> $out/births.$1.err
  echo "rc=$? births $1"; cut -c1-700 $out/births.$1.out
}
controls() {  # controls <lossy seeds> <gossip-off seeds> <D=3 seeds> <no-fanout seeds>
  sweep lossy.$1 $1 6 '{"chaos_loss_rate": 0.02}'
  sweep gossip_off.$2 $2 6 '{"program_mesh_params": {"D_lazy": 0, "gossip_factor": 0.0}}'
  sweep d3.$3 $3 6 '{"program_mesh_params": {"D": 3, "D_lo": 2, "D_score": 2, "D_out": 1}}'
  sweep no_fanout.$4 $4 6 '{"fanout_slots": 0}'
}
exact() {  # exact <segments> <seeds>: each seed for exactly that many segments
  python3 - "$1" "$2" > $out/exact.$1.out 2> $out/exact.$1.err <<'PY'
import json, sys, time
sys.path.insert(0, ".")
import jax
from benchmark import run as bench_run
from benchmark.harness import manifest as mf
from go_libp2p_pubsub_tpu.compile_cache import enable_persistent_cache
enable_persistent_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
manifest = mf.load_manifest()
cell = mf.find_cell(manifest, "eth2-100k.stepped")
for seed in (int(x) for x in sys.argv[2].split(",")):
    out = bench_run.measure(manifest, cell, seed, 1e9, False, jax.devices()[:1],
                            time.perf_counter(),
                            overrides={"max_segments": int(sys.argv[1])})
    res = out["result"]
    print(json.dumps({"seed": seed, "correct": res["correct"],
                      "rounds_run": out["run"]["rounds_run"],
                      "numbers": {x["name"]: x["value"] for x in res["compared"]
                                  if x["value"]}}), flush=True)
PY
  echo "rc=$? exact $1"; cut -c1-400 $out/exact.$1.out
}

case $1 in
first)
  traced . change $cell 3100000001
  run . change $cell 3100000002 0
  ;;
main)
  traced . change $cell 3100000003
  births 12 3100000071,3100000072,3100000073,3100000074,3100000075,3100000076,3100000077,3100000078
  sweep seeds 3100000011,3100000012,3100000013,3100000014,3100000015,3100000016,3100000017,3100000018 8
  controls "3100000021,3100000022,3100000023" "3100000024,3100000025,3100000026" \
    "3100000027,3100000028,3100000029" "3100000030,3100000031,3100000032"
  for s in 3100000041 3100000042 3100000043 3100000044 3100000045 3100000046; do
    run . change $cell $s 0; done
  traced . change $cell 3100000047
  run . change $cell 3100000048 1
  ;;
second)
  traced . change $cell 3100000004
  exact 17 3100000011,3100000012,3100000013,3100000014,3100000015,3100000016,3100000017,3100000018
  exact 32 3100000081,3100000082,3100000083
  for s in 3100000091 3100000092 3100000093 3100000094 3100000095 3100000096; do
    run . change $cell $s 0; done
  run . change $cell 3100000097 1
  ;;
last)
  for c in random-100k.stepped random-10k-t8.watched lattice-100k.steady; do
    run tmp/parent parent $c 3100000051 0; run tmp/final final $c 3100000051 0
    run tmp/final final $c 3100000052 0; run tmp/parent parent $c 3100000052 0
    run tmp/final final $c 3100000053 1; run tmp/parent parent $c 3100000053 1
  done
  run tmp/parent parent $cell 3100000054 0
  run tmp/final final $cell 3100000061 0
  run tmp/final final $cell 3100000062 1
  controls 3100000033 3100000034 3100000035 3100000036
  births 6 3100000071,3100000072,3100000073,3100000074,3100000075,3100000076,3100000077,3100000078
  ;;
confirm)  # mesh_build_rounds 48 -> 24 (births read in `last`): young runs judged
  exact 6 3100000101,3100000102,3100000103,3100000104,3100000105,3100000106,3100000107,3100000108
  run . change $cell 3100000109 1
  ;;
esac
