# random-100k.stepped: does the compile cache hit, a dozen sound seeds, the
# bf16 control and the planted faults in one process, the other controls
set -x
S=benchmark/tools/sweep.py
C=random-100k.stepped
mkdir -p chiprun_out
for seed in 3100000001 3100000002; do
  python3 benchmark/run.py --workload $C --seed $seed --seconds 20 --trace 0 2>chiprun_out/c2_err_$seed.txt | cut -c1-700
  grep '"setup_parts"' chiprun_out/c2_err_$seed.txt | cut -c1-900
  ls -lS ${JAX_COMPILATION_CACHE_DIR:-.jax_cache} | head -4
done
python3 $S --workload $C --seconds 4 --seeds 3100000011,3100000012,3100000013,3100000014,3100000015,3100000016,3100000017,3100000018,3100000019,3100000020,3100000021,3100000022 2>&1 | grep '^{' | tee chiprun_out/c2_sound.jsonl | cut -c1-420
python3 $S --workload $C --seconds 4 --seeds 3100000031,3100000032,3100000033 --control '{"score_dtype":"bfloat16"}' 2>&1 | grep '^{' | tee chiprun_out/c2_bf16.jsonl | cut -c1-600
python3 $S --workload $C --seconds 4 --seeds 3100000041 --faults state_unchanged,half_batch,answer_altered,fmd_dropped 2>&1 | grep '^{"workload' | grep '"seed"' | tee chiprun_out/c2_faults.jsonl | cut -c1-700
python3 $S --workload $C --seconds 8 --seeds 3100000051,3100000052 --control '{"program_mesh_params":{"D_lazy":0,"gossip_factor":0.0}}' 2>&1 | grep '^{' | tee chiprun_out/c2_gossip_off.jsonl | cut -c1-600
python3 $S --workload $C --seconds 8 --seeds 3100000061,3100000062,3100000063 --control '{"chaos_loss_rate":0.02}' 2>&1 | grep '^{' | tee chiprun_out/c2_lossy.jsonl | cut -c1-600
