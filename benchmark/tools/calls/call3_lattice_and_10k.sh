# lattice-100k.steady and random-10k-t8.watched: the two sets of 6 and three
# traced runs each, a dozen sound seeds, the controls, the planted faults,
# and (10k) the correct comparison on three other graph draws
set -x
S=benchmark/tools/sweep.py
mkdir -p chiprun_out
L=lattice-100k.steady
python3 benchmark/tools/sets.py --workload $L --traces 3 --seeds 3200000101,3200000102,3200000103,3200000104,3200000105,3200000106 2>&1 | grep '^{' | tee chiprun_out/c3_sets_$L.jsonl | cut -c1-1100
python3 $S --workload $L --seconds 3 --seeds 3200000111,3200000112,3200000113,3200000114,3200000115,3200000116,3200000117,3200000118,3200000119,3200000120,3200000121,3200000122 2>&1 | grep '^{' | tee chiprun_out/c3_sound_$L.jsonl | cut -c1-330
python3 $S --workload $L --seconds 3 --seeds 3200000131,3200000132,3200000133 --control '{"score_dtype":"bfloat16"}' 2>&1 | grep '^{' | tee chiprun_out/c3_bf16_$L.jsonl | cut -c1-420
python3 $S --workload $L --seconds 3 --seeds 3200000141 --faults state_unchanged,half_batch,answer_altered,fmd_dropped 2>&1 | grep '"seed"' | tee chiprun_out/c3_faults_$L.jsonl | cut -c1-600
python3 $S --workload $L --seconds 3 --seeds 3200000151,3200000152,3200000153 --control '{"chaos_loss_rate":0.02}' 2>&1 | grep '^{' | tee chiprun_out/c3_lossy_$L.jsonl | cut -c1-420
python3 $S --workload $L --seconds 3 --seeds 3200000161,3200000162,3200000163 --control '{"program_mesh_params":{"D_lazy":0,"gossip_factor":0.0}}' 2>&1 | grep '^{' | tee chiprun_out/c3_gossip_off_$L.jsonl | cut -c1-420
python3 $S --workload $L --seconds 3 --seeds 3200000171,3200000172,3200000173 --control '{"program_mesh_params":{"D":3,"D_lo":2,"D_score":2,"D_out":1}}' 2>&1 | grep '^{' | tee chiprun_out/c3_low_degree_$L.jsonl | cut -c1-420
R=random-10k-t8.watched
python3 benchmark/tools/sets.py --workload $R --traces 3 --seeds 3200000201,3200000202,3200000203,3200000204,3200000205,3200000206 2>&1 | grep '^{' | tee chiprun_out/c3_sets_$R.jsonl | cut -c1-1100
python3 $S --workload $R --seconds 3 --seeds 3200000211,3200000212,3200000213,3200000214,3200000215,3200000216,3200000217,3200000218,3200000219,3200000220,3200000221,3200000222 2>&1 | grep '^{' | tee chiprun_out/c3_sound_$R.jsonl | cut -c1-330
python3 $S --workload $R --seconds 3 --seeds 3200000241 --faults state_unchanged,half_batch,answer_altered 2>&1 | grep '"seed"' | tee chiprun_out/c3_faults_$R.jsonl | cut -c1-600
python3 $S --workload $R --seconds 3 --seeds 3200000251,3200000252,3200000253 --control '{"chaos_loss_rate":0.02}' 2>&1 | grep '^{' | tee chiprun_out/c3_lossy_$R.jsonl | cut -c1-420
python3 $S --workload $R --seconds 3 --seeds 3200000261,3200000262,3200000263 --control '{"program_mesh_params":{"D_lazy":0,"gossip_factor":0.0}}' 2>&1 | grep '^{' | tee chiprun_out/c3_gossip_off_$R.jsonl | cut -c1-420
python3 $S --workload $R --seconds 3 --seeds 3200000271,3200000272,3200000273 --control '{"program_mesh_params":{"D":3,"D_lo":2,"D_score":2,"D_out":1}}' 2>&1 | grep '^{' | tee chiprun_out/c3_low_degree_$R.jsonl | cut -c1-420
python3 $S --workload $R --seconds 3 --seeds 3200000281,3200000282 --graph-seeds 2,3,4 2>&1 | grep '^{' | tee chiprun_out/c3_graphs_$R.jsonl | cut -c1-330
