# on the final tree: three traced runs of random-100k.stepped, its low-degree
# control, correct on another 100k graph draw, and a cell of each kind run
# from an unpacked `git archive` of the index (tmp/archive_check)
set -x
mkdir -p chiprun_out
S=benchmark/tools/sweep.py
C=random-100k.stepped
for seed in 3400000001 3400000002 3400000003; do
  python3 benchmark/run.py --workload $C --seed $seed --seconds 20 --trace 1 2>/dev/null | tee -a chiprun_out/c5_traces_$C.jsonl | cut -c1-1500
done
(cd tmp/archive_check && python3 benchmark/run.py --workload random-10k-t8.watched --seed 3400000011 --seconds 20 --trace 0 2>&1 | tail -18 | cut -c1-900; echo "archive rc=$?"
 python3 benchmark/run.py --workload lattice-100k.steady --seed 3400000012 --seconds 20 --trace 1 2>/dev/null | cut -c1-700
 python3 benchmark/run.py --workload $C --seed 3400000013 --seconds 20 --trace 0 2>/dev/null | cut -c1-700
 mkdir -p ../bare && cp -r BENCHMARK.json benchmark ../bare/ && mkdir -p ../bare/tests && cp -r tests/benchmark_harness ../bare/tests/ && cd ../bare && python3 benchmark/run.py --workload $C --seed 1 --seconds 1 --trace 0; echo "bare rc=$?")
python3 $S --workload $C --seconds 4 --seeds 3400000021,3400000022,3400000023 --control '{"program_mesh_params":{"D":3,"D_lo":2,"D_score":2,"D_out":1}}' 2>&1 | grep '^{' | tee chiprun_out/c5_low_degree_$C.jsonl | cut -c1-420
python3 $S --workload $C --seconds 5 --seeds 3400000031,3400000032 --graph-seeds 2 2>&1 | grep '^{' | tee chiprun_out/c5_graphs_$C.jsonl | cut -c1-420
