# PR 29, first chip call: does the text of a TPU executable LOADED FROM THE
# PERSISTENT CACHE still carry op_name metadata, under the very instruction
# names the device trace shows? record_stages.py prints, per run, the op
# events inside the window's modules, the op names the map lacks (must be
# none) and the instructions under a gs.* scope. First run compiles the
# renamed window (jit_gs_window_v1), the second loads it from the cache in a
# new process; then one traced run through the command, and the recording
# of random-100k.stepped (3 segments) the stage test pins.
set -x
mkdir -p chiprun_out
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"
python3 benchmark/tools/record_stages.py --workload random-10k-t8.watched --segments 3 --seed 2147483659 --out chiprun_out/stages_random-10k-t8_cold.json 2>chiprun_out/c15_cold.err | cut -c1-3000
python3 benchmark/tools/record_stages.py --workload random-10k-t8.watched --segments 3 --seed 2147483660 --out chiprun_out/stages_random-10k-t8_warm.json 2>chiprun_out/c15_warm.err | cut -c1-3000
python3 benchmark/run.py --workload random-10k-t8.watched --seed 4100000001 --seconds 20 --trace 1 2>chiprun_out/c15_run.err | tee chiprun_out/c15_run_random-10k-t8.jsonl | cut -c1-2500
grep '^{"workload"' chiprun_out/c15_run.err | cut -c1-1200
python3 benchmark/tools/record_stages.py --workload random-100k.stepped --segments 3 --seed 2147483661 --out chiprun_out/trace_v5e_random-100k_stepped3.json 2>chiprun_out/c15_100k.err | cut -c1-3000
tail -5 chiprun_out/c15_cold.err chiprun_out/c15_100k.err | cut -c1-600
ls -la chiprun_out/*.json
