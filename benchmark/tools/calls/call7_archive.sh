# the final index, unpacked (tmp/archive_check): one cell of each random
# configuration, and the bare directory that must refuse
set -x
cd tmp/archive_check
python3 benchmark/run.py --workload random-100k.stepped --seed 3600000001 --seconds 20 --trace 0 2>&1 | tail -17 | cut -c1-600
python3 benchmark/run.py --workload random-10k-t8.watched --seed 3600000002 --seconds 20 --trace 1 2>/dev/null | cut -c1-900
mkdir -p ../bare && cp -r BENCHMARK.json benchmark ../bare/ && mkdir -p ../bare/tests && cp -r tests/benchmark_harness ../bare/tests/ && cd ../bare && python3 benchmark/run.py --workload random-100k.stepped --seed 1 --seconds 1 --trace 0 2>&1 | tail -2; echo "bare rc=${PIPESTATUS[0]}"
