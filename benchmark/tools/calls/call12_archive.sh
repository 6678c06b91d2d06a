# third session, final tree: a cell of each configuration run from an unpacked
# `git archive` of the index (tmp/archive_check), and the command alone with
# BENCHMARK.json and the files under `paths` (must exit non-zero, no result)
set -x
cd tmp/archive_check
python3 benchmark/run.py --workload lattice-100k.steady --seed 3800000001 --seconds 20 --trace 0 2>&1 | tail -22 | cut -c1-900; echo "archive rc=$?"
python3 benchmark/run.py --workload random-10k-t8.watched --seed 3800000002 --seconds 20 --trace 1 2>/dev/null | cut -c1-900
python3 benchmark/run.py --workload random-100k.stepped --seed 3800000003 --seconds 20 --trace 0 2>/dev/null | cut -c1-900
mkdir -p ../bare && cp -r BENCHMARK.json benchmark ../bare/ && mkdir -p ../bare/tests && cp -r tests/benchmark_harness ../bare/tests/ && cd ../bare && python3 benchmark/run.py --workload lattice-100k.steady --seed 1 --seconds 1 --trace 0; echo "bare rc=$?"
# the two seeds whose traced run read delivery_rounds_max 16 before
# mesh_build_rounds went from 16 to 32 (run from the repo's own tree)
cd ../..
for seed in 3700000307 3900000009; do
  python3 benchmark/run.py --workload random-100k.stepped --seed $seed --seconds 20 --trace 1 2>/dev/null | cut -c1-1400
done
