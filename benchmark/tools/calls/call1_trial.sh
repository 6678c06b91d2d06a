# first look at random-100k and the K=36 10k graph; names trial cells (random-100k.watched/.steady, random-10k-t8.steady) that did not stay in BENCHMARK.json
set -x

S=benchmark/tools/sweep.py
python3 $S --workload random-100k.stepped --seeds 3000000001,3000000002 --seconds 5 2>&1 | tail -4
python3 $S --workload random-100k.watched --seeds 3000000003 --seconds 6 2>&1 | tail -3
python3 $S --workload random-10k-t8.watched --seeds 3000000004,3000000005 --seconds 4 2>&1 | tail -3
python3 $S --workload random-10k-t8.steady --seeds 3000000006 --seconds 4 2>&1 | tail -3
python3 benchmark/run.py --workload random-100k.stepped --seed 3000000007 --seconds 5 --trace 1 2>&1 | tail -25
python3 $S --workload random-100k.stepped --seeds 3000000008 --seconds 3 --control '{"program_mesh_params":{"D_lazy":0,"gossip_factor":0.0}}' 2>&1 | tail -2
