# the new number delivery_rounds_max at size: how many rounds the slowest
# first receipt takes, against the 15 / 11 the configurations allow
set -x
mkdir -p chiprun_out
S=benchmark/tools/sweep.py
python3 $S --workload random-100k.stepped --seconds 8 --seeds 3500000001,3500000002,3500000003,3500000004,3500000005,3500000006,3500000007,3500000008 2>&1 | grep '^{' | tee chiprun_out/c6_margin_random-100k.stepped.jsonl | cut -c1-500
python3 $S --workload random-10k-t8.watched --seconds 4 --seeds 3500000011,3500000012,3500000013,3500000014,3500000015,3500000016,3500000017,3500000018 2>&1 | grep '^{' | tee chiprun_out/c6_margin_random-10k-t8.watched.jsonl | cut -c1-500
python3 benchmark/run.py --workload random-100k.stepped --seed 3500000021 --seconds 20 --trace 0 2>&1 | grep compared
python3 benchmark/run.py --workload random-10k-t8.watched --seed 3500000022 --seconds 20 --trace 0 2>&1 | grep compared
