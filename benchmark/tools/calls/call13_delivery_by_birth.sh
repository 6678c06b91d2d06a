# third session: a traced run of random-100k.stepped (56 rounds old) read
# delivery_rounds_max 16 against the limit 15 on seed 3700000307. Where are the
# slow messages born? That seed again at 40, 56 and 88 rounds, and 12 fresh
# seeds at 56 rounds (the traced run's age), 3 of them at 40 as well
set -x
mkdir -p chiprun_out
T=benchmark/tools/delivery_by_birth.py
C=random-100k.stepped
python3 $T --workload $C --seeds 3700000307 --segments 4,6,10 2>&1 | grep '^{' | tee chiprun_out/c13_birth_$C.jsonl | cut -c1-900
python3 $T --workload $C --seeds 3900000001,3900000002,3900000003 --segments 4,6 2>&1 | grep '^{' | tee -a chiprun_out/c13_birth_$C.jsonl | cut -c1-900
python3 $T --workload $C --seeds 3900000004,3900000005,3900000006,3900000007,3900000008,3900000009,3900000010,3900000011,3900000012 --segments 6 2>&1 | grep '^{' | tee -a chiprun_out/c13_birth_$C.jsonl | cut -c1-900
