# third session: 24 more seeds of random-100k.stepped at the traced run's age
# (56 rounds), to see how late the last slow message is born (mesh_build_rounds
# is 32 now; the reading that matters is every phase from 24 on)
set -x
mkdir -p chiprun_out
seeds=$(python3 -c "print(','.join(str(4000000000+i) for i in range(1,25)))")
python3 benchmark/tools/delivery_by_birth.py --workload random-100k.stepped --seeds $seeds --segments 6 2>&1 | grep '^{' | tee chiprun_out/c14_birth_random-100k.stepped.jsonl | cut -c1-700
