# third session: the two sets of 6 and two more traced runs of the other two
# cells with the loop that sends ahead (12 segments at 10k, 5 at 100k)
set -x
mkdir -p chiprun_out
R=random-10k-t8.watched
python3 benchmark/tools/sets.py --workload $R --traces 2 --seeds 3700000201,3700000202,3700000203,3700000204,3700000205,3700000206 2>&1 | grep '^{' | tee chiprun_out/c11_sets_$R.jsonl | cut -c1-1100
C=random-100k.stepped
python3 benchmark/tools/sets.py --workload $C --traces 2 --seeds 3700000301,3700000302,3700000303,3700000304,3700000305,3700000306 2>&1 | grep '^{' | tee chiprun_out/c11_sets_$C.jsonl | cut -c1-1100
