# random-100k.stepped: the two sets of 6 and three traced runs
set -x
mkdir -p chiprun_out
C=random-100k.stepped
python3 benchmark/tools/sets.py --workload $C --traces 3 --seeds 3300000101,3300000102,3300000103,3300000104,3300000105,3300000106 2>&1 | grep '^{' | tee chiprun_out/c4_sets_$C.jsonl | cut -c1-1100
