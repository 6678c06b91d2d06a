# third session, step 3 of the refusal: lattice-100k.steady six times in one
# call with the loop that sends 12 segments ahead (the first set), the second
# set on the same seeds, and two more traced runs
set -x
mkdir -p chiprun_out
L=lattice-100k.steady
python3 benchmark/tools/sets.py --workload $L --traces 2 --seeds 3700000101,3700000102,3700000103,3700000104,3700000105,3700000106 2>&1 | grep '^{' | tee chiprun_out/c10_sets_$L.jsonl | cut -c1-1100
