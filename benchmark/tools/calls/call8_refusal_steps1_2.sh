# third session, after the driver's refusal (lattice-100k.steady rounds_per_s
# too noisy for 0.025): steps 1 and 2 of the refusal on the tree AS REFUSED,
# before any edit. One seed twice and two others once, in one call; the first
# run of the call (which compiles) held against the others
set -x
mkdir -p chiprun_out
L=lattice-100k.steady
for seed in 3500000001 3500000001 3500000002 3500000003; do
  python3 benchmark/run.py --workload $L --seed $seed --seconds 20 --trace 0 2>chiprun_out/c8_err.txt | tee -a chiprun_out/c8_steps12_$L.jsonl | cut -c1-600
  grep '^{"workload"' chiprun_out/c8_err.txt | tee -a chiprun_out/c8_steps12_$L.log.jsonl | cut -c1-1200
done
