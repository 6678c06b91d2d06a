# PR 29: what the stage map costs a traced run (record_stages.py prints
# `map_s`: the first reader's retrace + cache load + parse), per cell; and
# whether kernels_per_round repeats on ONE seed at random-10k-t8, where it
# moved by 0.15 % between seeds (a conditional in the heartbeat).
set -x
mkdir -p chiprun_out/c17
for cell in lattice-100k.steady random-10k-t8.watched random-100k.stepped; do
  python3 benchmark/tools/record_stages.py --workload $cell --segments 3 --seed 2147483671 --out chiprun_out/c17/stages_$cell.json 2>chiprun_out/c17/$cell.err | cut -c1-1500
done
for k in 1 2; do
  python3 benchmark/run.py --workload random-10k-t8.watched --seed 4300000011 --seconds 20 --trace 1 2>/dev/null | tee chiprun_out/c17/same_seed_$k.out | cut -c1-1500
done
