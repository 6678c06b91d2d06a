# third session: the segment loop now keeps ~4 s of segments in flight
# (`ahead_segments` in each mix; this call ran with 44 / 46 / 5). One full-length run and one traced run of
# each cell: memory peak, whether the dispatch blocks, window length, correct
set -x
mkdir -p chiprun_out
n=0
for C in lattice-100k.steady random-10k-t8.watched random-100k.stepped; do
  for T in 0 1; do
    n=$((n+1))
    python3 benchmark/run.py --workload $C --seed $((3600000000+n)) --seconds 20 --trace $T 2>chiprun_out/c9_err.txt | tee -a chiprun_out/c9_trial.jsonl | cut -c1-1500
    echo "rc=$?"; tail -c 1500 chiprun_out/c9_err.txt | grep -v '^compared' | cut -c1-1500
    grep '^{"workload"' chiprun_out/c9_err.txt >> chiprun_out/c9_trial.log.jsonl
  done
done
