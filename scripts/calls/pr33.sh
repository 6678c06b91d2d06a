# PR 33: the one chip call of the PR.
#   chiprun --timeout 3400 -- bash scripts/calls/pr33.sh
# The files git would commit (tmp/final = git archive of the index) against
# the parent (tmp/parent = git archive of the parent commit), in every cell:
# parent timed, final timed, final traced, parent traced. Every cell's
# window lowers to the parent's own text, so one pair says what there is to
# say; the driver's pairs decide.
out=chiprun_out/pr33; mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"

run() {  # run <dir> <tag> <cell> <seed> <trace>
  ( cd $1 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 20 --trace $5 ) \
    > $out/$2.$3.$4.t$5.out 2> $out/$2.$3.$4.t$5.err
  echo "rc=$? $2 $3 $4 trace=$5: $(tail -n 1 $out/$2.$3.$4.t$5.out | cut -c1-2500)"
}

( cd tmp/final && JAX_PLATFORMS=cpu python3 - <<'PY'
import sys
import go_libp2p_pubsub_tpu.models.gossipsub_phase
print("final imports pallas:", sorted(m for m in sys.modules if "pallas" in m))
PY
)
seed=3300000000
for cell in random-100k.stepped eth2-100k.stepped random-10k-t8.watched lattice-100k.steady; do
  seed=$(( seed + 1 ))
  run tmp/parent parent $cell $seed 0
  run tmp/final final $cell $seed 0
  run tmp/final final $cell $seed 1
  run tmp/parent parent $cell $seed 1
done
