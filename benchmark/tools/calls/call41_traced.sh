# PR 41: one `--trace 1` run of each cell named, through the command itself
# (a second where the first one compiled: the warm readings are the ones
# PERF.md section 5 holds the twelve new per-layer metrics to).
# With IN=<dir> the runs are made from that directory: `git archive $(git
# write-tree)` unpacked under tmp/, the proof that the committed files are
# enough.
#   [IN=tmp/final41] bash benchmark/tools/calls/call41_traced.sh <seed base> <cell> [<cell> ...]
base=$1; shift
out=$PWD/chiprun_out/c41_traced; mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"
cd ${IN:-.} || exit 1; pwd; ls | head -n 40 | tr '\n' ' '; echo
i=0
for cell in "$@"; do
  i=$((i + 1))
  for seed in $((base + 10 * i)) $((base + 10 * i + 1)); do
    python3 benchmark/run.py --workload $cell --seed $seed --seconds 20 --trace 1 \
      > $out/$cell.$seed.out 2> $out/$cell.$seed.err
    echo "rc=$? $cell $seed: $(tail -n 1 $out/$cell.$seed.out | cut -c1-2400)"
    grep '^{"workload"' $out/$cell.$seed.err | cut -c1-700
    # warm already: one run is enough
    grep -q '"setup_cache_misses": {"value": 0,' $out/$cell.$seed.out && break
  done
done
