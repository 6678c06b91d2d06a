# PR 36: every chip call of the PR, one phase a call.
#   chiprun --timeout 3400 -- bash scripts/calls/pr36.sh <phase>
# first   the gather's law in the unit the cliff is in (scripts/gather_law.py,
#         cases kmajor / kmajorB whole and kmajorS / kmajorBS in tile-wide
#         slices): widths 5-16 at 100,000 peers, 5 8 13 at 50,000, then the
#         compact table swept from 1.5 M to 4.0 M rows by the peer count at
#         widths 5, 8 and 13; then the working tree's first look: the change
#         traced (dearest ops, the tally's new counters) and one timed pair
#         against the parent (tmp/parent = git archive of the parent commit)
#         at random-100k.stepped and sybil-50k.stepped
# final   the files git would commit (tmp/final = git archive of the index)
#         against the parent: random-100k.stepped 6 timed pairs with
#         alternating sides + 1 traced pair, sybil-50k.stepped 6 + 1, one
#         timed pair and the change traced in each cell whose window lowers
#         to the parent's text
out=chiprun_out/pr36; mkdir -p $out
# the parent prints the same tally lines (counters it lacks read null)
cp scripts/traced_tally.py scripts/window_whiles.py tmp/parent/scripts/
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"

law() {  # law <tag> <args...>
  tag=$1; shift
  python3 scripts/gather_law.py "$@" --out $out/law_$tag.json \
    > $out/law_$tag.out 2> $out/law_$tag.err
  echo "rc=$? law $tag"; tail -n 2 $out/law_$tag.err | cut -c1-300
  grep '"case"' $out/law_$tag.out | python3 -c "
import json, sys
for x in map(json.loads, sys.stdin):
    print(x['n'], x['case'], 'w', x['w'], 'k0', x['k0'], 'slices', x['slices'], x['rows_out'], x['rows_table'], round(x['ms_median'], 3), round(x['ms_min'], 3), x['equal'], round(x['first_call_s'], 1))"
}
run() {  # run <dir> <tag> <cell> <seed> <trace>
  ( cd $1 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 20 --trace $5 ) \
    > $out/$2.$3.$4.t$5.out 2> $out/$2.$3.$4.t$5.err
  echo "rc=$? $2 $3 $4 trace=$5: $(tail -n 1 $out/$2.$3.$4.t$5.out | cut -c1-2600)"
  grep '^{"workload"' $out/$2.$3.$4.t$5.err | cut -c1-900
}
traced() {  # traced <dir> <tag> <cell> <seed>: dearest ops and the tally
  ( cd $1 && python3 scripts/traced_tally.py --workload $3 --seed $4 \
      --readers edge_rows_per_round --top 40 ) \
    > $out/$2.$3.$4.traced.out 2> $out/$2.$3.$4.traced.err
  echo "rc=$? $2 $3 $4 traced: $(tail -n 1 $out/$2.$3.$4.traced.out | cut -c1-1900)"
  grep '^{"us_per_round_by\|^{"op"\|^{"window"' $out/$2.$3.$4.traced.err | cut -c1-330
  grep '^{"workload"' $out/$2.$3.$4.traced.err | cut -c1-900
}
pairs() {  # pairs <change dir> <cell> <seed base> <n timed pairs>: alternating sides
  for i in $(seq 1 $4); do
    s=$(( $3 + i ))
    if [ $(( i % 2 )) = 1 ]; then run tmp/parent parent $2 $s 0; run $1 change $2 $s 0
    else run $1 change $2 $s 0; run tmp/parent parent $2 $s 0; fi
  done
}

K="kmajor kmajorS kmajorB kmajorBS"
case $1 in
first)
  law 100k --n 100000 --k0 25 --widths 5 6 7 8 9 13 16 --cases $K
  law 50k --n 50000 --k0 24 --widths 5 8 13 --cases $K
  for n in 60000 70000 80000 108000 116000 124000 132000 140000 150000 160000; do
    law rows$n --n $n --k0 25 --widths 5 8 13 --cases kmajor kmajorS
  done
  traced . change random-100k.stepped 3600000001
  pairs . random-100k.stepped 3600000010 1
  traced . change sybil-50k.stepped 3600000002
  traced tmp/parent parent sybil-50k.stepped 3600000002
  pairs . sybil-50k.stepped 3600000020 1
  ;;
final)
  pairs tmp/final random-100k.stepped 3600000100 6
  traced tmp/final change random-100k.stepped 3600000150
  traced tmp/parent parent random-100k.stepped 3600000150
  pairs tmp/final sybil-50k.stepped 3600000200 6
  traced tmp/final change sybil-50k.stepped 3600000250
  for c in eth2-100k.stepped random-10k-t8.watched lattice-100k.steady; do
    pairs tmp/final $c 3600000300 1
    traced tmp/final change $c 3600000350
  done
  ;;
esac
