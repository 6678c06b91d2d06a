# PR 34: every chip call of the PR, one phase a call.
#   chiprun --timeout 3400 -- bash scripts/calls/pr34.sh <phase>
# first   the working tree's first look: the change traced (dearest ops, the
#         tally's edge_table_rows) and timed once at random-100k.stepped,
#         then the gather's law with the new cases `kmajor` / `kmajorB`
#         against `compact` / `tierB` (scripts/gather_law.py) on the three
#         planned graphs at their own K0
# ab1     the files git would commit (tmp/final = git archive of the index)
#         against the parent (tmp/parent = git archive of the parent
#         commit): random-100k.stepped, 6 timed pairs with alternating
#         sides + 1 traced pair
# ab2     the same at eth2-100k.stepped (4 timed pairs + 1 traced),
#         random-10k-t8.watched (3 + 1) and lattice-100k.steady (1 + 1: its
#         window lowers to the parent's text). random-10k-t8.watched came
#         out 10.5 % SLOWER: the full-table arm's one joined gather
# probe   why: the 10k cell traced op by op on both sides and with the
#         full-table arm's two gathers apart (the working tree), which is
#         then timed there and traced at eth2-100k.stepped
# ab3     the final files (tmp/final again; the compact arm, and with it
#         random-100k's window, lowers to ab1's text byte for byte) against
#         the parent in the two cells whose program the repair changed:
#         random-10k-t8.watched (3 + 1) and eth2-100k.stepped (2 + 1)
out=chiprun_out/pr34; mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"

law() {  # law <tag> <args...>
  tag=$1; shift
  python3 scripts/gather_law.py "$@" --out $out/law_$tag.json \
    > $out/law_$tag.out 2> $out/law_$tag.err
  echo "rc=$? law $tag"; tail -n 2 $out/law_$tag.err | cut -c1-300
  grep '"case"' $out/law_$tag.out | python3 -c "
import json, sys
for x in map(json.loads, sys.stdin):
    print(x['graph'], x['n'], x['case'], 'w', x['w'], 'k0', x['k0'], x['rows_out'], x['rows_table'], round(x['ms_median'], 3), round(x['ms_min'], 3), x['equal'], round(x['first_call_s'], 1))"
}
run() {  # run <dir> <tag> <cell> <seed> <trace>
  ( cd $1 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 20 --trace $5 ) \
    > $out/$2.$3.$4.t$5.out 2> $out/$2.$3.$4.t$5.err
  echo "rc=$? $2 $3 $4 trace=$5: $(tail -n 1 $out/$2.$3.$4.t$5.out | cut -c1-2500)"
  grep '^{"workload"' $out/$2.$3.$4.t$5.err | cut -c1-900
}
traced() {  # traced <dir> <tag> <cell> <seed>: dearest ops and the tally
  ( cd $1 && python3 scripts/traced_tally.py --workload $3 --seed $4 \
      --readers edge_rows_per_round --top 60 ) \
    > $out/$2.$3.$4.traced.out 2> $out/$2.$3.$4.traced.err
  echo "rc=$? $2 $3 $4 traced: $(tail -n 1 $out/$2.$3.$4.traced.out | cut -c1-1700)"
  grep '^{"us_per_round_by\|^{"op"\|^{"window"' $out/$2.$3.$4.traced.err | cut -c1-330
  grep '^{"workload"' $out/$2.$3.$4.traced.err | cut -c1-900
}
pairs() {  # pairs <cell> <seed base> <n timed pairs>: alternating sides
  for i in $(seq 1 $3); do
    s=$(( $2 + i ))
    if [ $(( i % 2 )) = 1 ]; then run tmp/parent parent $1 $s 0; run tmp/final final $1 $s 0
    else run tmp/final final $1 $s 0; run tmp/parent parent $1 $s 0; fi
  done
  s=$(( $2 + 50 ))
  run tmp/final final $1 $s 1; run tmp/parent parent $1 $s 1
}

case $1 in
first)
  traced . change random-100k.stepped 3400000001
  run . change random-100k.stepped 3400000002 0
  law 100k --n 100000 --k0 25 --widths 5 14 --cases compact tierB kmajor kmajorB
  law eth2 --n 100000 --graph subnet_connect --k0 46 --widths 6 --cases tierB kmajorB
  law 10k --n 10000 --k0 24 --widths 5 --cases tierB kmajorB
  ;;
ab1)
  pairs random-100k.stepped 3400000010 6
  ;;
ab2)
  pairs eth2-100k.stepped 3400000020 4
  pairs random-10k-t8.watched 3400000030 3
  pairs lattice-100k.steady 3400000040 1
  ;;
ab3)
  pairs random-10k-t8.watched 3400000110 3
  pairs eth2-100k.stepped 3400000120 2
  ;;
probe)
  # random-10k-t8.watched slowed 10.5 % in ab2: where, op by op, and what
  # the full-table form with the head's and the tail's gathers apart (the
  # working tree) makes of it; then that form's stages at eth2-100k
  traced tmp/final final random-10k-t8.watched 3400000101
  traced tmp/parent parent random-10k-t8.watched 3400000101
  traced . apart random-10k-t8.watched 3400000101
  run . apart random-10k-t8.watched 3400000102 0
  run . apart eth2-100k.stepped 3400000103 1
  ;;
esac
