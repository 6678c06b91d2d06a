# PR 32: every chip call of the PR, one phase a call.
#   chiprun --timeout 3400 -- bash scripts/calls/pr32.sh <phase>
# law     the gather's law with the new case `compact` against `tierB`
#         (scripts/gather_law.py): random_connect 100k at K0 24 / 28 (w 1 2 5
#         6 14) and 20 / 26 (w 5, for the cost rule), 10k at K0 24,
#         eth2-100k's own graph at K0 46 (w 6 14); then, only if `compact` is
#         at least 15 % under `tierB` at w 5, K0 24, the change's first
#         traced and timed run at random-100k.stepped
# mid     the same two cases at w 5, K0 24 on random_connect of 25k, 50k and
#         75k peers: where between 10k (compact loses 0.9 ms) and 100k
#         (compact wins 6.8 ms) the two forms cross
# ab      the files git would commit (tmp/final = git archive of the index)
#         against the parent (tmp/parent = git archive of the parent
#         commit, with scripts/traced_tally.py copied in: a tool, no part
#         of the program): random-100k.stepped 6 timed pairs + 1 traced
#         pair, then eth2-100k.stepped, random-10k-t8.watched and
#         lattice-100k.steady 1 timed pair + 1 traced pair each (their
#         programs are the parent's own: the plan keeps the full table)
out=chiprun_out/pr32; mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"

law() {  # law <tag> <args...>
  tag=$1; shift
  python3 scripts/gather_law.py "$@" --out $out/law_$tag.json \
    > $out/law_$tag.out 2> $out/law_$tag.err
  echo "rc=$? law $tag"; tail -n 2 $out/law_$tag.err | cut -c1-300
}
run() {  # run <dir> <tag> <cell> <seed> <trace>
  ( cd $1 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 20 --trace $5 ) \
    > $out/$2.$3.$4.t$5.out 2> $out/$2.$3.$4.t$5.err
  echo "rc=$? $2 $3 $4 trace=$5: $(tail -n 1 $out/$2.$3.$4.t$5.out | cut -c1-2500)"
  grep '^{"workload"' $out/$2.$3.$4.t$5.err | cut -c1-900
}
traced() {  # traced <dir> <tag> <cell> <seed>: dearest ops and the tally
  ( cd $1 && python3 scripts/traced_tally.py --workload $3 --seed $4 \
      --readers edge_rows_per_round --top 40 ) \
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
  traced tmp/final final $1 $s; traced tmp/parent parent $1 $s
}

case $1 in
law)
  law 100k_k24 --n 100000 --k0 24 --widths 1 2 5 6 14 --cases compact tierB
  law 100k_k28 --n 100000 --k0 28 --widths 1 2 5 6 14 --cases compact
  law 100k_fit --n 100000 --k0 20 26 28 --widths 5 --cases compact tierB
  law eth2 --n 100000 --graph subnet_connect --k0 46 --widths 6 14 --cases compact tierB
  law 10k --n 10000 --k0 24 --widths 1 2 5 6 14 --cases compact tierB
  python3 - <<'PY'
import glob, json, sys
lines = []
for f in sorted(glob.glob("chiprun_out/pr32/law_*.json")):
    lines += json.load(open(f))
for x in lines:
    print(x["graph"], x["n"], x["case"], "w", x["w"], "k0", x["k0"], x["rows_out"],
          x["rows_table"], round(x["ms_median"], 3), round(x["ms_min"], 3),
          x.get("equal"), round(x["first_call_s"], 1))
pick = lambda c: [x["ms_median"] for x in lines if x["case"] == c and x["w"] == 5
                  and x["k0"] == 24 and x["n"] == 100000 and x["graph"] == "random_connect"]
ratio = pick("compact")[0] / pick("tierB")[0]
print("compact / tierB at w=5, K0=24:", ratio)
sys.exit(0 if ratio <= 0.85 else 1)
PY
  if [ $? = 0 ]; then
    traced . change random-100k.stepped 3200000001
    run . change random-100k.stepped 3200000002 0
  else echo "the law's head reading did not survive the appended rows: stop"; fi
  ;;
mid)
  for n in 25000 50000 75000; do
    law mid_$n --n $n --k0 24 --widths 5 --cases compact tierB
    grep '"case"' $out/law_mid_$n.out | python3 -c "
import json, sys
for x in map(json.loads, sys.stdin):
    print(x['n'], x['case'], x['k0'], x['rows_out'], x['rows_table'], round(x['ms_median'], 3), round(x['ms_min'], 3), x['equal'], round(x['first_call_s'], 1))"
  done
  ;;
ab)
  cp scripts/traced_tally.py tmp/parent/scripts/
  pairs random-100k.stepped 3200000010 6
  pairs eth2-100k.stepped 3200000020 1
  pairs random-10k-t8.watched 3200000030 1
  pairs lattice-100k.steady 3200000040 1
  ;;
esac
