# PR 41, call 2 (chips were scarce: everything that must be read, in one call,
# the least needed last): one traced run of each of the six cells (a second
# where the first compiled), made from tmp/final41, the unpacked `git archive
# $(git write-tree)`: the committed files are enough; the controls (call41_controls.sh); three timed
# runs of each cell beside random-100k.stepped on seeds of their own.
#   bash benchmark/tools/calls/call41_2_all_cells.sh
here=benchmark/tools/calls
IN=tmp/final41 bash $here/call41_traced.sh 4100000600 random-100k.stepped random-10k-t8.watched \
  lattice-100k.steady eth2-100k.stepped sybil-50k.stepped churn-100k.stepped
bash $here/call41_controls.sh
out=chiprun_out/c41_three; mkdir -p $out
i=0
for cell in churn-100k.stepped eth2-100k.stepped sybil-50k.stepped random-10k-t8.watched lattice-100k.steady; do
  i=$((i + 1)); b=$((4100000700 + 10 * i))
  python3 benchmark/tools/sets.py --workload $cell --seeds $((b+1)),$((b+2)),$((b+3)) --sets 1 \
    > $out/$cell.out 2> $out/$cell.err
  echo "rc=$? $cell"; cut -c1-700 $out/$cell.out
done
# (as run, a last step made random-100k.stepped's two sets of 6; the call was cut
# at its 3,550 s limit inside eth2-100k.stepped's three runs by five cold
# starts, and the sets went to call41_3_six_seeds.sh)
