# PR 29, final tree: a cell of each configuration run from an unpacked
# `git archive` of the index (tmp/archive_check): the committed files are
# enough, and a traced run prints all ten new metrics
set -x
cd tmp/archive_check
python3 benchmark/run.py --workload lattice-100k.steady --seed 4500000001 --seconds 20 --trace 0 2>/dev/null | cut -c1-700; echo "rc=$?"
for cell in random-10k-t8.watched random-100k.stepped lattice-100k.steady; do
  python3 benchmark/run.py --workload $cell --seed 4500000002 --seconds 20 --trace 1 2>/dev/null | cut -c1-1900
done
