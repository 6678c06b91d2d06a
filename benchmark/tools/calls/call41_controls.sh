# PR 41: what `correct` must still fail, at size, 3 seeds each, after the
# repairs of `mesh_degree_out` (all four references) and of the score
# refresh's membership (references/gossipsub.py): the program built with
# D = 3 in the five cells beside churn-100k.stepped (call 1 has that one),
# the score plane in bfloat16 in the two scored cells of the first reference,
# and six more sound seeds of each of its three cells (short windows).
#   bash benchmark/tools/calls/call41_controls.sh
out=chiprun_out/c41_controls; mkdir -p $out
echo "JAX_COMPILATION_CACHE_DIR=$JAX_COMPILATION_CACHE_DIR"
d3='{"program_mesh_params": {"D": 3, "D_lo": 2, "D_score": 2, "D_out": 1}}'
sweep() {  # name cell seeds [control]
  python3 benchmark/tools/sweep.py --workload $2 --seeds $3 --seconds 3 ${4:+--control "$4"} \
    > $out/$1.$2.out 2> $out/$1.$2.err
  echo "rc=$? $1 $2"; cut -c1-1200 $out/$1.$2.out
}
for cell in random-100k.stepped random-10k-t8.watched lattice-100k.steady eth2-100k.stepped sybil-50k.stepped; do
  sweep d3 $cell 4100000401,4100000402,4100000403 "$d3"
done
for cell in random-100k.stepped lattice-100k.steady; do
  sweep bf16 $cell 4100000411,4100000412,4100000413 '{"score_dtype": "bfloat16"}'
done
for cell in random-100k.stepped random-10k-t8.watched lattice-100k.steady; do
  sweep sound $cell 4100000421,4100000422,4100000423,4100000424,4100000425,4100000426
done
