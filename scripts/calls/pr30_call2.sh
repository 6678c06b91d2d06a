# PR 30, second chip call: the first runs of the changed engine.
bash scripts/calls/pr30_ab.sh random-100k.stepped 3000000000 \
  "change:1:0 parent:1:0 parent:2:0 change:2:0 change:11:1 parent:11:1"
bash scripts/calls/pr30_ab.sh random-10k-t8.watched 3100000000 \
  "change:1:0 parent:1:0 parent:2:0 change:2:0 change:11:1"
