# PR 30, third chip call: the change as git would commit it (tmp/final)
# against the parent; the lattice (the same program expected), one more pair
# and two more traced runs in each random cell.
export CHANGE_DIR=tmp/final
bash scripts/calls/pr30_ab.sh lattice-100k.steady 3200000000 \
  "parent:1:0 change:1:0 change:2:0 parent:2:0 change:11:1 parent:11:1"
bash scripts/calls/pr30_ab.sh random-100k.stepped 3000000000 \
  "parent:3:0 change:3:0 change:12:1 change:13:1"
bash scripts/calls/pr30_ab.sh random-10k-t8.watched 3100000000 \
  "parent:3:0 change:3:0 change:12:1 change:13:1"
