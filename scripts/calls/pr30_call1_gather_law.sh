# PR 30, first chip call, before the engine is touched: the gather's law at
# the two random cells' sizes (scripts/gather_law.py).
#   chiprun --timeout 2400 -- bash scripts/calls/pr30_call1_gather_law.sh
set -x
mkdir -p chiprun_out
python3 scripts/gather_law.py --n 100000 --out chiprun_out/gather_law_100k.json \
  > chiprun_out/gather_law_100k.out 2> chiprun_out/gather_law_100k.err
echo "rc=$?"; tail -n 3 chiprun_out/gather_law_100k.err
python3 scripts/gather_law.py --n 10000 --k0 16 20 24 --out chiprun_out/gather_law_10k.json \
  > chiprun_out/gather_law_10k.out 2> chiprun_out/gather_law_10k.err
echo "rc=$?"; tail -n 3 chiprun_out/gather_law_10k.err
python3 - <<'PY'
import json
for f in ("chiprun_out/gather_law_100k.json", "chiprun_out/gather_law_10k.json"):
    for x in json.load(open(f)):
        print(x["n"], x["case"], x["w"], x.get("k0", x.get("k", "")), x["rows_out"], x["rows_table"],
              round(x["ms_median"], 3), round(x["ns_per_row"], 2), x.get("equal", ""))
PY
