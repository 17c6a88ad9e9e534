# A builder's tool: the two sets of six runs (same seeds in both) and
# three traced runs of one cell, in one call on the chip.
#   bash chipbench/tests/full_sets.sh <cell> <seconds> <first seed>
W=$1; SEC=$2; S0=$3
mkdir -p chiprun_out
OUT=chiprun_out/$W.sets.jsonl; ERR=chiprun_out/$W.sets.err
for SET in 1 2; do for I in 1 2 3 4 5 6; do
  S=$((S0 + I * 1000003))
  python3 chipbench/run.py --workload $W --seed $S --seconds $SEC --trace 0 2> chiprun_out/.err | tail -1 | sed "s/^/{\"set\": $SET, \"seed\": $S, \"rc\": $?, \"line\": /; s/$/}/" >> $OUT
  grep "chipbench\|Error\|error" chiprun_out/.err | tail -12 >> $ERR
done; done
for I in 7 8 9; do
  S=$((S0 + I * 1000003))
  CHIPBENCH_DUMP=chiprun_out/dump python3 chipbench/run.py --workload $W --seed $S --seconds $SEC --trace 1 2> chiprun_out/.err | tail -1 | sed "s/^/{\"set\": \"trace\", \"seed\": $S, \"line\": /; s/$/}/" >> $OUT
  grep "chipbench\|Error\|error" chiprun_out/.err | tail -24 >> $ERR
done
rm -f chiprun_out/.err
wc -l $OUT
