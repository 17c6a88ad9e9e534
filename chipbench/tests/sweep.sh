W=$1; SEC=$2; shift 2
mkdir -p chiprun_out
S=3200000000
for R in "$@"; do S=$((S+1)); python3 chipbench/tests/one_rate.py $W $S $SEC $R 2> chiprun_out/sweep_$R.err | tail -1; grep "chipbench" chiprun_out/sweep_$R.err | grep "window\|lateness\|set-up\|reference"; done
