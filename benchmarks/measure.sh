#!/bin/sh
# Two sets of runs of one cell, the same seeds in both, then traced runs:
#   sh benchmarks/measure.sh <cell> <seconds> "<seeds>" "<traced seeds>"
# One line per run is appended to chiprun_out/sets_<cell>.jsonl, and every number
# compared (or only printed) to chiprun_out/checks_<cell>.txt.
cell=$1; seconds=$2; seeds=$3; traced=$4
mkdir -p chiprun_out
out=chiprun_out/sets_$cell.jsonl
run() {  # set, seed, trace
    t0=$(date +%s)
    timeout 500 python3 benchmarks/run.py --workload "$cell" --seed "$2" --seconds "$seconds" \
        --trace "$3" > chiprun_out/last.out 2> chiprun_out/last.err
    rc=$?
    printf '{"set": "%s", "seed": %s, "trace": %s, "rc": %s, "took_s": %s, "line": %s}\n' \
        "$1" "$2" "$3" "$rc" "$(( $(date +%s) - t0 ))" "$(tail -n 1 chiprun_out/last.out | grep '^{' || echo null)" >> "$out"
    { echo "# set $1 seed $2 trace $3"; grep '^check\|^correct' chiprun_out/last.err; } >> chiprun_out/checks_$cell.txt
    [ "$rc" = 0 ] || tail -n 15 chiprun_out/last.err
}
for s in 1 2; do for seed in $seeds; do run "$s" "$seed" 0; done; done
for seed in $traced; do run t "$seed" 1; done
