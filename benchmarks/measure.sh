#!/bin/sh
# A builder's aid, not part of a run: the runs the contract asks of a new cell,
# made in one chip call so that they share the compile cache.
#
#   chiprun --timeout 3000 -- sh benchmarks/measure.sh <cell> <seconds> <set> [first]
#
# <set> = n runs set n, the same six seeds every time (0 = no set); first = 1
# makes one run before it (a checkout's first run compiles) and one --trace 1
# run. Each run's output goes to ${OUT:-chiprun_out}/<cell>/ and its last line
# is echoed. With DEADLINE (epoch seconds) set, no run starts after it.
cell=$1; seconds=$2; set=$3; first=${4:-0}
out=${OUT:-chiprun_out}/$cell; mkdir -p "$out"
seeds="2147483659 1000003 2100000011 7 1234567891 1999999973"
one() {  # name seed trace
    if [ -n "$DEADLINE" ] && [ "$(date +%s)" -gt "$DEADLINE" ]; then
        echo "== $1 not run: past the deadline"; return 0
    fi
    python3 benchmarks/run.py --workload "$cell" --seed "$2" \
        --seconds "$seconds" --trace "$3" > "$out/$1.log" 2> "$out/$1.err"
    rc=$?
    echo "== $1 seed=$2 trace=$3 rc=$rc $(tail -n 1 "$out/$1.log" | cut -c1-1500)"
    return $rc
}
if [ "$first" = 1 ]; then
    one first 2147483659 0 || { tail -n 30 "$out/first.err" "$out/first.log"; exit 1; }
    grep -h "^\[bench\]" "$out/first.log"
    one traced 2147483659 1
    grep -h "^\[bench\]" "$out/traced.log"; tail -n 5 "$out/traced.err"
fi
if [ "$set" != 0 ]; then
    for seed in $seeds; do one "set${set}_seed${seed}" "$seed" 0; done
fi
