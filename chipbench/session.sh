#!/bin/bash
# The chip runs that a cell's limits and bounds are set from, phase by phase
# over all the cells given, so that a call cut short still holds each cell's
# earlier phases: a cold first run; the program's and the fp8 control's
# check readings on CAL_SEEDS seeds (calibrate.py, one process, windows of
# CAL_SECONDS); TRACED runs with --trace 1; two sets of SETSIZE runs on the
# same seeds.  Seeds are large and differ between output directories.
#   bash chipbench/session.sh OUT_DIR SECONDS SETSIZE CAL_SEEDS TRACED \
#       CELL:CAL_SECONDS [CELL:CAL_SECONDS ...]
out=$1; secs=$2; n=$3; ncal=$4; ntr=$5; shift 5
mkdir -p $out
base() { echo $(( $(echo -n "$out/$1" | cksum | cut -d' ' -f1) % 1000000 * 1000 + 2147483648 )); }
for spec in "$@"; do
  cell=${spec%%:*}; b=$(base $cell)
  python3 chipbench/sweep.py $out/$cell.cold $cell:$b:$secs:0
done
for spec in "$@"; do
  cell=${spec%%:*}; cal=${spec##*:}; b=$(base $cell)
  python3 chipbench/calibrate.py $cell $cal $(seq $((b+100)) $((b+99+ncal))) \
    > $out/$cell.cal.log 2>&1
  tail -3 $out/$cell.cal.log
done
for spec in "$@"; do
  cell=${spec%%:*}; b=$(base $cell); runs=""
  for i in $(seq 1 $ntr); do runs="$runs $cell:$((b+300+i)):$secs:1"; done
  python3 chipbench/sweep.py $out/$cell.T $runs
done
for set in A B; do
  for spec in "$@"; do
    cell=${spec%%:*}; b=$(base $cell); runs=""
    for i in $(seq 1 $n); do runs="$runs $cell:$((b+200+i)):$secs:0"; done
    python3 chipbench/sweep.py $out/$cell.$set $runs
  done
done
