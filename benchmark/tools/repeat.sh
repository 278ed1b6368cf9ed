#!/bin/sh
# Runs of one cell back to back, one process each, seeds counting up:
#   sh benchmark/tools/repeat.sh <workload> <runs> <seconds> <first seed> [trace]
# Prints each run's set-up line and result line; everything goes to
# chiprun_out/<workload>.<first seed>.log as well.
w=$1; n=$2; s=$3; seed=$4; trace=${5:-0}
mkdir -p chiprun_out
i=0
while [ $i -lt $n ]; do
  echo "== run $i seed $((seed + i))"
  python3 benchmark/run.py --workload $w --seed $((seed + i)) --seconds $s --trace $trace \
    2>>chiprun_out/$w.$seed.err >chiprun_out/$w.last
  echo "== exit $?"
  cat chiprun_out/$w.last | tee -a chiprun_out/$w.$seed.log
  i=$((i + 1))
done
