#!/bin/sh
# The two sets of runs the contract asks for, one cell in one call: the
# same seeds in both sets, another seed for each run of a set.
#   sh benchmark/tools/sets.sh <workload> <runs a set> <seconds> [first seed]
w=$1; n=$2; s=$3; seed=${4:-2147483000}
for set in A B; do
  echo "==== set $set"
  sh benchmark/tools/repeat.sh $w $n $s $seed 0
done
