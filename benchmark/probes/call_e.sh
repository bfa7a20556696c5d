#!/bin/sh
# PR 25's chip calls: the parent commit against this PR's committed files, both unpacked from
# git (parent: git archive HEAD into .parent; change: git archive $(git write-tree) into
# .call/change; .call/overlay is the parent with this PR's BENCHMARK.json and benchmark/ laid
# over it, as the driver does for traced runs).
#   sh benchmark/probes/call_e.sh trace <cell> <seed> <overlay-seed>
#   sh benchmark/probes/call_e.sh pairs <cell> <seed>...    parent and change, order swapped by seed
#   sh benchmark/probes/call_e.sh armed <cell> <seed>...    change, its Tracer armed (slow_steps.py)
ROOT=/root/repo
OUT=$ROOT/chiprun_out
mkdir -p $OUT $ROOT/.call/trace
# one cache for the three checkouts: the machine's where it brings one
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-$ROOT/.call/cache}
what=$1; cell=$2; shift 2
run() {   # side directory module trace seed [more arguments]
  side=$1; dir=$2; module=$3; trace=$4; seed=$5; shift 5
  cd $ROOT/$dir || exit 1
  python3 -m $module --workload $cell --seed $seed --seconds 30 --trace $trace "$@" \
    > $OUT/last.out 2> $OUT/last.err
  rc=$?
  res=$(tail -n 1 $OUT/last.out); [ -z "$res" ] && res=null
  echo "{\"side\": \"$side\", \"seed\": $seed, \"trace\": $trace, \"rc\": $rc, \"result\": $res}" >> $OUT/pr25_$cell.jsonl
  echo "== $side seed $seed trace $trace rc $rc" >> $OUT/pr25_$cell.err.log
  grep "^\[bench\]\|^\[slow_steps\]" $OUT/last.err | grep -v "^\[bench\] device" | tail -n 16 >> $OUT/pr25_$cell.err.log
  [ $rc -ne 0 ] && tail -n 30 $OUT/last.err
  echo "$cell $side $seed trace $trace rc $rc $(echo "$res" | cut -c1-700)"
}
case $what in
trace)
  kept=$ROOT/.call/trace/$cell.xplane.pb
  run change .call/change benchmark.run 1 $1 --keep-trace $kept
  python3 -m benchmark.probes.gaps $kept > $OUT/pr25_gaps_$cell.txt 2> $OUT/last.err || tail -n 20 $OUT/last.err
  grep -v "^{" $OUT/pr25_gaps_$cell.txt
  run overlay .call/overlay benchmark.run 1 $2
  ;;
pairs)
  i=0
  for seed in "$@"; do
    i=$((i + 1))
    if [ $((i % 2)) -eq 1 ]; then
      run parent .parent benchmark.run 0 $seed; run change .call/change benchmark.run 0 $seed
    else
      run change .call/change benchmark.run 0 $seed; run parent .parent benchmark.run 0 $seed
    fi
  done
  ;;
armed)
  for seed in "$@"; do
    run armed .call/change benchmark.probes.slow_steps 0 $seed
  done
  grep "^\[slow_steps\]" $OUT/pr25_$cell.err.log | cut -c1-1200
  ;;
esac
