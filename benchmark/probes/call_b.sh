#!/bin/sh
# Call B of PR 24's second session: the LSTM cell from a checkout that holds
# only what git would commit (chiprun -- sh benchmark/probes/call_b.sh, after
# `git add -A; git archive $(git write-tree) | tar -x -C .archive_proof`).
# 1. a first run, which compiles; 2. six seeds whose first batch holds
# exactly 128 rows of each label (the adversarial case of Probe P: the
# gradient's common part cancels); 3. the control and the faults planted in
# the program, three seeds each; 4. two sets of six runs at run_seconds, the
# same seeds in both; 5. three traced runs.
W=lstm_imdb_h1280.train_bs256
OUT=/root/repo/chiprun_out
mkdir -p $OUT
cd .archive_proof || exit 1
run() {   # set trace seed seconds
  python3 -m benchmark.run --workload $W --seed $3 --seconds $4 --trace $2 \
    > $OUT/last.out 2> $OUT/last.err
  rc=$?
  res=$(tail -n 1 $OUT/last.out); [ -z "$res" ] && res=null
  echo "{\"set\": \"$1\", \"seed\": $3, \"trace\": $2, \"rc\": $rc, \"result\": $res}" >> $OUT/sets2_$W.jsonl
  echo "== $1 seed $3 trace $2 rc $rc" >> $OUT/sets2_$W.err.log
  grep "^\[bench\]" $OUT/last.err | grep -v "^\[bench\] device" | tail -n 14 >> $OUT/sets2_$W.err.log
  [ $rc -ne 0 ] && tail -n 30 $OUT/last.err
  echo "$1 $3 rc $rc $(echo "$res" | cut -c1-400)"
}
run first 0 2146000001 5
python3 -m benchmark.probes.probe $W --seeds 3500001816,3500002567,3500003620,3500002200,3500002586,3500002711 \
  --program config --against stated > $OUT/call_b_balanced.log 2>&1
grep "vs stated\|== " $OUT/call_b_balanced.log
python3 -m benchmark.control --workload $W --seeds 2220000001,2230000003,2240000005 \
  --what control,half,unchanged > $OUT/call_b_control.jsonl 2> $OUT/call_b_control.err
cut -c1-700 $OUT/call_b_control.jsonl
for s in 2147483701 2147484001 2150000003 2160000005 2170000007 2180000009; do run A 0 $s 30; done
for s in 2147483701 2147484001 2150000003 2160000005 2170000007 2180000009; do run B 0 $s 30; done
for s in 2190000011 2200000013 2210000017; do run T 1 $s 30; done
