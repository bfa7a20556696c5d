#!/bin/sh
# Call C of PR 24's second session: both cells on the final tree from a
# checkout that holds only what git would commit (see call_b.sh), ResNet's
# control and fault read again through benchmark/control.py (the first
# session's scripts were not kept), and one LSTM run under the final limits.
OUT=/root/repo/chiprun_out
mkdir -p $OUT
cd .archive_proof || exit 1
run() {   # workload set trace seed seconds
  python3 -m benchmark.run --workload $1 --seed $4 --seconds $5 --trace $3 \
    > $OUT/last.out 2> $OUT/last.err
  rc=$?
  res=$(tail -n 1 $OUT/last.out); [ -z "$res" ] && res=null
  echo "{\"set\": \"$2\", \"seed\": $4, \"trace\": $3, \"rc\": $rc, \"result\": $res}" >> $OUT/sets2_$1.jsonl
  echo "== $2 seed $4 trace $3 rc $rc" >> $OUT/sets2_$1.err.log
  grep "^\[bench\]" $OUT/last.err | grep -v "^\[bench\] device" | tail -n 14 >> $OUT/sets2_$1.err.log
  [ $rc -ne 0 ] && tail -n 30 $OUT/last.err
  echo "$1 $2 $4 rc $rc $(echo "$res" | cut -c1-500)"
}
R=resnet50_bf16.train_bs256
run $R first 0 2146000003 30
run $R C 0 2250000001 30
run $R C 0 2260000003 30
run $R C 0 2270000007 30
run $R T 1 2280000009 30
run lstm_imdb_h1280.train_bs256 C 0 2290000011 30
python3 -m benchmark.control --workload $R --seeds 2300000001,2310000003,2320000007 \
  --what control,half > $OUT/call_c_control.jsonl 2> $OUT/call_c_control.err
cut -c1-600 $OUT/call_c_control.jsonl
