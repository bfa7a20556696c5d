#!/bin/sh
# Call D of PR 24's second session: the final tree from a checkout of the committed files, one
# run of each kind, and the refusal to run where only BENCHMARK.json and the paths are.
OUT=/root/repo/chiprun_out
cd .archive_proof || exit 1
for spec in "lstm_imdb_h1280.train_bs256 2330000011 0" "lstm_imdb_h1280.train_bs256 2340000013 1" "resnet50_bf16.train_bs256 2350000017 0"; do
  set -- $spec
  python3 -m benchmark.run --workload $1 --seed $2 --seconds 30 --trace $3 > $OUT/final.out 2> $OUT/final.err; rc=$?
  echo "== $1 $2 trace $3 rc $rc"; tail -n 9 $OUT/final.err | cut -c1-200; tail -n 1 $OUT/final.out | cut -c1-1500
done
# a directory that holds only BENCHMARK.json and the paths: exits non-zero, prints no result
mkdir -p /root/repo/.archive_proof/bare && cp -r BENCHMARK.json benchmark /root/repo/.archive_proof/bare/ && cd /root/repo/.archive_proof/bare
python3 -m benchmark.run --workload lstm_imdb_h1280.train_bs256 --seed 1 --seconds 1 --trace 0 > $OUT/bare.out 2> $OUT/bare.err; echo "bare rc $? stdout bytes $(wc -c < $OUT/bare.out)"; tail -n 2 $OUT/bare.err | cut -c1-300
