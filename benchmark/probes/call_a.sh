#!/bin/sh
# Call A of PR 24's second session (chiprun -- sh benchmark/probes/call_a.sh):
# the readings behind lstm_imdb_h1280.train_bs256's limits (PERF.md, Probe P).
# 1. is a float32 product at default precision the one-pass product the
#    reference computes (plain.one_pass_matmul), forward and backward?
# 2. the configuration as PR 24 first had it (no L2, no clipping), seed
#    123456789 twice and two others: the program against the float32-highest
#    reference (the outlier reading), against the stated arithmetic, and
#    traced under highest (the second witness)
# 3. the configuration as it is, three seeds in full, ten more in short
set -x
W=lstm_imdb_h1280.train_bs256
mkdir -p chiprun_out
python3 - <<'PY' 2>&1 | tee chiprun_out/call_a_products.log
import jax, jax.numpy as jnp
from benchmark.reference import plain
print(jax.devices())
k = jax.random.split(jax.random.PRNGKey(7), 3)
a = jax.random.normal(k[0], (25600, 1280)); b = jax.random.normal(k[1], (1280, 5120)) / 36
g = jax.random.normal(k[2], (25600, 5120))
rel = lambda x, y: float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y))
mm = plain.one_pass_matmul("bfloat16")
f = lambda dot: jax.jit(lambda a, b: jax.vjp(dot, a, b)[1](g) + (dot(a, b),))
da, db, y = f(lambda a, b: a @ b)(a, b)
ea, eb, z = f(mm)(a, b)
with jax.default_matmul_precision("highest"):
    ha, hb, x = f(lambda a, b: a @ b)(a, b)
print("default against one-pass: product", rel(y, z), "da", rel(da, ea), "db", rel(db, eb))
print("default against highest:  product", rel(y, x), "da", rel(da, ha), "db", rel(db, hb))
PY
OLD="--set optimizer.args.l2_rate=0 --set optimizer.args.gradient_clipping_threshold=0"
python3 -m benchmark.probes.probe $W --seeds 123456789,123456789,11,2147483659 $OLD \
  --program config,highest --against stated,highest --elements > chiprun_out/call_a_old.log 2>&1
grep -v "sign differs" chiprun_out/call_a_old.log | grep "\[probe\]"
python3 -m benchmark.probes.probe $W --seeds 123456789,3100000007,42 \
  --program config,bf16,highest --against stated,highest --faults --arith carry,store --elements \
  > chiprun_out/call_a_full.log 2>&1
grep -v "sign differs" chiprun_out/call_a_full.log | grep "\[probe\]"
python3 -m benchmark.probes.probe $W --seeds 7,1000003,2147483647,2147483648,2200000001,2500000033,2900000011,3000000019,3300000077,4000000007 \
  --program config,bf16 --against stated > chiprun_out/call_a_short.log 2>&1
grep "\[probe\]" chiprun_out/call_a_short.log
