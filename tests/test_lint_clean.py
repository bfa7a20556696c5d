"""Tier-1 enforcement: graftlint's five passes run CLEAN over this
repo with an EMPTY baseline.

This is the test that turns the rule catalog from advice into an
invariant: a PR that closure-captures params into a jit, down-casts a
mask, packs with jnp.pad, adds an unguarded hot-path jit, registers a
layer without a grad-matrix row, inverts a lock order, commits a
malformed evidence artifact, grows a parallel program's collective
footprint past comm_budget.toml, drops a zero1 pin, leaves a dead
shard rule, replicates a must-shard buffer past mem_budget.toml,
un-donates an aliased leaf, or materializes a full-gather temp fails
HERE, with file:line and a rule id.
"""

import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_pass1_ast_lints_clean():
    from paddle_tpu.analysis.ast_lints import run_pass1
    from paddle_tpu.analysis.findings import format_report
    findings, _suppressed = run_pass1(ROOT)
    assert not findings, "\n" + format_report(
        findings, "Pass 1 (AST invariant lints) found violations:")


def test_pass3_lock_order_clean_and_covers_threaded_modules():
    from paddle_tpu.analysis.findings import format_report
    from paddle_tpu.analysis.lockorder import run_pass3
    findings, checker = run_pass3(ROOT)
    assert not findings, "\n" + format_report(
        findings, "Pass 3 (lock-order) found violations:")
    for mod in ("paddle_tpu/serving/batcher.py",
                "paddle_tpu/serving/router.py",
                "paddle_tpu/serving/supervisor.py",
                "paddle_tpu/dist/master.py",
                "paddle_tpu/dist/checkpoint.py",
                "paddle_tpu/trainer/checkpoint.py",
                "paddle_tpu/data/prefetch.py",
                "paddle_tpu/obs/trace.py",
                "paddle_tpu/obs/flight.py",
                "paddle_tpu/obs/registry.py",
                "paddle_tpu/obs/events.py",
                "paddle_tpu/obs/health.py",
                "paddle_tpu/online/replay.py",
                "paddle_tpu/online/tailer.py",
                "paddle_tpu/online/publish.py",
                "paddle_tpu/online/loop.py"):
        assert mod in checker.modules
    # the analysis is not vacuous: it found the repo's locks (incl. the
    # replica router's state lock, RouterMetrics, the r14 replica
    # supervisor's bookkeeping lock, and the r15 obs plane's tracer +
    # metrics-registry locks) and real held-while-acquiring edges
    # (engine->metrics, master->store/chaos)
    assert len(checker.locks) >= 13
    assert len(checker.edges) >= 3
    sup_locks = [l for l in checker.locks if "supervisor" in str(l)]
    assert sup_locks == [
        "paddle_tpu.serving.supervisor.ReplicaSupervisor._lock"]
    assert not any("supervisor" in str(a) or "supervisor" in str(b)
                   for a, b in checker.edges), (
        "the supervisor lock must stay edge-free (bookkeeping only)")
    # r15 observability pins: the tracer's span-buffer lock and the
    # registry's provider-table lock exist AND sit edge-free in the
    # graph (obs never calls back into a subsystem under its locks;
    # subsystems record spans only outside their own). The flight
    # ring is LOCK-FREE by design — it must not contribute a lock at
    # all, or recording under the master RPC lock would grow edges.
    # r16 training-health pins join the same contract: the event
    # timeline's queue lock (serialization + file I/O happen on the
    # writer thread OUTSIDE it) and the health monitor's snapshot
    # lock (the monitor appends to the timeline / records flight
    # events only after releasing it).
    obs_locks = sorted(l for l in checker.locks if ".obs." in str(l))
    assert obs_locks == [
        "paddle_tpu.obs.events.EventLog._lock",
        "paddle_tpu.obs.health.HealthMonitor._lock",
        "paddle_tpu.obs.registry.MetricsRegistry._lock",
        "paddle_tpu.obs.trace.Tracer._lock"]
    assert not any(".obs." in str(a) or ".obs." in str(b)
                   for a, b in checker.edges), (
        "obs locks must stay edge-free (append/snapshot only)")
    # r20 online-loop pins: the replay writer's append lock is the
    # subsystem's ONLY lock (tailer scanner + publisher are lock-free
    # over the master's RLock / GIL-atomic state), and the chaos hit
    # firing under it is the one edge it may grow — the same
    # master->chaos precedent, needed so a seeded fault can lose the
    # row it targets instead of a neighboring one.
    online_locks = sorted(l for l in checker.locks
                          if ".online." in str(l))
    assert online_locks == [
        "paddle_tpu.online.replay.ReplayWriter._lock"]
    for a, b in checker.edges:
        if ".online." in str(a) or ".online." in str(b):
            assert (str(a), str(b)) == (
                "paddle_tpu.online.replay.ReplayWriter._lock",
                "paddle_tpu.testing.chaos.FaultPlan._lock"), (a, b)


def test_bench_schema_clean():
    from paddle_tpu.analysis.bench_schema import run_schema_check
    from paddle_tpu.analysis.findings import format_report
    findings = run_schema_check(ROOT)
    assert not findings, "\n" + format_report(
        findings, "BENCH artifact schema violations:")


def test_the_tree_has_one_benchmark():
    """``BENCHMARK.json`` + ``benchmark/`` is the one yardstick (``python3
    -m benchmark.run``): the root has no second bench program, and no
    source, tool or document sends a reader to one."""
    import glob

    from paddle_tpu.analysis.ast_lints import _iter_source_files
    assert not os.path.exists(os.path.join(ROOT, "bench.py"))
    naming = []
    for path in (list(_iter_source_files(ROOT, ("paddle_tpu", "tools")))
                 + glob.glob(os.path.join(ROOT, "docs", "*.md"))):
        with open(path, encoding="utf-8") as f:
            if "bench.py" in f.read():
                naming.append(os.path.relpath(path, ROOT))
    assert not naming, f"these still name bench.py: {sorted(naming)}"


def test_baseline_is_empty():
    """Policy: the baseline only parks findings while a new rule lands,
    and this tree is clean — any entry here needs a shrinking plan, and
    a PR that grows it fails."""
    from paddle_tpu.analysis.baseline import load_baseline
    assert load_baseline() == []


def test_pass2_jaxpr_audit_train_and_serving():
    """Trace-time invariants on the REAL programs: the bf16 train step
    donates params+opt fully (every leaf aliases an output) with masks
    surviving f32; the serving warm-path executables (_infer of a
    masked sequence scorer, _encode of a generating config) embed no
    model-sized constants and alias every aliasable donated buffer."""
    from paddle_tpu.analysis.findings import format_report
    from paddle_tpu.analysis.jaxpr_audit import (audit_serving,
                                                 audit_train_step)
    findings = audit_train_step(log=None) + audit_serving(log=None)
    assert not findings, "\n" + format_report(
        findings, "Pass 2 (jaxpr audit) found violations:")


@pytest.fixture(scope="module")
def compiled_programs():
    """ONE SPMD-compile of the nine traced programs feeding both the
    pass-4 and pass-5 tier-1 tests — the same sharing the CLI does
    (compile is the slowest step on the 1-core host)."""
    from paddle_tpu.analysis.shard_audit import compile_programs
    return compile_programs()


def test_pass4_shard_audit_clean_and_budget_pins_all_programs(
        compiled_programs):
    """The collective manifest of every traced parallel program —
    dp_train's grad all-reduce, zero1's ONE fused all-gather plus its
    pinned pack buffers, the GPipe handoff ppermutes, the TP model-axis
    reduce, the ring-attention rotation — matches comm_budget.toml
    exactly; placements honor each program's must-shard contract; the
    rule tables the programs construct carry no dead/shadowed keys.
    This is the FSDP-refactor contract: ROADMAP item 1 lands against
    these budgets, not against hope."""
    from paddle_tpu.analysis.findings import format_report
    from paddle_tpu.analysis.shard_audit import (PROGRAM_NAMES,
                                                 load_budget, run_pass4)
    findings = run_pass4(ROOT, log=None, programs=compiled_programs)
    assert not findings, "\n" + format_report(
        findings, "Pass 4 (sharding/collective audit) found violations:")
    budgeted = {e.program for e in load_budget()}
    for name in ("dp_train", "zero1", "pipeline", "tp_embed",
                 "seq_ring", "fsdp_train", "fsdp_pipe"):
        assert name in budgeted, f"{name} lost its pinned manifest"
    assert set(budgeted) <= set(PROGRAM_NAMES)
    # serving stays collective-free BY ABSENCE: any collective it
    # grows is unbudgeted drift (PT501), so no entry may name it —
    # the quantized twin holds to the same contract
    assert "serving_warm" not in budgeted
    assert "serving_quant" not in budgeted


def test_pass5_mem_audit_clean_and_budget_pins_all_programs(
        compiled_programs):
    """The per-device memory manifest of every traced program —
    memory_analysis() totals, the params/slots/activations role split,
    zero1's ~1/8 slot law, the pipeline 1/S stacked-body law, the TP
    half-table law, donation reaching every compiled alias set —
    matches mem_budget.toml exactly. Unlike the comm budget, EVERY
    program must be pinned: serving_warm's resident working set is the
    ROADMAP item-4 admission number, committed as an artifact. This is
    the second half of the FSDP-refactor contract (pass 4 pins what
    the programs communicate; this pins what they hold)."""
    from paddle_tpu.analysis.findings import format_report
    from paddle_tpu.analysis.mem_audit import load_mem_budget, run_pass5
    from paddle_tpu.analysis.shard_audit import PROGRAM_NAMES
    findings, manifests = run_pass5(ROOT, log=None,
                                    programs=compiled_programs)
    assert not findings, "\n" + format_report(
        findings, "Pass 5 (memory-footprint audit) found violations:")
    pinned = {e.program for e in load_mem_budget()}
    assert pinned == set(PROGRAM_NAMES), (
        "every traced program needs its memory manifest pinned "
        f"(missing: {set(PROGRAM_NAMES) - pinned})")
    # the item-4 admission number is a committed artifact
    by_name = {e.program: e for e in load_mem_budget()}
    serving = by_name["serving_warm"]
    assert serving.resident_bytes > 0
    assert manifests["serving_warm"]["resident_bytes"] == \
        serving.resident_bytes
    # the quantization win is a committed artifact too: the int8
    # scorer's pinned param residency beats its fp32 twin by >= 3x,
    # and the temp bytes pin how much of the dequant is fused. Under the
    # compiler of the previous pin the two programs' temps were equal;
    # under jax 0.9.0 XLA:CPU gives the quantized program exactly one
    # more buffer (read off its buffer assignment): the dequantized
    # f32[6,2] `_out.w0`, 48 B at a 64 B-aligned offset, materialized as
    # the dot's operand. The embedding table's dequant (384 B as f32)
    # still fuses into its gather. Anything beyond that one buffer fails.
    quant = by_name["serving_quant"]
    assert quant.param_bytes * 3 <= serving.param_bytes
    assert quant.temp_bytes - serving.temp_bytes == 64


def test_pass2_jaxpr_audit_entry():
    """The flagship driver entry: zero embedded-constant params (the
    ResNet-50 weights are traced arguments, never XLA constants) and a
    recorded donation declaration for the per-call image buffer."""
    from paddle_tpu.analysis.findings import format_report
    from paddle_tpu.analysis.jaxpr_audit import audit_entry
    findings = audit_entry(log=None)
    assert not findings, "\n" + format_report(
        findings, "Pass 2 (entry audit) found violations:")
