"""CLI: ``python -m paddle_tpu.analysis`` — run the four graftlint
passes (plus the artifact schema check) over the repo.

Exit status 0 = clean; 1 = findings; 2 = analysis itself failed.
``tools/lint.py`` is the thin CI wrapper over this module.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List

from paddle_tpu.analysis.baseline import apply_baseline, load_baseline
from paddle_tpu.analysis.findings import (RULE_BY_NAME, RULES, Finding,
                                          format_report, rule_counts)


from paddle_tpu.analysis._astutil import repo_root


def print_budget_tables(emit, as_json: bool = False) -> int:
    """``--budgets``: compile the traced programs once and print
    current-vs-pinned for both ratchet files. Strictly read-only — a
    drifted row is shown with a ``!`` marker, but updating a budget
    stays a deliberate manual edit (and the lint, not this report,
    enforces it). With ``--json``, the same data goes to stdout as the
    one JSON object the mode promises (tables to stderr via emit)."""
    from paddle_tpu.analysis import mem_audit, shard_audit
    programs = shard_audit.compile_programs(log=emit)
    comm = {e.key(): e for e in shard_audit.load_budget()}
    comm_rows = []
    seen = set()
    for cp in programs:
        manifest = shard_audit.collect_manifest(cp.hlo, cp.spec.mesh)
        for (op, axis), (n, nbytes) in sorted(manifest.items()):
            e = comm.get((cp.spec.name, op, axis))
            seen.add((cp.spec.name, op, axis))
            comm_rows.append({
                "program": cp.spec.name, "op": op, "axis": axis,
                "current": {"ops": n, "bytes": nbytes},
                "pinned": ({"ops": e.ops, "bytes": e.bytes}
                           if e else None)})
    for key in sorted(set(comm) - seen):
        e = comm[key]
        comm_rows.append({
            "program": key[0], "op": key[1], "axis": key[2],
            "current": None,
            "pinned": {"ops": e.ops, "bytes": e.bytes}})
    mem = {e.program: e for e in mem_audit.load_mem_budget()}
    mem_rows = []
    for cp in programs:
        manifest = mem_audit.memory_manifest(cp)
        e = mem.get(cp.spec.name)
        for f in mem_audit.MANIFEST_FIELDS:
            mem_rows.append({
                "program": cp.spec.name, "field": f,
                "current": manifest[f],
                "pinned": getattr(e, f) if e else None})
    for name in sorted(set(mem) - {cp.spec.name for cp in programs}):
        mem_rows.append({"program": name, "field": "(stale entry)",
                         "current": None,
                         "pinned": mem[name].arg_bytes})

    emit("\ncomm_budget.toml (pass 4) — current vs pinned:")
    emit(f"  {'program':<14}{'op':<20}{'axis':<12}"
         f"{'current':>16}{'pinned':>16}")
    for r in comm_rows:
        cur = (f"{r['current']['ops']}x/{r['current']['bytes']}B"
               if r["current"] else "(absent)")
        pin = (f"{r['pinned']['ops']}x/{r['pinned']['bytes']}B"
               if r["pinned"] else "UNPINNED")
        mark = " " if r["current"] == r["pinned"] else "!"
        emit(f" {mark}{r['program']:<14}{r['op']:<20}{r['axis']:<12}"
             f"{cur:>16}{pin:>16}")
    emit("\nmem_budget.toml (pass 5) — current vs pinned, "
         "bytes/device:")
    emit(f"  {'program':<14}{'field':<16}{'current':>12}{'pinned':>12}")
    for r in mem_rows:
        cur = r["current"] if r["current"] is not None else "(absent)"
        pin = r["pinned"] if r["pinned"] is not None else "UNPINNED"
        mark = " " if r["current"] == r["pinned"] else "!"
        emit(f" {mark}{r['program']:<14}{r['field']:<16}{cur:>12}"
             f"{pin:>12}")
    emit("\nread-only report: the ratchet is enforced by the lint "
         "passes, and budget edits stay deliberate")
    if as_json:
        print(json.dumps({"comm_budget": comm_rows,
                          "mem_budget": mem_rows}, indent=1))
    return 0


def run(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis",
        description="graftlint: framework-aware static analysis "
                    "(AST invariant lints, jaxpr/donation audits, "
                    "lock-order checker, sharding/collective audit, "
                    "artifact schema)")
    ap.add_argument("--root", default=repo_root())
    ap.add_argument("--skip-ast", action="store_true")
    ap.add_argument("--skip-jaxpr", action="store_true",
                    help="skip the trace-time audits (the slow pass)")
    ap.add_argument("--skip-locks", action="store_true")
    ap.add_argument("--skip-schema", action="store_true")
    ap.add_argument("--skip-shard", action="store_true",
                    help="skip pass 4 (sharding/collective audit of "
                         "the parallel programs; the slowest pass — "
                         "it compiles on the 8-device virtual mesh)")
    ap.add_argument("--skip-mem", action="store_true",
                    help="skip pass 5 (per-device memory-footprint "
                         "audit; reuses pass 4's compiles, so it is "
                         "cheap when pass 4 runs and compile-heavy "
                         "alone)")
    ap.add_argument("--budgets", action="store_true",
                    help="READ-ONLY: compile the traced programs and "
                         "print both budgets' current-vs-pinned "
                         "tables (comm_budget.toml + mem_budget.toml)"
                         ", then exit 0; regenerating a budget stays "
                         "a deliberate manual edit (ratchet policy)")
    ap.add_argument("--no-entry", action="store_true",
                    help="jaxpr pass without the flagship "
                         "__graft_entry__ build (~20s on 1 core)")
    ap.add_argument("--describe-locks", action="store_true",
                    help="print the lock graph even when clean")
    ap.add_argument("--baseline", default=None,
                    help="baseline.toml path (default: the package's)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output: one JSON object on "
                         "stdout (findings + counts); progress goes "
                         "to stderr")
    args = ap.parse_args(argv)

    # with --json, stdout is the machine contract — progress narration
    # moves to stderr so `python -m paddle_tpu.analysis --json | jq .`
    # always parses, INCLUDING the exit-2 paths (an audit crash still
    # hands the JSON consumer the findings collected before it)
    if args.json:
        def emit(*a, **k):
            print(*a, file=sys.stderr, **k)
    else:
        emit = print

    def finding_dicts(fs):
        return [{"rule": f.rule, "name": f.name, "file": f.path,
                 "line": f.line, "message": f.message} for f in fs]

    def fail_json(error: str, collected) -> int:
        if args.json:
            print(json.dumps({
                "error": error,
                "findings": finding_dicts(collected),
                "counts": rule_counts(collected),
            }, indent=1))
        return 2

    findings: List[Finding] = []
    inline_suppressed = 0
    # rule bands whose pass actually ran — stale-baseline detection is
    # scoped to these, or a baselined PT2xx entry would read as STALE
    # under --skip-jaxpr and the fast/full paths could never both pass
    ran_prefixes: List[str] = []
    t0 = time.time()
    pass4_dt = None
    pass5_dt = None
    mem_manifests = None
    # pass 4 and pass 5 audit the SAME compiled executables — whichever
    # runs first pays the compile, the other reuses it
    programs = None

    if args.budgets or not (args.skip_jaxpr and args.skip_shard
                            and args.skip_mem):
        # force the CPU platform before the first backend client: the
        # audits trace real programs at audit widths, and the pinned
        # manifests are XLA:CPU's. (jax itself is already imported by
        # the package, so the config is flipped, not the environment.)
        # Pass 4 additionally needs the 8-device virtual mesh.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")

    if args.budgets:
        return print_budget_tables(emit, as_json=args.json)

    if not args.skip_ast:
        from paddle_tpu.analysis.ast_lints import run_pass1
        fs, sup = run_pass1(args.root)
        emit(f"[pass 1] AST invariant lints: {len(fs)} findings "
             f"({sup} inline-suppressed)")
        findings.extend(fs)
        inline_suppressed += sup
        ran_prefixes.append("PT1")

    if not args.skip_locks:
        from paddle_tpu.analysis.lockorder import run_pass3
        fs, checker = run_pass3(args.root)
        emit(f"[pass 3] lock-order: {len(checker.locks)} locks, "
             f"{len(checker.edges)} order edges, {len(fs)} findings")
        if args.describe_locks:
            emit(checker.describe())
        findings.extend(fs)
        ran_prefixes.append("PT3")

    if not args.skip_schema:
        from paddle_tpu.analysis.bench_schema import run_schema_check
        fs = run_schema_check(args.root)
        emit(f"[schema] BENCH/MULTICHIP/ACCURACY artifacts: "
             f"{len(fs)} findings")
        findings.extend(fs)
        ran_prefixes.append("PT4")

    if not args.skip_jaxpr:
        from paddle_tpu.analysis.jaxpr_audit import run_pass2
        emit("[pass 2] jaxpr/lowering audits:")
        try:
            fs = run_pass2(args.root, log=emit,
                           include_entry=not args.no_entry)
        except Exception as e:  # noqa: BLE001 — surfaced as exit 2
            emit(f"[pass 2] AUDIT FAILED to run: {e!r}")
            if findings:
                # the crash must not bury what the other passes found
                emit(format_report(
                    findings, "findings collected before the crash:"))
            return fail_json(f"pass 2 audit failed to run: {e!r}",
                             findings)
        emit(f"[pass 2] {len(fs)} findings")
        findings.extend(fs)
        ran_prefixes.append("PT2")

    if not args.skip_shard:
        from paddle_tpu.analysis.shard_audit import (compile_programs,
                                                     run_pass4)
        emit("[pass 4] sharding/collective audit (8-device virtual "
             "mesh):")
        t4 = time.time()
        try:
            programs = compile_programs()
            fs = run_pass4(args.root, log=emit, programs=programs)
        except Exception as e:  # noqa: BLE001 — surfaced as exit 2
            emit(f"[pass 4] AUDIT FAILED to run: {e!r}")
            if findings:
                emit(format_report(
                    findings, "findings collected before the crash:"))
            return fail_json(f"pass 4 audit failed to run: {e!r}",
                             findings)
        pass4_dt = time.time() - t4
        emit(f"[pass 4] {len(fs)} findings ({pass4_dt:.1f}s)")
        findings.extend(fs)
        ran_prefixes.append("PT5")

    if not args.skip_mem:
        from paddle_tpu.analysis.mem_audit import run_pass5
        emit("[pass 5] per-device memory-footprint audit"
             + (" (reusing pass 4's compiles):" if programs is not None
                else " (compiling the traced programs):"))
        t5 = time.time()
        try:
            if programs is None:
                from paddle_tpu.analysis.shard_audit import \
                    compile_programs
                programs = compile_programs()
            fs, mem_manifests = run_pass5(args.root, log=emit,
                                          programs=programs)
        except Exception as e:  # noqa: BLE001 — surfaced as exit 2
            emit(f"[pass 5] AUDIT FAILED to run: {e!r}")
            if findings:
                emit(format_report(
                    findings, "findings collected before the crash:"))
            return fail_json(f"pass 5 audit failed to run: {e!r}",
                             findings)
        pass5_dt = time.time() - t5
        emit(f"[pass 5] {len(fs)} findings ({pass5_dt:.1f}s)")
        findings.extend(fs)
        ran_prefixes.append("PT6")

    try:
        entries = load_baseline(args.baseline)
    except ValueError as e:
        emit(f"baseline error: {e}")
        return fail_json(f"baseline error: {e}", findings)
    findings, baselined, stale = apply_baseline(findings, entries)
    from paddle_tpu.analysis.baseline import default_baseline_path
    baseline_rel = os.path.relpath(
        args.baseline or default_baseline_path(), args.root)
    for e in stale:
        rid = RULE_BY_NAME.get(e.rule, e.rule)
        if rid in RULES and not any(rid.startswith(p)
                                    for p in ran_prefixes):
            continue  # its pass was skipped this run — not evidence
        # unknown/typo'd rules fall through: they can never match any
        # pass's findings, so they are stale on EVERY run and must be
        # reported, or they sit in the baseline forever unexamined
        findings.append(Finding(
            rid, baseline_rel, 1,
            f"STALE baseline entry (rule={e.rule} path={e.path!r} "
            f"line={e.line}) matches nothing — delete it (the "
            "baseline only shrinks)"))

    dt = time.time() - t0
    # the pass-4/5 wall times ride the summary line so runtime creep in
    # the compile-heavy passes is visible run over run
    p4 = f", pass4 {pass4_dt:.1f}s" if pass4_dt is not None else ""
    p5 = f", pass5 {pass5_dt:.1f}s" if pass5_dt is not None else ""
    emit(f"\ngraftlint: {len(findings)} findings, "
         f"{baselined} baselined, {inline_suppressed} "
         f"inline-suppressed ({dt:.1f}s{p4}{p5})")
    if args.json:
        print(json.dumps({
            "findings": finding_dicts(findings),
            "counts": rule_counts(findings),
            "baselined": baselined,
            "inline_suppressed": inline_suppressed,
            "elapsed_s": round(dt, 3),
            "pass4_s": (round(pass4_dt, 3)
                        if pass4_dt is not None else None),
            "pass5_s": (round(pass5_dt, 3)
                        if pass5_dt is not None else None),
            # the MEM_* snapshot family: `--json | jq .mem_manifest
            # > MEM_rNN.json` commits a per-program per-device bytes
            # trend point; PT401 schema-checks committed ones
            "mem_manifest": ({"programs": mem_manifests}
                             if mem_manifests is not None else None),
        }, indent=1))
        return 1 if findings else 0
    if findings:
        print(format_report(findings))
        return 1
    print("rule catalog: " + ", ".join(
        f"{rid}({name})" for rid, (name, _) in sorted(RULES.items())))
    return 0


if __name__ == "__main__":
    sys.exit(run())
