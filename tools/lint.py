#!/usr/bin/env python
"""CI lint entry: graftlint's five passes + the artifact schema check,
with rule-count summary and non-zero exit on any finding.

    python tools/lint.py            # everything (jaxpr+shard+mem audits)
    python tools/lint.py --fast     # AST + locks + schema only
    python tools/lint.py --no-entry # audit without the ResNet build
    python tools/lint.py --json     # machine-readable findings (CI)
    python tools/lint.py --budgets  # current-vs-pinned budget tables
                                    # (read-only; comm + mem ratchets)

This is a thin wrapper over ``python -m paddle_tpu.analysis`` so CI
and humans run the identical engine; see docs/static_analysis.md for
the rule catalog and suppression policy.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    # CPU-platform forcing lives in ONE place: paddle_tpu.analysis.__main__.run(), which this calls
    argv = sys.argv[1:]
    if "--fast" in argv:
        # passes 4/5 (sharding/collective + memory audits) are
        # full-mode only: they compile the parallel programs on the
        # virtual mesh, and --fast must stay under ~10s on the 1-core
        # host
        argv = [a for a in argv if a != "--fast"] + [
            "--skip-jaxpr", "--skip-shard", "--skip-mem"]
    from paddle_tpu.analysis.__main__ import run

    return run(argv)


if __name__ == "__main__":
    sys.exit(main())
