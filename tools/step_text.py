r"""A cell's compiled train step as text, and whether two such texts are
one program: what a PR that changes only names (``jax.named_scope``,
a moved line) has to show, with no chip.

    python3 tools/step_text.py dump <cell> FILE     from a checkout's root:
        ``python3 -m benchmark.rehearse <cell>`` (compiled for a described
        v5e; it prints the step's bytes and Mosaic calls), its text kept
    python3 tools/step_text.py same FILE FILE

``same`` removes every metadata and compares the rest byte for byte:
each instruction's ``metadata={...}``, the tables of files, functions
and stack frames it points to, and the locations inside every Mosaic
call's serialized body (MLIR bytecode in ``backend_config``, which
carries the line numbers of the kernel's callers: each body is parsed
and printed without them). It prints ``same`` or the first
instructions that differ, and exits 0 or 1. How PR 35 showed that its
scopes leave the three language-model steps as they were.
"""

from __future__ import annotations

import base64
import hashlib
import os
import re
import sys

TABLES = re.compile(r"\n(?:FileNames|FunctionNames|FileLocations|StackFrames)"
                    r"\n(?:\d+ .*\n)*")
METADATA = re.compile(r",? ?metadata=\{[^{}]*\}")
BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def kernel_text(body: str) -> str:
    """A Mosaic call's serialized module, printed without locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True      # 'stable_mosaic'
    with ctx:
        return ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False)


def bare(text: str) -> str:
    """The compiled text with every metadata removed."""
    text = METADATA.sub("", TABLES.sub("\n", text))
    return BODY.sub(lambda m: '"body":"%s"' % hashlib.sha256(
        kernel_text(m.group(1)).encode()).hexdigest(), text)


def dump(cell: str, out: str) -> int:
    # `benchmark.rehearse` prints its counts and keeps no text, and is the
    # benchmark's to change: its call to `as_text` is listened in on
    sys.path.insert(0, os.getcwd())
    import jax.stages
    as_text = jax.stages.Compiled.as_text

    def keep(self, *a, **kw):
        text = as_text(self, *a, **kw)
        with open(out, "w") as f:
            f.write(text)
        return text

    jax.stages.Compiled.as_text = keep
    from benchmark import rehearse
    return rehearse.main([cell])


def same(a: str, b: str) -> int:
    with open(a) as f, open(b) as g:
        left, right = bare(f.read()), bare(g.read())
    if left == right:
        print(f"same: {len(left):,} bytes without metadata, "
              f"{len(BODY.findall(left))} Mosaic bodies, sha256 "
              f"{hashlib.sha256(left.encode()).hexdigest()[:16]}")
        return 0
    pairs = [(x, y) for x, y in zip(left.splitlines(), right.splitlines())
             if x != y]
    print(f"differ: {len(left):,} and {len(right):,} bytes, "
          f"{len(pairs)} lines of the shorter's")
    for x, y in pairs[:5]:
        print("<", x[:240])
        print(">", y[:240])
    return 1


def main(argv) -> int:
    if len(argv) == 3 and argv[0] == "dump":
        return dump(argv[1], argv[2])
    if len(argv) == 3 and argv[0] == "same":
        return same(argv[1], argv[2])
    print(__doc__, file=sys.stderr)
    return 2


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
