"""Pass 1 — AST invariant lints over paddle_tpu/ + tests/ + tools/.

Each rule is the static twin of a runtime invariant this repo already
enforces (or a convention that so far lived only in CLAUDE.md):

- PT101 jit-closure-capture: params/feeds must be traced jit arguments.
  XLA treats closure captures as program constants; the r10 measurement
  was ~4x/step deopt when the decode step closed over its params
  (core/generation.py:_make_step docstring).
- PT102 mask-bf16-cast: masks are f32 count data
  (trainer/trainer.py:_cast_compute); a bf16 mask saturates at 256.
- PT103 pad-in-bitexact-pack: optim/zero1.py packs with concatenate —
  a jnp.pad fused into the elementwise update breaks XLA:CPU
  bit-exactness. The rule bans jnp.pad in paddle_tpu/optim/ and in any
  function marked ``# graftlint: bit-exact``.
- PT104 unguarded-jit: persistent jits in hot-path modules need a
  RecompileGuard (data/prefetch.py) or a ``# graftlint: jit-cache:``
  note naming the cache policy that bounds them.
- PT105 broad-pkill: ``pkill -f`` with a short/generic pattern matches
  the invoking shell's own command line (the exit-144 self-kill).
- PT106 layer-grad-matrix-row: every ``register_layer`` canonical type
  needs a row in tests/test_layer_grad_matrix.py — the static version
  of test_registry_fully_covered, so the gap is visible at lint time
  (no test collection needed).

Suppression: ``# graftlint: disable=PT101`` (or the rule's short name)
on the flagged line or the line above. Suppressions are counted and
reported; policy in docs/static_analysis.md.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from paddle_tpu.analysis.findings import RULE_BY_NAME, Finding

# ---------------------------------------------------------------- config

# PT104 scope: modules whose jitted callables sit on a request/step hot
# path. Library builders (parallel/moe.py, parallel/pipeline.py,
# core/network.py init) hand the jit to a caller who owns cache policy
# and are deliberately out of scope — see docs/static_analysis.md.
HOT_PATH_MODULES = (
    "paddle_tpu/trainer/trainer.py",
    "paddle_tpu/serving/",
    "paddle_tpu/core/generation.py",
    "paddle_tpu/models/",
    "paddle_tpu/compat/swig_api.py",
)

# PT101: names that conventionally bind batch/param arrays in this repo.
ARRAYISH_NAMES = {
    "feed", "feeds", "feed_dict", "params", "tparams", "nparams",
    "pparams", "batch", "weights", "noise", "grads", "mask", "masks",
    "opt_state",
}
ARRAYISH_SUFFIXES = ("_feed", "_params", "_batch", "_mask")

# PT101: calls whose result is (or contains) device/numpy arrays.
_ARRAY_CALL_EXACT = {
    "jax.device_put", "jax.device_get", "np.asarray", "np.array",
    "np.ones", "np.zeros", "np.full", "numpy.asarray", "numpy.array",
}
_ARRAY_CALL_PREFIX = ("jnp.", "jax.numpy.", "jax.random.")
_ARRAY_CALL_SUFFIX = (".shard_batch",)

_SUPPRESS_RE = re.compile(r"#\s*graftlint:\s*disable=([A-Za-z0-9_,\- ]+)")
_JIT_CACHE_RE = re.compile(r"#\s*graftlint:\s*jit-cache:")
_BIT_EXACT_RE = re.compile(r"#\s*graftlint:\s*bit-exact")

_LOW_DTYPES = ("bfloat16", "float16", "bf16", "f16", "half")


from paddle_tpu.analysis._astutil import dotted as _dotted


def _is_array_call(node: ast.AST) -> bool:
    """Does this expression produce an array (recursively through
    IfExp/BinOp/BoolOp shells)?"""
    if isinstance(node, ast.IfExp):
        return _is_array_call(node.body) or _is_array_call(node.orelse)
    if isinstance(node, ast.BinOp):
        return _is_array_call(node.left) or _is_array_call(node.right)
    if isinstance(node, ast.BoolOp):
        return any(_is_array_call(v) for v in node.values)
    if not isinstance(node, ast.Call):
        return False
    d = _dotted(node.func)
    if d is None:
        return False
    if d in _ARRAY_CALL_EXACT:
        return True
    if d.startswith(_ARRAY_CALL_PREFIX):
        return True
    if any(d.endswith(s) for s in _ARRAY_CALL_SUFFIX):
        return True
    if d.endswith(".astype"):
        return True
    return False


def _arrayish_name(name: str) -> bool:
    return (name in ARRAYISH_NAMES
            or any(name.endswith(s) for s in ARRAYISH_SUFFIXES))


def _name_targets(tgt: ast.AST) -> List[str]:
    """Plain names BOUND by an assignment target. A Name inside an
    Attribute/Subscript target (``self.x = ...``) is a *load* of the
    base object, not a binding of that name — walking it naively makes
    ``self`` look array-bound the first time ``self.rng = PRNGKey(...)``
    appears."""
    if isinstance(tgt, ast.Name):
        return [tgt.id]
    if isinstance(tgt, (ast.Tuple, ast.List)):
        out: List[str] = []
        for elt in tgt.elts:
            out.extend(_name_targets(elt))
        return out
    if isinstance(tgt, ast.Starred):
        return _name_targets(tgt.value)
    return []


class _Scope:
    """One function (or module) scope: names it binds, and the assign
    RHS nodes per name (for array-likeness checks)."""

    def __init__(self, node: ast.AST, parent: Optional["_Scope"]):
        self.node = node
        self.parent = parent
        self.bound: Set[str] = set()
        self.assigns: Dict[str, List[ast.AST]] = {}

    @property
    def is_function(self) -> bool:
        return isinstance(self.node,
                          (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.Lambda))

    def bind(self, name: str, rhs: Optional[ast.AST] = None):
        self.bound.add(name)
        if rhs is not None:
            self.assigns.setdefault(name, []).append(rhs)


def _bound_names(fn: ast.AST) -> Set[str]:
    """Names bound inside a function body (args, assignments, defs,
    imports, loop/with/comprehension targets) — NOT descending into
    nested function bodies' own locals is unnecessary for free-variable
    math: a name bound anywhere inside the subtree is not free."""
    bound: Set[str] = set()

    class V(ast.NodeVisitor):
        def visit_arg(self, node):
            bound.add(node.arg)

        def visit_Name(self, node):
            if isinstance(node.ctx, (ast.Store, ast.Del)):
                bound.add(node.id)

        def visit_FunctionDef(self, node):
            bound.add(node.name)
            self.generic_visit(node)

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_ClassDef(self, node):
            bound.add(node.name)
            self.generic_visit(node)

        def visit_Import(self, node):
            for a in node.names:
                bound.add((a.asname or a.name).split(".")[0])

        visit_ImportFrom = visit_Import

    v = V()
    if isinstance(fn, ast.Lambda):
        for a in (fn.args.args + fn.args.posonlyargs + fn.args.kwonlyargs):
            bound.add(a.arg)
        if fn.args.vararg:
            bound.add(fn.args.vararg.arg)
        if fn.args.kwarg:
            bound.add(fn.args.kwarg.arg)
        v.visit(fn.body)
    else:
        for a in (fn.args.args + fn.args.posonlyargs + fn.args.kwonlyargs):
            bound.add(a.arg)
        if fn.args.vararg:
            bound.add(fn.args.vararg.arg)
        if fn.args.kwarg:
            bound.add(fn.args.kwarg.arg)
        for stmt in fn.body:
            v.visit(stmt)
    return bound


def _free_loads(fn: ast.AST) -> List[ast.Name]:
    bound = _bound_names(fn)
    loads: List[ast.Name] = []
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    for stmt in body:
        for node in ast.walk(stmt):
            if (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id not in bound):
                loads.append(node)
    return loads


class FileLinter:
    """All Pass-1 rules over one source file."""

    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.findings: List[Finding] = []
        self.suppressed = 0
        self._scopes: List[_Scope] = []
        self._module_scope = _Scope(self.tree, None)
        # one child->parent map per file; several rules consult it
        self._parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self._parents[child] = node

    # -------------------------------------------------- suppressions
    def _annotation_lines(self, line: int):
        """The flagged line plus the contiguous comment block above it
        (suppressions/policy notes may need more than one line)."""
        if 1 <= line <= len(self.lines):
            yield self.lines[line - 1]
        ln = line - 1
        while ln >= 1 and self.lines[ln - 1].lstrip().startswith("#"):
            yield self.lines[ln - 1]
            ln -= 1

    def _suppressed_rules(self, line: int) -> Set[str]:
        out: Set[str] = set()
        for text in self._annotation_lines(line):
            m = _SUPPRESS_RE.search(text)
            if m:
                for tok in re.split(r"[,\s]+", m.group(1).strip()):
                    if not tok:
                        continue
                    out.add(RULE_BY_NAME.get(tok, tok))
        return out

    def _emit(self, rule: str, line: int, msg: str):
        if rule in self._suppressed_rules(line):
            self.suppressed += 1
            return
        self.findings.append(Finding(rule, self.rel, line, msg))

    def _line_has(self, line: int, regex) -> bool:
        return any(regex.search(text)
                   for text in self._annotation_lines(line))

    # ------------------------------------------------------ driving
    def run(self) -> List[Finding]:
        self._collect_scopes()
        self._lint_jit_sites()
        self._lint_mask_casts()
        self._lint_pad_bitexact()
        self._lint_pkill()
        return self.findings

    # ------------------------------------------- scope bookkeeping
    def _collect_scopes(self):
        """Map every function node to its scope object + parent chain,
        and record assignments per scope (for PT101 binding lookups)."""
        self.scope_of: Dict[ast.AST, _Scope] = {}

        def walk(node: ast.AST, scope: _Scope):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    sub = _Scope(child, scope)
                    self.scope_of[child] = sub
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef)):
                        scope.bind(child.name)
                    # the function's PARAMETERS are bindings of its
                    # scope: a jitted inner function capturing an
                    # enclosing function's `feed`/`params` ARGUMENT is
                    # the canonical PT101 shape and must resolve to a
                    # function scope, not fall through as a global
                    a = child.args
                    for arg in (a.args + a.posonlyargs + a.kwonlyargs):
                        sub.bind(arg.arg)
                    if a.vararg:
                        sub.bind(a.vararg.arg)
                    if a.kwarg:
                        sub.bind(a.kwarg.arg)
                    walk(child, sub)
                    continue
                if isinstance(child, ast.ClassDef):
                    scope.bind(child.name)
                    # class body: functions inside still close over the
                    # enclosing FUNCTION scope, not the class scope
                    walk(child, scope)
                    continue
                if isinstance(child, ast.Assign):
                    for tgt in child.targets:
                        for n in _name_targets(tgt):
                            scope.bind(n, child.value)
                elif isinstance(child, ast.AnnAssign):
                    if isinstance(child.target, ast.Name):
                        scope.bind(child.target.id, child.value)
                elif isinstance(child, ast.AugAssign):
                    if isinstance(child.target, ast.Name):
                        scope.bind(child.target.id, child.value)
                elif isinstance(child, (ast.For, ast.AsyncFor)):
                    for n in _name_targets(child.target):
                        scope.bind(n, child.iter)
                elif isinstance(child, (ast.With, ast.AsyncWith)):
                    for item in child.items:
                        if item.optional_vars is not None:
                            for n in _name_targets(item.optional_vars):
                                scope.bind(n, item.context_expr)
                elif isinstance(child, (ast.Import, ast.ImportFrom)):
                    for a in child.names:
                        scope.bind((a.asname or a.name).split(".")[0])
                walk(child, scope)

        self.scope_of[self.tree] = self._module_scope
        walk(self.tree, self._module_scope)

    # --------------------------------------------------- PT101/PT104
    def _jitted_functions(self) -> List[Tuple[ast.AST, ast.AST, bool]]:
        """(function-node, report-node, persistent?) for every jit site.

        persistent = the jitted callable outlives the statement (bound
        to a name/attribute or returned), as opposed to
        ``jax.jit(f)(x)`` one-shots.
        """
        out: List[Tuple[ast.AST, ast.AST, bool]] = []
        parents = self._parents

        def local_fn(name: str, at: ast.AST) -> Optional[ast.AST]:
            """Resolve a Name to a FunctionDef/Lambda in the scope
            chain of the jit call site."""
            scope = self._enclosing_scope(at)
            while scope is not None:
                if name in scope.assigns:
                    for rhs in scope.assigns[name]:
                        if isinstance(rhs, ast.Lambda):
                            return rhs
                # sibling def in the scope's body
                body = getattr(scope.node, "body", [])
                if isinstance(body, list):
                    for stmt in body:
                        if (isinstance(stmt, (ast.FunctionDef,
                                              ast.AsyncFunctionDef))
                                and stmt.name == name):
                            return stmt
                scope = scope.parent
            return None

        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    d = _dotted(dec)
                    dc = _dotted(dec.func) if isinstance(dec, ast.Call) \
                        else None
                    if d in ("jax.jit", "jit", "pjit", "jax.pjit") or (
                            dc in ("functools.partial", "partial")
                            and isinstance(dec, ast.Call) and dec.args
                            and _dotted(dec.args[0]) in (
                                "jax.jit", "jit", "pjit", "jax.pjit")):
                        out.append((node, node, True))
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d not in ("jax.jit", "jit", "pjit", "jax.pjit"):
                    continue
                parent = parents.get(node)
                persistent = not (isinstance(parent, ast.Call)
                                  and parent.func is node)
                fn_node: Optional[ast.AST] = None
                if node.args:
                    arg0 = node.args[0]
                    if isinstance(arg0, ast.Lambda):
                        fn_node = arg0
                    elif isinstance(arg0, ast.Name):
                        fn_node = local_fn(arg0.id, node)
                out.append((fn_node, node, persistent))
        return out

    def _enclosing_scope(self, node: ast.AST) -> _Scope:
        """Nearest function scope containing ``node`` (by position)."""
        best = self._module_scope
        best_span = None
        for fn, scope in self.scope_of.items():
            if fn is self.tree:
                continue
            if (hasattr(fn, "lineno")
                    and fn.lineno <= node.lineno
                    and node.lineno <= (fn.end_lineno or fn.lineno)):
                span = (fn.end_lineno or fn.lineno) - fn.lineno
                if best_span is None or span < best_span:
                    best, best_span = scope, span
        return best

    def _lint_jit_sites(self):
        guard_args = self._recompile_guard_args()
        for fn_node, report, persistent in self._jitted_functions():
            line = report.lineno
            # ------------------------------------------------ PT101
            if fn_node is not None:
                scope = self.scope_of.get(
                    fn_node, self._enclosing_scope(fn_node))
                flagged: Set[str] = set()
                for load in _free_loads(fn_node):
                    name = load.id
                    if name in flagged:
                        continue
                    binding_scope = scope.parent if scope else None
                    s = binding_scope
                    while s is not None and name not in s.bound:
                        s = s.parent
                    if s is None or not s.is_function:
                        continue  # global/builtin: config, nets, modules
                    rhs_list = s.assigns.get(name, [])
                    arrayish = any(_is_array_call(r) for r in rhs_list
                                   if r is not None)
                    if arrayish or _arrayish_name(name):
                        flagged.add(name)
                        # a disable on the jitted function's def line
                        # silences too (the jit call may sit far away)
                        if hasattr(fn_node, "lineno") and "PT101" in \
                                self._suppressed_rules(fn_node.lineno):
                            self.suppressed += 1
                            continue
                        self._emit(
                            "PT101", line,
                            f"jitted function closure-captures {name!r} "
                            "(bound in an enclosing function scope to "
                            "an array-like value); XLA embeds closure "
                            "captures as program constants — pass it as "
                            "a traced argument")
            # ------------------------------------------------ PT104
            if (persistent
                    and any(self.rel.startswith(m) or self.rel == m
                            for m in HOT_PATH_MODULES)):
                if self._line_has(line, _JIT_CACHE_RE):
                    continue
                target = self._jit_target_text(report)
                if target is not None and target in guard_args:
                    continue
                self._emit(
                    "PT104", line,
                    "persistent jax.jit in a hot-path module with no "
                    "RecompileGuard registration"
                    + (f" for {target!r}" if target else "")
                    + " and no '# graftlint: jit-cache:' policy note")

    def _jit_target_text(self, report: ast.AST) -> Optional[str]:
        """Where does this jit land? Assignment target text, the
        function's own name (decorator form), or — for ``return
        jax.jit(...)`` inside a builder method — the attribute that the
        builder's result is assigned to (resolved through one level of
        ``return self._build_x()`` chaining)."""
        if isinstance(report, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return report.name
        parents = self._parents
        p = parents.get(report)
        while p is not None and not isinstance(
                p, (ast.Assign, ast.Return, ast.FunctionDef,
                    ast.AsyncFunctionDef, ast.Module)):
            p = parents.get(p)
        if isinstance(p, ast.Assign) and len(p.targets) == 1:
            return ast.unparse(p.targets[0])
        if isinstance(p, ast.Return):
            # builder method: find what its call result is assigned to,
            # following `return self.other_builder()` one hop
            meth = parents.get(p)
            while meth is not None and not isinstance(
                    meth, (ast.FunctionDef, ast.AsyncFunctionDef)):
                meth = parents.get(meth)
            if meth is None:
                return None
            names = {meth.name}
            for _ in range(3):  # bounded chaining
                grew = False
                for node in ast.walk(self.tree):
                    if (isinstance(node, ast.Return)
                            and isinstance(node.value, ast.Call)):
                        d = _dotted(node.value.func) or ""
                        if d.split(".")[-1] in names:
                            m = parents.get(node)
                            while m is not None and not isinstance(
                                    m, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                                m = parents.get(m)
                            if m is not None and m.name not in names:
                                names.add(m.name)
                                grew = True
                if not grew:
                    break
            for node in ast.walk(self.tree):
                if (isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)):
                    d = _dotted(node.value.func) or ""
                    if d.split(".")[-1] in names \
                            and len(node.targets) == 1:
                        return ast.unparse(node.targets[0])
        return None

    def _recompile_guard_args(self) -> Set[str]:
        """First-argument texts of every RecompileGuard(...) call in the
        file — the set of 'registered' jit targets."""
        out: Set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                d = _dotted(node.func) or ""
                if d.split(".")[-1] == "RecompileGuard" and node.args:
                    out.add(ast.unparse(node.args[0]))
        return out

    # ------------------------------------------------------- PT102
    def _lint_mask_casts(self):
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            recv_text = None
            args_text = ""
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "astype"):
                recv_text = ast.unparse(node.func.value)
                args_text = " ".join(
                    ast.unparse(a) for a in node.args) + " ".join(
                    ast.unparse(k.value) for k in node.keywords)
            else:
                d = _dotted(node.func) or ""
                if d in ("jnp.asarray", "jnp.array", "jax.numpy.asarray",
                         "jax.numpy.array") and node.args:
                    recv_text = ast.unparse(node.args[0])
                    args_text = " ".join(
                        ast.unparse(k.value) for k in node.keywords
                        if k.arg == "dtype")
                    args_text += " ".join(ast.unparse(a)
                                          for a in node.args[1:])
            if recv_text is None:
                continue
            if not re.search(r"mask", recv_text, re.IGNORECASE):
                continue
            if any(t in args_text for t in _LOW_DTYPES):
                self._emit(
                    "PT102", node.lineno,
                    f"mask expression {recv_text!r} cast to a sub-f32 "
                    "dtype; masks are f32 count data (bf16 saturates at "
                    "256) — see trainer/trainer.py:_cast_compute")

    # ------------------------------------------------------- PT103
    def _lint_pad_bitexact(self):
        in_optim = "/optim/" in ("/" + self.rel)
        marked_spans: List[Tuple[int, int]] = []
        if not in_optim:
            for node in ast.walk(self.tree):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    # the marker sits on the def line, the line above
                    # it, or anywhere inside the function's first lines
                    for ln in range(max(1, node.lineno - 1),
                                    min(node.lineno + 2,
                                        len(self.lines) + 1)):
                        if _BIT_EXACT_RE.search(self.lines[ln - 1]):
                            marked_spans.append(
                                (node.lineno,
                                 node.end_lineno or node.lineno))
                            break
            if not marked_spans:
                return
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func) or ""
            if d not in ("jnp.pad", "jax.numpy.pad"):
                continue
            hit = in_optim or any(a <= node.lineno <= b
                                  for a, b in marked_spans)
            if hit:
                self._emit(
                    "PT103", node.lineno,
                    "jnp.pad in a bit-exact pack path; XLA:CPU fuses "
                    "the pad into downstream elementwise math and "
                    "rounds real elements differently — pack with "
                    "concatenate/slices (optim/zero1.py:_pack)")

    # ------------------------------------------------------- PT105
    _EXEC_CALLS = {
        "os.system", "os.popen", "subprocess.run", "subprocess.call",
        "subprocess.Popen", "subprocess.check_call",
        "subprocess.check_output", "subprocess.getoutput",
    }

    def _lint_pkill(self):
        """In Python sources only string arguments of exec-style calls
        are shell commands — scanning every line would flag docstrings
        that merely *mention* pkill (including this linter's own)."""
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func) or ""
            if d not in self._EXEC_CALLS:
                continue
            for arg in list(node.args) + [k.value for k in node.keywords]:
                for sub in ast.walk(arg):
                    if not (isinstance(sub, ast.Constant)
                            and isinstance(sub.value, str)):
                        continue
                    for m in re.finditer(_PKILL_RE, sub.value):
                        if self._pkill_broad(m.group(2)):
                            self._emit(
                                "PT105", sub.lineno,
                                f"broad `pkill -f {m.group(2)}` — the "
                                "-f pattern matches your own shell's "
                                "command string (exit-144 self-kill); "
                                "use a narrow, command-specific "
                                "pattern")

    @staticmethod
    def _pkill_broad(pattern: str) -> bool:
        generic = {"python", "python3", "pytest", "jax", "bench",
                   "nohup", "bash", "sh", "timeout"}
        stripped = pattern.strip("'\"")
        if stripped.lower() in generic:
            return True
        return len(stripped) < 12


_PKILL_RE = r"pkill\s+(?:-\w+\s+)*-f\s+(['\"]?)([^'\"\s;|&]+)\1"


# ----------------------------------------------------- shell-file rule
def lint_shell_file(path: str, rel: str, source: str) -> List[Finding]:
    """PT105 over shell scripts (no AST; line scan)."""
    findings: List[Finding] = []
    for i, line in enumerate(source.splitlines(), 1):
        if _SUPPRESS_RE.search(line):
            continue
        if line.lstrip().startswith("#"):
            continue
        for m in re.finditer(_PKILL_RE, line):
            if FileLinter._pkill_broad(m.group(2)):
                findings.append(Finding(
                    "PT105", rel.replace(os.sep, "/"), i,
                    f"broad `pkill -f {m.group(2)}` in a shell tool — "
                    "narrow the pattern (it matches the invoking "
                    "shell's own command string)"))
    return findings


# -------------------------------------------------------------- PT107
_CHAOS_REL = "paddle_tpu/testing/chaos.py"
_FLIGHT_MATRIX_REL = "tests/test_obs_flight.py"


def _hit_sites_from_tree(tree: ast.Module) -> List[Tuple[str, int]]:
    """(site-name, line) per ``_chaos._ACTIVE.hit("<site>", ...)`` call
    — the canonical production spelling (the receiver must end in
    ``_ACTIVE``, so a test's ``plan.hit(...)`` never counts)."""
    out: List[Tuple[str, int]] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "hit"):
            continue
        recv = _dotted(node.func.value) or ""
        if not recv.endswith("_ACTIVE"):
            continue
        if node.args and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            out.append((node.args[0].value, node.lineno))
    return out


def _sites_from_tree(tree: ast.Module
                     ) -> Tuple[Optional[Set[str]], int]:
    """chaos.py's declared ``SITES`` tuple (None when missing)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "SITES" \
                and isinstance(node.value, (ast.Tuple, ast.List)):
            sites = {e.value for e in node.value.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, str)}
            return sites, node.lineno
    return None, 1


def _site_cases_from_tree(tree: ast.Module) -> Optional[Set[str]]:
    """The flight matrix's ``SITE_CASES`` dict keys (None = absent)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "SITE_CASES" \
                and isinstance(node.value, ast.Dict):
            return {k.value for k in node.value.keys
                    if isinstance(k, ast.Constant)
                    and isinstance(k.value, str)}
    return None


def _chaos_site_findings(hits: Dict[str, Tuple[str, int]],
                         chaos_tree: Optional[ast.Module],
                         matrix_tree: Optional[ast.Module]
                         ) -> List[Finding]:
    """PT107: every .hit site declared in chaos.SITES; every declared
    site exercised by the closure-enforced flight matrix AND by at
    least one production hit (a dead declaration is drift too)."""
    if chaos_tree is None:
        return [Finding("PT107", _CHAOS_REL, 1,
                        "chaos module missing/unparsed — chaos-site "
                        "coverage cannot be checked")]
    sites, sites_line = _sites_from_tree(chaos_tree)
    if sites is None:
        return [Finding("PT107", _CHAOS_REL, 1,
                        "chaos.SITES catalog missing — declare the "
                        "closed set of hook sites")]
    findings: List[Finding] = []
    for site, (rel, line) in sorted(hits.items()):
        if site not in sites:
            findings.append(Finding(
                "PT107", rel, line,
                f"chaos site {site!r} fired here but is not declared "
                "in chaos.SITES — declare it (and add its "
                "tests/test_obs_flight.py SITE_CASES row) so the "
                "flight-recorder matrix and the docs cover it"))
    cases = (_site_cases_from_tree(matrix_tree)
             if matrix_tree is not None else None)
    if cases is None:
        findings.append(Finding(
            "PT107", _FLIGHT_MATRIX_REL, 1,
            "flight-recorder matrix (SITE_CASES) missing — every "
            "chaos.SITES member needs a firing row proving it emits "
            "its flight event"))
    else:
        for site in sorted(sites - cases):
            findings.append(Finding(
                "PT107", _CHAOS_REL, sites_line,
                f"chaos site {site!r} declared without a firing row "
                "in tests/test_obs_flight.py:SITE_CASES — a site "
                "without its matrix row ships without its postmortem "
                "event"))
    for site in sorted(sites - set(hits)):
        findings.append(Finding(
            "PT107", _CHAOS_REL, sites_line,
            f"chaos site {site!r} declared in chaos.SITES but no "
            "_chaos._ACTIVE.hit(...) in paddle_tpu/ fires it — dead "
            "declaration (remove it, with its matrix row)"))
    return findings


def lint_chaos_sites(root: str) -> List[Finding]:
    """Standalone PT107 (fixture tests use this directly); the repo
    driver aggregates from run_pass1's already-parsed trees."""
    hits: Dict[str, Tuple[str, int]] = {}
    pkg = os.path.join(root, "paddle_tpu")
    chaos_tree = None
    for dirpath, _dirs, files in os.walk(pkg):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            try:
                tree = ast.parse(open(path, encoding="utf-8").read(),
                                 filename=path)
            except (SyntaxError, OSError):
                continue
            if rel == _CHAOS_REL:
                chaos_tree = tree
            for site, line in _hit_sites_from_tree(tree):
                hits.setdefault(site, (rel, line))
    matrix_path = os.path.join(root, _FLIGHT_MATRIX_REL)
    matrix_tree = None
    if os.path.exists(matrix_path):
        try:
            matrix_tree = ast.parse(
                open(matrix_path, encoding="utf-8").read(),
                filename=matrix_path)
        except SyntaxError:
            matrix_tree = None
    return _chaos_site_findings(hits, chaos_tree, matrix_tree)


# -------------------------------------------------------------- PT106
def _registrations_from_tree(tree: ast.Module) -> List[Tuple[str, int]]:
    """(canonical-type-name, line) per register_layer decorator."""
    out: List[Tuple[str, int]] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        for dec in node.decorator_list:
            if (isinstance(dec, ast.Call)
                    and (_dotted(dec.func) or "").split(".")[-1]
                    == "register_layer" and dec.args
                    and isinstance(dec.args[0], ast.Constant)):
                out.append((dec.args[0].value, dec.lineno))
    return out


def _covered_from_tree(mtree: ast.Module) -> Set[str]:
    covered: Set[str] = set()
    for node in ast.walk(mtree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            tname = ast.unparse(node.targets[0])
            if tname in ("GRAD_CASES", "FWD_CASES", "COVERED_ELSEWHERE") \
                    and isinstance(node.value, ast.Dict):
                for k in node.value.keys:
                    if isinstance(k, ast.Constant) \
                            and isinstance(k.value, str):
                        covered.add(k.value)
    return covered


_MATRIX_REL = "tests/test_layer_grad_matrix.py"


def _matrix_findings(registered: Dict[str, Tuple[str, int]],
                     mtree: Optional[ast.Module]) -> List[Finding]:
    if mtree is None:
        return [Finding("PT106", _MATRIX_REL, 1, "matrix file missing")]
    covered = _covered_from_tree(mtree)
    findings: List[Finding] = []
    for canonical, (rel, line) in sorted(registered.items()):
        if canonical not in covered:
            findings.append(Finding(
                "PT106", rel.replace(os.sep, "/"), line,
                f"layer type {canonical!r} registered without a row in "
                "tests/test_layer_grad_matrix.py (GRAD_CASES / "
                "FWD_CASES / COVERED_ELSEWHERE)"))
    return findings


def lint_layer_matrix(root: str) -> List[Finding]:
    """Standalone PT106 (fixture tests use this directly); the repo
    driver collects registrations from run_pass1's already-parsed
    trees instead of re-walking."""
    registered: Dict[str, Tuple[str, int]] = {}
    pkg = os.path.join(root, "paddle_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            try:
                tree = ast.parse(open(path, encoding="utf-8").read(),
                                 filename=path)
            except SyntaxError:
                continue
            for canonical, line in _registrations_from_tree(tree):
                registered.setdefault(
                    canonical, (os.path.relpath(path, root), line))
    matrix_path = os.path.join(root, _MATRIX_REL)
    mtree = None
    if os.path.exists(matrix_path):
        mtree = ast.parse(open(matrix_path, encoding="utf-8").read(),
                          filename=matrix_path)
    return _matrix_findings(registered, mtree)


# ------------------------------------------------------------- driver
def _iter_source_files(root: str,
                       subdirs: Sequence[str] = ("paddle_tpu", "tests",
                                                 "tools")):
    for sub in subdirs:
        base = os.path.join(root, sub)
        if os.path.isfile(base):
            yield base
            continue
        for dirpath, dirs, files in os.walk(base):
            dirs[:] = [d for d in dirs
                       if d not in ("__pycache__", "proto")]
            for fname in sorted(files):
                if fname.endswith((".py", ".sh")):
                    yield os.path.join(dirpath, fname)


def run_pass1(root: str,
              paths: Optional[Sequence[str]] = None
              ) -> Tuple[List[Finding], int]:
    """(findings, suppressed-count) over the repo (or explicit paths)."""
    findings: List[Finding] = []
    suppressed = 0
    # PT106/PT107 ride the same parse: registrations, chaos hit sites,
    # and the matrix trees are collected from the linters' ASTs
    # (re-walking the package would double the fast lint's parse work)
    registered: Dict[str, Tuple[str, int]] = {}
    hit_sites: Dict[str, Tuple[str, int]] = {}
    matrix_tree: Optional[ast.Module] = None
    chaos_tree: Optional[ast.Module] = None
    flight_matrix_tree: Optional[ast.Module] = None
    files = list(paths) if paths else list(_iter_source_files(root))
    for path in files:
        rel = os.path.relpath(path, root)
        try:
            source = open(path, encoding="utf-8").read()
        except (OSError, UnicodeDecodeError):
            continue
        if path.endswith(".sh"):
            findings.extend(lint_shell_file(path, rel, source))
            continue
        try:
            linter = FileLinter(path, rel, source)
        except SyntaxError as e:
            # own rule id: a parse failure must never be swallowed by
            # a PT101 baseline/disable entry for unrelated findings
            findings.append(Finding("PT100", rel, e.lineno or 1,
                                    f"unparseable source: {e.msg}"))
            continue
        findings.extend(linter.run())
        suppressed += linter.suppressed
        if linter.rel == _MATRIX_REL:
            matrix_tree = linter.tree
        elif linter.rel == _FLIGHT_MATRIX_REL:
            flight_matrix_tree = linter.tree
        elif linter.rel.startswith("paddle_tpu/"):
            if linter.rel == _CHAOS_REL:
                chaos_tree = linter.tree
            for canonical, line in _registrations_from_tree(
                    linter.tree):
                registered.setdefault(canonical, (linter.rel, line))
            for site, line in _hit_sites_from_tree(linter.tree):
                hit_sites.setdefault(site, (linter.rel, line))
    if paths is None:
        findings.extend(_matrix_findings(registered, matrix_tree))
        findings.extend(_chaos_site_findings(hit_sites, chaos_tree,
                                             flight_matrix_tree))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings, suppressed
