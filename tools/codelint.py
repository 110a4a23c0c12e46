#!/usr/bin/env python
"""Repo-specific AST lints for the bug classes the generic linters miss.

Rule 1 — **compile-cache-token completeness** (the PR 6
``quantize_min_size`` bug class): every
BuildStrategy knob that the lowering paths under
``framework/compiler.py`` / ``framework/trace.py`` READ must be folded
into ``CompiledProgram._cache_token`` (directly or via a helper the
token calls), or carry an explicit allowlist entry saying why it cannot
change the lowered executable. A knob that steers lowering but misses
the token means a stale jitted step silently keeps the old behavior
when the knob flips.

Rule 2 — **free-floating locks** (coordination-thread sanity): a
``threading.Lock()``/``RLock()``/``Condition()`` constructed directly
inside a ``with`` statement guards nothing — every caller gets a fresh
lock, which is exactly the interleaving bug the lock was meant to
prevent. The lock must be stored (module global, ``self._lock``, a
closure var shared with the threads) before it can serialize anything.

Rule 3 — **failpoint site catalog** (the fault-injection plane's typo
guard): every ``faultinject.hit("...")`` call site must name its site
as a string LITERAL that appears in ``framework/faultinject.py``'s
``SITES`` catalog. A typo'd or uncatalogued site string would parse,
arm, and then silently never fire — a chaos test that tests nothing.

Rule 4 — **no op reads the environment**: what an op lowers to is decided
by the call's own arguments (its inputs' shapes and dtypes, its attrs), so
no module under ``paddle_tpu/ops/`` touches ``os.environ``/``os.getenv``:
a variable set in a shell would change every step of a process and is in
no cache key. The reads that remain are listed in ``OPS_ENV_ALLOWLIST``
with what each is for.

All rules run as a tier-1 test (tests/test_codelint.py) so the bug
classes stay extinct. Exit 0 clean, 1 violations.

Usage:
  python tools/codelint.py            # lint the repo
  python tools/codelint.py --json
"""
import argparse
import ast
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPILER_PY = os.path.join(REPO, "paddle_tpu", "framework", "compiler.py")
TRACE_PY = os.path.join(REPO, "paddle_tpu", "framework", "trace.py")

# knob -> why it is allowed to stay out of the compile-cache token.
# Every entry must argue "cannot change the lowered executable".
TOKEN_ALLOWLIST = {
    # diagnostics only: the verifier reads the program, never rewrites
    # it — strict/warn/off produce byte-identical lowerings (asserted
    # by tests/test_analysis.py's off-mode inertness test)
    "verify_program": "read-only program verification at compile time",
}

_LOCKY = {"Lock", "RLock", "Condition", "Semaphore", "BoundedSemaphore"}


def _knob_reads(tree, knobs, aliases=("bs", "build_strategy", "strategy")):
    """{knob: [(qualname, lineno)]} of BuildStrategy attribute READS
    (ast.Load) and getattr(bs, "knob", ...) calls, per enclosing
    function. Recognizes the conventional aliases (``bs``,
    ``build_strategy``, ``strategy``), any ``<expr>._build_strategy``
    chain, AND locals bound from one (``cfg = self._build_strategy``)
    — a fresh binding must not hide a knob read from the lint."""
    reads = {}
    base_aliases = set(aliases)

    class V(ast.NodeVisitor):
        def __init__(self):
            self.stack = []
            self.scopes = [set()]   # per-function local alias sets

        def _is_bs(self, node):
            if isinstance(node, ast.Name):
                return node.id in base_aliases or \
                    any(node.id in s for s in self.scopes)
            if isinstance(node, ast.Attribute):
                return node.attr == "_build_strategy"
            return False

        def _record(self, name, lineno):
            if name in knobs:
                qual = ".".join(self.stack) or "<module>"
                reads.setdefault(name, []).append((qual, lineno))

        def visit_ClassDef(self, node):
            self.stack.append(node.name)
            self.generic_visit(node)
            self.stack.pop()

        def visit_FunctionDef(self, node):
            self.stack.append(node.name)
            self.scopes.append(set())
            self.generic_visit(node)
            self.scopes.pop()
            self.stack.pop()

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Assign(self, node):
            if self._is_bs(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.scopes[-1].add(t.id)
            self.generic_visit(node)

        def visit_Attribute(self, node):
            if isinstance(node.ctx, ast.Load) and \
                    self._is_bs(node.value):
                self._record(node.attr, node.lineno)
            self.generic_visit(node)

        def visit_Call(self, node):
            if isinstance(node.func, ast.Name) and \
                    node.func.id == "getattr" and len(node.args) >= 2 \
                    and self._is_bs(node.args[0]) \
                    and isinstance(node.args[1], ast.Constant) \
                    and isinstance(node.args[1].value, str):
                self._record(node.args[1].value, node.lineno)
            self.generic_visit(node)

    V().visit(tree)
    return reads


def _build_strategy_knobs(tree):
    """Knob names: every `self.<name> = ...` in BuildStrategy.__init__."""
    for cls in ast.walk(tree):
        if isinstance(cls, ast.ClassDef) and cls.name == "BuildStrategy":
            for fn in cls.body:
                if isinstance(fn, ast.FunctionDef) and \
                        fn.name == "__init__":
                    knobs = set()
                    for n in ast.walk(fn):
                        if isinstance(n, ast.Assign):
                            for t in n.targets:
                                if isinstance(t, ast.Attribute) and \
                                        isinstance(t.value, ast.Name) \
                                        and t.value.id == "self":
                                    knobs.add(t.attr)
                    return knobs
    raise ValueError("BuildStrategy.__init__ not found")


def _token_closure_functions(tree, entry="_cache_token",
                             cls_name="CompiledProgram"):
    """Names of CompiledProgram methods reachable from `entry` via
    self.<method>() calls — the functions whose BuildStrategy reads
    count as 'in the token'."""
    methods = {}
    for cls in ast.walk(tree):
        if isinstance(cls, ast.ClassDef) and cls.name == cls_name:
            for fn in cls.body:
                if isinstance(fn, ast.FunctionDef):
                    methods[fn.name] = fn
    if entry not in methods:
        raise ValueError("%s.%s not found" % (cls_name, entry))
    seen, todo = set(), [entry]
    while todo:
        name = todo.pop()
        if name in seen or name not in methods:
            continue
        seen.add(name)
        for n in ast.walk(methods[name]):
            if isinstance(n, ast.Call) and \
                    isinstance(n.func, ast.Attribute) and \
                    isinstance(n.func.value, ast.Name) and \
                    n.func.value.id == "self":
                todo.append(n.func.attr)
    return {methods[m] for m in seen}


def lint_cache_token(compiler_src=None, trace_src=None,
                     allowlist=None):
    """Rule 1. Returns a list of violation strings (empty = clean)."""
    allowlist = TOKEN_ALLOWLIST if allowlist is None else allowlist
    if compiler_src is None:
        with open(COMPILER_PY) as f:
            compiler_src = f.read()
    if trace_src is None:
        with open(TRACE_PY) as f:
            trace_src = f.read()
    ctree = ast.parse(compiler_src)
    ttree = ast.parse(trace_src)
    knobs = _build_strategy_knobs(ctree)

    closure = _token_closure_functions(ctree)
    closure_spans = [(fn.lineno, max(n.lineno for n in ast.walk(fn)
                                     if hasattr(n, "lineno")))
                     for fn in closure]

    def in_token(lineno):
        return any(a <= lineno <= b for a, b in closure_spans)

    reads = _knob_reads(ctree, knobs)
    for knob, sites in _knob_reads(ttree, knobs).items():
        reads.setdefault(knob, []).extend(
            [(q + " [trace.py]", ln) for q, ln in sites])

    tokened = {k for k, sites in reads.items()
               if any(in_token(ln) for q, ln in sites
                      if not q.endswith("[trace.py]"))}
    violations = []
    for knob in sorted(reads):
        outside = [(q, ln) for q, ln in reads[knob]
                   if q.endswith("[trace.py]") or not in_token(ln)]
        if not outside:
            continue     # only read while building the token itself
        if knob in tokened or knob in allowlist:
            continue
        where = ", ".join("%s:%d" % (q, ln) for q, ln in outside[:4])
        violations.append(
            "BuildStrategy.%s is read on the lowering path (%s) but is "
            "NOT folded into CompiledProgram._cache_token and has no "
            "allowlist entry — flipping it would silently reuse a stale "
            "executable (the PR 6 quantize_min_size bug class)"
            % (knob, where))
    return violations


def lint_free_floating_locks(root=None, paths=None):
    """Rule 2. Flags `with threading.Lock():`-style inline lock
    construction anywhere under paddle_tpu/ (plus tools/)."""
    if paths is None:
        root = root or REPO
        paths = []
        for base in ("paddle_tpu", "tools"):
            for dirpath, _, files in os.walk(os.path.join(root, base)):
                paths.extend(os.path.join(dirpath, f) for f in files
                             if f.endswith(".py"))
    violations = []
    for path in sorted(paths):
        with open(path) as f:
            src = f.read()
        try:
            tree = ast.parse(src)
        except SyntaxError as e:
            violations.append("%s: unparseable: %s" % (path, e))
            continue
        for node in ast.walk(tree):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            for item in node.items:
                call = item.context_expr
                if not isinstance(call, ast.Call):
                    continue
                fn = call.func
                name = fn.attr if isinstance(fn, ast.Attribute) \
                    else getattr(fn, "id", None)
                if name in _LOCKY:
                    violations.append(
                        "%s:%d: `with %s()` constructs a FRESH lock "
                        "per entry — it serializes nothing; store the "
                        "lock (module/self/closure) and `with` that"
                        % (path, node.lineno, name))
    return violations


FAULTINJECT_PY = os.path.join(REPO, "paddle_tpu", "framework",
                              "faultinject.py")
# module aliases a hit() call may hang off; anything else (a local
# helper also named hit, a mock) is not this plane's call
_FAULTINJECT_ALIASES = {"faultinject", "fi"}


def _site_catalog(src=None):
    """The SITES keys from faultinject.py — parsed from the AST so the
    lint never imports (and thereby arms) the plane it checks."""
    if src is None:
        with open(FAULTINJECT_PY) as f:
            src = f.read()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Dict):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "SITES":
                    return {k.value for k in node.value.keys
                            if isinstance(k, ast.Constant)
                            and isinstance(k.value, str)}
    raise ValueError("SITES catalog not found in faultinject.py")


def lint_failpoint_sites(root=None, paths=None, catalog=None):
    """Rule 3. Returns a list of violation strings (empty = clean)."""
    catalog = _site_catalog() if catalog is None else set(catalog)
    if paths is None:
        root = root or REPO
        paths = []
        for base in ("paddle_tpu", "tools"):
            for dirpath, _, files in os.walk(os.path.join(root, base)):
                paths.extend(os.path.join(dirpath, f) for f in files
                             if f.endswith(".py"))
    violations = []
    for path in sorted(paths):
        with open(path) as f:
            src = f.read()
        try:
            tree = ast.parse(src)
        except SyntaxError as e:
            violations.append("%s: unparseable: %s" % (path, e))
            continue
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "hit"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in _FAULTINJECT_ALIASES):
                continue
            a0 = node.args[0] if node.args else None
            if not (isinstance(a0, ast.Constant)
                    and isinstance(a0.value, str)):
                violations.append(
                    "%s:%d: faultinject.hit() site must be a string "
                    "literal from the SITES catalog — a computed site "
                    "name defeats the static typo guard"
                    % (path, node.lineno))
            elif a0.value not in catalog:
                violations.append(
                    "%s:%d: faultinject.hit(%r) names a site missing "
                    "from framework/faultinject.py's SITES catalog — "
                    "it would arm and then silently never fire"
                    % (path, node.lineno, a0.value))
    return violations


OPS_DIR = os.path.join(REPO, "paddle_tpu", "ops")
# variable -> what the one read that remains is for
OPS_ENV_ALLOWLIST = {
    "PADDLE_TPU_PALLAS_INTERPRET": "tests and CPU rehearsals run the "
                                   "kernels through the interpreter",
    "PADDLE_TPU_OP_COVERAGE": "where the registry writes which ops ran",
    "PADDLE_TPU_FAST_DROPOUT": "dropout's generator (PORTING.md)",
}


def _is_environ(node):
    """`os.environ` / a bare `environ` name."""
    return (isinstance(node, ast.Attribute) and node.attr == "environ") \
        or (isinstance(node, ast.Name) and node.id == "environ")


def _env_reads(tree):
    """[(variable or None, lineno)]: every touch of the environment in a
    module; None where the variable is not a string literal (or the
    mapping itself is handed on)."""
    parent = {child: node for node in ast.walk(tree)
              for child in ast.iter_child_nodes(node)}
    reads = []
    for node in ast.walk(tree):
        key = None
        if _is_environ(node):
            up = parent.get(node)
            call = parent.get(up)
            if isinstance(up, ast.Subscript):
                key = up.slice
            elif isinstance(up, ast.Attribute) and isinstance(call, ast.Call) \
                    and call.args:
                key = call.args[0]          # environ.get("X"), .pop, ...
        elif isinstance(node, ast.Call) and "getenv" in (
                getattr(node.func, "attr", None),
                getattr(node.func, "id", None)):
            key = node.args[0] if node.args else None
        else:
            continue
        literal = isinstance(key, ast.Constant) and isinstance(key.value, str)
        reads.append((key.value if literal else None, node.lineno))
    return reads


def lint_ops_environment(sources=None, allowlist=None):
    """Rule 4. `sources` ({path: source}) replaces the walk of
    paddle_tpu/ops/. Returns a list of violation strings."""
    allowlist = OPS_ENV_ALLOWLIST if allowlist is None else allowlist
    if sources is None:
        sources = {}
        for dirpath, _, files in os.walk(OPS_DIR):
            for name in files:
                if name.endswith(".py"):
                    path = os.path.join(dirpath, name)
                    with open(path) as f:
                        sources[path] = f.read()
    violations = []
    for path in sorted(sources):
        try:
            tree = ast.parse(sources[path])
        except SyntaxError as e:
            violations.append("%s: unparseable: %s" % (path, e))
            continue
        for var, lineno in sorted(_env_reads(tree), key=lambda r: r[1]):
            if var not in allowlist:
                violations.append(
                    "%s:%d: an op module reads the environment (%s): what "
                    "an op lowers to comes from the call's own arguments; a "
                    "variable set in a shell is in no cache key"
                    % (path, lineno, var or "a computed name"))
    return violations


def run_all():
    return {"cache_token": lint_cache_token(),
            "free_floating_locks": lint_free_floating_locks(),
            "failpoint_sites": lint_failpoint_sites(),
            "ops_environment": lint_ops_environment()}


def main(argv=None):
    ap = argparse.ArgumentParser(description="paddle_tpu repo lints")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    report = run_all()
    n = sum(len(v) for v in report.values())
    if args.json:
        print(json.dumps({"metric": "codelint", "violations": report,
                          "ok": n == 0}))
    else:
        for rule, vs in report.items():
            for v in vs:
                print("[%s] %s" % (rule, v))
        print("codelint: %d violation(s)" % n)
    return 0 if n == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
