"""The source lint: AST rules enforcing the repo's hard-won coding rules.

* ``bare-except`` — ``except:`` swallows KeyboardInterrupt and bugs alike;
  the PR 2 class of incident (a bare except in the bench harness ate real
  schedule failures for two rounds).
* ``broad-except`` — ``except Exception`` (or BaseException) without a
  re-raise or a logging call in the handler.  Catch-and-drop turns every
  future bug into silence; the accepted spellings are (a) narrow the type,
  (b) re-raise after containment, (c) log what was swallowed, or (d) an
  explicit inline ``# noqa``/``# lint: allow-broad-except`` with a reason —
  visible suppression at the site, reviewable in diffs.
* ``compute-outside-scope`` — in ``models/``/``parallel/``/``ops/``,
  FLOP-bearing ``jnp.``/``lax.`` calls (and the ``@`` operator) must sit
  lexically inside a ``tracing.scope(...)`` block, or the op compiles with
  no phase metadata and the program sanitizer's phase-coverage rule fires
  downstream on every program that inlines it.  Severity warn: lexical
  analysis cannot see callers that wrap the whole function in a scope, so a
  human decides (fix, or baseline with a comment).
* ``unregistered-phase-tag`` — string literals passed to ``scope(...)`` or
  ``tap(point=...)`` must be in `tracing.PHASE_REGISTRY`.  scope() refuses
  unknown tags at trace time; this rule moves the failure to lint time,
  before a rarely-traced branch ships the ValueError to production.
* ``host-only-dispatch`` — the serve dispatch plane (``serve/router.py``,
  ``serve/replica.py``) must not import jax at module level: the router
  and its spawned client/worker shims run in processes that either never
  need a device runtime (pure host-side dispatch) or must apply their env
  overrides BEFORE jax initializes (the ProcessReplica spawn contract).
  Engine access goes through the lazy in-worker import; a module-level
  ``import jax`` here silently re-couples the dispatch plane to the
  device runtime.

Pure stdlib ``ast`` — no file is imported, so linting broken code or code
with heavy import side effects is safe.
"""

from __future__ import annotations

import ast
import os
from typing import Optional

from capital_tpu.lint import rules
from capital_tpu.utils import tracing

BARE_EXCEPT = "bare-except"
BROAD_EXCEPT = "broad-except"
COMPUTE_OUTSIDE_SCOPE = "compute-outside-scope"
UNREGISTERED_PHASE_TAG = "unregistered-phase-tag"
HOST_ONLY_DISPATCH = "host-only-dispatch"

SOURCE_RULES = (
    BARE_EXCEPT, BROAD_EXCEPT, COMPUTE_OUTSIDE_SCOPE, UNREGISTERED_PHASE_TAG,
    HOST_ONLY_DISPATCH,
)

#: Files (path suffixes) that form the serve dispatch plane: host-only by
#: contract, no module-level jax import allowed.
HOST_ONLY_FILES = (
    os.path.join("serve", "router.py"),
    os.path.join("serve", "replica.py"),
)

#: Module roots whose import at module level couples a file to the device
#: runtime (jax itself and its subpackages).
_DEVICE_ROOTS = frozenset({"jax", "jaxlib"})

#: FLOP-bearing jnp/lax entry points (mirrors program.FLOP_PRIMITIVES at the
#: API level: what lowers to those primitives).
FLOP_FNS = frozenset({
    "matmul", "dot", "einsum", "tensordot", "dot_general",
    "conv_general_dilated", "cholesky", "triangular_solve", "lu", "qr",
    "svd", "eigh",
})

#: Roots a FLOP call must hang off to count as traced compute (host numpy
#: is not traced and carries no phase metadata anyway).
_COMPUTE_ROOTS = frozenset({"jnp", "lax", "jax", "linalg"})

#: Method names whose presence in a broad-except handler counts as "logged".
_LOG_METHODS = frozenset({
    "debug", "info", "warning", "warn", "error", "exception", "critical",
})

#: Inline suppression markers on the ``except`` line itself.
_SUPPRESS_MARKERS = ("noqa", "lint: allow-broad-except")

#: Directories (package segments) where compute-outside-scope applies.
SCOPED_DIRS = ("models", "parallel", "ops")


def _attr_chain(node: ast.AST) -> list[str]:
    """['jnp', 'linalg', 'cholesky'] for jnp.linalg.cholesky; [] when the
    expression is not a plain dotted name."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return list(reversed(parts))
    return []


def _is_scope_call(node: ast.AST) -> bool:
    """True for ``scope(...)`` / ``tracing.scope(...)`` context managers
    (NOT device_scope / named_scope — those don't tag phases)."""
    if not isinstance(node, ast.Call):
        return False
    fn = node.func
    if isinstance(fn, ast.Name):
        return fn.id == "scope"
    if isinstance(fn, ast.Attribute):
        return fn.attr == "scope"
    return False


def _is_logging_call(node: ast.Call) -> bool:
    fn = node.func
    if isinstance(fn, ast.Attribute) and fn.attr in _LOG_METHODS:
        return True
    if isinstance(fn, ast.Name) and fn.id in ("warn", "log"):
        return True
    return False


def _handler_contains_exit(handler: ast.ExceptHandler) -> bool:
    """Re-raise or logging anywhere inside the handler body."""
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call) and _is_logging_call(node):
            return True
    return False


def _phase_literal(call: ast.Call) -> Optional[tuple[str, int]]:
    """(tag, lineno) when `call` is scope(<str-literal>) or
    tap(..., point=<str-literal>); None otherwise."""
    fn = call.func
    name = fn.id if isinstance(fn, ast.Name) else (
        fn.attr if isinstance(fn, ast.Attribute) else None)
    if name == "scope" and call.args:
        arg = call.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            return arg.value, arg.lineno
    if name == "tap":
        for kw in call.keywords:
            if kw.arg == "point" and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, str):
                return kw.value.value, kw.value.lineno
        if len(call.args) >= 2:
            arg = call.args[1]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                return arg.value, arg.lineno
    return None


def _in_scoped_dir(path: str) -> bool:
    parts = os.path.normpath(path).split(os.sep)
    return any(p in SCOPED_DIRS for p in parts)


def _flop_call_name(node: ast.AST) -> Optional[str]:
    """The FLOP function name when `node` is a jnp/lax compute call or an
    ``@`` matmul expression; None otherwise."""
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.MatMult):
        return "@"
    if isinstance(node, ast.Call):
        chain = _attr_chain(node.func)
        if len(chain) >= 2 and chain[-1] in FLOP_FNS \
                and chain[0] in _COMPUTE_ROOTS:
            return ".".join(chain)
    return None


def lint_source(path: str, text: Optional[str] = None) -> list[rules.Finding]:
    """Every source finding for one file.  `text` overrides reading `path`
    (the tests lint synthetic snippets under invented paths)."""
    if text is None:
        with open(path) as f:
            text = f.read()
    try:
        tree = ast.parse(text, filename=path)
    except SyntaxError as e:
        return [rules.make(
            "syntax", rules.ERROR, path,
            f"not parseable: {e.msg}", line=e.lineno or 0,
        )]
    lines = text.splitlines()
    findings: list[rules.Finding] = []

    def _suppressed(lineno: int) -> bool:
        if 0 < lineno <= len(lines):
            line = lines[lineno - 1]
            return any(m in line for m in _SUPPRESS_MARKERS)
        return False

    # -- except rules + phase-tag rule: flat walk --------------------------
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler):
            if node.type is None:
                findings.append(rules.make(
                    BARE_EXCEPT, rules.ERROR, path,
                    "bare `except:` swallows KeyboardInterrupt and bugs "
                    "alike — name the exception types",
                    line=node.lineno,
                ))
                continue
            tname = node.type.id if isinstance(node.type, ast.Name) else (
                node.type.attr if isinstance(node.type, ast.Attribute)
                else None)
            if tname in ("Exception", "BaseException") \
                    and not _handler_contains_exit(node) \
                    and not _suppressed(node.lineno):
                findings.append(rules.make(
                    BROAD_EXCEPT, rules.ERROR, path,
                    f"`except {tname}` without re-raise or logging — "
                    "narrow the type, re-raise after containment, log "
                    "what was swallowed, or suppress inline with a reason "
                    "(# lint: allow-broad-except)",
                    line=node.lineno,
                ))
        elif isinstance(node, ast.Call):
            lit = _phase_literal(node)
            if lit is not None and lit[0] not in tracing.PHASE_REGISTRY:
                findings.append(rules.make(
                    UNREGISTERED_PHASE_TAG, rules.ERROR, path,
                    f"phase tag {lit[0]!r} is not in tracing.PHASE_REGISTRY "
                    "— scope() will raise at trace time; register it (or "
                    "register_phase) so downstream views can bucket it",
                    line=lit[1],
                ))

    # -- host-only-dispatch: module-level device-runtime imports -----------
    norm = os.path.normpath(path)
    if any(norm.endswith(sfx) for sfx in HOST_ONLY_FILES):
        def _import_roots(node: ast.AST) -> list[tuple[str, int]]:
            if isinstance(node, ast.Import):
                return [(a.name.split(".")[0], node.lineno)
                        for a in node.names]
            if isinstance(node, ast.ImportFrom) and node.module:
                return [(node.module.split(".")[0], node.lineno)]
            return []

        def scan_module_level(node: ast.AST) -> None:
            # function bodies are exempt: the lazy in-worker import (after
            # the spawn child applies its env overrides) is the sanctioned
            # way to reach the engine from the dispatch plane
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                for root, lineno in _import_roots(child):
                    if root in _DEVICE_ROOTS and not _suppressed(lineno):
                        findings.append(rules.make(
                            HOST_ONLY_DISPATCH, rules.ERROR, path,
                            f"module-level `{root}` import in the serve "
                            "dispatch plane — router/replica must stay "
                            "host-only (import lazily inside the worker, "
                            "after env overrides apply)",
                            line=lineno,
                        ))
                scan_module_level(child)

        scan_module_level(tree)

    # -- compute-outside-scope: recursive walk with scope context ----------
    if _in_scoped_dir(path):
        def visit(node: ast.AST, covered: bool) -> None:
            if isinstance(node, ast.With):
                covered = covered or any(
                    _is_scope_call(item.context_expr) for item in node.items
                )
            name = _flop_call_name(node)
            if name is not None and not covered \
                    and not _suppressed(node.lineno):
                findings.append(rules.make(
                    COMPUTE_OUTSIDE_SCOPE, rules.WARN, path,
                    f"FLOP-bearing `{name}` outside every tracing.scope() "
                    "block — the op compiles with no phase metadata and "
                    "lands in 'other' in every downstream view",
                    line=node.lineno,
                ))
            for child in ast.iter_child_nodes(node):
                visit(child, covered)

        visit(tree, covered=False)
    return rules.sort_findings(findings)


def lint_tree(root: str) -> list[rules.Finding]:
    """Lint every ``*.py`` under `root` (skipping __pycache__), findings
    keyed by path relative to the current directory."""
    findings: list[rules.Finding] = []
    if os.path.isfile(root):
        return lint_source(root)
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                findings.extend(lint_source(os.path.join(dirpath, fn)))
    return rules.sort_findings(findings)
