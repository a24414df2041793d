"""AST lint over ``src/``, ``benchmarks/``, ``examples/``.

Three rules, all scoped to what is statically decidable without imports:

* **HOST_SYNC** — ``.item()`` / ``.tolist()`` / ``np.asarray`` / ``np.array``
  anywhere inside a *traced* function, and ``float(...)`` / ``int(...)``
  whose argument mentions a parameter of the traced function. A function
  counts as traced when it is decorated with ``jit`` (including
  ``partial(jax.jit, ...)``), passed by name or inline to a tracing
  combinator (``jit``/``scan``/``vmap``/``pmap``/``shard_map``/``cond``/
  ``while_loop``/``fori_loop``/``grad``/``checkpoint``/...), or lexically
  nested inside one that is. Host code that merely *drives* jitted functions
  (run loops, result recording) is deliberately out of scope. Host-callback
  staging — ``jax.debug.callback`` / ``io_callback`` / ``pure_callback`` —
  is flagged wherever it appears (callbacks are host bridges by
  construction), with one recorded allowance: calls in ``src/repro/obs/``
  (the opt-in debug tap, :mod:`repro.obs.tap`) are reported as
  allowed-with-reason rather than kept — see :func:`apply_obs_allowance`.
* **RECOMPILE_HAZARD** — ``jax.jit(...)`` called inside a ``for``/``while``
  body; ``jax.jit(f)(args)`` immediately invoked (the wrapper and its trace
  cache are discarded per call); and a call to a module-level
  ``f = jax.jit(g, static_argnums=...)`` binding that passes a
  list/dict/set literal in a static position (unhashable -> TypeError or a
  str() workaround that recompiles per ordering).
* **KEY_IN_LOOP** — ``jax.random.PRNGKey(e)`` lexically inside a loop where
  ``e`` is non-constant and loop-varying (mentions the ``for`` target,
  contains a call, or sits in a ``while``). Adjacent integer seeds are not
  independent streams under threefry; derive per-iteration keys from one
  root key via ``split``/``fold_in`` instead.
"""
from __future__ import annotations

import ast
import os

from repro.analysis.findings import Finding

TRACING_FUNCS = frozenset({
    "jit", "scan", "vmap", "pmap", "shard_map",
    "cond", "switch", "while_loop", "fori_loop", "checkpoint", "remat",
    "grad", "value_and_grad", "jacfwd", "jacrev", "hessian",
    "eval_shape", "make_jaxpr", "custom_jvp", "custom_vjp",
    "associative_scan", "filter_jit",
})

HOST_SYNC_METHODS = frozenset({"item", "tolist"})
HOST_SYNC_NP = frozenset({"asarray", "array"})
HOST_CALLBACKS = frozenset({"io_callback", "pure_callback"})

# The one sanctioned host-callback site: repro.obs's opt-in in-scan debug tap
# (repro/obs/tap.py). HOST_SYNC findings under this prefix are re-filed as
# allowed-with-reason instead of kept; the allowance is path-scoped so a
# callback added anywhere else still fails the lint gate
# (tests/test_analysis.py pins that it does not leak).
OBS_ALLOWANCE_PREFIX = "src/repro/obs/"
OBS_ALLOWANCE_REASON = ("repro.obs debug tap: opt-in host callback for "
                        "streaming metrics out of a fused scan; never on a "
                        "benchmarked path")


def _callee_name(func: ast.expr) -> str | None:
    """Last dotted segment of a call target: ``jax.lax.scan`` -> ``scan``."""
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _dotted(func: ast.expr) -> str:
    parts = []
    node = func
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_jit_call(node: ast.expr) -> bool:
    """``jax.jit(...)`` / ``jit(...)`` / ``partial(jax.jit, ...)``."""
    if not isinstance(node, ast.Call):
        return False
    name = _callee_name(node.func)
    if name in ("jit", "filter_jit"):
        return True
    if name == "partial" and node.args:
        return _callee_name(node.args[0]) in ("jit", "filter_jit")
    return False


def _names_in(node: ast.expr) -> set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _has_call(node: ast.expr) -> bool:
    return any(isinstance(n, ast.Call) for n in ast.walk(node))


def _snippet(node: ast.expr, limit: int = 60) -> str:
    try:
        s = ast.unparse(node)
    except Exception:
        s = "<expr>"
    return s if len(s) <= limit else s[:limit] + "..."


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, traced_names: set[str]):
        self.path = path
        self.traced_names = traced_names
        self.findings: list[Finding] = []
        # stacks
        self._func_stack: list[tuple[ast.AST, bool]] = []  # (node, traced)
        self._traced_params: list[str] = []
        self._loop_stack: list[ast.AST] = []

    # -- helpers -----------------------------------------------------------

    def _emit(self, rule: str, node: ast.AST, message: str):
        self.findings.append(Finding(
            rule=rule, path=self.path,
            line=getattr(node, "lineno", 0), message=message))

    @property
    def _in_traced(self) -> bool:
        return any(traced for _, traced in self._func_stack)

    def _func_is_traced(self, node) -> bool:
        if self._in_traced:
            return True  # nested def inside a traced function
        for dec in getattr(node, "decorator_list", []):
            if _is_jit_call(dec) or _callee_name(dec) in TRACING_FUNCS:
                return True
            if isinstance(dec, ast.Call) and (
                    _callee_name(dec.func) in TRACING_FUNCS):
                return True
        name = getattr(node, "name", None)
        return name is not None and name in self.traced_names

    # -- function scoping --------------------------------------------------

    def _visit_func(self, node, params: list[str]):
        traced = self._func_is_traced(node)
        self._func_stack.append((node, traced))
        if traced:
            self._traced_params.extend(params)
        self.generic_visit(node)
        if traced:
            del self._traced_params[len(self._traced_params) - len(params):]
        self._func_stack.pop()

    def visit_FunctionDef(self, node):
        args = node.args
        params = [a.arg for a in (args.posonlyargs + args.args
                                  + args.kwonlyargs)]
        for extra in (args.vararg, args.kwarg):
            if extra is not None:
                params.append(extra.arg)
        self._visit_func(node, params)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        args = node.args
        params = [a.arg for a in (args.posonlyargs + args.args
                                  + args.kwonlyargs)]
        self._visit_func(node, params)

    # -- loops -------------------------------------------------------------

    def visit_For(self, node):
        self._loop_stack.append(node)
        self.generic_visit(node)
        self._loop_stack.pop()

    visit_AsyncFor = visit_For

    def visit_While(self, node):
        self._loop_stack.append(node)
        self.generic_visit(node)
        self._loop_stack.pop()

    # -- calls: all three rules fire here ----------------------------------

    def visit_Call(self, node):
        self._check_host_callback(node)
        self._check_host_sync(node)
        self._check_recompile(node)
        self._check_key_in_loop(node)
        self.generic_visit(node)

    def _check_host_callback(self, node: ast.Call):
        """Host-callback staging is a host bridge wherever it appears (the
        callback body runs Python against device execution), so this fires
        regardless of traced context — the obs tap allowance is applied
        afterwards by path, not here."""
        name = _callee_name(node.func)
        dotted = _dotted(node.func)
        if name in HOST_CALLBACKS or dotted.endswith("debug.callback"):
            self._emit("HOST_SYNC", node,
                       f"{dotted}(...) stages a host callback into device "
                       "execution — a device->host bridge on every invocation")

    def _check_host_sync(self, node: ast.Call):
        if not self._in_traced:
            return
        name = _callee_name(node.func)
        if (isinstance(node.func, ast.Attribute)
                and name in HOST_SYNC_METHODS and not node.args):
            self._emit("HOST_SYNC", node,
                       f".{name}() inside a traced function forces a "
                       "device->host sync")
            return
        if (isinstance(node.func, ast.Attribute) and name in HOST_SYNC_NP
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in ("np", "numpy", "onp")):
            self._emit("HOST_SYNC", node,
                       f"{_dotted(node.func)}(...) inside a traced function "
                       "materializes on host (use jnp)")
            return
        if (isinstance(node.func, ast.Name)
                and node.func.id in ("float", "int") and node.args):
            touched = _names_in(node.args[0]) & set(self._traced_params)
            if touched:
                self._emit(
                    "HOST_SYNC", node,
                    f"{node.func.id}({_snippet(node.args[0])}) on traced "
                    f"value(s) {sorted(touched)} forces a device->host sync")

    def _check_recompile(self, node: ast.Call):
        if _is_jit_call(node) and self._loop_stack:
            self._emit("RECOMPILE_HAZARD", node,
                       "jax.jit(...) called inside a loop builds a fresh "
                       "traced wrapper (and compile) per iteration — hoist "
                       "the jit out of the loop")
        if _is_jit_call(node.func):
            self._emit("RECOMPILE_HAZARD", node,
                       "jax.jit(f)(...) immediately invoked discards the "
                       "wrapper and its trace cache after every call — bind "
                       "`f = jax.jit(...)` once and reuse it")

    def _check_key_in_loop(self, node: ast.Call):
        if not self._loop_stack or _dotted(node.func).split(".")[-1] != \
                "PRNGKey":
            return
        if not node.args or isinstance(node.args[0], ast.Constant):
            return
        arg = node.args[0]
        loop_vars: set[str] = set()
        in_while = False
        for loop in self._loop_stack:
            if isinstance(loop, (ast.For, ast.AsyncFor)):
                loop_vars |= _names_in(loop.target)
            else:
                in_while = True
        if (_names_in(arg) & loop_vars) or _has_call(arg) or in_while:
            self._emit(
                "KEY_IN_LOOP", node,
                f"PRNGKey({_snippet(arg)}) minted inside a loop — adjacent "
                "seeds are not independent streams; split one root key "
                "instead (see core.engine.key_schedule)")


def _collect_traced_names(tree: ast.AST) -> set[str]:
    """Names of functions passed to tracing combinators anywhere in module."""
    traced: set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _callee_name(node.func)
        if callee in TRACING_FUNCS or _is_jit_call(node):
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    traced.add(arg.id)
    return traced


def _collect_static_jits(tree: ast.AST) -> dict[str, tuple[int, ...]]:
    """Module bindings ``f = jax.jit(g, static_argnums=...)`` -> positions."""
    out: dict[str, tuple[int, ...]] = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and _is_jit_call(node.value)):
            continue
        for kw in node.value.keywords:
            if kw.arg == "static_argnums":
                try:
                    val = ast.literal_eval(kw.value)
                except (ValueError, SyntaxError):
                    continue
                pos = (val,) if isinstance(val, int) else tuple(val)
                out[node.targets[0].id] = pos
    return out


def _check_static_calls(tree: ast.AST, path: str,
                        static_jits: dict[str, tuple[int, ...]],
                        ) -> list[Finding]:
    findings = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id in static_jits):
            continue
        for pos in static_jits[node.func.id]:
            if pos < len(node.args) and isinstance(
                    node.args[pos], (ast.List, ast.Dict, ast.Set)):
                findings.append(Finding(
                    rule="RECOMPILE_HAZARD", path=path, line=node.lineno,
                    message=f"{node.func.id}(...) passes an unhashable "
                            f"{type(node.args[pos]).__name__.lower()} "
                            f"literal in static position {pos}"))
    return findings


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def apply_obs_allowance(findings: list[Finding],
                        ) -> tuple[list[Finding], list[tuple[Finding, str]]]:
    """Split ``findings`` into (kept, allowed-with-reason): HOST_SYNC
    findings whose path sits under ``src/repro/obs/`` are the sanctioned
    debug-tap callbacks and are recorded rather than kept. Every other rule
    — and HOST_SYNC anywhere else — passes through untouched."""
    kept: list[Finding] = []
    allowed: list[tuple[Finding, str]] = []
    for f in findings:
        p = f.path.replace(os.sep, "/")
        if f.rule == "HOST_SYNC" and p.startswith(OBS_ALLOWANCE_PREFIX):
            allowed.append((f, OBS_ALLOWANCE_REASON))
        else:
            kept.append(f)
    return kept, allowed


def lint_source(text: str, path: str) -> list[Finding]:
    try:
        tree = ast.parse(text, filename=path)
    except SyntaxError as e:
        return [Finding(rule="RECOMPILE_HAZARD", path=path,
                        line=e.lineno or 0,
                        message=f"file does not parse: {e.msg}")]
    linter = _Linter(path, _collect_traced_names(tree))
    linter.visit(tree)
    findings = linter.findings
    findings += _check_static_calls(tree, path, _collect_static_jits(tree))
    return findings


def lint_file(abspath: str, relpath: str) -> list[Finding]:
    with open(abspath, encoding="utf-8") as fh:
        return lint_source(fh.read(), relpath)


def iter_python_files(root: str, paths: list[str]):
    """Yield (abspath, repo-relative path) for every .py under ``paths``."""
    for p in paths:
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(ap):
            yield ap, os.path.relpath(ap, root)
            continue
        for dirpath, dirnames, filenames in os.walk(ap):
            dirnames[:] = [d for d in sorted(dirnames)
                           if d not in ("__pycache__", ".git")]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    full = os.path.join(dirpath, fn)
                    yield full, os.path.relpath(full, root)
