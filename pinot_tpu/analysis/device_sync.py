"""Host-device sync auditor (W013/W014) over a Project.

Propagates "device value" taint from `jnp.*` / `lax.*` /
`jax.device_put` / jitted-callable sources through local dataflow and
the call graph (a function whose return value is tainted marks every
call site tainted — computed as a fixpoint over the whole package), then
flags the two ways a device value silently stalls the async dispatch
pipeline *on the warm query path*:

  W013  implicit device->host sync: float()/int()/bool()/.item()/
        .tolist()/np.asarray() on a device value, or any
        block_until_ready (the warm path gets exactly one sanctioned
        fence — the r8 `device_wait` in ServerInstance.execute, carried
        on the allowlist below).
  W014  host control flow (if/while) branching on a device value —
        forces a blocking transfer at trace boundaries; the decision
        belongs at plan time or inside the graph (jnp.where/lax.cond).

Warm path = parallel/engine.py, query/reduce.py, cluster/server.py,
ops/* (the modules between "plan hit" and "rows returned").  Function
bodies that are themselves traced (passed to jit/pallas_call/shard_map/
vmap/fori_loop/...) are excluded — inside a trace these ops are either
fine or a trace error, not a silent sync.  Taint does not flow through
parameters (only through returns); that keeps the pass fast and
false-positive-shy at the cost of missing device values handed down as
arguments — the per-file W002 covers the jitted side of that gap.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from pinot_tpu.analysis.engine import FunctionInfo, Pass, Project
from pinot_tpu.analysis.repo_lint import Finding

WARM_PATH_SUFFIXES = (
    "parallel/engine.py",
    "query/reduce.py",
    "cluster/server.py",
)
WARM_PATH_DIRS = ("/ops/",)

# the sanctioned warm-path fence (r8 device_wait): one block_until_ready
# over all pending outputs, splitting device time from host dispatch in the
# trace tree (trace-enabled only)
ALLOWED_SYNCS: Set[Tuple[str, str]] = {
    ("cluster/server.py", "ServerInstance.execute"),
}

_DEVICE_PREFIXES = ("jax.numpy.", "jax.lax.")
_DEVICE_CALLS = {"jax.device_put", "jax.block_until_ready", "jax.eval_shape"}
_SANITIZERS = {"jax.device_get"}
# jnp functions whose RESULT lives on host (dtype/shape metadata predicates)
_HOST_RESULT_JAX = {
    "jax.numpy.issubdtype",
    "jax.numpy.isdtype",
    "jax.numpy.result_type",
    "jax.numpy.promote_types",
    "jax.numpy.can_cast",
    "jax.numpy.dtype",
    "jax.numpy.shape",
    "jax.numpy.ndim",
    "jax.numpy.iinfo",
    "jax.numpy.finfo",
    "jax.default_backend",
    "jax.devices",
    "jax.local_devices",
    "jax.device_count",
}
_METADATA_ATTRS = {"shape", "ndim", "dtype", "size", "nbytes", "sharding", "weak_type", "at"}
_HOST_RESULT_METHODS = {"item", "tolist"}  # sinks; their result is host

_TRACE_WRAPPERS = (
    "jit", "pallas_call", "shard_map", "vmap", "pmap", "fori_loop",
    "while_loop", "scan", "cond", "checkpoint", "custom_vjp", "custom_jvp",
    "named_call", "grad",
)


def _callable_name(func: ast.AST) -> str:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return ""


def _is_trace_wrapper(func: ast.AST) -> bool:
    name = _callable_name(func)
    return any(w in name for w in _TRACE_WRAPPERS)


def traced_names(tree: ast.Module) -> Set[str]:
    """Function names whose bodies execute under a JAX trace: decorated
    with @*jit*, or passed by name to jit/pallas_call/shard_map/vmap/
    fori_loop/... anywhere in the module."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_trace_wrapper(node.func):
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    names.add(arg.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                d = dec.func if isinstance(dec, ast.Call) else dec
                if _is_trace_wrapper(d) or any(
                    _is_trace_wrapper(a)
                    for a in (dec.args if isinstance(dec, ast.Call) else [])
                ):
                    names.add(node.name)
    return names


class _Scope:
    """Flow-sensitive local taint for one function body."""

    def __init__(
        self,
        pass_: "DeviceSyncPass",
        fi: FunctionInfo,
        project: Project,
        returns_device: Set[str],
        module_traced: Set[str],
        findings: Optional[List[Finding]],
    ) -> None:
        self.p = pass_
        self.fi = fi
        self.project = project
        self.returns_device = returns_device
        self.module_traced = module_traced
        self.findings = findings
        self.taint: Set[str] = set()
        self.jitted_locals: Set[str] = set()
        self.returns_tainted = False
        self._reported: Set[Tuple[int, str]] = set()

    # -- expression taint --------------------------------------------------

    def tainted(self, e: Optional[ast.AST]) -> bool:
        if e is None:
            return False
        if isinstance(e, ast.Name):
            return e.id in self.taint
        if isinstance(e, ast.Call):
            return self._call_tainted(e)
        if isinstance(e, ast.Attribute):
            return self.tainted(e.value) and e.attr not in _METADATA_ATTRS
        if isinstance(e, ast.Subscript):
            return self.tainted(e.value)
        if isinstance(e, ast.Compare):
            # `is`/`is not` never touch values; `in`/`not in` against a host
            # container of device values (the params-dict idiom) is a host
            # key lookup, not a sync
            _HOST_OPS = (ast.Is, ast.IsNot, ast.In, ast.NotIn)
            t = False
            if not isinstance(e.ops[0], _HOST_OPS):
                t = self.tainted(e.left)
            for op, comp in zip(e.ops, e.comparators):
                if not isinstance(op, _HOST_OPS):
                    t = t or self.tainted(comp)
            return t
        if isinstance(e, (ast.Lambda, ast.FunctionDef, ast.AsyncFunctionDef)):
            return False
        # generic containers/operators: tainted if any child expression is
        return any(
            self.tainted(c)
            for c in ast.iter_child_nodes(e)
            if isinstance(c, ast.expr)
        )

    def _call_tainted(self, e: ast.Call) -> bool:
        target = self.project.resolve_expr(self.fi, e.func)
        if target is not None:
            if target in _SANITIZERS or target in _HOST_RESULT_JAX:
                return False
            if target in _DEVICE_CALLS or target.startswith(_DEVICE_PREFIXES):
                return True
            if target.startswith("jax.tree_util.") or target.startswith("jax.tree."):
                return any(self.tainted(a) for a in e.args)
            if target in self.returns_device:
                return True
            if target.startswith("numpy."):
                return False  # host result (and possibly a sink — checked there)
        if isinstance(e.func, ast.Name) and e.func.id in self.jitted_locals:
            return True
        if isinstance(e.func, ast.Attribute):
            if e.func.attr in _HOST_RESULT_METHODS:
                return False
            # method call on a device value stays on device (x.sum(), x.astype())
            return self.tainted(e.func.value)
        return False

    # -- sinks -------------------------------------------------------------

    def _warm(self) -> bool:
        rel = self.fi.module.relpath
        return rel.endswith(self.p.warm_suffixes) or any(
            d in f"/{rel}" for d in self.p.warm_dirs
        )

    def _allowed(self) -> bool:
        sym = self._symbol()
        rel = self.fi.module.relpath
        return any(rel.endswith(p) and sym == s for p, s in self.p.allowed_syncs)

    def _symbol(self) -> str:
        if self.fi.cls is not None:
            return f"{self.fi.cls.name}.{self.fi.name}"
        return self.fi.name

    def _emit(self, line: int, rule: str, msg: str, hint: str) -> None:
        if self.findings is None:
            return
        key = (line, rule)
        if key in self._reported:
            return
        self._reported.add(key)
        self.findings.append(
            Finding(self.fi.module.relpath, line, rule, msg, hint=hint, symbol=self._symbol())
        )

    def check_call_sink(self, e: ast.Call) -> None:
        if self.findings is None or not self._warm():
            return
        target = self.project.resolve_expr(self.fi, e.func)
        name = _callable_name(e.func)
        if name == "block_until_ready" or target == "jax.block_until_ready":
            if not self._allowed():
                self._emit(
                    e.lineno,
                    "W013",
                    "block_until_ready on the warm path — every call is a "
                    "full pipeline stall",
                    "drain via jax.device_get at the collect point; the warm "
                    "path's one sanctioned fence is ServerInstance.execute's "
                    "device_wait",
                )
            return
        if (
            isinstance(e.func, ast.Name)
            and e.func.id in ("float", "int", "bool")
            and any(self.tainted(a) for a in e.args)
        ):
            self._emit(
                e.lineno,
                "W013",
                f"{e.func.id}() on a device value forces an implicit "
                "device->host sync",
                "materialize once via jax.device_get() at the drain point, "
                "then convert on host",
            )
            return
        if (
            isinstance(e.func, ast.Attribute)
            and e.func.attr in _HOST_RESULT_METHODS
            and self.tainted(e.func.value)
        ):
            self._emit(
                e.lineno,
                "W013",
                f".{e.func.attr}() on a device value forces an implicit "
                "device->host sync",
                "materialize once via jax.device_get() at the drain point",
            )
            return
        if target is not None and target.startswith("numpy.") and any(
            self.tainted(a) for a in e.args
        ):
            self._emit(
                e.lineno,
                "W013",
                f"{target}() on a device value forces an implicit "
                "device->host transfer",
                "keep the computation in jnp on device, or jax.device_get() "
                "once and reuse the host array",
            )

    def check_branch(self, test: ast.AST, lineno: int) -> None:
        if self.findings is None or not self._warm():
            return
        if isinstance(test, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops
        ):
            return
        if self.tainted(test):
            self._emit(
                lineno,
                "W014",
                "host control flow branches on a device value (blocking "
                "transfer at the branch)",
                "hoist the decision to plan time or compute both sides with "
                "jnp.where/lax.cond",
            )

    # -- statement walk ----------------------------------------------------

    def run(self, body: List[ast.stmt]) -> None:
        self.process_block(body)

    def process_block(self, stmts: Iterable[ast.stmt]) -> None:
        for s in stmts:
            self.process_stmt(s)

    def _scan_sinks(self, node: ast.AST) -> None:
        """Check every call in an expression tree, skipping deferred bodies."""
        stack = [node]
        while stack:
            cur = stack.pop()
            if isinstance(cur, (ast.Lambda, ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(cur, ast.Call):
                self.check_call_sink(cur)
            stack.extend(ast.iter_child_nodes(cur))

    def _assign_target(self, target: ast.AST, value_tainted: bool) -> None:
        if isinstance(target, ast.Name):
            if value_tainted:
                self.taint.add(target.id)
            else:
                self.taint.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._assign_target(elt, value_tainted)
        elif isinstance(target, ast.Starred):
            self._assign_target(target.value, value_tainted)
        elif isinstance(target, ast.Subscript) and value_tainted:
            # storing a device value into a local container taints the container
            if isinstance(target.value, ast.Name):
                self.taint.add(target.value.id)

    def _note_jitted_local(self, target: ast.AST, value: ast.AST) -> None:
        if not isinstance(target, ast.Name) or not isinstance(value, ast.Call):
            return
        name = _callable_name(value.func)
        if any(w in name for w in ("jit", "shard_map", "pmap")):
            self.jitted_locals.add(target.id)

    def process_stmt(self, s: ast.stmt) -> None:
        if isinstance(s, ast.Assign):
            self._scan_sinks(s.value)
            t = self.tainted(s.value)
            for target in s.targets:
                self._assign_target(target, t)
                self._note_jitted_local(target, s.value)
        elif isinstance(s, ast.AnnAssign):
            if s.value is not None:
                self._scan_sinks(s.value)
                self._assign_target(s.target, self.tainted(s.value))
        elif isinstance(s, ast.AugAssign):
            self._scan_sinks(s.value)
            if self.tainted(s.value):
                self._assign_target(s.target, True)
        elif isinstance(s, ast.Return):
            if s.value is not None:
                self._scan_sinks(s.value)
                if self.tainted(s.value):
                    self.returns_tainted = True
        elif isinstance(s, ast.Expr):
            self._scan_sinks(s.value)
        elif isinstance(s, ast.If):
            self._scan_sinks(s.test)
            self.check_branch(s.test, s.lineno)
            self.process_block(s.body)
            self.process_block(s.orelse)
        elif isinstance(s, ast.While):
            self._scan_sinks(s.test)
            self.check_branch(s.test, s.lineno)
            for _ in range(2):  # second pass picks up loop-carried taint
                self.process_block(s.body)
            self.process_block(s.orelse)
        elif isinstance(s, (ast.For, ast.AsyncFor)):
            self._scan_sinks(s.iter)
            iter_tainted = self.tainted(s.iter)
            for _ in range(2):
                self._assign_target(s.target, iter_tainted)
                self.process_block(s.body)
            self.process_block(s.orelse)
        elif isinstance(s, (ast.With, ast.AsyncWith)):
            for item in s.items:
                self._scan_sinks(item.context_expr)
                if item.optional_vars is not None:
                    self._assign_target(
                        item.optional_vars, self.tainted(item.context_expr)
                    )
            self.process_block(s.body)
        elif isinstance(s, ast.Try):
            self.process_block(s.body)
            for h in s.handlers:
                self.process_block(h.body)
            self.process_block(s.orelse)
            self.process_block(s.finalbody)
        elif isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if s.name in self.module_traced:
                return  # traced body: device ops there are the point
            inner = _Scope(
                self.p, self.fi, self.project, self.returns_device,
                self.module_traced, self.findings,
            )
            inner.taint = set(self.taint)
            inner.jitted_locals = set(self.jitted_locals)
            inner._reported = self._reported
            inner.process_block(s.body)
        elif isinstance(s, (ast.Assert, ast.Raise)):
            for child in ast.iter_child_nodes(s):
                self._scan_sinks(child)
        elif isinstance(s, ast.Delete):
            for t in s.targets:
                if isinstance(t, ast.Name):
                    self.taint.discard(t.id)


class DeviceSyncPass(Pass):
    name = "device_sync"

    def __init__(
        self,
        warm_suffixes: Optional[Tuple[str, ...]] = None,
        warm_dirs: Optional[Tuple[str, ...]] = None,
        allowed_syncs: Optional[Set[Tuple[str, str]]] = None,
    ) -> None:
        self.warm_suffixes = warm_suffixes or WARM_PATH_SUFFIXES
        self.warm_dirs = warm_dirs or WARM_PATH_DIRS
        self.allowed_syncs = allowed_syncs if allowed_syncs is not None else ALLOWED_SYNCS

    def run(self, project: Project) -> List[Finding]:
        module_traced: Dict[str, Set[str]] = {
            name: traced_names(mi.tree) for name, mi in project.modules.items()
        }

        def analyze(fi: FunctionInfo, returns_device: Set[str], findings):
            scope = _Scope(
                self, fi, project, returns_device,
                module_traced[fi.module.name], findings,
            )
            scope.run(fi.node.body)
            return scope.returns_tainted

        # fixpoint: which project functions return device values
        returns_device: Set[str] = set()
        for _ in range(8):
            changed = False
            for fi in project.functions.values():
                if fi.name in module_traced[fi.module.name]:
                    continue
                if fi.qname in returns_device:
                    continue
                if analyze(fi, returns_device, None):
                    returns_device.add(fi.qname)
                    changed = True
            if not changed:
                break

        findings: List[Finding] = []
        for fi in project.functions.values():
            if fi.name in module_traced[fi.module.name]:
                continue
            analyze(fi, returns_device, findings)
        return findings
