"""HOST-SYNC: every device->host sync goes through the materialize seam.

The PR-1 invariant: ``JaxWrapper.materialize``/``wait`` (modin_tpu/parallel/
engine.py) is the ONE place a device value crosses to the host, because the
crossing is where the resilience policy lives — classification, bounded
retry, and the wall-clock watchdog.  A stray ``jax.device_get``, a
``.block_until_ready()``, or an ``np.asarray``/``float``/``int``/``bool``
coercion of a device value performs the identical blocking transfer with
*none* of that machinery: a wedged device hangs the query forever and an
XlaRuntimeError surfaces raw at a random call site.

Detection is a per-function forward pass:

- ``jax.device_get(...)`` / ``x.block_until_ready()`` anywhere outside the
  seam modules is flagged unconditionally;
- names are tracked as *device-valued* when assigned from ``jnp.*`` /
  ``jax.lax.*`` calls or the ``_jit_foo(statics)(args)`` double-call pattern
  (the codebase idiom for compiled kernels), and as *host-valued* when
  assigned from a ``materialize`` call; coercion sinks
  (``np.asarray(x)``, ``float/int/bool(x)``, ``x.item()``) over a
  device-valued expression are flagged.

Host metadata escapes (``x.shape``, ``x.dtype``, ``jnp.issubdtype``) are
recognized, so shape arithmetic and dtype dispatch never trip the rule.

**Streaming leg (graftstream):** a function decorated ``@window_body`` is a
registered window-loop body — it runs once per resident window, and the
out-of-core budget only holds if it touches nothing but the window handed
to it.  Whole-column forces of *captured* (closure) state inside one —
``captured.to_numpy()``, ``materialize(captured)``, ``captured.host_cache``
— would materialize the full frame from inside the loop, so they are
flagged; the same sinks over the body's own parameters/locals (the window)
are the loop's normal work and stay clean.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional

from modin_tpu.lint.framework import FileContext, Finding, Project, Rule, register_rule
from modin_tpu.lint.rules._ast_utils import (
    STATIC_ATTRS,
    assigned_names,
    dotted_parts,
)

#: modules that ARE the seam (or deliberately below it): the engine wrapper,
#: the resilience policy itself, the shared jax import site, and the
#: fault-injection harness that wraps the seam in tests
SEAM_MODULES = (
    "modin_tpu/parallel/engine.py",
    "modin_tpu/core/execution/resilience.py",
    "modin_tpu/parallel/jax_compat.py",
    "modin_tpu/testing/faults.py",
)

#: jnp/jax functions that return host Python values (metadata), not arrays
_HOST_RETURNING = frozenset(
    {
        "issubdtype",
        "isdtype",
        "result_type",
        "promote_types",
        "can_cast",
        "iinfo",
        "finfo",
        "dtype",
        "devices",
        "device_count",
        "local_device_count",
        "local_devices",
        "default_backend",
        "process_index",
        "process_count",
    }
)

#: names whose call results are host values fetched through the seam
_MATERIALIZE_NAMES = frozenset({"materialize", "_engine_materialize"})

_DEVICE_ROOTS = frozenset({"jnp", "lax"})

_COERCION_BUILTINS = frozenset({"float", "int", "bool", "complex"})


def _is_jit_factory_call(func: ast.AST) -> bool:
    """The ``_jit_foo(...)`` half of the ``_jit_foo(...)(cols)`` idiom."""
    return isinstance(func, ast.Name) and func.id.startswith("_jit_")


def _is_window_body(fn: ast.AST) -> bool:
    """Whether ``fn`` carries the ``@window_body`` registration decorator
    (bare name or any dotted spelling, e.g. ``streaming.window_body``)."""
    for dec in getattr(fn, "decorator_list", ()):
        target = dec.func if isinstance(dec, ast.Call) else dec
        parts = dotted_parts(target)
        if parts and parts[-1] == "window_body":
            return True
    return False


def _window_local_names(fn: ast.AST) -> set:
    """Names bound inside a window-loop body (parameters and every
    assignment/loop/with/comprehension target): reads of these are the
    window; reads of anything else are captured whole-frame state."""
    args = fn.args
    names = {a.arg for a in args.args + args.kwonlyargs + args.posonlyargs}
    for special in (args.vararg, args.kwarg):
        if special is not None:
            names.add(special.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            for target in node.targets:
                names.update(assigned_names(target))
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign, ast.NamedExpr)):
            names.update(assigned_names(node.target))
        elif isinstance(node, ast.For):
            names.update(assigned_names(node.target))
        elif isinstance(node, ast.With):
            for item in node.items:
                if item.optional_vars is not None:
                    names.update(assigned_names(item.optional_vars))
        elif isinstance(node, ast.comprehension):
            names.update(assigned_names(node.target))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node is not fn:
                names.add(node.name)
    return names


def _base_name(node: ast.AST) -> Optional[str]:
    """Leftmost Name of an attribute/subscript chain, or None (a call
    result or literal has no stable identity to classify)."""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


class _FunctionState:
    """Name -> 'device' | 'host' knowledge within one function scope."""

    def __init__(self, inherited: Optional[Dict[str, str]] = None):
        self.names: Dict[str, str] = dict(inherited or {})

    def classify(self, node: ast.AST) -> Optional[str]:
        """'device', 'host', or None (unknown) for an expression."""
        if isinstance(node, ast.Name):
            return self.names.get(node.id)
        if isinstance(node, ast.Call):
            return self._classify_call(node)
        if isinstance(node, ast.Attribute):
            base = self.classify(node.value)
            if base == "device":
                return "host" if node.attr in STATIC_ATTRS else "device"
            return base
        if isinstance(node, ast.Subscript):
            return self.classify(node.value)
        if isinstance(node, ast.UnaryOp):
            return self.classify(node.operand)
        if isinstance(node, (ast.BinOp,)):
            left = self.classify(node.left)
            right = self.classify(node.right)
            if "device" in (left, right):
                return "device"
            if left == "host" and right == "host":
                return "host"
            return None
        if isinstance(node, ast.Compare):
            sides = [self.classify(node.left)] + [
                self.classify(c) for c in node.comparators
            ]
            if "device" in sides:
                return "device"
            return None
        if isinstance(node, (ast.Tuple, ast.List)):
            kinds = {self.classify(e) for e in node.elts}
            if "device" in kinds:
                return "device"
            if kinds == {"host"}:
                return "host"
            return None
        if isinstance(node, ast.IfExp):
            kinds = {self.classify(node.body), self.classify(node.orelse)}
            if "device" in kinds:
                return "device"
            return None
        if isinstance(node, ast.Constant):
            return "host"
        return None

    def _classify_call(self, node: ast.Call) -> Optional[str]:
        func = node.func
        parts = dotted_parts(func)
        if parts:
            leaf = parts[-1]
            root = parts[0]
            if leaf in _MATERIALIZE_NAMES:
                return "host"
            if len(parts) >= 2 and parts[-2] == "JaxWrapper" and leaf == "materialize":
                return "host"
            if root in _DEVICE_ROOTS or parts[:2] == ["jax", "numpy"] or parts[:2] == [
                "jax",
                "lax",
            ]:
                return "host" if leaf in _HOST_RETURNING else "device"
            if root == "jax":
                return "host" if leaf in _HOST_RETURNING else None
            if root in ("np", "numpy"):
                return "host"
            if root == "pandas" or root == "pd":
                return "host"
            if leaf in _COERCION_BUILTINS or leaf in ("len", "str", "repr", "tuple", "list"):
                return "host"
            # method call on a tracked object: device methods stay device,
            # host metadata methods (item/tolist handled as sinks) aside
            if isinstance(func, ast.Attribute):
                base = self.classify(func.value)
                if base == "device":
                    return "host" if func.attr in ("item", "tolist") else "device"
                return None
        if isinstance(func, ast.Call) and _is_jit_factory_call(func.func):
            # _jit_foo(statics)(cols) -> compiled-kernel output: device
            return "device"
        return None

    def bind(self, target: ast.AST, kind: Optional[str]) -> None:
        for name in assigned_names(target):
            if kind is None:
                self.names.pop(name, None)
            else:
                self.names[name] = kind


@register_rule
class HostSyncRule(Rule):
    id = "HOST-SYNC"
    description = (
        "device->host syncs (device_get / block_until_ready / np.asarray / "
        "float/int/bool coercion of device values) must go through "
        "JaxWrapper.materialize so the resilience policy applies"
    )

    def check_file(self, ctx: FileContext, project: Project) -> Iterator[Finding]:
        if ctx.rel in SEAM_MODULES or any(
            ctx.rel.endswith(m) for m in SEAM_MODULES
        ):
            return
        # 1. unconditional: raw seam primitives outside the seam modules
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            parts = dotted_parts(node.func)
            leaf = parts[-1] if parts else None
            if leaf == "device_get":
                yield Finding(
                    path=ctx.rel,
                    line=node.lineno,
                    rule=self.id,
                    message="raw jax.device_get bypasses the resilience seam",
                    fix_hint="route through modin_tpu.parallel.engine."
                    "materialize (JaxWrapper.materialize)",
                    scope=ctx.scope_of(node),
                    symbol="device_get",
                )
            elif leaf == "block_until_ready" and isinstance(node.func, ast.Attribute):
                yield Finding(
                    path=ctx.rel,
                    line=node.lineno,
                    rule=self.id,
                    message="raw block_until_ready bypasses the resilience seam",
                    fix_hint="route through JaxWrapper.wait",
                    scope=ctx.scope_of(node),
                    symbol="block_until_ready",
                )
        # 2. dataflow: device-valued expressions reaching coercion sinks
        yield from self._check_scope(ctx, ctx.tree, _FunctionState())
        # 3. streaming leg: whole-frame forces inside window-loop bodies
        for node in ast.walk(ctx.tree):
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)
            ) and _is_window_body(node):
                yield from self._check_window_body(ctx, node)

    # -- streaming leg ---------------------------------------------------- #

    def _check_window_body(
        self, ctx: FileContext, fn: ast.AST
    ) -> Iterator[Finding]:
        local = _window_local_names(fn)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                sink = self._window_call_sink(node, local)
                if sink is not None:
                    yield self._window_finding(ctx, fn, node, sink)
            elif (
                isinstance(node, ast.Attribute)
                and node.attr == "host_cache"
                and isinstance(node.ctx, ast.Load)
            ):
                base = _base_name(node.value)
                if base is not None and base not in local:
                    yield self._window_finding(ctx, fn, node, ".host_cache")

    def _window_call_sink(
        self, call: ast.Call, local: set
    ) -> Optional[str]:
        func = call.func
        if isinstance(func, ast.Attribute) and func.attr in (
            "to_numpy",
            "materialize",
        ):
            base = _base_name(func.value)
            if base is not None and base not in local and base != "JaxWrapper":
                return f".{func.attr}()"
        parts = dotted_parts(func)
        if parts and parts[-1] in _MATERIALIZE_NAMES | {"materialize"}:
            for arg in call.args:
                base = _base_name(arg)
                if base is not None and base not in local:
                    return f"{parts[-1]}()"
        return None

    def _window_finding(
        self, ctx: FileContext, fn: ast.AST, node: ast.AST, sink: str
    ) -> Finding:
        return Finding(
            path=ctx.rel,
            line=node.lineno,
            rule=self.id,
            message=f"{sink} forces whole-frame state captured from outside "
            "the window-loop body (one window must never materialize the "
            "full frame)",
            fix_hint="operate only on the window handed to the body; hoist "
            "whole-column fetches out of the loop or slice them per window",
            scope=ctx.scope_of(node),
            symbol=f"stream-{fn.name}-{sink.strip('().')}",
        )

    # -- dataflow pass -------------------------------------------------- #

    def _check_scope(
        self, ctx: FileContext, scope_node: ast.AST, state: _FunctionState
    ) -> Iterator[Finding]:
        body = getattr(scope_node, "body", [])
        yield from self._check_stmts(ctx, body, state)

    def _check_stmts(
        self, ctx: FileContext, stmts: List[ast.stmt], state: _FunctionState
    ) -> Iterator[Finding]:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # inner scope: inherits current knowledge (closures), params
                # are unknown; its bindings don't leak back out
                inner = _FunctionState(state.names)
                for arg in stmt.args.args + stmt.args.kwonlyargs:
                    inner.names.pop(arg.arg, None)
                yield from self._check_scope(ctx, stmt, inner)
                continue
            if isinstance(stmt, ast.ClassDef):
                yield from self._check_scope(ctx, stmt, _FunctionState(state.names))
                continue
            # compound statements: scan only their header expressions for
            # sinks (state before binding), then recurse into the bodies
            if isinstance(stmt, ast.For):
                yield from self._scan_expr(ctx, stmt.iter, state)
                state.bind(stmt.target, state.classify(stmt.iter))
                yield from self._check_stmts(ctx, stmt.body, state)
                yield from self._check_stmts(ctx, stmt.orelse, state)
            elif isinstance(stmt, (ast.While, ast.If)):
                yield from self._scan_expr(ctx, stmt.test, state)
                yield from self._check_stmts(ctx, stmt.body, state)
                yield from self._check_stmts(ctx, stmt.orelse, state)
            elif isinstance(stmt, ast.With):
                for item in stmt.items:
                    yield from self._scan_expr(ctx, item.context_expr, state)
                yield from self._check_stmts(ctx, stmt.body, state)
            elif isinstance(stmt, ast.Try):
                yield from self._check_stmts(ctx, stmt.body, state)
                for handler in stmt.handlers:
                    yield from self._check_stmts(ctx, handler.body, state)
                yield from self._check_stmts(ctx, stmt.orelse, state)
                yield from self._check_stmts(ctx, stmt.finalbody, state)
            else:
                # simple statement: scan the whole thing, then apply bindings
                yield from self._scan_expr(ctx, stmt, state)
                if isinstance(stmt, ast.Assign):
                    kind = state.classify(stmt.value)
                    for target in stmt.targets:
                        state.bind(target, kind)
                elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                    state.bind(stmt.target, state.classify(stmt.value))
                elif isinstance(stmt, ast.AugAssign):
                    state.bind(stmt.target, state.classify(stmt.value))

    def _scan_expr(
        self, ctx: FileContext, node: ast.AST, state: _FunctionState
    ) -> Iterator[Finding]:
        for expr in ast.walk(node):
            if isinstance(expr, ast.Call):
                finding = self._check_sink(ctx, expr, state)
                if finding is not None:
                    yield finding

    def _check_sink(
        self, ctx: FileContext, call: ast.Call, state: _FunctionState
    ) -> Optional[Finding]:
        func = call.func
        # float(x) / int(x) / bool(x)
        if (
            isinstance(func, ast.Name)
            and func.id in _COERCION_BUILTINS
            and len(call.args) == 1
            and state.classify(call.args[0]) == "device"
        ):
            return self._coercion_finding(ctx, call, f"{func.id}()")
        # np.asarray(x) / numpy.asarray(x) / np.array(x)
        if (
            isinstance(func, ast.Attribute)
            and func.attr in ("asarray", "array")
            and isinstance(func.value, ast.Name)
            and func.value.id in ("np", "numpy")
            and call.args
            and state.classify(call.args[0]) == "device"
        ):
            return self._coercion_finding(ctx, call, f"np.{func.attr}()")
        # x.item() / x.tolist()
        if (
            isinstance(func, ast.Attribute)
            and func.attr in ("item", "tolist")
            and not call.args
            and state.classify(func.value) == "device"
        ):
            return self._coercion_finding(ctx, call, f".{func.attr}()")
        return None

    def _coercion_finding(
        self, ctx: FileContext, call: ast.Call, sink: str
    ) -> Finding:
        return Finding(
            path=ctx.rel,
            line=call.lineno,
            rule=self.id,
            message=f"{sink} coerces a device value on the host "
            "(implicit blocking transfer outside the resilience seam)",
            fix_hint="fetch through materialize(...) first, then coerce the "
            "host value",
            scope=ctx.scope_of(call),
            symbol=f"coerce-{sink.strip('().')}"
            f"-{ctx.enclosing_function_name(call)}",
        )
