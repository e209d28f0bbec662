"""JIT-HAZARD: jitted functions must not trace Python control flow or shapes.

Inside ``jax.jit``, the function runs once over abstract tracers; three
Python-level habits silently break (or silently bake in stale state):

1. **traced value in Python control flow** — ``if``/``while``/``assert`` on
   a traced argument (or a value derived from one) forces a concretization
   error at trace time, or worse, a host sync per call.  The fix is
   ``jnp.where``/``lax.cond``, or marking the argument static.
2. **traced value in shape position** — ``jnp.zeros(m)``, ``x.reshape(k)``,
   ``range(n)`` with a traced ``m``/``k``/``n``: XLA shapes are compile-time
   constants, so the value must be a Python int (closure constant or
   ``static_argnums``), not a tracer.
3. **closure capture of mutable state** — a jitted body reading a
   module-level ``list``/``dict``/``set`` freezes its contents at trace time;
   later mutations are silently ignored (classic stale-cache bug).

The codebase idiom (SNIPPETS-style factory closures:
``def _jit_op(static...): def fn(cols): ...; return jax.jit(fn)``) is the
*endorsed* way to make shapes static — the statics live in the closure and
participate in the ``lru_cache`` key.  This rule recognizes the idiom and
checks the inner function's parameters as traced.

Static escapes: ``x.shape``/``x.dtype``/``x.ndim`` and ``len(x)`` of a
traced array are host metadata, fine anywhere; parameters named by
``static_argnums``/``static_argnames`` at the jit site are exempt.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from modin_tpu.lint.framework import FileContext, Finding, Project, Rule, register_rule
from modin_tpu.lint.rules._ast_utils import STATIC_ATTRS, assigned_names, dotted_parts

#: jnp/lax constructors whose FIRST argument is a shape (or length)
_SHAPE_FIRST_ARG = frozenset(
    {"zeros", "ones", "empty", "full", "arange", "linspace", "eye", "tri"}
)
#: array methods whose arguments are shapes
_SHAPE_METHODS = frozenset({"reshape", "broadcast_to", "resize"})

#: module-level bindings considered mutable when read from a jitted body
_MUTABLE_LITERALS = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp, ast.SetComp)
_MUTABLE_CALLS = frozenset({"list", "dict", "set", "defaultdict", "OrderedDict"})

#: cross-device collective primitives: legal ONLY inside a shard_map body
#: (and never under a traced Python conditional there) — a collective in a
#: plain jit / under data-dependent Python control flow is the SPMD
#: miscompile class documented at ops/reductions.py:57 (a global lax.cond
#: over sharded operands partitions each branch inconsistently per device,
#: and a collective outside shard_map has no named mesh axis to rendezvous
#: on)
_COLLECTIVES = frozenset(
    {"all_to_all", "psum", "all_gather", "ppermute", "pmean", "psum_scatter"}
)


def _jit_static_params(
    call: ast.Call, fn: ast.FunctionDef
) -> Set[str]:
    """Parameter names made static by static_argnums/static_argnames."""
    static: Set[str] = set()
    params = [a.arg for a in fn.args.args]
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            nums: List[int] = []
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    nums.append(e.value)
            for n in nums:
                if 0 <= n < len(params):
                    static.add(params[n])
        elif kw.arg == "static_argnames":
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    static.add(e.value)
    return static


def _is_jit_callable(node: ast.AST) -> bool:
    """Is this expression ``jax.jit`` / ``jit`` (possibly under partial), or
    ``named_jit`` (ops/_program.py: the same call under a program name)?"""
    parts = dotted_parts(node)
    if parts is None:
        return False
    if parts[-1] == "named_jit":
        return True
    return parts[-1] == "jit" and (
        len(parts) == 1 or parts[-2] in ("jax", "compat")
    )


class _TracedState:
    """Names known to hold traced (tracer) values in one jitted body."""

    def __init__(self, traced: Set[str]):
        self.traced = set(traced)

    def is_traced_expr(self, node: ast.AST) -> bool:
        """Does this expression carry a traced value (not just metadata)?"""
        if isinstance(node, ast.Name):
            return node.id in self.traced
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return False  # x.shape etc: host metadata
            return self.is_traced_expr(node.value)
        if isinstance(node, ast.Subscript):
            return self.is_traced_expr(node.value) or self.is_traced_expr(node.slice)
        if isinstance(node, ast.Call):
            parts = dotted_parts(node.func)
            if parts and parts[-1] == "len":
                return False  # len(tracer) is its static leading dim
            if parts and parts[-1] in ("issubdtype", "isinstance"):
                return False
            # a call over traced inputs yields a traced output (jnp.sum(x)...)
            return any(self.is_traced_expr(a) for a in node.args) or any(
                self.is_traced_expr(kw.value) for kw in node.keywords
            )
        if isinstance(node, ast.BinOp):
            return self.is_traced_expr(node.left) or self.is_traced_expr(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.is_traced_expr(node.operand)
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None` resolves at trace time from the
            # Python structure — identity never concretizes a tracer
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops) and all(
                isinstance(c, ast.Constant) and c.value is None
                for c in node.comparators
            ):
                return False
            return self.is_traced_expr(node.left) or any(
                self.is_traced_expr(c) for c in node.comparators
            )
        if isinstance(node, ast.BoolOp):
            return any(self.is_traced_expr(v) for v in node.values)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.is_traced_expr(e) for e in node.elts)
        if isinstance(node, ast.IfExp):
            return (
                self.is_traced_expr(node.test)
                or self.is_traced_expr(node.body)
                or self.is_traced_expr(node.orelse)
            )
        if isinstance(node, ast.Slice):
            return any(
                part is not None and self.is_traced_expr(part)
                for part in (node.lower, node.upper, node.step)
            )
        return False


@register_rule
class JitHazardRule(Rule):
    id = "JIT-HAZARD"
    description = (
        "jitted functions must not use traced values in Python control flow "
        "or shape positions, and must not close over mutable module state"
    )

    def check_file(self, ctx: FileContext, project: Project) -> Iterator[Finding]:
        module_mutables = self._module_mutables(ctx)
        shard_bodies = self._shard_map_bodies(ctx)
        shard_scopes = {ctx.scope_of(fn) for fn in shard_bodies}
        yield from self._check_collective_placement(ctx, shard_scopes)
        yield from self._check_donation(ctx)
        for fn, static_params in self._jitted_functions(ctx):
            traced = {
                a.arg for a in fn.args.args if a.arg not in static_params
            } - {"self", "cls"}
            yield from self._check_body(
                ctx,
                fn,
                _TracedState(traced),
                module_mutables,
                in_shard_map=fn in shard_bodies,
            )

    # -- donation leg (graftfuse) --------------------------------------- #
    #
    # A buffer passed in a donated jit position is CONSUMED by the
    # dispatch: XLA reuses its memory for the program's outputs and any
    # later read answers garbage or raises "deleted or donated".  The leg
    # flags, within one function scope, any load of a name AFTER it was
    # passed at a donated argument position of a callable built by
    # ``jax.jit(..., donate_argnums=...)`` in the same scope.

    @staticmethod
    def _donate_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
        """Literal donate_argnums of a jit call, or None when absent or
        not statically known."""
        for kw in call.keywords:
            if kw.arg != "donate_argnums":
                continue
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            nums = []
            for e in elts:
                if not (isinstance(e, ast.Constant) and isinstance(e.value, int)):
                    return None
                nums.append(e.value)
            return tuple(nums)
        return None

    @staticmethod
    def _own_nodes(fn: ast.FunctionDef) -> Iterator[ast.AST]:
        """Walk ``fn``'s body WITHOUT descending into nested function
        bodies: a nested def's reads execute when IT is called, not at
        definition time, so mixing its positions into the enclosing
        function's timeline flags pre-call reads and double-reports the
        nested function's own hazards (it gets its own walk)."""
        stack: List[ast.AST] = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                stack.extend(ast.iter_child_nodes(node))

    @staticmethod
    def _branch_path(
        ctx: FileContext, node: ast.AST, stop: ast.AST
    ) -> Tuple[Dict[int, str], bool]:
        """({id(If): branch}, any-enclosing-loop) for ``node`` up to
        ``stop`` — the mutual-exclusion evidence: a load in the OTHER
        branch of an If the consuming call sits in can never execute
        after it in the same pass, unless a loop re-enters."""
        path: Dict[int, str] = {}
        loops = False
        child: ast.AST = node
        cur = ctx.parent_of(node)
        while cur is not None and cur is not stop:
            if isinstance(cur, (ast.For, ast.While, ast.AsyncFor)):
                loops = True
            if isinstance(cur, ast.If):
                path[id(cur)] = "orelse" if child in cur.orelse else "body"
            child = cur
            cur = ctx.parent_of(cur)
        return path, loops

    def _check_donation(self, ctx: FileContext) -> Iterator[Finding]:
        # scope -> {jitted-callable name: donated positions}
        donated_fns: Dict[Tuple[str, str], Tuple[int, ...]] = {}
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Assign) and isinstance(node.value, ast.Call)):
                continue
            call = node.value
            if not _is_jit_callable(call.func):
                continue
            positions = self._donate_positions(call)
            if not positions:
                continue
            scope = ctx.scope_of(node)
            for target in node.targets:
                for name in assigned_names(target):
                    donated_fns[(scope, name)] = positions
        if not donated_fns:
            return
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, ast.FunctionDef):
                continue
            scope = ctx.scope_of(fn)
            # donated names -> the source position where the consuming call
            # ENDS.  Positions are (line, col) pairs, not bare lines: in
            # `f(x) + x` the second load is on the call's own line but
            # still runs after the dispatch consumed x's buffer — Python
            # evaluates left to right, so textually-after-the-call is
            # after-the-consumption
            consumed: Dict[str, Tuple[Tuple[int, int], ast.Call]] = {}
            for node in self._own_nodes(fn):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                ):
                    # resolve through the scope chain: a nested body may
                    # call a jitted closure its ENCLOSING function built
                    positions = None
                    chain_scope = scope
                    while positions is None:
                        positions = donated_fns.get(
                            (chain_scope, node.func.id)
                        )
                        if "." not in chain_scope:
                            break
                        chain_scope = chain_scope.rsplit(".", 1)[0]
                    if positions is None:
                        positions = donated_fns.get(
                            ("<module>", node.func.id)
                        )
                    if positions:
                        end = (
                            getattr(node, "end_lineno", node.lineno),
                            getattr(node, "end_col_offset", 0),
                        )
                        for pos in positions:
                            if pos < len(node.args) and isinstance(
                                node.args[pos], ast.Name
                            ):
                                name = node.args[pos].id
                                prev = consumed.get(name)
                                # keep the EARLIEST consuming position —
                                # ast.walk is BFS, so first-seen order is
                                # not source order
                                if prev is None or end < prev[0]:
                                    consumed[name] = (end, node)
            if not consumed:
                continue
            # a rebind AFTER the consuming call makes later reads clean
            # (the name no longer holds the donated buffer).  A rebind's
            # effective position is the END of its statement, not the
            # target Name's own (left-hand) position: in the idiomatic
            # `x = f(x)` the Store is textually before the call but the
            # assignment completes after it — later reads of x hold the
            # program's OUTPUT and are clean.
            rebinds: Dict[str, List[Tuple[int, int]]] = {}
            for node in self._own_nodes(fn):
                if isinstance(
                    node, (ast.Assign, ast.AugAssign, ast.AnnAssign)
                ):
                    end = (
                        getattr(node, "end_lineno", node.lineno),
                        getattr(node, "end_col_offset", 0),
                    )
                    targets = (
                        node.targets
                        if isinstance(node, ast.Assign)
                        else [node.target]
                    )
                    for target in targets:
                        for name in assigned_names(target):
                            rebinds.setdefault(name, []).append(end)
                elif isinstance(node, (ast.For, ast.comprehension)):
                    for name in assigned_names(node.target):
                        rebinds.setdefault(name, []).append(
                            (node.target.lineno, node.target.col_offset)
                        )
            for node in self._own_nodes(fn):
                if not (
                    isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in consumed
                ):
                    continue
                pos, call = consumed[node.id]
                if (node.lineno, node.col_offset) <= pos:
                    continue
                if any(
                    # <=: a rebind ending exactly at the consuming call's
                    # end IS the assignment that captured its result
                    # (`x = f(x)`)
                    pos <= store < (node.lineno, node.col_offset)
                    for store in rebinds.get(node.id, ())
                ):
                    continue
                # mutual exclusion: a load in the OTHER branch of an If
                # the consuming call sits in never runs after it in the
                # same pass — unless a loop can re-enter the whole shape
                call_path, call_loops = self._branch_path(ctx, call, fn)
                load_path, load_loops = self._branch_path(ctx, node, fn)
                if (
                    not call_loops
                    and not load_loops
                    and any(
                        call_path.get(k) != b
                        for k, b in load_path.items()
                        if k in call_path
                    )
                ):
                    continue
                yield Finding(
                    path=ctx.rel,
                    line=node.lineno,
                    rule=self.id,
                    message=(
                        f"`{node.id}` read after being passed in a "
                        "donated jit position — the dispatch consumed "
                        "its buffer (use-after-donate); re-read it "
                        "through its owning column's lineage instead"
                    ),
                    fix_hint=(
                        "donated buffers are dead after the call: mark "
                        "the owning DeviceColumn donated (spilled) and "
                        "access via col.raw, or drop donate_argnums"
                    ),
                    scope=ctx.scope_of(node),
                    symbol=f"donated-{node.id}",
                )

    # -- discovery ------------------------------------------------------ #

    def _module_mutables(self, ctx: FileContext) -> Set[str]:
        names: Set[str] = set()
        for stmt in ctx.tree.body:
            if isinstance(stmt, ast.Assign):
                value = stmt.value
                mutable = isinstance(value, _MUTABLE_LITERALS) or (
                    isinstance(value, ast.Call)
                    and (p := dotted_parts(value.func)) is not None
                    and p[-1] in _MUTABLE_CALLS
                )
                if mutable:
                    for t in stmt.targets:
                        names.update(assigned_names(t))
        return names

    def _defs_by_scope(
        self, ctx: FileContext
    ) -> Dict[Tuple[str, str], ast.FunctionDef]:
        """(containing scope, name) -> FunctionDef for call-form resolution."""
        defs: Dict[Tuple[str, str], ast.FunctionDef] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.FunctionDef):
                own = ctx.scope_of(node)
                containing = (
                    own.rsplit(".", 1)[0] if "." in own else "<module>"
                )
                defs[(containing, node.name)] = node
        return defs

    def _resolve_in_chain(
        self,
        ctx: FileContext,
        call: ast.Call,
        defs: Dict[Tuple[str, str], ast.FunctionDef],
    ) -> Optional[ast.FunctionDef]:
        """The same-file FunctionDef a call's first positional arg names,
        resolved through the call site's scope chain (innermost first)."""
        if not call.args or not isinstance(call.args[0], ast.Name):
            return None
        fname = call.args[0].id
        scope = ctx.scope_of(call)
        chain = [scope]
        while "." in scope:
            scope = scope.rsplit(".", 1)[0]
            chain.append(scope)
        chain.append("<module>")
        for s in chain:
            fn = defs.get((s, fname))
            if fn is not None:
                return fn
        return None

    def _shard_map_bodies(self, ctx: FileContext) -> Set[ast.FunctionDef]:
        """Function defs passed to ``shard_map(...)`` in this file — the
        only scopes where a cross-device collective is legal."""
        defs = self._defs_by_scope(ctx)
        bodies: Set[ast.FunctionDef] = set()
        for node in ast.walk(ctx.tree):
            if not (
                isinstance(node, ast.Call)
                and (p := dotted_parts(node.func)) is not None
                and p[-1] == "shard_map"
            ):
                continue
            fn = self._resolve_in_chain(ctx, node, defs)
            if fn is not None:
                bodies.add(fn)
        return bodies

    @staticmethod
    def _is_collective_call(node: ast.AST) -> Optional[str]:
        """The collective's name when ``node`` is a lax collective call."""
        if not isinstance(node, ast.Call):
            return None
        parts = dotted_parts(node.func)
        if parts is None or parts[-1] not in _COLLECTIVES:
            return None
        # module form (lax.psum / jax.lax.all_to_all) or a bare name
        # imported from lax; dotted access on anything else (obj.psum) is
        # some other API
        if len(parts) == 1 or parts[-2] == "lax":
            return parts[-1]
        return None

    def _check_collective_placement(
        self, ctx: FileContext, shard_scopes: Set[str]
    ) -> Iterator[Finding]:
        """Collectives outside every shard_map body: no named mesh axis to
        rendezvous on — at best a trace error, at worst the per-device
        inconsistent-partitioning miscompile (ops/reductions.py:57)."""
        for node in ast.walk(ctx.tree):
            name = self._is_collective_call(node)
            if name is None:
                continue
            scope = ctx.scope_of(node)
            inside = any(
                scope == s or scope.startswith(s + ".")
                for s in shard_scopes
            )
            if inside:
                continue
            yield Finding(
                path=ctx.rel,
                line=getattr(node, "lineno", 1),
                rule=self.id,
                message=(
                    f"collective `{name}` outside a shard_map body — no "
                    "mesh axis binding; under SPMD partitioning this is "
                    "the miscompile class documented at ops/reductions.py"
                ),
                fix_hint=(
                    "move the collective into a function passed to "
                    "shard_map (parallel/jax_compat.py) with the mesh axis "
                    "in scope, or use a sharded jnp reduction and let XLA "
                    "emit the collective"
                ),
                scope=scope,
                symbol=f"collective-{name}",
            )

    def _jitted_functions(
        self, ctx: FileContext
    ) -> Iterator[Tuple[ast.FunctionDef, Set[str]]]:
        """(function def, static param names) for every jitted function."""
        seen: Set[ast.FunctionDef] = set()
        # decorator forms: @jax.jit, @jit, @partial(jax.jit, ...)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.FunctionDef):
                continue
            for dec in node.decorator_list:
                if _is_jit_callable(dec):
                    seen.add(node)
                    yield node, set()
                elif isinstance(dec, ast.Call):
                    if _is_jit_callable(dec.func) or (
                        (p := dotted_parts(dec.func)) is not None
                        and p[-1] == "partial"
                        and dec.args
                        and _is_jit_callable(dec.args[0])
                    ):
                        seen.add(node)
                        yield node, _jit_static_params(dec, node)
        # call form: jax.jit(fn, ...) where fn is a def in the same file.
        # scope_of(def) includes the def's own name; key by the CONTAINING
        # scope so the jit call site's scope chain resolves it.
        defs_by_scope = self._defs_by_scope(ctx)
        for node in ast.walk(ctx.tree):
            is_jit = isinstance(node, ast.Call) and _is_jit_callable(node.func)
            # shard_map(fn, ...) traces fn exactly like jit does
            is_shard_map = (
                isinstance(node, ast.Call)
                and (p := dotted_parts(node.func)) is not None
                and p[-1] == "shard_map"
            )
            if not (is_jit or is_shard_map):
                continue
            fn = self._resolve_in_chain(ctx, node, defs_by_scope)
            if fn is not None and fn not in seen:
                seen.add(fn)
                yield fn, _jit_static_params(node, fn)

    # -- hazard checks -------------------------------------------------- #

    def _check_body(
        self,
        ctx: FileContext,
        fn: ast.FunctionDef,
        state: _TracedState,
        module_mutables: Set[str],
        in_shard_map: bool = False,
    ) -> Iterator[Finding]:
        local_bindings: Set[str] = set()
        for node in ast.walk(fn):
            # propagate tracedness through simple assignments (pre-pass is
            # one-shot; ast.walk is pre-order so defs come before uses in
            # straight-line code, which is what kernels are)
            if isinstance(node, ast.Assign):
                if state.is_traced_expr(node.value):
                    for t in node.targets:
                        state.traced.update(assigned_names(t))
                for t in node.targets:
                    local_bindings.update(assigned_names(t))

        for node in ast.walk(fn):
            # 1. Python control flow on traced values
            if isinstance(node, (ast.If, ast.While)) and state.is_traced_expr(
                node.test
            ):
                kind = "if" if isinstance(node, ast.If) else "while"
                yield self._finding(
                    ctx,
                    node,
                    fn,
                    f"`{kind}` on a traced value concretizes the tracer",
                    "use jnp.where / lax.cond, or make the value static "
                    "(closure constant or static_argnums)",
                    f"branch-{kind}",
                )
            elif isinstance(node, ast.Assert) and state.is_traced_expr(node.test):
                yield self._finding(
                    ctx,
                    node,
                    fn,
                    "`assert` on a traced value concretizes the tracer",
                    "use checkify or drop the assert from the jitted body",
                    "branch-assert",
                )
            elif isinstance(node, ast.IfExp) and state.is_traced_expr(node.test):
                yield self._finding(
                    ctx,
                    node,
                    fn,
                    "conditional expression on a traced value concretizes "
                    "the tracer",
                    "use jnp.where(test, a, b)",
                    "branch-ifexp",
                )
            # 2. traced values in shape positions
            if isinstance(node, ast.Call):
                yield from self._check_shape_call(ctx, node, fn, state)
            # 2b. collective under a traced Python conditional inside a
            # shard_map body: the branch partitions inconsistently per
            # device and the collective rendezvous never lines up — the
            # SPMD miscompile class documented at ops/reductions.py:57
            if (
                in_shard_map
                and isinstance(node, (ast.If, ast.While, ast.IfExp))
                and state.is_traced_expr(node.test)
            ):
                for sub in ast.walk(node):
                    name = self._is_collective_call(sub)
                    if name is not None:
                        yield self._finding(
                            ctx,
                            sub,
                            fn,
                            f"collective `{name}` under a traced Python "
                            "conditional — per-device branch divergence "
                            "deadlocks/miscompiles the rendezvous",
                            "hoist the collective out of the branch; "
                            "select its INPUT with jnp.where instead",
                            f"collective-branch-{name}",
                        )
                        break

        # 3. closure capture of mutable module state
        reported: Set[str] = set()
        params = {a.arg for a in fn.args.args}
        for node in ast.walk(fn):
            if (
                isinstance(node, ast.Name)
                and isinstance(node.ctx, ast.Load)
                and node.id in module_mutables
                and node.id not in params
                and node.id not in local_bindings
                and node.id not in reported
            ):
                reported.add(node.id)
                yield self._finding(
                    ctx,
                    node,
                    fn,
                    f"jitted body reads mutable module state `{node.id}` — "
                    "tracing freezes its current contents",
                    "pass it as an argument, hoist an immutable snapshot "
                    "(tuple/frozenset), or look it up outside the jit",
                    f"closure-{node.id}",
                )

    def _check_shape_call(
        self,
        ctx: FileContext,
        call: ast.Call,
        fn: ast.FunctionDef,
        state: _TracedState,
    ) -> Iterator[Finding]:
        parts = dotted_parts(call.func)
        leaf = parts[-1] if parts else None
        if leaf == "range":
            if any(state.is_traced_expr(a) for a in call.args):
                yield self._finding(
                    ctx,
                    call,
                    fn,
                    "range() over a traced value unrolls at trace time "
                    "(or fails to concretize)",
                    "use lax.fori_loop / lax.scan, or a static bound",
                    "shape-range",
                )
            return
        shape_args: List[ast.AST] = []
        module_form = parts is not None and (
            parts[0] in ("jnp", "np", "numpy", "lax")
            or parts[:2] in (["jax", "numpy"], ["jax", "lax"])
        )
        if leaf in _SHAPE_FIRST_ARG and module_form:
            if call.args:
                shape_args = [call.args[0]]
                if leaf in ("arange", "linspace"):
                    shape_args = list(call.args)  # any bound being traced is the bug
        elif leaf in _SHAPE_METHODS and isinstance(call.func, ast.Attribute):
            # jnp.reshape(arr, shape) / jnp.broadcast_to(arr, shape) carry
            # the data in arg 0; the method form x.reshape(shape) doesn't
            shape_args = list(call.args[1:] if module_form else call.args)
        for arg in shape_args:
            if state.is_traced_expr(arg):
                yield self._finding(
                    ctx,
                    call,
                    fn,
                    f"traced value in the shape position of {leaf}() — XLA "
                    "shapes are compile-time constants",
                    "make the size a Python int: closure constant, "
                    "static_argnums at the jit site, or x.shape metadata",
                    f"shape-{leaf}",
                )
                break

    def _finding(
        self,
        ctx: FileContext,
        node: ast.AST,
        fn: ast.FunctionDef,
        message: str,
        fix_hint: str,
        symbol: str,
    ) -> Finding:
        return Finding(
            path=ctx.rel,
            line=getattr(node, "lineno", fn.lineno),
            rule=self.id,
            message=f"in jitted `{fn.name}`: {message}",
            fix_hint=fix_hint,
            scope=ctx.scope_of(node),
            symbol=f"{fn.name}-{symbol}",
        )
