"""REGISTRY-DRIFT: metrics, spans, and env vars must be declared/documented.

Three quiet ways observability rots:

1. **metrics** — an ``emit_metric("some.new.counter", 1)`` call ships
   without anyone updating dashboards or docs; months later nobody knows
   what feeds it.  Every emitted metric name (f-string placeholders become
   ``*`` wildcards) must match a pattern declared in ``METRICS`` in
   ``modin_tpu/logging/metrics.py``, every declared pattern must have a
   live emit site, and each pattern's stable dotted prefix must appear in
   ``docs/``.  graftmeter adds the **kind** leg: every ``METRICS`` entry
   must declare a valid meter kind (``counter`` / ``gauge`` /
   ``histogram``) in position 1, and the histogram declarations are
   cross-checked both ways against ``HISTOGRAM_BUCKETS`` in
   ``modin_tpu/observability/meters.py`` — a histogram family without a
   bucket spec would silently aggregate as a counter, and a bucket spec
   without a histogram family is dead configuration.

2. **spans** — graftscope's statically-named span emissions
   (``graftscope.span("...")`` / ``graftscope.start_span("...")``) are held
   to the same contract against the ``SPANS`` registry in
   ``modin_tpu/observability/spans.py``: undeclared span name, dead
   registry pattern, or undocumented family all fail.  Runtime-built names
   go through ``layer_span`` and are exempt (they are covered by the
   layer-tag classification, not the registry).

3. **env vars** — a ``MODIN_TPU_*`` variable read via raw ``os.environ``
   bypasses ``config/envvars.py`` entirely: no default, no type checking,
   no ``_check_vars`` typo warning, no docs.  Every ``MODIN_TPU_*`` literal
   in the package must be a declared ``varname`` in ``config/envvars.py``,
   and every declared varname must be mentioned in ``docs/``.

4. **locks** — graftdep's ``LOCKS`` registry
   (``concurrency/registry.py``) is cross-checked against the actual
   ``named_lock``/``named_rlock`` construction sites both ways: a
   construction whose literal name is undeclared (would raise at import
   time — caught at lint time instead), a declared name no site
   constructs (dead declaration the order table keeps ordering), and a
   kind mismatch (``named_lock`` for an ``"rlock"`` declaration or vice
   versa).  A raw ``threading.Lock()``/``RLock()`` construction outside
   ``concurrency/`` is flagged too — even one never acquired in-tree
   (which LOCK-ORDER would miss) is invisible to lockdep.  Every
   declared lock name must appear in ``docs/``.

Docstrings are exempt from the literal scan (prose references a knob by
name legitimately); docs checks are skipped when the scanned tree has no
``docs/`` directory (snippet unit tests, vendored subsets).
"""

from __future__ import annotations

import ast
import fnmatch
import re
from typing import Dict, Iterator, List, Optional, Set, Tuple

from modin_tpu.lint.framework import FileContext, Finding, Project, Rule, register_rule
from modin_tpu.lint.rules._ast_utils import is_docstring

METRICS_SUFFIX = "logging/metrics.py"
SPANS_SUFFIX = "observability/spans.py"
METERS_SUFFIX = "observability/meters.py"
ENVVARS_SUFFIX = "config/envvars.py"
LOCKS_SUFFIX = "concurrency/registry.py"
METRIC_REGISTRY_NAME = "METRICS"
SPAN_REGISTRY_NAME = "SPANS"
BUCKETS_NAME = "HISTOGRAM_BUCKETS"
LOCK_REGISTRY_NAME = "LOCKS"

#: lock factory name -> the kind its declaration must carry
LOCK_FACTORIES = {"named_lock": "lock", "named_rlock": "rlock"}

#: meter kinds graftmeter can aggregate (meters.VALID_KINDS, restated here
#: so the lint tree does not import runtime modules)
VALID_METER_KINDS = frozenset({"counter", "gauge", "histogram"})

#: function names whose first string argument is a registry-checked span
#: name (the dynamic-name emitter ``layer_span`` is deliberately absent)
SPAN_EMITTER_NAMES = frozenset({"span", "start_span"})

#: MODIN_TPU_* env var literal; the lookbehind keeps internal tokens like
#: ``__MODIN_TPU_BT_0__`` (eval.py backtick mangling) out of the scan
ENVVAR_RE = re.compile(r"(?<![A-Za-z0-9_])MODIN_TPU_[A-Z0-9_]+")


def _metric_name_pattern(arg: ast.AST) -> Optional[str]:
    """The emitted metric name with f-string placeholders as ``*``."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    if isinstance(arg, ast.JoinedStr):
        out: List[str] = []
        for piece in arg.values:
            if isinstance(piece, ast.Constant) and isinstance(piece.value, str):
                out.append(piece.value)
            else:
                out.append("*")
        return "".join(out)
    return None  # dynamically built name: can't check statically


def _registry_entries(
    ctx: FileContext, registry_name: str
) -> Optional[List[ast.expr]]:
    """The entry nodes of ``<NAME> = ((...), ...)`` — one walk shared by the
    name and kind legs, so a change to how the registry is declared cannot
    fix one leg and silently blind the other.  None when the file has no
    such assignment."""
    for node in ctx.tree.body:
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == registry_name
            for t in node.targets
        ):
            value = node.value
        elif (
            isinstance(node, ast.AnnAssign)
            and isinstance(node.target, ast.Name)
            and node.target.id == registry_name
            and node.value is not None
        ):
            value = node.value
        else:
            continue
        if isinstance(value, (ast.Tuple, ast.List)):
            return list(value.elts)
        return []
    return None


def _entry_pattern(entry: ast.expr) -> Optional[Tuple[str, int]]:
    """``(pattern, lineno)`` when the entry is a tuple/list whose position 0
    is a string constant; None for any other shape."""
    if (
        isinstance(entry, (ast.Tuple, ast.List))
        and entry.elts
        and isinstance(entry.elts[0], ast.Constant)
        and isinstance(entry.elts[0].value, str)
    ):
        return entry.elts[0].value, entry.lineno
    return None


def _declared_patterns(
    ctx: FileContext, registry_name: str
) -> Optional[Dict[str, int]]:
    """{pattern: lineno} from ``<NAME> = (("pattern", "why"), ...)``."""
    entries = _registry_entries(ctx, registry_name)
    if entries is None:
        return None
    patterns: Dict[str, int] = {}
    for entry in entries:
        named = _entry_pattern(entry)
        if named is not None:
            patterns[named[0]] = named[1]
    return patterns


def _declared_kinds(ctx: FileContext) -> Dict[str, Tuple[Optional[str], int]]:
    """{pattern: (declared kind or None, lineno)} from the METRICS registry.

    The kind is entry position 1 — ``("pattern", "kind", "description")``.
    A 2-tuple entry (the pre-graftmeter shape) or a non-constant kind maps
    to None, which the kind check flags.
    """
    out: Dict[str, Tuple[Optional[str], int]] = {}
    for entry in _registry_entries(ctx, METRIC_REGISTRY_NAME) or ():
        named = _entry_pattern(entry)
        if named is None:
            continue
        kind: Optional[str] = None
        if (
            len(entry.elts) >= 3
            and isinstance(entry.elts[1], ast.Constant)
            and isinstance(entry.elts[1].value, str)
        ):
            kind = entry.elts[1].value
        out[named[0]] = (kind, named[1])
    return out


def _declared_buckets(ctx: FileContext) -> Optional[Dict[str, int]]:
    """{pattern: lineno} from the ``HISTOGRAM_BUCKETS`` dict literal in
    observability/meters.py (plain or annotated assignment)."""
    for node in ctx.tree.body:
        target = None
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == BUCKETS_NAME:
                    target = node.value
        elif isinstance(node, ast.AnnAssign):
            if (
                isinstance(node.target, ast.Name)
                and node.target.id == BUCKETS_NAME
            ):
                target = node.value
        if target is None:
            continue
        if isinstance(target, ast.Dict):
            return {
                key.value: key.lineno
                for key in target.keys
                if isinstance(key, ast.Constant) and isinstance(key.value, str)
            }
    return None


def _declared_envvars(ctx: FileContext) -> Dict[str, int]:
    """{varname: lineno} from ``varname = "MODIN_TPU_X"`` class attributes."""
    out: Dict[str, int] = {}
    for node in ast.walk(ctx.tree):
        if (
            isinstance(node, ast.Assign)
            and any(
                isinstance(t, ast.Name) and t.id == "varname"
                for t in node.targets
            )
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, str)
        ):
            out[node.value.value] = node.lineno
    return out


def _doc_mention_key(pattern: str) -> str:
    """The stable dotted prefix of a metric pattern that docs must mention.

    ``resilience.engine.*.*`` -> ``resilience.engine``; a fully static name
    is its own key.
    """
    parts = pattern.split(".")
    stable: List[str] = []
    for part in parts:
        if "*" in part:
            break
        stable.append(part)
    return ".".join(stable) if stable else pattern


@register_rule
class RegistryDriftRule(Rule):
    id = "REGISTRY-DRIFT"
    description = (
        "every emit_metric name must match the METRICS registry (with a "
        "valid meter kind, histogram families cross-checked against "
        "HISTOGRAM_BUCKETS both ways), every graftscope span/start_span "
        "name must match the SPANS registry, every MODIN_TPU_* env var "
        "must be declared in config/envvars.py, and every "
        "named_lock/named_rlock site must match the LOCKS registry "
        "(both ways, kinds included, no raw threading.Lock outside "
        "concurrency/); all must be mentioned in docs/"
    )

    def check_project(self, project: Project) -> Iterator[Finding]:
        yield from self._check_name_registry(
            project,
            suffix=METRICS_SUFFIX,
            registry_name=METRIC_REGISTRY_NAME,
            kind="metric",
            emit_desc="emit_metric",
            is_emitter=self._is_metric_emitter,
        )
        yield from self._check_metric_kinds(project)
        yield from self._check_name_registry(
            project,
            suffix=SPANS_SUFFIX,
            registry_name=SPAN_REGISTRY_NAME,
            kind="span",
            emit_desc="span/start_span",
            is_emitter=self._is_span_emitter,
        )
        yield from self._check_envvars(project)
        yield from self._check_locks(project)

    # -- named-emission registries (metrics, spans) ---------------------- #

    @staticmethod
    def _is_metric_emitter(node: ast.Call) -> bool:
        return isinstance(node.func, ast.Name) and node.func.id == "emit_metric"

    @staticmethod
    def _is_span_emitter(node: ast.Call) -> bool:
        func = node.func
        if isinstance(func, ast.Name):
            return func.id in SPAN_EMITTER_NAMES
        if isinstance(func, ast.Attribute):
            return func.attr in SPAN_EMITTER_NAMES
        return False

    def _check_name_registry(
        self,
        project: Project,
        suffix: str,
        registry_name: str,
        kind: str,
        emit_desc: str,
        is_emitter,
    ) -> Iterator[Finding]:
        registry: Optional[Dict[str, int]] = None
        registry_ctx: Optional[FileContext] = None
        for ctx in project.files_matching(suffix):
            registry = _declared_patterns(ctx, registry_name)
            registry_ctx = ctx
            if registry is not None:
                break

        emitted: List[Tuple[FileContext, ast.Call, str]] = []
        for ctx in project.files:
            for node in ast.walk(ctx.tree):
                if isinstance(node, ast.Call) and is_emitter(node) and node.args:
                    name = _metric_name_pattern(node.args[0])
                    if name is not None:
                        emitted.append((ctx, node, name))

        if registry is None:
            if registry_ctx is not None and emitted:
                yield Finding(
                    path=registry_ctx.rel,
                    line=1,
                    rule=self.id,
                    message=f"no {registry_name} registry found in "
                    f"the {kind}s module",
                    fix_hint=f'declare {registry_name} = (("pattern", '
                    '"description"), ...) covering every emitted name',
                    symbol=f"no-{kind}-registry",
                )
            return

        matched_patterns: Set[str] = set()
        for ctx, node, name in emitted:
            hits = [p for p in registry if fnmatch.fnmatchcase(name, p)]
            if hits:
                matched_patterns.update(hits)
                continue
            yield Finding(
                path=ctx.rel,
                line=node.lineno,
                rule=self.id,
                message=f"{kind} '{name}' matches no pattern in "
                f"{registry_name} ({suffix})",
                fix_hint=f"declare the {kind} (pattern, description) in the "
                "registry and document it",
                scope=ctx.scope_of(node),
                symbol=f"undeclared-{kind}-{name}",
            )

        docs = project.docs_text() if project.has_docs() else None
        for pattern, lineno in sorted(registry.items()):
            if pattern not in matched_patterns:
                yield Finding(
                    path=registry_ctx.rel,
                    line=lineno,
                    rule=self.id,
                    message=f"{kind} pattern '{pattern}' is declared but no "
                    f"{emit_desc} call matches it",
                    fix_hint="remove the dead registry entry or restore the "
                    "emit site",
                    symbol=f"dead-{kind}-{pattern}",
                )
            if docs is not None and _doc_mention_key(pattern) not in docs:
                yield Finding(
                    path=registry_ctx.rel,
                    line=lineno,
                    rule=self.id,
                    message=f"{kind} '{pattern}' (prefix "
                    f"'{_doc_mention_key(pattern)}') is not mentioned in "
                    "docs/",
                    fix_hint=f"document the {kind} family "
                    "(docs/configuration.md and docs/observability.md hold "
                    "the catalogs)",
                    symbol=f"undocumented-{kind}-{pattern}",
                )

    # -- meter kinds (graftmeter) ---------------------------------------- #

    def _check_metric_kinds(self, project: Project) -> Iterator[Finding]:
        """Every METRICS entry declares a valid meter kind; histogram
        declarations and HISTOGRAM_BUCKETS specs match one-to-one."""
        kinds: Optional[Dict[str, Tuple[Optional[str], int]]] = None
        registry_ctx: Optional[FileContext] = None
        for ctx in project.files_matching(METRICS_SUFFIX):
            kinds = _declared_kinds(ctx)
            registry_ctx = ctx
            if kinds:
                break
        if not kinds:
            return  # no METRICS registry in this tree: nothing to check

        for pattern, (kind, lineno) in sorted(kinds.items()):
            if kind not in VALID_METER_KINDS:
                yield Finding(
                    path=registry_ctx.rel,
                    line=lineno,
                    rule=self.id,
                    message=f"metric '{pattern}' declares "
                    + (
                        f"invalid meter kind {kind!r}"
                        if kind is not None
                        else "no meter kind"
                    )
                    + " (position 1 must be counter/gauge/histogram)",
                    fix_hint="declare the entry as (pattern, kind, "
                    "description) with a kind graftmeter can aggregate",
                    symbol=f"metric-kind-{pattern}",
                )

        buckets: Optional[Dict[str, int]] = None
        buckets_ctx: Optional[FileContext] = None
        for ctx in project.files_matching(METERS_SUFFIX):
            buckets = _declared_buckets(ctx)
            buckets_ctx = ctx
            if buckets is not None:
                break
        if buckets is None:
            return  # meters module absent (snippet trees): skip bucket legs

        for pattern, (kind, lineno) in sorted(kinds.items()):
            if kind == "histogram" and pattern not in buckets:
                yield Finding(
                    path=registry_ctx.rel,
                    line=lineno,
                    rule=self.id,
                    message=f"histogram metric '{pattern}' has no bucket "
                    f"spec in {METERS_SUFFIX}:{BUCKETS_NAME} (it would "
                    "silently degrade to a counter)",
                    fix_hint="add fixed bucket bounds for the family to "
                    f"{BUCKETS_NAME}",
                    symbol=f"histogram-without-buckets-{pattern}",
                )
        for pattern, lineno in sorted(buckets.items()):
            declared = kinds.get(pattern)
            if declared is None or declared[0] != "histogram":
                yield Finding(
                    path=buckets_ctx.rel,
                    line=lineno,
                    rule=self.id,
                    message=f"{BUCKETS_NAME} declares buckets for "
                    f"'{pattern}' but METRICS does not declare it as a "
                    "histogram",
                    fix_hint="remove the dead bucket spec or declare the "
                    "family with kind 'histogram' in METRICS",
                    symbol=f"buckets-without-histogram-{pattern}",
                )

    # -- env vars ------------------------------------------------------- #

    def _check_envvars(self, project: Project) -> Iterator[Finding]:
        declared: Optional[Dict[str, int]] = None
        envvars_ctx: Optional[FileContext] = None
        for ctx in project.files_matching(ENVVARS_SUFFIX):
            declared = _declared_envvars(ctx)
            envvars_ctx = ctx
            break
        if declared is None:
            return  # no envvars module in this tree: nothing to check against

        for ctx in project.files:
            if ctx is envvars_ctx:
                continue
            for node in ast.walk(ctx.tree):
                if not (
                    isinstance(node, ast.Constant) and isinstance(node.value, str)
                ):
                    continue
                if is_docstring(ctx.parents, node):
                    continue
                for var in ENVVAR_RE.findall(node.value):
                    if var not in declared:
                        yield Finding(
                            path=ctx.rel,
                            line=node.lineno,
                            rule=self.id,
                            message=f"env var '{var}' is read/written but "
                            f"not declared in {ENVVARS_SUFFIX}",
                            fix_hint="add an EnvironmentVariable subclass "
                            "with this varname (default, type, docstring) "
                            "and read it through the config layer",
                            scope=ctx.scope_of(node),
                            symbol=f"undeclared-envvar-{var}",
                        )

        if project.has_docs():
            docs = project.docs_text()
            for var, lineno in sorted(declared.items()):
                if var not in docs:
                    yield Finding(
                        path=envvars_ctx.rel,
                        line=lineno,
                        rule=self.id,
                        message=f"declared env var '{var}' is not mentioned "
                        "in docs/",
                        fix_hint="add it to the configuration reference "
                        "(docs/configuration.md)",
                        symbol=f"undocumented-envvar-{var}",
                    )

    # -- locks (graftdep) ------------------------------------------------ #

    def _check_locks(self, project: Project) -> Iterator[Finding]:
        """The LOCKS registry vs the named_lock/named_rlock construction
        sites, both ways, plus the no-raw-locks-outside-concurrency leg."""
        declared: Optional[Dict[str, Tuple[Optional[str], int]]] = None
        registry_ctx: Optional[FileContext] = None
        for ctx in project.files_matching(LOCKS_SUFFIX):
            declared = self._declared_locks(ctx)
            registry_ctx = ctx
            break
        if declared is None:
            return  # no lock registry in this tree: nothing to check against

        constructed: Set[str] = set()
        for ctx in project.files:
            in_concurrency = "concurrency/" in ctx.rel or ctx.rel.startswith(
                "concurrency"
            )
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                leaf = (
                    func.id
                    if isinstance(func, ast.Name)
                    else func.attr if isinstance(func, ast.Attribute) else None
                )
                if leaf in LOCK_FACTORIES:
                    if not (
                        node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)
                    ):
                        continue  # forwarding wrapper (e.g. the factory itself)
                    name = node.args[0].value
                    entry = declared.get(name)
                    if entry is None:
                        yield Finding(
                            path=ctx.rel,
                            line=node.lineno,
                            rule=self.id,
                            message=f"{leaf}({name!r}) constructs a lock not "
                            f"declared in {LOCK_REGISTRY_NAME} "
                            f"({LOCKS_SUFFIX}) — named_lock will raise at "
                            "import time",
                            fix_hint="declare (name, kind, what-it-guards) "
                            "in the LOCKS registry",
                            scope=ctx.scope_of(node),
                            symbol=f"undeclared-lock-{name}",
                        )
                    elif entry[0] != LOCK_FACTORIES[leaf]:
                        yield Finding(
                            path=ctx.rel,
                            line=node.lineno,
                            rule=self.id,
                            message=f"{leaf}({name!r}) contradicts the "
                            f"declared kind {entry[0]!r} — reentrancy "
                            "intent is declared data, not a site-local "
                            "choice",
                            fix_hint="use the factory matching the "
                            "declaration, or change the declaration "
                            "deliberately",
                            scope=ctx.scope_of(node),
                            symbol=f"lock-kind-{name}",
                        )
                    constructed.add(name)
                elif (
                    leaf in ("Lock", "RLock")
                    and isinstance(func, ast.Attribute)
                    and isinstance(func.value, ast.Name)
                    and func.value.id == "threading"
                    and not in_concurrency
                ):
                    yield Finding(
                        path=ctx.rel,
                        line=node.lineno,
                        rule=self.id,
                        message=f"raw threading.{leaf}() outside "
                        "concurrency/ — invisible to the LOCKS registry, "
                        "the declared order, and the lockdep validator "
                        "even if nothing in-tree acquires it yet",
                        fix_hint="declare it in LOCKS and construct it "
                        "with named_lock()/named_rlock()",
                        scope=ctx.scope_of(node),
                        symbol=f"raw-lock-{leaf}",
                    )

        docs = project.docs_text() if project.has_docs() else None
        for name, (kind, lineno) in sorted(declared.items()):
            if name not in constructed:
                yield Finding(
                    path=registry_ctx.rel,
                    line=lineno,
                    rule=self.id,
                    message=f"lock '{name}' is declared in "
                    f"{LOCK_REGISTRY_NAME} but no "
                    "named_lock/named_rlock site constructs it",
                    fix_hint="remove the dead declaration (and its "
                    "LOCK_ORDER edges) or restore the construction site",
                    symbol=f"dead-lock-{name}",
                )
            if docs is not None and name not in docs:
                yield Finding(
                    path=registry_ctx.rel,
                    line=lineno,
                    rule=self.id,
                    message=f"lock '{name}' is not mentioned in docs/",
                    fix_hint="add it to the lock-ordering table in "
                    "docs/architecture.md",
                    symbol=f"undocumented-lock-{name}",
                )

    @staticmethod
    def _declared_locks(
        ctx: FileContext,
    ) -> Optional[Dict[str, Tuple[Optional[str], int]]]:
        """{name: (kind, lineno)} from ``LOCKS = ((name, kind, desc), ...)``."""
        entries = _registry_entries(ctx, LOCK_REGISTRY_NAME)
        if entries is None:
            return None
        out: Dict[str, Tuple[Optional[str], int]] = {}
        for entry in entries:
            named = _entry_pattern(entry)
            if named is None:
                continue
            kind: Optional[str] = None
            if (
                len(entry.elts) >= 2
                and isinstance(entry.elts[1], ast.Constant)
                and isinstance(entry.elts[1].value, str)
            ):
                kind = entry.elts[1].value
            out[named[0]] = (kind, named[1])
        return out
