"""Project-specific AST lint: the repo's performance invariants as rules.

The engine/executor/sweep performance story rests on invariants that used to
be enforced by convention only (ROADMAP "standing constraints", docstrings,
after-the-fact runtime counters).  This module turns them into machine-checked
contracts over the source AST -- no imports, no tracing, no device:

* ``mesh-via-make-mesh`` -- device meshes are built ONLY through
  :func:`repro.launch.mesh.make_mesh` (Auto-typed axes); any direct
  ``jax.sharding.Mesh(...)`` / ``jax.make_mesh(...)`` elsewhere is an error.
* ``traced-host-sync``   -- host synchronization (``.item()``, ``float()``
  on arrays, ``np.asarray``, ``time.*``, Python RNG) inside functions
  *reachable from traced entry points* (``jax.jit`` / ``lax.scan`` /
  ``shard_map`` / ``pallas_call`` consumers).  Host-side-by-design code is
  simply not reachable; the rest is a dispatch stall on the hot path.
* ``traced-span``        -- ``tracing.span`` (a profiler
  ``TraceAnnotation``) in the same traced reach: traced code runs once at
  trace time, so a host span there measures nothing; device regions are
  named with ``jax.named_scope``.
* ``jit-donation``       -- a ``jax.jit`` whose wrapped function takes
  carry-style state arguments must declare ``donate_argnums`` (the engine's
  fused rounds all donate; a new hot jit that forgets doubles its HBM
  footprint silently).
* ``f64-without-x64``    -- ``jnp.float64``/``jnp.int64`` in functions with
  no ``enable_x64`` guard silently truncate to 32 bit on the default config.
* ``registry-hooks``     -- every ``@register_protocol`` / compressor /
  delay / solver entry implements the abstract hooks its base class
  declares (the Protocol hook-contract docstrings, statically enforced).
  Protocol entries must additionally state ``default_sigma_prime`` and
  ``coalesce_supported`` in their own class chain: both are concrete on
  the base, so inheriting them silently means nobody decided the new
  entry's safety parameter or its serve-batching eligibility.

Rules are registry entries (:func:`register_rule`), mirroring the protocol /
compressor / delay registries: subclass :class:`Rule`, decorate, and the rule
runs in every ``python -m repro analyze`` invocation -- the worked example
lives in ``docs/static-analysis.md`` (executed by tests/test_docs.py).

Findings are suppressed line- or scope-wise with pragmas::

    x = host_value.item()        # analysis: host-ok        (this line)
    def eval_loop(...):          # analysis: ignore[traced-host-sync]
    f64 = jnp.float64            # analysis: x64-ok

and pre-existing accepted findings live in the checked-in baseline
(``ANALYSIS_BASELINE.json``, see :mod:`repro.analysis.findings`).
"""

from __future__ import annotations

import ast
import pathlib
import re

from repro.analysis.findings import Finding, sort_findings

# ---------------------------------------------------------------------------
# Rule registry (mirrors the protocol/compressor/delay registries).
# ---------------------------------------------------------------------------

_RULES: dict[str, type["Rule"]] = {}


def register_rule(name: str):
    """Class decorator: add a :class:`Rule` to the analyzer's registry."""

    def deco(cls: type["Rule"]) -> type["Rule"]:
        cls.rule_name = name
        _RULES[name] = cls
        return cls

    return deco


def available_rules() -> tuple[str, ...]:
    return tuple(sorted(_RULES))


def get_rule(name: str) -> type["Rule"]:
    try:
        return _RULES[name]
    except KeyError:
        raise ValueError(
            f"unknown analysis rule {name!r}; available: {available_rules()}"
        ) from None


def default_rules() -> tuple[str, ...]:
    """All registered rules except ``*-example`` entries (the docs guides
    register worked examples at test time; they must not police the repo)."""
    return tuple(n for n in available_rules()
                 if not n.endswith(("-example", "_example")))


class Rule:
    """One statically checkable invariant.

    Subclass, set ``description``, implement :meth:`check`, and decorate with
    :func:`register_rule`.  ``check`` receives one parsed module plus the
    whole-project index (for cross-module rules) and returns raw findings;
    the driver applies pragma suppression and baseline matching afterwards.
    """

    rule_name = "abstract"
    description = ""

    def check(self, module: "ModuleInfo",
              project: "ProjectIndex") -> list[Finding]:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Parsed-module model: pragmas, imports, scoped function table.
# ---------------------------------------------------------------------------

_PRAGMA_RE = re.compile(r"#\s*analysis:\s*([a-z0-9_\-\[\],\s*]+)")
_PRAGMA_ALIASES = {"host-ok": "traced-host-sync", "x64-ok": "f64-without-x64",
                   "fail-fast-ok": "typed-errors"}


def _parse_pragmas(lines: list[str]) -> dict[int, set[str]]:
    """line number -> suppressed rule names (``{"*"}`` suppresses all)."""
    out: dict[int, set[str]] = {}
    for i, text in enumerate(lines, start=1):
        m = _PRAGMA_RE.search(text)
        if not m:
            continue
        spec = m.group(1).strip()
        rules: set[str] = set()
        for tok in re.split(r"[\s,]+", spec):
            if not tok:
                continue
            im = re.fullmatch(r"ignore(?:\[([a-z0-9_\-,]+)\])?", tok)
            if im:
                rules |= set(im.group(1).split(",")) if im.group(1) else {"*"}
            else:
                rules.add(_PRAGMA_ALIASES.get(tok, tok))
        out[i] = rules
    return out


def _dotted(node: ast.AST) -> str | None:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


class FunctionNode:
    """One ``def`` (or traced ``lambda``) with its scope and call edges."""

    def __init__(self, module: "ModuleInfo", node, qualname: str):
        self.module = module
        self.node = node
        self.qualname = qualname
        self.edges: set["FunctionNode"] = set()
        self.partial_aliases: dict[str, str] = {}  # local name -> target

    @property
    def params(self) -> list[str]:
        a = self.node.args
        return [p.arg for p in
                list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)]

    def own_statements(self):
        """Direct AST nodes of this function, nested defs/lambdas excluded
        (they are their own FunctionNodes)."""
        skip = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
        stack = (list(self.node.body) if not isinstance(self.node, ast.Lambda)
                 else [self.node.body])
        while stack:
            n = stack.pop()
            yield n
            for child in ast.iter_child_nodes(n):
                if not isinstance(child, skip):
                    stack.append(child)


class ModuleInfo:
    """One parsed source file: AST + pragmas + import map + function table."""

    def __init__(self, path: pathlib.Path, source: str, relpath: str):
        self.path = path
        self.relpath = relpath
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=str(path))
        self.pragmas = _parse_pragmas(self.lines)
        self.modname = _modname_for(relpath)
        self.imports: dict[str, str] = {}
        self.functions: dict[str, FunctionNode] = {}
        self.classes: dict[str, ast.ClassDef] = {}
        self._scope_lines: dict[str, tuple[int, int]] = {}
        self._collect_imports()
        self._collect_defs()

    # -- construction ------------------------------------------------------

    def _collect_imports(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.imports[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(node, ast.ImportFrom) and node.module:
                if node.level:  # relative imports: not used in this repo
                    continue
                for a in node.names:
                    self.imports[a.asname or a.name] = (
                        f"{node.module}.{a.name}")

    def _collect_defs(self) -> None:
        def visit(node, prefix):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    q = f"{prefix}{child.name}"
                    self.functions[q] = FunctionNode(self, child, q)
                    self._scope_lines[q] = (child.lineno,
                                            child.end_lineno or child.lineno)
                    visit(child, f"{q}.")
                elif isinstance(child, ast.ClassDef):
                    q = f"{prefix}{child.name}"
                    self.classes[q] = child
                    self._scope_lines[q] = (child.lineno,
                                            child.end_lineno or child.lineno)
                    visit(child, f"{q}.")
                else:
                    visit(child, prefix)

        visit(self.tree, "")

    # -- helpers rules use -------------------------------------------------

    def canonical(self, node: ast.AST) -> str | None:
        """Alias-resolved dotted name of an expression (``jnp.float64`` ->
        ``jax.numpy.float64``), or None for non-name expressions."""
        dotted = _dotted(node)
        if dotted is None:
            return None
        head, _, rest = dotted.partition(".")
        head = self.imports.get(head, head)
        return f"{head}.{rest}" if rest else head

    def enclosing(self, line: int) -> str:
        """Qualname of the innermost def/class containing ``line``."""
        best, best_span = "", None
        for q, (lo, hi) in self._scope_lines.items():
            if lo <= line <= hi and (best_span is None
                                     or hi - lo <= best_span):
                best, best_span = q, hi - lo
        return best

    def snippet(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def suppressed(self, rule: str, line: int) -> bool:
        """Pragma on the line itself or on any enclosing def/class header."""
        check = [line]
        for q, (lo, hi) in self._scope_lines.items():
            if lo <= line <= hi:
                check.append(lo)
        for ln in check:
            rules = self.pragmas.get(ln)
            if rules and ("*" in rules or rule in rules):
                return True
        return False

    def finding(self, rule: str, line: int, message: str) -> Finding:
        return Finding(rule=rule, path=self.relpath, line=line,
                       message=message, context=self.enclosing(line),
                       snippet=self.snippet(line))


def _modname_for(relpath: str) -> str:
    p = pathlib.PurePosixPath(relpath)
    parts = list(p.with_suffix("").parts)
    if "src" in parts:
        parts = parts[parts.index("src") + 1:]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


# ---------------------------------------------------------------------------
# Project index: cross-module name resolution + traced-reachability.
# ---------------------------------------------------------------------------

# Callables whose function-valued arguments run inside a trace.
TRACE_CONSUMERS = frozenset({
    "jax.jit", "jax.vmap", "jax.pmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat", "jax.make_jaxpr", "jax.eval_shape",
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan", "jax.lax.custom_root",
    "jax.shard_map",
    "jax.experimental.pallas.pallas_call",
})

_TRACED_DECORATORS = frozenset({"jax.jit", "jax.vmap", "jax.pmap"})


class ProjectIndex:
    """All parsed modules + the traced-code call graph over them."""

    def __init__(self, modules: list[ModuleInfo]):
        self.modules = modules
        self.by_modname = {m.modname: m for m in modules}
        self._roots: set[FunctionNode] = set()
        self._build_graph()
        self._reachable = self._close_over_roots()

    # -- name resolution ---------------------------------------------------

    def resolve_function(self, module: ModuleInfo, scope: str,
                         name: str) -> FunctionNode | None:
        """Resolve a bare ``name`` referenced from ``scope`` in ``module``:
        nested defs outward, then module level, then project imports."""
        prefix = scope
        while True:
            fn = module.functions.get(f"{prefix}.{name}" if prefix else name)
            if fn is not None:
                return fn
            # Walk outward: f.g.h -> f.g -> f -> module level.
            if not prefix:
                break
            prefix = prefix.rpartition(".")[0]
        target = module.imports.get(name)
        if target:
            mod, _, attr = target.rpartition(".")
            other = self.by_modname.get(mod)
            if other and attr:
                return other.functions.get(attr)
        return None

    def resolve_call(self, module: ModuleInfo, scope: str,
                     func: ast.AST) -> FunctionNode | None:
        """Resolve a call's target FunctionNode (project functions only)."""
        if isinstance(func, ast.Name):
            # Local partial/shard_map aliases first (x = partial(f, ...)).
            fnode = module.functions.get(scope)
            while fnode is not None:
                target = fnode.partial_aliases.get(func.id)
                if target is not None:
                    return self._resolve_dotted_target(module, scope, target)
                up = fnode.qualname.rpartition(".")[0]
                fnode = module.functions.get(up) if up else None
            return self.resolve_function(module, scope, func.id)
        dotted = _dotted(func)
        if dotted is None:
            return None
        return self._resolve_dotted_target(module, scope, dotted)

    def _resolve_dotted_target(self, module: ModuleInfo, scope: str,
                               dotted: str) -> FunctionNode | None:
        if "." not in dotted:
            return self.resolve_function(module, scope, dotted)
        head, _, rest = dotted.partition(".")
        target_mod = module.imports.get(head)
        if target_mod is None:
            return None
        other = self.by_modname.get(target_mod)
        if other is None:
            # ``from repro.core import engine`` -> engine._local_round
            other = self.by_modname.get(f"{target_mod}")
        return other.functions.get(rest) if other else None

    # -- graph construction ------------------------------------------------

    def _callable_args(self, call: ast.Call):
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            if isinstance(arg, (ast.Name, ast.Attribute, ast.Lambda)):
                yield arg
            elif isinstance(arg, ast.Call):  # partial(f, ...): unwrap f
                inner = _dotted(arg.func)
                if inner and inner.split(".")[-1] == "partial" and arg.args:
                    yield arg.args[0]

    def _mark_traced_lambda(self, module: ModuleInfo, node: ast.Lambda):
        q = f"<lambda:{node.lineno}>"
        fn = FunctionNode(module, node, module.enclosing(node.lineno) or q)
        module.functions.setdefault(f"{fn.qualname}.{q}", fn)
        self._roots.add(fn)

    def _build_graph(self) -> None:
        for module in self.modules:
            # Decorator-traced roots.
            for fn in list(module.functions.values()):
                node = fn.node
                if isinstance(node, ast.Lambda):
                    continue
                for dec in node.decorator_list:
                    canon = module.canonical(dec)
                    if canon in _TRACED_DECORATORS:
                        self._roots.add(fn)
                    elif isinstance(dec, ast.Call):
                        dcanon = module.canonical(dec.func)
                        if dcanon in _TRACED_DECORATORS:
                            self._roots.add(fn)
                        elif (dcanon and dcanon.endswith("partial")
                              and dec.args
                              and module.canonical(dec.args[0])
                              in _TRACED_DECORATORS):
                            self._roots.add(fn)
            # Consumer-call roots + partial aliases + call edges.
            for fn in list(module.functions.values()):
                scope = fn.qualname
                for stmt in fn.own_statements():
                    if isinstance(stmt, ast.Assign) and isinstance(
                            stmt.value, ast.Call):
                        self._record_alias(module, fn, stmt)
                    if not isinstance(stmt, ast.Call):
                        continue
                    canon = module.canonical(stmt.func)
                    if canon in TRACE_CONSUMERS:
                        for arg in self._callable_args(stmt):
                            if isinstance(arg, ast.Lambda):
                                self._mark_traced_lambda(module, arg)
                                continue
                            target = self.resolve_call(module, scope, arg)
                            if target is not None:
                                self._roots.add(target)
                    target = self.resolve_call(module, scope, stmt.func)
                    if target is not None:
                        fn.edges.add(target)
            # Module-level consumer calls (e.g. ``f = jax.jit(g)``).
            self._module_level_roots(module)

    def _record_alias(self, module: ModuleInfo, fn: FunctionNode,
                      stmt: ast.Assign) -> None:
        """``x = partial(f, ...)`` / ``x = shard_map(f, ...)``: calling ``x``
        later must resolve (and trace-mark) ``f``."""
        call = stmt.value
        canon = module.canonical(call.func) or ""
        is_partial = canon.endswith("partial")
        if not (is_partial or canon in TRACE_CONSUMERS) or not call.args:
            return
        inner = call.args[0]
        dotted = _dotted(inner)
        if dotted is None:
            return
        for tgt in stmt.targets:
            if isinstance(tgt, ast.Name):
                fn.partial_aliases[tgt.id] = dotted
        if canon in TRACE_CONSUMERS:
            target = self.resolve_call(module, fn.qualname, inner)
            if target is not None:
                self._roots.add(target)

    def _module_level_roots(self, module: ModuleInfo) -> None:
        in_function = set()
        for fn in module.functions.values():
            if isinstance(fn.node, ast.Lambda):
                continue
            lo, hi = fn.node.lineno, fn.node.end_lineno or fn.node.lineno
            in_function.add((lo, hi))

        def inside_def(line):
            return any(lo <= line <= hi for lo, hi in in_function)

        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call) or inside_def(node.lineno):
                continue
            if module.canonical(node.func) in TRACE_CONSUMERS:
                for arg in self._callable_args(node):
                    if isinstance(arg, ast.Lambda):
                        self._mark_traced_lambda(module, arg)
                        continue
                    target = self.resolve_call(module, "", arg)
                    if target is not None:
                        self._roots.add(target)

    def _close_over_roots(self) -> set[FunctionNode]:
        seen: set[FunctionNode] = set()
        stack = list(self._roots)
        while stack:
            fn = stack.pop()
            if fn in seen:
                continue
            seen.add(fn)
            stack.extend(fn.edges)
        return seen

    def is_traced(self, fn: FunctionNode) -> bool:
        """Is ``fn`` reachable from any traced entry point?"""
        return fn in self._reachable

    def traced_functions(self, module: ModuleInfo):
        return [fn for fn in module.functions.values() if self.is_traced(fn)]


# ---------------------------------------------------------------------------
# Rules.
# ---------------------------------------------------------------------------


@register_rule("mesh-via-make-mesh")
class MeshRule(Rule):
    """The ROADMAP mesh rule, in code: meshes only via launch/mesh."""

    description = ("flags direct jax.sharding.Mesh(...) / jax.make_mesh(...) "
                   "construction outside launch/mesh.py; route through "
                   "repro.launch.mesh.make_mesh")

    ALLOWED_IN = ("launch/mesh.py",)
    CONSTRUCTORS = {"jax.sharding.Mesh", "jax.make_mesh",
                    "jax.experimental.mesh_utils.create_device_mesh"}

    def check(self, module, project):
        if module.relpath.endswith(self.ALLOWED_IN):
            return []
        out = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            canon = module.canonical(node.func)
            if canon in self.CONSTRUCTORS:
                out.append(module.finding(
                    self.rule_name, node.lineno,
                    f"direct {canon}(...) construction; build meshes only "
                    f"through repro.launch.mesh.make_mesh (one place "
                    f"sets axis_types)"))
        return out


@register_rule("traced-host-sync")
class TracedHostSyncRule(Rule):
    """No host synchronization inside traced code (the PR-1/4 perf story)."""

    description = ("flags .item()/.tolist()/float()/np.asarray/time.*/Python "
                   "RNG inside functions reachable from jax.jit / lax.scan / "
                   "shard_map / pallas_call call sites; mark host-side-by-"
                   "design lines with `# analysis: host-ok`")

    _METHODS = {"item": ".item() forces a device->host sync",
                "tolist": ".tolist() forces a device->host sync",
                "block_until_ready": ".block_until_ready() stalls dispatch"}
    _NUMPY = {"numpy.asarray", "numpy.array", "numpy.ascontiguousarray",
              "numpy.copyto", "numpy.save"}
    _BUILTINS = {"float", "int", "bool"}

    def _call_finding(self, module, fn, call) -> str | None:
        func = call.func
        if isinstance(func, ast.Attribute) and func.attr in self._METHODS:
            return self._METHODS[func.attr]
        canon = module.canonical(func)
        if canon is None:
            return None
        if canon in self._NUMPY or canon.startswith("numpy.random."):
            return (f"{canon} materializes a host array inside traced code "
                    f"(use jnp, or hoist to the host side)")
        if canon.startswith("time."):
            return f"{canon}() reads the host clock inside traced code"
        if canon.startswith("random."):
            return (f"{canon}() draws host randomness inside traced code "
                    f"(use jax.random with a threaded key)")
        if canon == "jax.device_get":
            return "jax.device_get forces a device->host transfer"
        if canon in self._BUILTINS and len(call.args) == 1 and not isinstance(
                call.args[0], ast.Constant):
            return (f"{canon}() on a traced value forces concretization "
                    f"(host sync); keep it an array or hoist it")
        return None

    def check(self, module, project):
        out = []
        for fn in project.traced_functions(module):
            for stmt in fn.own_statements():
                if not isinstance(stmt, ast.Call):
                    continue
                msg = self._call_finding(module, fn, stmt)
                if msg:
                    out.append(module.finding(
                        self.rule_name, stmt.lineno,
                        f"{msg} [traced via {fn.qualname}]"))
        return out


@register_rule("traced-span")
class TracedSpanRule(Rule):
    """No host span inside traced code: it runs once, at trace time."""

    description = ("flags repro.core.tracing.span / jax.profiler "
                   "TraceAnnotation inside functions reachable from jax.jit / "
                   "lax.scan / shard_map / pallas_call call sites: traced "
                   "code runs once at trace time, so the span measures "
                   "nothing (name the device region with jax.named_scope)")

    _SPANS = {"repro.core.tracing.span", "jax.profiler.TraceAnnotation",
              "jax.profiler.StepTraceAnnotation"}

    def check(self, module, project):
        out = []
        for fn in project.traced_functions(module):
            for stmt in fn.own_statements():
                if not isinstance(stmt, ast.Call):
                    continue
                canon = module.canonical(stmt.func)
                if canon in self._SPANS:
                    out.append(module.finding(
                        self.rule_name, stmt.lineno,
                        f"{canon}(...) in traced code records one span at "
                        f"trace time, none per run; use jax.named_scope "
                        f"[traced via {fn.qualname}]"))
        return out


@register_rule("jit-donation")
class JitDonationRule(Rule):
    """Hot jits with carry-style state arguments must donate them."""

    description = ("flags jax.jit over functions with carry-style parameters "
                   "(state/carry/residual/caches/...) and no donate_argnums; "
                   "un-donated carries double the buffer footprint per "
                   "dispatch")

    CARRY_PARAMS = frozenset({
        "carry", "state", "opt_state", "caches", "residual", "ref_buf",
        "w_local", "w_server", "dw_tilde", "alpha_applied",
    })
    _DONATE_KWS = {"donate_argnums", "donate_argnames"}

    def _jit_kwargs(self, call: ast.Call) -> set[str]:
        return {kw.arg for kw in call.keywords if kw.arg}

    def _check_params(self, module, params, line, what) -> Finding | None:
        hot = sorted(set(params) & self.CARRY_PARAMS)
        if not hot:
            return None
        return module.finding(
            self.rule_name, line,
            f"{what} takes carry-style argument(s) {hot} but declares no "
            f"donate_argnums/donate_argnames; donate the carry (see the "
            f"engine's fused rounds) or rename if it is not a carry")

    def _lambda_params(self, node: ast.Lambda) -> list[str]:
        a = node.args
        return [p.arg for p in
                list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)]

    def check(self, module, project):
        out = []
        for fn in module.functions.values():
            node = fn.node
            if isinstance(node, ast.Lambda):
                continue
            for dec in node.decorator_list:
                canon = module.canonical(dec)
                if canon == "jax.jit":
                    f = self._check_params(module, fn.params, dec.lineno,
                                           f"@jax.jit on {fn.qualname}")
                    if f:
                        out.append(f)
                elif isinstance(dec, ast.Call):
                    dcanon = module.canonical(dec.func) or ""
                    is_partial_jit = (
                        dcanon.endswith("partial") and dec.args
                        and module.canonical(dec.args[0]) == "jax.jit")
                    if not (is_partial_jit or dcanon == "jax.jit"):
                        continue
                    if self._jit_kwargs(dec) & self._DONATE_KWS:
                        continue
                    f = self._check_params(module, fn.params, dec.lineno,
                                           f"jit of {fn.qualname}")
                    if f:
                        out.append(f)
        # Direct jax.jit(f, ...) / jax.jit(lambda ...) call sites.
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            if module.canonical(node.func) != "jax.jit" or not node.args:
                continue
            if self._jit_kwargs(node) & self._DONATE_KWS:
                continue
            target = node.args[0]
            if isinstance(target, ast.Lambda):
                params = self._lambda_params(target)
                f = self._check_params(module, params, node.lineno,
                                       "jax.jit(lambda ...)")
            else:
                scope = module.enclosing(node.lineno)
                resolved = project.resolve_call(module, scope, target)
                if resolved is None or isinstance(resolved.node, ast.Lambda):
                    continue
                f = self._check_params(module, resolved.params, node.lineno,
                                       f"jax.jit({resolved.qualname})")
            if f:
                out.append(f)
        return out


@register_rule("f64-without-x64")
class F64Rule(Rule):
    """f64 dtypes only under an enable_x64 guard (default config truncates)."""

    description = ("flags jnp.float64/jnp.int64 in functions with no "
                   "enable_x64 guard in scope; mark call-sites guarded by "
                   "their caller with `# analysis: x64-ok`")

    F64 = {"jax.numpy.float64", "jax.numpy.int64", "jax.numpy.uint64",
           "jax.numpy.complex128"}

    def _has_x64_guard(self, module, line) -> bool:
        """Any enclosing def whose body mentions enable_x64 (with-block or
        import) guards the usage."""
        for q, (lo, hi) in module._scope_lines.items():
            if lo <= line <= hi:
                body = "\n".join(module.lines[lo - 1:hi])
                if "enable_x64" in body:
                    return True
        return False

    def check(self, module, project):
        out = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Attribute):
                continue
            canon = module.canonical(node)
            if canon not in self.F64:
                continue
            if self._has_x64_guard(module, node.lineno):
                continue
            out.append(module.finding(
                self.rule_name, node.lineno,
                f"{canon} outside an enable_x64 guard silently truncates to "
                f"32 bit under the default config; guard with "
                f"jax.enable_x64(True) or mark the traced callee "
                f"`# analysis: x64-ok`"))
        return out


@register_rule("registry-hooks")
class RegistryHooksRule(Rule):
    """Registered protocol/compressor/delay/solver entries implement their
    base's abstract hooks (the Protocol hook-contract docstrings)."""

    description = ("flags @register_protocol/compressor/delay classes missing "
                   "abstract hooks of their base (plus the protocol registry's "
                   "explicit extras: default_sigma_prime, coalesce_supported), "
                   "and register_solver entries off the solver signature")

    # decorator canonical name ->
    #   (base module, base class, fallback hooks, extra required hooks).
    # Extras are hooks the base implements CONCRETELY (so they cannot be
    # auto-derived from NotImplementedError bodies) but that every registered
    # entry must still state in its own chain: sigma' is the safety parameter
    # of the entry's aggregation rule, and coalesce eligibility decides
    # whether the serve layer may batch the entry's runs -- inheriting either
    # silently from Protocol means nobody decided them for the new entry.
    REGISTRIES = {
        "repro.core.engine.register_protocol":
            ("repro.core.engine", "Protocol",
             ("num_rounds", "initial_messages", "arrivals_needed",
              "process_round", "snapshot", "finalize"),
             ("default_sigma_prime", "coalesce_supported")),
        "repro.core.compress.register_compressor":
            ("repro.core.compress", "Compressor",
             ("compress", "compress_grouped"), ()),
        "repro.core.delays.register_delay":
            ("repro.core.delays", "DelayModel", ("compute_time",), ()),
    }
    SOLVER_REGISTRAR = "repro.core.solvers.register_solver"
    SOLVER_MIN_ARGS = 9  # w_eff, alpha, X, y, norms_sq, lam, n, sigma', key
    SOLVER_KWONLY = {"loss", "num_steps"}

    # -- abstract-hook extraction ------------------------------------------

    @staticmethod
    def _is_abstract(method: ast.FunctionDef) -> bool:
        body = [s for s in method.body
                if not (isinstance(s, ast.Expr)
                        and isinstance(s.value, ast.Constant))]
        return (len(body) == 1 and isinstance(body[0], ast.Raise)
                and "NotImplementedError" in ast.dump(body[0]))

    def _abstract_hooks(self, project, base_mod, base_cls, fallback):
        module = project.by_modname.get(base_mod)
        cls = module.classes.get(base_cls) if module else None
        if cls is None:
            return tuple(fallback)
        return tuple(m.name for m in cls.body
                     if isinstance(m, ast.FunctionDef)
                     and self._is_abstract(m))

    # -- class chain walking -----------------------------------------------

    def _defined_hooks(self, project, module, cls: ast.ClassDef,
                       stop_at: str) -> set[str]:
        """Concrete method names along the base chain (project files only)."""
        defined: set[str] = set()
        seen = set()
        stack = [(module, cls)]
        while stack:
            mod, node = stack.pop()
            if (mod.modname, node.name) in seen or node.name == stop_at:
                continue
            seen.add((mod.modname, node.name))
            for m in node.body:
                if isinstance(m, ast.FunctionDef) and not self._is_abstract(m):
                    defined.add(m.name)
            for base in node.bases:
                resolved = self._resolve_class(project, mod, base)
                if resolved is not None:
                    stack.append(resolved)
        return defined

    def _resolve_class(self, project, module, base):
        dotted = _dotted(base)
        if dotted is None:
            return None
        if "." not in dotted:
            if dotted in module.classes:
                return (module, module.classes[dotted])
            target = module.imports.get(dotted)
        else:
            head, _, rest = dotted.partition(".")
            target_mod = module.imports.get(head)
            target = f"{target_mod}.{rest}" if target_mod else None
        if not target:
            return None
        mod_name, _, cls_name = target.rpartition(".")
        other = project.by_modname.get(mod_name)
        if other and cls_name in other.classes:
            return (other, other.classes[cls_name])
        return None

    # -- the check ---------------------------------------------------------

    def check(self, module, project):
        out = []
        for qual, cls in module.classes.items():
            for dec in cls.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                canon = module.canonical(dec.func)
                reg = self.REGISTRIES.get(canon or "")
                if reg is None:
                    continue
                base_mod, base_cls, fallback, extra = reg
                required = self._abstract_hooks(project, base_mod, base_cls,
                                                fallback) + tuple(extra)
                defined = self._defined_hooks(project, module, cls, base_cls)
                missing = sorted(set(required) - defined)
                if missing:
                    out.append(module.finding(
                        self.rule_name, dec.lineno,
                        f"registered entry {qual!r} does not implement "
                        f"required hook(s) {missing} of {base_cls} (see the "
                        f"hook-contract docstring)"))
        out.extend(self._check_solvers(module, project))
        return out

    def _check_solvers(self, module, project):
        out = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            # register_solver("name")(fn) -- the call-registration form.
            if not (isinstance(node.func, ast.Call)
                    and module.canonical(node.func.func)
                    == self.SOLVER_REGISTRAR and node.args):
                continue
            scope = module.enclosing(node.lineno)
            fn = project.resolve_call(module, scope, node.args[0])
            if fn is None:
                continue
            a = fn.node.args
            n_pos = len(a.posonlyargs) + len(a.args)
            kwonly = {p.arg for p in a.kwonlyargs}
            if (n_pos < self.SOLVER_MIN_ARGS
                    or not self.SOLVER_KWONLY <= kwonly):
                out.append(module.finding(
                    self.rule_name, node.lineno,
                    f"solver {fn.qualname!r} does not match the local-solver "
                    f"signature (>= {self.SOLVER_MIN_ARGS} positional args + "
                    f"keyword-only {sorted(self.SOLVER_KWONLY)}; see "
                    f"repro.core.solvers)"))
        return out


@register_rule("typed-errors")
class TypedErrorsRule(Rule):
    """Serve-layer error discipline: no silent broad excepts.

    The serve layer's whole failure contract is TYPED errors delivered
    through streams and the pinned HTTP status table -- a broad
    ``except Exception`` that neither re-raises nor is explicitly marked
    swallows a failure into a hang or an untyped 500 (the PR-9 bugfixes).
    This rule flags every ``except Exception`` / ``except BaseException``
    handler under ``serve/`` whose body contains no ``raise``; handlers that
    deliberately terminate the error path (delivering it to a tenant handle,
    mapping it to a status code, poisoning streams on teardown) carry
    ``# analysis: fail-fast-ok`` with a parenthesized why.
    """

    description = ("flags except Exception/BaseException without a re-raise "
                   "under serve/; convert to a typed error or mark the "
                   "handler '# analysis: fail-fast-ok (why)'")

    BROAD = ("Exception", "BaseException")

    def check(self, module, project):
        if "serve" not in module.relpath:
            return []
        out = []
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.ExceptHandler) or node.type is None:
                continue
            names = []
            if isinstance(node.type, ast.Tuple):
                names = [_dotted(e) for e in node.type.elts]
            else:
                names = [_dotted(node.type)]
            if not any(n in self.BROAD for n in names if n):
                continue
            if any(isinstance(n, ast.Raise) for n in ast.walk(node)):
                continue
            out.append(module.finding(
                self.rule_name, node.lineno,
                f"broad except {', '.join(n for n in names if n)} swallows "
                f"the error; re-raise a typed serve error "
                f"(repro.serve.recovery) or mark the handler "
                f"'# analysis: fail-fast-ok (why)'"))
        return out


# ---------------------------------------------------------------------------
# Drivers.
# ---------------------------------------------------------------------------


def _iter_py_files(paths) -> list[pathlib.Path]:
    out = []
    for p in paths:
        p = pathlib.Path(p)
        if p.is_dir():
            out.extend(sorted(p.rglob("*.py")))
        elif p.suffix == ".py":
            out.append(p)
    return out


def parse_project(paths, *, root: pathlib.Path | None = None) -> ProjectIndex:
    """Parse every ``*.py`` under ``paths`` into a :class:`ProjectIndex`."""
    root = pathlib.Path.cwd() if root is None else pathlib.Path(root)
    modules = []
    for path in _iter_py_files(paths):
        try:
            rel = path.resolve().relative_to(root.resolve()).as_posix()
        except ValueError:
            rel = path.as_posix()
        try:
            modules.append(ModuleInfo(path, path.read_text(), rel))
        except SyntaxError as e:
            raise SyntaxError(f"analysis cannot parse {path}: {e}") from e
    return ProjectIndex(modules)


def lint_project(project: ProjectIndex, *, rules=None) -> list[Finding]:
    """Run ``rules`` (default: every non-example registry entry) over every
    module; pragma-suppressed findings are dropped here."""
    names = default_rules() if rules is None else tuple(rules)
    instances = [get_rule(n)() for n in names]
    out = []
    for module in project.modules:
        for rule in instances:
            for f in rule.check(module, project):
                if not module.suppressed(f.rule, f.line):
                    out.append(f)
    return sort_findings(out)


def lint_paths(paths, *, root=None, rules=None) -> list[Finding]:
    """Parse + lint in one call (the CLI / CI entry)."""
    return lint_project(parse_project(paths, root=root), rules=rules)


def lint_source(source: str, *, path: str = "<snippet>",
                rules=None) -> list[Finding]:
    """Lint one in-memory snippet (the docs/test harness entry)."""
    module = ModuleInfo(pathlib.Path(path), source, path)
    return lint_project(ProjectIndex([module]), rules=rules)
