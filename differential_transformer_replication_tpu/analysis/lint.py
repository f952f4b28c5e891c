"""graftlint's AST engine: jit-region discovery + rule dispatch.

Pure stdlib (ast + tokenize) — linting the package must not import jax
(or the package itself), so the CI gate runs in milliseconds on a
CPU-only box and can lint code that would fail to import.

How a file is analyzed:

1. **Parse + suppressions.** Each module is parsed once; ``# graftlint:``
   comment directives are collected per line (see analysis/rules.py for
   the syntax).
2. **Function graph.** Every ``def``/``lambda`` becomes a node with its
   lexical scope chain (for name resolution) and outgoing calls. Import
   statements build an alias map so ``from x import f; f()`` and
   ``import x as m; m.f()`` resolve to cross-module edges.
3. **Jit roots.** A function is a *tracing root* when it is decorated
   with (or passed to) a JAX tracing transform — ``jit``/``pjit``/
   ``pmap``/``vmap``/``grad``/``value_and_grad``/``checkpoint``/
   ``shard_map``/``lax.scan``/``cond``/``while_loop``/``fori_loop``/
   ``switch`` — including ``partial(jax.jit, ...)`` decorator forms.
   The maker idiom is followed one level: ``jax.jit(make_step(cfg))``
   marks the local functions ``make_step`` *returns* as roots.
4. **Reachability.** BFS over resolved call edges from the roots; every
   reached function is a *jit region* — its body is (part of) a traced
   program, so the GL1xx rules apply to it.
5. **Taint.** Within a jit region, values produced by ``jnp.*`` /
   ``jax.lax.*`` / ``jax.random.*`` / ``jax.nn.*`` calls (and
   anything derived from them through arithmetic, comparisons,
   subscripts and non-static attributes) are *traced*; ``.shape`` /
   ``.dtype`` / ``.ndim`` / ``len()`` strip taint (static under jit).
   The function's own parameters are *weak* taint seeds — they are the
   primary traced values of a jit region, so ``if x > 0`` / ``float(x)``
   on a bare parameter fires — but an attribute read on a bare
   parameter stays static, so static-config branches
   (``if cfg.dropout > 0``) stay clean. Parameters named by a constant
   ``static_argnums``/``static_argnames`` on the jit decorator or call
   site are not seeded at all.
6. **Interprocedural edges** (the machinery under the GL4xx/5xx/6xx
   families). Beyond direct calls, the graph follows: *maker
   variables* (``step = make_step_fn(cfg)`` then ``step(...)`` calls
   the maker's returned local defs); *function-valued parameters*
   (``make_step_fn(cfg, loss_sync=lambda l: ...)`` — a call to
   ``loss_sync`` anywhere inside the maker's scope chain resolves to
   the lambda bound at each call site); ``<fn>.defvjp(fwd, bwd)``
   (the VJP pair executes wherever the primal does); lambdas passed
   as call arguments; and functions whose parameter is handed to a
   tracing transform inside their body (a ``shard_map`` wrapper's
   ``fn`` — so every wrapped body is discovered through the wrapper).
7. **Axis environments.** ``shard_map``/``pmap`` bodies *bind* mesh
   axis names (``pmap`` binds its literal ``axis_name``; ``shard_map``
   binds the wildcard ``*`` — the mesh's axes are runtime values).
   The environment propagates along the edge graph; a named-axis
   collective in a function no binder reaches is GL401. Branch arms
   of ``lax.cond``/``switch``/``while_loop`` propagate the same way
   for GL402, and ``pallas_call`` kernels / BlockSpec index_maps form
   *kernel regions* for the GL5xx checks (impure calls in a kernel
   report GL504, not GL103).
8. **Lock-order graph** (GL6xx). Per class owning a ``threading``
   lock, acquisitions are ``with self.<lock>`` / ``.acquire()``;
   while a lock is held, a directed edge is drawn to every lock
   acquired inside the block — directly, through same-class method
   calls, or through methods of attributes whose class the engine can
   resolve (``self.x = SomeClass(...)`` in ``__init__``). A cycle is
   GL601; blocking calls under a held lock are GL602.

The engine deliberately under-approximates (no interprocedural taint,
no aliasing): a finding means "this exact expression does the hazardous
thing here", which keeps the clean-tree gate (tests/test_lint_clean.py)
meaningful — suppressions mark the few deliberate exceptions instead of
papering over noise.
"""

from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set, Tuple

from differential_transformer_replication_tpu.analysis.rules import (
    RULES_BY_ID,
    resolve_rule_token,
)

# -- suppressions -------------------------------------------------------

_DIRECTIVE_RE = re.compile(r"#\s*graftlint:\s*([^#]*)")

# tracing transforms: a function passed to (or decorated by) one of
# these is traced — its body becomes part of a compiled program
_TRACING_TRANSFORMS = frozenset({
    "jit", "pjit", "pmap", "vmap", "xmap", "grad", "value_and_grad",
    "checkpoint", "remat", "custom_vjp", "custom_jvp", "scan", "cond",
    "while_loop", "fori_loop", "switch", "associative_scan",
    "shard_map", "named_call", "eval_shape",
})

# dotted prefixes whose call results are traced arrays inside a jit
# region (the taint seeds)
_ARRAY_NAMESPACES = (
    "jnp.", "jax.numpy.", "jax.lax.", "lax.", "jax.nn.", "jax.random.",
    "jax.scipy.", "jax.tree_util.tree_map", "jax.vmap", "jax.ops.",
)

# attribute reads that yield static (trace-time-concrete) metadata
_STATIC_ATTRS = frozenset({"shape", "dtype", "ndim", "size", "sharding"})

# impure dotted-name prefixes for GL103 (checked against the RESOLVED
# dotted name, so `from jax import random` does not read as stdlib
# random)
_IMPURE_PREFIXES = (
    "time.", "np.random.", "numpy.random.", "logging.", "os.environ",
    "os.getenv", "sys.stdout", "sys.stderr",
)
_IMPURE_BARE = frozenset({"print", "open", "input"})

_DONATE_NAME_RE = re.compile(r"(step|decode|prefill|update)", re.I)
_DONATE_EXEMPT_RE = re.compile(r"eval", re.I)

_STEP_CALL_RE = re.compile(r"(^|_)step$")

_LOCK_FACTORIES = frozenset({"Lock", "RLock", "Condition", "Semaphore",
                             "BoundedSemaphore"})
_EVENT_FACTORIES = frozenset({"Event"})
_COND_FACTORIES = frozenset({"Condition"})
_QUEUE_FACTORIES = frozenset({"Queue", "SimpleQueue", "LifoQueue",
                              "PriorityQueue"})

# GL4xx: named-axis collectives (communicate across shards) and
# axis-environment queries (need a binding, but never deadlock)
_COLLECTIVE_OPS = frozenset({
    "psum", "pmean", "pmax", "pmin", "all_gather", "ppermute",
    "pshuffle", "all_to_all", "psum_scatter",
})
_AXIS_QUERY_OPS = frozenset({"axis_index", "axis_size"})

# transforms that BIND mesh axis names for their body
_BINDING_TRANSFORMS = frozenset({"shard_map", "pmap", "xmap"})
# transforms whose function args run under a traced predicate (GL402)
_BRANCH_TRANSFORMS = frozenset({"cond", "switch", "while_loop"})

# GL503: dtype byte widths the estimator understands
_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8, "complex64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1, "bool_": 1,
    "float8_e4m3fn": 1, "float8_e5m2": 1,
}
_SUB_FP32_FLOATS = frozenset({"bfloat16", "float16"})

# GL602: dotted-call prefixes that block the calling thread
_BLOCKING_PREFIXES = (
    "time.sleep", "urllib.request.", "http.client.", "socket.",
    "subprocess.run", "subprocess.call", "subprocess.check_call",
    "subprocess.check_output", "subprocess.Popen", "requests.",
)

DEFAULT_VMEM_BUDGET_MIB = 16.0


@dataclass
class Finding:
    path: str
    line: int
    rule: str
    message: str
    hint: str
    suppressed: bool = False
    severity: str = "error"

    @property
    def name(self) -> str:
        r = RULES_BY_ID.get(self.rule)
        return r.name if r else self.rule

    def as_dict(self) -> dict:
        return {
            "path": self.path, "line": self.line, "rule": self.rule,
            "name": self.name, "severity": self.severity,
            "message": self.message, "hint": self.hint,
            "suppressed": self.suppressed,
        }

    def render(self) -> str:
        tag = " (suppressed)" if self.suppressed else (
            " (warning)" if self.severity == "warning" else ""
        )
        return (f"{self.path}:{self.line}: {self.rule} [{self.name}]"
                f"{tag}: {self.message}\n    hint: {self.hint}")


class _Suppressions:
    """Per-line and per-file rule suppression, parsed from comments."""

    def __init__(self, source: str) -> None:
        self.by_line: Dict[int, Set[str]] = {}
        self.file_wide: Set[str] = set()
        self.file_all = False
        try:
            toks = tokenize.generate_tokens(io.StringIO(source).readline)
            for tok in toks:
                if tok.type != tokenize.COMMENT:
                    continue
                m = _DIRECTIVE_RE.search(tok.string)
                if not m:
                    continue
                self._apply(m.group(1).strip(), tok.start[0])
        except (tokenize.TokenError, IndentationError, SyntaxError):
            pass  # a torn file: lint what parsed, skip its comments

    def _apply(self, body: str, line: int) -> None:
        for clause in body.split(";"):
            # a trailing parenthetical is the documented spot for the
            # why: `# graftlint: disable=GL202 (log-boundary sync)`
            clause = clause.split("(")[0].strip()
            if not clause:
                continue
            if clause == "threadsafe" or clause.startswith("threadsafe "):
                self.by_line.setdefault(line, set()).add("GL301")
            elif clause.startswith("disable-file"):
                rest = clause[len("disable-file"):].lstrip("=").strip()
                if not rest:
                    self.file_all = True
                else:
                    for t in rest.split(","):
                        if t.strip():
                            self.file_wide.add(resolve_rule_token(t))
            elif clause.startswith("disable"):
                rest = clause[len("disable"):].lstrip("=").strip()
                ids = {resolve_rule_token(t) for t in rest.split(",") if t.strip()}
                self.by_line.setdefault(line, set()).update(ids)

    def covers(self, rule: str, lines: Sequence[int]) -> bool:
        if self.file_all or rule in self.file_wide:
            return True
        return any(rule in self.by_line.get(ln, ()) for ln in lines)


# -- per-module collection ----------------------------------------------


@dataclass
class _Func:
    module: "_Mod"
    qualname: str
    node: ast.AST  # FunctionDef | AsyncFunctionDef | Lambda
    parent: Optional["_Func"]
    cls: Optional[str] = None  # enclosing class name, for self.* calls
    local_defs: Dict[str, "_Func"] = field(default_factory=dict)
    is_root: bool = False
    returns_jitted_probe: bool = False
    static_params: Set[str] = field(default_factory=set)
    # interprocedural machinery (PR 11): local names holding functions
    # ("func" -> the named defs; "maker" -> a maker whose RETURNED local
    # defs the name calls through)
    var_targets: Dict[str, List[Tuple[str, "_Func"]]] = field(
        default_factory=dict
    )

    @property
    def key(self) -> Tuple[str, str]:
        return (self.module.modname, self.qualname)

    def all_params(self) -> List[str]:
        a = self.node.args  # FunctionDef and Lambda expose .args alike
        return [p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)]


@dataclass
class _Mod:
    path: str
    relpath: str
    modname: str
    tree: ast.Module
    source: str
    suppressions: _Suppressions
    imports: Dict[str, str] = field(default_factory=dict)  # alias -> dotted
    top_defs: Dict[str, _Func] = field(default_factory=dict)
    funcs: List[_Func] = field(default_factory=list)
    classes: Dict[str, Dict[str, _Func]] = field(default_factory=dict)
    # module-level name -> the functions its assigned value mentions
    tables: Dict[str, List[_Func]] = field(default_factory=dict)


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _collect_imports(tree: ast.Module) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    out[a.asname] = a.name
                else:
                    head = a.name.split(".")[0]
                    out[head] = head
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                out[a.asname or a.name] = f"{node.module}.{a.name}"
    return out


class _FuncCollector(ast.NodeVisitor):
    """Build the function/scope tree for one module."""

    def __init__(self, mod: _Mod) -> None:
        self.mod = mod
        self.stack: List[_Func] = []
        self.class_stack: List[str] = []

    def _add(self, node, name: str) -> _Func:
        parent = self.stack[-1] if self.stack else None
        qual = f"{parent.qualname}.{name}" if parent else (
            f"{self.class_stack[-1]}.{name}" if self.class_stack else name
        )
        fn = _Func(module=self.mod, qualname=qual, node=node, parent=parent,
                   cls=self.class_stack[-1] if self.class_stack else None)
        self.mod.funcs.append(fn)
        if parent is not None:
            parent.local_defs[name] = fn
        elif self.class_stack:
            self.mod.classes.setdefault(
                self.class_stack[-1], {}
            )[name] = fn
        else:
            self.mod.top_defs[name] = fn
        return fn

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()

    def _visit_func(self, node, name: str) -> None:
        fn = self._add(node, name)
        self.stack.append(fn)
        # only descend into the body; decorators belong to the enclosing
        # scope (handled by the root-marking pass)
        for child in node.body if not isinstance(node, ast.Lambda) else [node.body]:
            self.visit(child)
        self.stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_func(node, node.name)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._visit_func(node, node.name)

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self._visit_func(node, f"<lambda:{node.lineno}>")
    # call edges are collected by the graph builder's EdgeVisitor


def _load_module(path: str, relpath: str, modname: str) -> Optional[_Mod]:
    try:
        with open(path, "r", encoding="utf-8") as f:
            source = f.read()
        tree = ast.parse(source)
    except (OSError, SyntaxError, ValueError):
        return None
    mod = _Mod(path=path, relpath=relpath, modname=modname, tree=tree,
               source=source, suppressions=_Suppressions(source))
    mod.imports = _collect_imports(tree)
    _FuncCollector(mod).visit(tree)
    return mod


# -- jit-root marking + reachability ------------------------------------


def _is_tracing_transform(dotted: Optional[str]) -> bool:
    if not dotted:
        return False
    last = dotted.split(".")[-1]
    if last not in _TRACING_TRANSFORMS:
        return False
    head = dotted.split(".")[0]
    # bare `jit`/`vmap` (from jax import jit) or jax./lax./jnp.-rooted;
    # anything else (e.g. self.scan) is not JAX
    return head in _TRACING_TRANSFORMS or head in (
        "jax", "lax", "jnp", "pjit", "functools"
    )


def _positional_params(node: ast.AST) -> List[str]:
    a = node.args
    return [p.arg for p in (a.posonlyargs + a.args)]


def _const_seq(node: ast.AST) -> List[object]:
    """Constant, or tuple/list of constants, as a Python list; []
    when any element is non-constant (a dynamic static_argnums spec
    makes NOTHING static — errs toward seeding, i.e. reporting)."""
    if isinstance(node, ast.Constant):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        if all(isinstance(e, ast.Constant) for e in node.elts):
            return [e.value for e in node.elts]
    return []


def _collect_static_params(fn: _Func, keywords: List[ast.keyword]) -> None:
    """Record params a jit call marks static via constant
    static_argnums/static_argnames — they are trace-time concrete, so
    they must not seed taint."""
    pos = _positional_params(fn.node)
    for kw in keywords:
        if kw.arg == "static_argnums":
            for i in _const_seq(kw.value):
                if isinstance(i, int) and 0 <= i < len(pos):
                    fn.static_params.add(pos[i])
        elif kw.arg == "static_argnames":
            for s in _const_seq(kw.value):
                if isinstance(s, str):
                    fn.static_params.add(s)


def _scope_lookup(fn: Optional[_Func], mod: _Mod, name: str) -> Optional[_Func]:
    cur = fn
    while cur is not None:
        if name in cur.local_defs:
            return cur.local_defs[name]
        cur = cur.parent
    return mod.top_defs.get(name)


def _mark_roots(mods: Dict[str, _Mod]) -> None:
    for mod in mods.values():
        # decorators
        for fn in mod.funcs:
            node = fn.node
            if isinstance(node, ast.Lambda):
                continue
            for dec in node.decorator_list:
                d = dec.func if isinstance(dec, ast.Call) else dec
                name = _dotted(d)
                if _is_tracing_transform(name) or _alias_transform_last(
                    mod, name
                ):
                    fn.is_root = True
                    if isinstance(dec, ast.Call):
                        _collect_static_params(fn, dec.keywords)
                elif isinstance(dec, ast.Call) and name and (
                    name.split(".")[-1] == "partial"
                ):
                    # @partial(jax.jit, ...) — first positional arg is
                    # the transform
                    if dec.args and _is_tracing_transform(_dotted(dec.args[0])):
                        fn.is_root = True
                        _collect_static_params(fn, dec.keywords)

        # call-site transforms: jax.jit(f), lax.scan(body, ...),
        # partial(jax.jit, ...)(f) is rare enough to skip
        class RootVisitor(ast.NodeVisitor):
            def __init__(self) -> None:
                self.stack: List[Optional[_Func]] = [None]

            def visit_FunctionDef(self, node):
                self._push(node)

            visit_AsyncFunctionDef = visit_FunctionDef

            def visit_Lambda(self, node):
                self._push(node)

            def _push(self, node):
                owner = next(
                    (f for f in mod.funcs if f.node is node), None
                )
                self.stack.append(owner)
                self.generic_visit(node)
                self.stack.pop()

            def visit_Call(self, node: ast.Call):
                name = _dotted(node.func)
                if _is_tracing_transform(name) or _alias_transform_last(
                    mod, name
                ):
                    scope = self.stack[-1]
                    for arg in list(node.args) + [
                        kw.value for kw in node.keywords
                    ]:
                        if isinstance(arg, ast.Lambda):
                            target = next(
                                (f for f in mod.funcs if f.node is arg),
                                None,
                            )
                            if target is not None:
                                target.is_root = True
                        elif isinstance(arg, ast.Name):
                            target = _scope_lookup(scope, mod, arg.id)
                            if target is not None:
                                target.is_root = True
                                _collect_static_params(
                                    target, node.keywords
                                )
                        elif isinstance(arg, ast.Call):
                            # jax.jit(make_step(cfg)) — the MAKER's
                            # returned local functions are the roots
                            inner = _dotted(arg.func)
                            if inner and "." not in inner:
                                maker = _scope_lookup(scope, mod, inner)
                                if maker is not None:
                                    maker.returns_jitted_probe = True
                self.generic_visit(node)

        RootVisitor().visit(mod.tree)

        # maker idiom: functions whose RESULT is jitted — their returned
        # local defs become roots
        for fn in mod.funcs:
            if not fn.returns_jitted_probe or isinstance(fn.node, ast.Lambda):
                continue
            for node in ast.walk(fn.node):
                if isinstance(node, ast.Return) and isinstance(
                    node.value, ast.Name
                ):
                    target = fn.local_defs.get(node.value.id)
                    if target is not None:
                        target.is_root = True


def _find_module(mods: Dict[str, _Mod], name: str) -> Optional[_Mod]:
    """Exact modname match, else a unique suffix match — import
    statements name modules by their import path, which may be shorter
    than the lint-root-relative modname (fixture dirs, relative
    imports)."""
    m = mods.get(name)
    if m is not None:
        return m
    suffix = "." + name
    cands = [mm for k, mm in mods.items() if k.endswith(suffix)]
    return cands[0] if len(cands) == 1 else None


def _resolve_dotted_func(
    full: str, mods: Dict[str, _Mod], depth: int = 0
) -> Optional[_Func]:
    """``pkg.mod.f`` -> the _Func, following re-export chains (a name
    imported by ``pkg/__init__.py`` from a submodule resolves through
    that module's own import aliases)."""
    if depth > 8:
        return None
    target_mod, _, func_name = full.rpartition(".")
    target = _find_module(mods, target_mod) if target_mod else None
    if target is None:
        return None
    fn = target.top_defs.get(func_name)
    if fn is not None:
        return fn
    # re-export: `from .sub import f` in the target module
    alias = target.imports.get(func_name)
    if alias is not None and alias != full:
        return _resolve_dotted_func(alias, mods, depth + 1)
    return None


def _resolve_call(
    fn: _Func, name: str, mods: Dict[str, _Mod]
) -> Optional[_Func]:
    mod = fn.module
    if "." not in name:
        local = _scope_lookup(fn, mod, name)
        if local is not None:
            return local
        # `from pkg.mod import f; f()` — the alias points at a
        # cross-module function
        alias = mod.imports.get(name)
        if alias is not None:
            return _resolve_dotted_func(alias, mods)
        return None
    head, _, rest = name.partition(".")
    if head == "self" and fn.cls and "." not in rest:
        return mod.classes.get(fn.cls, {}).get(rest)
    dotted_head = mod.imports.get(head)
    if dotted_head is None:
        return None
    full = f"{dotted_head}.{rest}" if rest else dotted_head
    return _resolve_dotted_func(full, mods)


def _alias_transform_last(mod: _Mod, name: Optional[str]) -> Optional[str]:
    """The tracing transform's SHORT name when ``name`` — as written, or
    resolved through the module's import aliases — names one; None
    otherwise. The alias path accepts jax-rooted resolutions (``from
    jax import shard_map as _shard_map`` must still read as
    shard_map)."""
    if not name:
        return None
    resolved = _call_dotted_resolved(mod, name)
    for cand in (name, resolved):
        last = cand.split(".")[-1]
        if last not in _TRACING_TRANSFORMS:
            continue
        parts = cand.split(".")
        if parts[0] in _TRACING_TRANSFORMS or parts[0] in (
            "jax", "lax", "jnp", "pjit", "functools"
        ):
            return last
        if cand is not name and "jax" in parts:
            return last
    return None


def _resolve_call_any(
    scope: Optional[_Func], mod: _Mod, name: str, mods: Dict[str, _Mod]
) -> Optional[_Func]:
    """:func:`_resolve_call` that also works at module level (no
    enclosing function)."""
    if scope is not None:
        return _resolve_call(scope, name, mods)
    if "." not in name:
        fn = mod.top_defs.get(name)
        if fn is not None:
            return fn
        alias = mod.imports.get(name)
        return _resolve_dotted_func(alias, mods) if alias else None
    head, _, rest = name.partition(".")
    dotted_head = mod.imports.get(head)
    if dotted_head is None:
        return None
    return _resolve_dotted_func(
        f"{dotted_head}.{rest}" if rest else dotted_head, mods
    )


def _returned_defs(mk: _Func, depth: int = 0) -> List[_Func]:
    """The local functions a maker returns — what a call THROUGH the
    maker's result actually runs (``step = make_step_fn(cfg)``)."""
    if depth > 4 or isinstance(mk.node, ast.Lambda):
        return []
    out: List[_Func] = []
    for node in ast.walk(mk.node):
        if isinstance(node, ast.Return) and isinstance(node.value, ast.Name):
            t = mk.local_defs.get(node.value.id)
            if t is not None:
                out.append(t)
                continue
            for kind, f in _iter_var_targets(mk, node.value.id):
                if kind == "func":
                    out.append(f)
                else:
                    out.extend(_returned_defs(f, depth + 1))
    return out


def _iter_var_targets(fn: _Func, name: str):
    """Pre-resolved ("func"|"maker", _Func) pairs a local variable may
    hold (populated by the graph builder's var pass)."""
    return list(fn.var_targets.get(name, []))


@dataclass
class _PallasSite:
    mod: _Mod
    fn: Optional[_Func]
    node: ast.Call
    kernels: List[_Func]


@dataclass
class _Pending:
    # (owner_key, param) -> functions that CALL that parameter
    param_calls: Dict[Tuple[Tuple[str, str], str], List[_Func]] = field(
        default_factory=dict
    )
    # wrapper idiom: ((owner_key, param), transform_last, axes)
    transform_params: List[
        Tuple[Tuple[Tuple[str, str], str], str, Set[str]]
    ] = field(default_factory=list)
    # every resolved direct call: (caller_or_None, mod, callee, node)
    call_sites: List[
        Tuple[Optional[_Func], _Mod, _Func, ast.Call]
    ] = field(default_factory=list)


@dataclass
class _Graph:
    by_key: Dict[Tuple[str, str], _Func]
    edges: Dict[Tuple[str, str], Set[Tuple[str, str]]]
    binder_axes: Dict[Tuple[str, str], Set[str]]
    arm_seeds: Set[Tuple[str, str]]
    kernel_seeds: List[Tuple[_Func, Optional[_Func]]]
    pallas_sites: List[_PallasSite]

    def add_edge(self, src: Optional[_Func], dst: Optional[_Func]) -> None:
        if src is None or dst is None or src is dst:
            return
        self.edges.setdefault(src.key, set()).add(dst.key)


def _build_graph(mods: Dict[str, _Mod]) -> _Graph:
    """The interprocedural edge graph: direct calls plus maker
    variables, function-valued parameter bindings, ``defvjp`` pairs,
    lambda call-arguments, transform wrapper parameters, pallas
    kernels, and BlockSpec index_maps (module docstring, step 6)."""
    g = _Graph(
        by_key={f.key: f for m in mods.values() for f in m.funcs},
        edges={}, binder_axes={}, arm_seeds=set(), kernel_seeds=[],
        pallas_sites=[],
    )
    pending = _Pending()
    lambda_funcs: Dict[int, _Func] = {}
    for m in mods.values():
        for f in m.funcs:
            if isinstance(f.node, ast.Lambda):
                lambda_funcs[id(f.node)] = f

    # -- pass 1: variable -> function candidates per scope ------------
    class VarCollector(ast.NodeVisitor):
        def __init__(self, mod: _Mod) -> None:
            self.mod = mod
            self.stack: List[Optional[_Func]] = [None]

        def _push(self, node):
            owner = next((f for f in self.mod.funcs if f.node is node), None)
            self.stack.append(owner)
            self.generic_visit(node)
            self.stack.pop()

        visit_FunctionDef = visit_AsyncFunctionDef = visit_Lambda = _push

        def visit_Assign(self, node: ast.Assign):
            self.generic_visit(node)
            if len(node.targets) != 1 or not isinstance(
                node.targets[0], ast.Name
            ):
                return
            owner = self.stack[-1]
            if owner is None:
                # a module-level table of functions: what it mentions may
                # run wherever the table is read (EdgeVisitor.visit_Name)
                self.mod.tables[node.targets[0].id] = [f for f in (
                    lambda_funcs.get(id(n)) if isinstance(n, ast.Lambda) else
                    _resolve_call_any(None, self.mod, _dotted(n) or "", mods)
                    for n in ast.walk(node.value)
                    if isinstance(n, (ast.Lambda, ast.Name, ast.Attribute))
                ) if f is not None]
                return
            owner.var_targets.setdefault(
                node.targets[0].id, []
            ).extend(self._classify(node.value, owner, 0))

        def _classify(self, value, owner, depth):
            if depth > 4:
                return []
            if isinstance(value, ast.Lambda):
                f = lambda_funcs.get(id(value))
                return [("func", f)] if f is not None else []
            if isinstance(value, ast.IfExp):
                return (self._classify(value.body, owner, depth + 1)
                        + self._classify(value.orelse, owner, depth + 1))
            if isinstance(value, (ast.Name, ast.Attribute)):
                name = _dotted(value)
                if not name:
                    return []
                t = _resolve_call_any(owner, self.mod, name, mods)
                return [("func", t)] if t is not None else []
            if isinstance(value, ast.Call):
                name = _dotted(value.func)
                if name and _alias_transform_last(self.mod, name):
                    # jitted = jax.jit(f) / sharded = shard_map(raw, ...):
                    # calling the variable runs the wrapped function
                    out = []
                    for a in list(value.args) + [
                        k.value for k in value.keywords
                    ]:
                        out.extend(self._classify(a, owner, depth + 1))
                    return out
                mk = (
                    _resolve_call_any(owner, self.mod, name, mods)
                    if name else None
                )
                return [("maker", mk)] if mk is not None else []
            return []

    for m in mods.values():
        VarCollector(m).visit(m.tree)

    # -- shared expression -> functions resolver ----------------------
    def funcs_from_expr(expr, scope, mod, depth=0) -> List[_Func]:
        if expr is None or depth > 6:
            return []
        if isinstance(expr, ast.Lambda):
            f = lambda_funcs.get(id(expr))
            return [f] if f is not None else []
        if isinstance(expr, ast.IfExp):
            return (funcs_from_expr(expr.body, scope, mod, depth + 1)
                    + funcs_from_expr(expr.orelse, scope, mod, depth + 1))
        if isinstance(expr, ast.Call):
            name = _dotted(expr.func)
            if name and name.split(".")[-1] == "partial" and expr.args:
                return funcs_from_expr(expr.args[0], scope, mod, depth + 1)
            out: List[_Func] = []
            for mk in funcs_from_expr(expr.func, scope, mod, depth + 1):
                out.extend(_returned_defs(mk))
            return out
        if isinstance(expr, (ast.Name, ast.Attribute)):
            name = _dotted(expr)
            if not name:
                return []
            direct = _resolve_call_any(scope, mod, name, mods)
            if direct is not None:
                return [direct]
            if "." not in name:
                cur = scope
                while cur is not None:
                    cands = _iter_var_targets(cur, name)
                    if cands:
                        out = []
                        for kind, f in cands:
                            if kind == "func":
                                out.append(f)
                            else:
                                out.extend(_returned_defs(f))
                        return out
                    cur = cur.parent
        return []

    def param_of(owner: _Func, name: str):
        cur = owner
        while cur is not None:
            if name in cur.all_params():
                return (cur.key, name)
            cur = cur.parent
        return None

    # -- pass 2: edges, seeds, sites ----------------------------------
    class EdgeVisitor(ast.NodeVisitor):
        def __init__(self, mod: _Mod) -> None:
            self.mod = mod
            self.stack: List[Optional[_Func]] = [None]

        def _push(self, node):
            owner = next((f for f in self.mod.funcs if f.node is node), None)
            self.stack.append(owner)
            self.generic_visit(node)
            self.stack.pop()

        visit_FunctionDef = visit_AsyncFunctionDef = visit_Lambda = _push

        def visit_Name(self, node: ast.Name):
            # dispatch through a module-level table (``KINDS[kind].step``)
            for t in self.mod.tables.get(node.id, ()):
                g.add_edge(self.stack[-1], t)

        def _axes(self, tl: str, node: ast.Call) -> Set[str]:
            if tl == "pmap":
                for kw in node.keywords:
                    if kw.arg == "axis_name" and isinstance(
                        kw.value, ast.Constant
                    ) and isinstance(kw.value.value, str):
                        return {kw.value.value}
            return {"*"}

        def _mark(self, t: _Func, tl: str, axes: Set[str], owner) -> None:
            # overlaps _mark_roots' RootVisitor on bare Name/Lambda args
            # (that pass also owns static_argnums collection and the
            # jit(make_step(cfg)) probe); this one adds IfExp/partial/
            # var-held/list-literal resolution — the root sets UNION, so
            # a resolver fix usually belongs here, a jit-semantics fix
            # there
            g.add_edge(owner, t)
            t.is_root = True
            if tl in _BINDING_TRANSFORMS:
                g.binder_axes.setdefault(t.key, set()).update(axes)
            if tl in _BRANCH_TRANSFORMS:
                g.arm_seeds.add(t.key)

        def visit_Call(self, node: ast.Call):
            owner = self.stack[-1]
            name = _dotted(node.func)
            tl = _alias_transform_last(self.mod, name) if name else None
            if tl and tl != "partial":
                axes = self._axes(tl, node)
                argexprs = list(node.args) + [
                    k.value for k in node.keywords
                ]
                flat = []
                for a in argexprs:
                    flat.extend(
                        a.elts if isinstance(a, (ast.List, ast.Tuple))
                        else [a]
                    )
                for a in flat:
                    targets = funcs_from_expr(a, owner, self.mod)
                    if (not targets and isinstance(a, ast.Name)
                            and owner is not None):
                        pw = param_of(owner, a.id)
                        if pw is not None:
                            pending.transform_params.append((pw, tl, axes))
                        continue
                    for t in targets:
                        self._mark(t, tl, axes, owner)
            elif name and name.split(".")[-1] == "pallas_call":
                kernels = (
                    funcs_from_expr(node.args[0], owner, self.mod)
                    if node.args else []
                )
                for k in kernels:
                    g.kernel_seeds.append((k, owner))
                    g.add_edge(owner, k)
                g.pallas_sites.append(
                    _PallasSite(self.mod, owner, node, kernels)
                )
            elif name and name.split(".")[-1] == "BlockSpec":
                for a in list(node.args) + [k.value for k in node.keywords]:
                    if isinstance(a, ast.Lambda):
                        f = lambda_funcs.get(id(a))
                        if f is not None:
                            g.kernel_seeds.append((f, owner))
                            g.add_edge(owner, f)
            elif (name and "." in name
                  and name.split(".")[-1] in ("defvjp", "defjvp")):
                for b in funcs_from_expr(node.func.value, owner, self.mod):
                    for arg in node.args:
                        for t in funcs_from_expr(arg, owner, self.mod):
                            g.add_edge(b, t)
            elif name:
                callee = _resolve_call_any(owner, self.mod, name, mods)
                if callee is not None:
                    g.add_edge(owner, callee)
                    pending.call_sites.append(
                        (owner, self.mod, callee, node)
                    )
                elif "." not in name and owner is not None:
                    targets = funcs_from_expr(
                        node.func, owner, self.mod
                    )
                    if targets:
                        for t in targets:
                            g.add_edge(owner, t)
                    else:
                        pw = param_of(owner, name)
                        if pw is not None:
                            pending.param_calls.setdefault(
                                pw, []
                            ).append(owner)
            # a lambda (or a ``partial``) passed as ANY call argument runs
            # inside the callee's dynamic extent; approximate with a caller edge
            for a in list(node.args) + [k.value for k in node.keywords]:
                if isinstance(a, (ast.Lambda, ast.Call)):
                    for f in funcs_from_expr(a, owner, self.mod):
                        g.add_edge(owner, f)
            self.generic_visit(node)

    for m in mods.values():
        EdgeVisitor(m).visit(m.tree)

    # -- pass 3: resolve parameter bindings ---------------------------
    def bindings_for(owner_fn: _Func, pname: str):
        params = _positional_params(owner_fn.node)
        for (scope, mod, callee, node) in pending.call_sites:
            if callee is not owner_fn:
                continue
            offset = (
                1 if params and params[0] == "self"
                and isinstance(node.func, ast.Attribute) else 0
            )
            if pname in params:
                argpos = params.index(pname) - offset
                if 0 <= argpos < len(node.args):
                    yield (scope, mod, node.args[argpos])
            for kw in node.keywords:
                if kw.arg == pname:
                    yield (scope, mod, kw.value)

    for (owner_key, pname), callers in sorted(
        pending.param_calls.items(), key=lambda kv: (kv[0][0], kv[0][1])
    ):
        owner_fn = g.by_key.get(owner_key)
        if owner_fn is None:
            continue
        for (scope, mod, expr) in bindings_for(owner_fn, pname):
            for t in funcs_from_expr(expr, scope, mod):
                for caller in callers:
                    g.add_edge(caller, t)

    for (owner_key, pname), tl, axes in pending.transform_params:
        owner_fn = g.by_key.get(owner_key)
        if owner_fn is None:
            continue
        for (scope, mod, expr) in bindings_for(owner_fn, pname):
            for t in funcs_from_expr(expr, scope, mod):
                t.is_root = True
                g.add_edge(owner_fn, t)
                if tl in _BINDING_TRANSFORMS:
                    g.binder_axes.setdefault(t.key, set()).update(axes)
                if tl in _BRANCH_TRANSFORMS:
                    g.arm_seeds.add(t.key)

    return g


def _closure(
    seeds, edges: Dict[Tuple[str, str], Set[Tuple[str, str]]],
    stop: Set[Tuple[str, str]] = frozenset(),
) -> Set[Tuple[str, str]]:
    """Reachability from ``seeds``. Nodes in ``stop`` are reached but
    not expanded — how the regular-jit closure avoids flowing THROUGH a
    pallas kernel and claiming its private helpers for GL103."""
    seen = set(seeds)
    work = [k for k in seen if k not in stop]
    while work:
        k = work.pop()
        for n in edges.get(k, ()):
            if n not in seen:
                seen.add(n)
                if n not in stop:
                    work.append(n)
    return seen


def _env_closure(
    binder_axes: Dict[Tuple[str, str], Set[str]],
    edges: Dict[Tuple[str, str], Set[Tuple[str, str]]],
) -> Dict[Tuple[str, str], Set[str]]:
    """Axis environments: seeded at binder bodies, unioned along edges
    to a fixpoint. A key's ABSENCE means "no binder reaches this
    function" — the GL401 trigger."""
    env = {k: set(v) for k, v in binder_axes.items()}
    work = list(env)
    while work:
        k = work.pop()
        for n in edges.get(k, ()):
            cur = env.setdefault(n, set())
            add = env[k] - cur
            if add:
                cur.update(add)
                work.append(n)
    return env


# -- taint + jit-region rules -------------------------------------------


def _call_dotted_resolved(mod: _Mod, name: str) -> str:
    """Rewrite the leading alias of a dotted call through the import
    map, so `np.x` in a module that did `import numpy as np` resolves
    to `numpy.x` and `random.x` after `from jax import random` resolves
    to `jax.random.x`."""
    head, dot, rest = name.partition(".")
    full_head = mod.imports.get(head)
    if full_head is None:
        return name
    return f"{full_head}{dot}{rest}" if rest else full_head


def _is_array_call(mod: _Mod, name: str) -> bool:
    resolved = _call_dotted_resolved(mod, name)
    for cand in (name, resolved):
        for ns in _ARRAY_NAMESPACES:
            if cand == ns.rstrip(".") or cand.startswith(ns):
                return True
        if cand.startswith("numpy.") and not cand.startswith("numpy.random"):
            # numpy ops on traced values error; on host constants they
            # are static — numpy calls do not SEED taint, but they also
            # do not strip it (handled by expr taint propagation)
            return False
    return False


class _Taint:
    """One function's forward-pass taint state.

    Two tiers: *strong* names (``names``) are known array values —
    results of jnp/lax/random calls and anything assigned from a
    tainted expression; *weak* names (``weak``) are the function's own
    parameters. A weak name is traced when used bare (``if x > 0``,
    ``float(x)``, ``x.sum()`` — the canonical jit-region hazards) but
    an attribute read on it stays static, so config-object parameters
    (``if cfg.dropout > 0``) do not poison the clean-tree gate."""

    def __init__(self, mod: _Mod, weak: Set[str] = frozenset()) -> None:
        self.mod = mod
        self.names: Set[str] = set()
        self.weak: Set[str] = set(weak)

    def expr(self, node: ast.AST) -> bool:
        """Does evaluating ``node`` yield a traced value?"""
        if isinstance(node, ast.Name):
            return node.id in self.names or node.id in self.weak
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            if name and _is_array_call(self.mod, name):
                return True
            # method call on a traced value: x.sum(), x.astype(...)
            if isinstance(node.func, ast.Attribute):
                return self.expr(node.func.value)
            return False
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            if (
                isinstance(node.value, ast.Name)
                and node.value.id in self.weak
                and node.value.id not in self.names
            ):
                return False  # cfg.foo on a parameter: static config
            return self.expr(node.value)
        if isinstance(node, ast.Subscript):
            return self.expr(node.value)
        if isinstance(node, (ast.BinOp,)):
            return self.expr(node.left) or self.expr(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.expr(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.expr(v) for v in node.values)
        if isinstance(node, ast.Compare):
            # identity tests never boolify a tracer — `x is None` /
            # `cos is not None` are core JAX idioms on traced values
            if all(isinstance(o, (ast.Is, ast.IsNot)) for o in node.ops):
                return False
            return self.expr(node.left) or any(
                self.expr(c) for c in node.comparators
            )
        if isinstance(node, ast.IfExp):
            return self.expr(node.body) or self.expr(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.expr(e) for e in node.elts)
        if isinstance(node, ast.Starred):
            return self.expr(node.value)
        return False

    def assign(self, target: ast.AST, tainted: bool) -> None:
        if isinstance(target, ast.Name):
            if tainted:
                self.names.add(target.id)
            else:
                self.names.discard(target.id)
                self.weak.discard(target.id)  # param rebound to host value
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self.assign(e, tainted)
        # attribute/subscript targets: no tracked state


def _weak_param_seeds(fn: _Func) -> Set[str]:
    """The function's parameter names, minus ``self``/``cls`` and any
    param a constant static_argnums/static_argnames made trace-time
    static — the weak taint seeds for its jit region.

    Only tracing ROOTS get seeded: a root's params are by construction
    the traced arguments of a compiled program (the canonical hazard is
    `if loss > thresh` inside a @jax.jit step), while transitively
    reached helpers routinely take host-static params (chunk sizes,
    positions, flags) that would drown the gate in false positives."""
    if not fn.is_root:
        return set()
    a = fn.node.args
    names = [p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)]
    for extra in (a.vararg, a.kwarg):
        if extra is not None:
            names.append(extra.arg)
    return {
        n for n in names if n not in ("self", "cls")
    } - fn.static_params


class _JitRegionChecker(ast.NodeVisitor):
    """GL101-GL107 over one jit-region function body (nested function
    bodies are their own jit regions and are skipped here). With
    ``kernel=True`` the body is a Pallas kernel / index_map: the same
    hazards apply, but impure calls report GL504 (impure-kernel) —
    inside Mosaic lowering they are a different failure mode than a
    trace-time freeze — and parameters are refs, never weak-seeded."""

    def __init__(self, fn: _Func, enabled: Set[str],
                 emit, kernel: bool = False) -> None:
        self.fn = fn
        self.mod = fn.module
        self.enabled = enabled
        self.emit = emit
        self.kernel = kernel
        self.impure_rule = "GL504" if kernel else "GL103"
        weak = set() if kernel else _weak_param_seeds(fn)
        self.taint = _Taint(fn.module, weak=weak)
        self.raise_depth = 0
        self._body_owner = fn.node

    # -- scope boundaries ---------------------------------------------
    def visit_FunctionDef(self, node):
        if node is self._body_owner:
            self.generic_visit(node)
        # nested defs: separate jit regions, checked on their own

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        if node is self._body_owner:
            self.visit(node.body)

    # -- taint bookkeeping --------------------------------------------
    def visit_Assign(self, node: ast.Assign):
        self.generic_visit(node)
        t = self.taint.expr(node.value)
        for target in node.targets:
            self.taint.assign(target, t)

    def visit_AugAssign(self, node: ast.AugAssign):
        self.generic_visit(node)
        if self.taint.expr(node.value):
            self.taint.assign(node.target, True)

    def visit_AnnAssign(self, node: ast.AnnAssign):
        self.generic_visit(node)
        if node.value is not None:
            self.taint.assign(node.target, self.taint.expr(node.value))

    # -- GL104: traced branch -----------------------------------------
    def _check_branch(self, test: ast.AST, kind: str) -> None:
        if "GL104" in self.enabled and self.taint.expr(test):
            self.emit(
                "GL104", test.lineno,
                f"Python `{kind}` on a traced value in jit region "
                f"`{self.fn.qualname}`",
            )

    def visit_If(self, node: ast.If):
        self._check_branch(node.test, "if")
        self.generic_visit(node)

    def visit_While(self, node: ast.While):
        self._check_branch(node.test, "while")
        self.generic_visit(node)

    def visit_Assert(self, node: ast.Assert):
        self._check_branch(node.test, "assert")
        # the assert MESSAGE runs on static data (GL105 exemption)
        self.raise_depth += 1
        self.generic_visit(node)
        self.raise_depth -= 1

    def visit_Raise(self, node: ast.Raise):
        self.raise_depth += 1
        self.generic_visit(node)
        self.raise_depth -= 1

    # -- GL106: set iteration -----------------------------------------
    def visit_For(self, node: ast.For):
        if "GL106" in self.enabled and isinstance(
            node.iter, (ast.Set, ast.SetComp)
        ):
            self.emit(
                "GL106", node.iter.lineno,
                f"iteration over a set in jit region "
                f"`{self.fn.qualname}` — trace order is hash-dependent",
            )
        self.generic_visit(node)

    def _check_comp(self, node):
        if "GL106" in self.enabled:
            for gen in node.generators:
                if isinstance(gen.iter, (ast.Set, ast.SetComp)):
                    self.emit(
                        "GL106", gen.iter.lineno,
                        f"comprehension over a set in jit region "
                        f"`{self.fn.qualname}` — trace order is "
                        "hash-dependent",
                    )
        self.generic_visit(node)

    visit_ListComp = _check_comp
    visit_SetComp = _check_comp
    visit_DictComp = _check_comp
    visit_GeneratorExp = _check_comp

    # -- GL107: global/nonlocal ---------------------------------------
    def visit_Global(self, node: ast.Global):
        if "GL107" in self.enabled:
            self.emit(
                "GL107", node.lineno,
                f"`global {', '.join(node.names)}` in jit region "
                f"`{self.fn.qualname}`",
            )

    def visit_Nonlocal(self, node: ast.Nonlocal):
        if "GL107" in self.enabled:
            self.emit(
                "GL107", node.lineno,
                f"`nonlocal {', '.join(node.names)}` in jit region "
                f"`{self.fn.qualname}`",
            )

    # -- GL105: f-strings ---------------------------------------------
    def visit_JoinedStr(self, node: ast.JoinedStr):
        if (
            "GL105" in self.enabled
            and self.raise_depth == 0
            and any(
                isinstance(v, ast.FormattedValue) for v in node.values
            )
        ):
            self.emit(
                "GL105", node.lineno,
                f"f-string in jit region `{self.fn.qualname}` "
                "(outside raise/assert)",
            )
        self.generic_visit(node)

    # -- GL101/GL102/GL103: calls -------------------------------------
    def visit_Call(self, node: ast.Call):
        self.generic_visit(node)
        name = _dotted(node.func)

        # attribute-form host syncs fire regardless of taint: these
        # methods have no legitimate trace-time use on array-like values
        if isinstance(node.func, ast.Attribute) and "GL101" in self.enabled:
            if node.func.attr in ("item", "tolist", "block_until_ready"):
                self.emit(
                    "GL101", node.lineno,
                    f".{node.func.attr}() in jit region "
                    f"`{self.fn.qualname}`",
                )
                return

        if not name:
            return
        resolved = _call_dotted_resolved(self.mod, name)

        if "GL101" in self.enabled:
            if resolved.endswith("jax.device_get") or name == "jax.device_get":
                self.emit(
                    "GL101", node.lineno,
                    f"jax.device_get() in jit region `{self.fn.qualname}`",
                )
                return
            if resolved.split(".")[0] in ("numpy",) and resolved.split(".")[-1] in (
                "asarray", "array"
            ):
                if any(self.taint.expr(a) for a in node.args):
                    self.emit(
                        "GL101", node.lineno,
                        f"{name}() on a traced value in jit region "
                        f"`{self.fn.qualname}`",
                    )
                    return

        if "GL102" in self.enabled and name in ("float", "int", "bool",
                                                "complex"):
            if node.args and self.taint.expr(node.args[0]):
                self.emit(
                    "GL102", node.lineno,
                    f"{name}() on a traced value in jit region "
                    f"`{self.fn.qualname}`",
                )
                return

        if "GL105" in self.enabled and name == "str" and self.raise_depth == 0:
            if node.args and self.taint.expr(node.args[0]):
                self.emit(
                    "GL105", node.lineno,
                    f"str() of a traced value in jit region "
                    f"`{self.fn.qualname}`",
                )
                return

        if self.impure_rule in self.enabled:
            where = (
                "Pallas kernel" if self.kernel else "jit region"
            )
            if name in _IMPURE_BARE and name not in self.mod.top_defs:
                self.emit(
                    self.impure_rule, node.lineno,
                    f"impure call {name}() in {where} "
                    f"`{self.fn.qualname}`",
                )
                return
            for cand in {name, resolved}:
                if any(cand.startswith(p) for p in _IMPURE_PREFIXES):
                    self.emit(
                        self.impure_rule, node.lineno,
                        f"impure call {name}() in {where} "
                        f"`{self.fn.qualname}`",
                    )
                    return
                # stdlib `random.` — only when `random` is not an alias
                # for jax.random
                if cand.startswith("random.") and not resolved.startswith(
                    "jax.random"
                ):
                    self.emit(
                        self.impure_rule, node.lineno,
                        f"host RNG call {name}() in {where} "
                        f"`{self.fn.qualname}`",
                    )
                    return


# -- GL201: donation on step-like jit entry points ----------------------


class _DonateChecker(ast.NodeVisitor):
    def __init__(self, mod: _Mod, enabled: Set[str], emit) -> None:
        self.mod = mod
        self.enabled = enabled
        self.emit = emit

    def visit_Call(self, node: ast.Call):
        self.generic_visit(node)
        if "GL201" not in self.enabled:
            return
        name = _dotted(node.func)
        if not name or name.split(".")[-1] not in ("jit", "pjit"):
            return
        if name.split(".")[0] not in ("jax", "jit", "pjit"):
            return
        if not node.args:
            return
        target = node.args[0]
        tname = None
        if isinstance(target, ast.Name):
            tname = target.id
        elif isinstance(target, ast.Call):
            tname = _dotted(target.func)
        elif isinstance(target, ast.Attribute):
            tname = _dotted(target)
        if not tname:
            return  # lambdas etc.: nothing nameable to hold a policy on
        short = tname.split(".")[-1]
        if not _DONATE_NAME_RE.search(short) or _DONATE_EXEMPT_RE.search(short):
            return
        kws = {kw.arg for kw in node.keywords}
        if not ({"donate_argnums", "donate_argnames"} & kws):
            self.emit(
                "GL201", node.lineno,
                f"jax.jit({tname}, ...) — a step-like entry point "
                "jitted without donate_argnums",
            )

    def visit_FunctionDef(self, node: ast.FunctionDef):
        self.generic_visit(node)
        if "GL201" not in self.enabled:
            return
        short = node.name
        if not _DONATE_NAME_RE.search(short) or _DONATE_EXEMPT_RE.search(short):
            return
        for dec in node.decorator_list:
            d = dec.func if isinstance(dec, ast.Call) else dec
            dname = _dotted(d) or ""
            if dname.split(".")[-1] in ("jit", "pjit") and dname.split(
                "."
            )[0] in ("jax", "jit", "pjit"):
                has_donate = isinstance(dec, ast.Call) and any(
                    kw.arg in ("donate_argnums", "donate_argnames")
                    for kw in dec.keywords
                )
                if not has_donate:
                    self.emit(
                        "GL201", dec.lineno,
                        f"@{dname} on step-like `{node.name}` without "
                        "donate_argnums",
                    )
            elif isinstance(dec, ast.Call) and dname.split(".")[-1] == "partial":
                if dec.args and (_dotted(dec.args[0]) or "").split(".")[-1] in (
                    "jit", "pjit"
                ):
                    if not any(
                        kw.arg in ("donate_argnums", "donate_argnames")
                        for kw in dec.keywords
                    ):
                        self.emit(
                            "GL201", dec.lineno,
                            f"@partial(jax.jit, ...) on step-like "
                            f"`{node.name}` without donate_argnums",
                        )


# -- GL202: host syncs inside step-dispatch loops -----------------------


class _StepLoopChecker(ast.NodeVisitor):
    """Flags blocking syncs in loops that drive a jitted step. Applies
    to HOST functions only (jit regions get the stricter GL1xx)."""

    def __init__(self, fn: _Func, enabled: Set[str], emit) -> None:
        self.fn = fn
        self.enabled = enabled
        self.emit = emit
        self.loop_depth = 0  # inside a step-dispatching loop?
        self._body_owner = fn.node

    def visit_FunctionDef(self, node):
        if node is self._body_owner:
            self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        if node is self._body_owner:
            self.visit(node.body)

    @staticmethod
    def _loop_dispatches_step(node) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                name = _dotted(sub.func)
                if name and _STEP_CALL_RE.search(name.split(".")[-1]):
                    return True
        return False

    def _visit_loop(self, node) -> None:
        dispatches = self._loop_dispatches_step(node)
        if dispatches:
            self.loop_depth += 1
        self.generic_visit(node)
        if dispatches:
            self.loop_depth -= 1

    visit_For = _visit_loop
    visit_While = _visit_loop

    def visit_Call(self, node: ast.Call):
        self.generic_visit(node)
        if "GL202" not in self.enabled or self.loop_depth == 0:
            return
        name = _dotted(node.func)
        if isinstance(node.func, ast.Attribute) and node.func.attr == "item":
            self.emit(
                "GL202", node.lineno,
                f".item() inside the step loop of `{self.fn.qualname}`",
            )
            return
        if not name:
            return
        if name in ("float", "int") and node.args and not isinstance(
            node.args[0], ast.Constant
        ):
            self.emit(
                "GL202", node.lineno,
                f"{name}() host sync inside the step loop of "
                f"`{self.fn.qualname}`",
            )
            return
        resolved = _call_dotted_resolved(self.fn.module, name)
        if name == "jax.device_get" or resolved == "jax.device_get":
            self.emit(
                "GL202", node.lineno,
                f"jax.device_get() inside the step loop of "
                f"`{self.fn.qualname}`",
            )


# -- GL401/GL402/GL403: sharding + collective discipline ----------------


def _axis_arg_literals(node: ast.Call, last: str) -> List[str]:
    """Literal axis names named by a collective call, [] when the axis
    expression is not statically a string (a threaded-in variable —
    bound by construction at the binding site, so unknown = no check)."""
    cand = None
    if last in _AXIS_QUERY_OPS:
        cand = node.args[0] if node.args else None
    elif len(node.args) >= 2:
        cand = node.args[1]
    for kw in node.keywords:
        # axis_name is THE name kwarg across lax collectives; `axis=`
        # on all_gather/all_to_all is the ARRAY dimension (an int) and
        # must not clobber the positional name candidate
        if kw.arg == "axis_name":
            cand = kw.value
    if cand is None:
        return []
    if isinstance(cand, ast.Constant) and isinstance(cand.value, str):
        return [cand.value]
    if isinstance(cand, (ast.Tuple, ast.List)):
        out = []
        for e in cand.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
            else:
                return []  # partially dynamic: treat as unknown
        return out
    return []


class _CollectiveChecker(ast.NodeVisitor):
    """Runs on EVERY function — host or jit region — with the axis
    environment (None = no shard_map/pmap binder reaches it) and the
    branch-arm flag computed by the interprocedural closures."""

    def __init__(self, fn: _Func, enabled: Set[str], emit,
                 env: Optional[Set[str]], in_arm: bool) -> None:
        self.fn = fn
        self.mod = fn.module
        self.enabled = enabled
        self.emit = emit
        self.env = env
        self.in_arm = in_arm
        self._body_owner = fn.node

    def visit_FunctionDef(self, node):
        if node is self._body_owner:
            self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        if node is self._body_owner:
            self.visit(node.body)

    def visit_Call(self, node: ast.Call):
        self.generic_visit(node)
        name = _dotted(node.func)
        if not name:
            return
        resolved = _call_dotted_resolved(self.mod, name)
        jaxish = any(
            c.split(".")[0] in ("jax", "lax") or c.startswith("jax.")
            for c in (name, resolved)
        )
        if not jaxish:
            return
        last = name.split(".")[-1]
        if last == "device_put":
            if "GL403" in self.enabled and self.env is not None:
                self.emit(
                    "GL403", node.lineno,
                    f"jax.device_put() inside the shard_map/pmap-bound "
                    f"region `{self.fn.qualname}`",
                )
            return
        if last not in _COLLECTIVE_OPS and last not in _AXIS_QUERY_OPS:
            return
        if "GL401" in self.enabled:
            if self.env is None:
                self.emit(
                    "GL401", node.lineno,
                    f"collective {name}() in `{self.fn.qualname}`, which "
                    "no shard_map/pmap axis-binding context reaches",
                )
                return
            if "*" not in self.env:
                missing = [
                    a for a in _axis_arg_literals(node, last)
                    if a not in self.env
                ]
                if missing:
                    self.emit(
                        "GL401", node.lineno,
                        f"collective {name}() names axis "
                        f"{', '.join(repr(a) for a in missing)} not bound "
                        f"by any reachable context (bound: "
                        f"{', '.join(sorted(self.env)) or 'none'})",
                    )
                    return
        if ("GL402" in self.enabled and self.in_arm
                and last in _COLLECTIVE_OPS):
            self.emit(
                "GL402", node.lineno,
                f"collective {name}() reachable from a lax.cond/switch/"
                f"while_loop branch (`{self.fn.qualname}`) — shards "
                "taking different branches deadlock",
            )


# -- GL5xx: pallas_call sites and kernel bodies -------------------------


def _own_scope_nodes(fnnode):
    """AST nodes within ONE function's own scope — nested defs/lambdas/
    classes are separate scopes (their locals are not this scope's
    constants, and their lock acquisitions happen when the closure runs
    later, not here)."""
    stack = [fnnode]
    while stack:
        node = stack.pop()
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda, ast.ClassDef)):
                continue
            yield child
            stack.append(child)


def _own_scope_assigns(fnnode) -> List[ast.stmt]:
    """Assign/AugAssign statements in ONE function's own scope — a
    sibling nested helper's `BM = 100` is not the call site's BM."""
    return [
        n for n in _own_scope_nodes(fnnode)
        if isinstance(n, (ast.Assign, ast.AugAssign))
    ]


class _ConstEnv:
    """Best-effort constant folding for pallas-site checks: module-level
    single assignments plus the enclosing function chain's single
    assignments (own scopes only). Reassigned names are poisoned
    (unknown)."""

    def __init__(self, mod: _Mod, fn: Optional[_Func]) -> None:
        self.vals: Dict[str, ast.AST] = {}
        self._poison: Set[str] = set()
        self._feed(mod.tree.body)
        chain: List[_Func] = []
        cur = fn
        while cur is not None:
            chain.append(cur)
            cur = cur.parent
        for f in reversed(chain):
            if not isinstance(f.node, ast.Lambda):
                self._feed(_own_scope_assigns(f.node))

    def _feed(self, stmts) -> None:
        for st in stmts:
            if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                    and isinstance(st.targets[0], ast.Name):
                n = st.targets[0].id
                if n in self.vals or n in self._poison:
                    self._poison.add(n)
                    self.vals.pop(n, None)
                else:
                    self.vals[n] = st.value
            elif isinstance(st, ast.AugAssign) and isinstance(
                st.target, ast.Name
            ):
                self._poison.add(st.target.id)
                self.vals.pop(st.target.id, None)

    def int_of(self, node, depth: int = 0) -> Optional[int]:
        if node is None or depth > 8:
            return None
        if isinstance(node, ast.Constant) and isinstance(node.value, int) \
                and not isinstance(node.value, bool):
            return node.value
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            v = self.int_of(node.operand, depth + 1)
            return -v if v is not None else None
        if isinstance(node, ast.Name):
            return self.int_of(self.vals.get(node.id), depth + 1)
        if isinstance(node, ast.BinOp):
            lv = self.int_of(node.left, depth + 1)
            rv = self.int_of(node.right, depth + 1)
            if lv is None or rv is None:
                return None
            if isinstance(node.op, ast.Add):
                return lv + rv
            if isinstance(node.op, ast.Sub):
                return lv - rv
            if isinstance(node.op, ast.Mult):
                return lv * rv
            if isinstance(node.op, ast.FloorDiv):
                return lv // rv if rv else None
            if isinstance(node.op, ast.Mod):
                return lv % rv if rv else None
            return None
        if isinstance(node, ast.Subscript):
            idx = self.int_of(node.slice, depth + 1)
            seq = node.value
            if isinstance(seq, ast.Name):
                seq = self.vals.get(seq.id)
            if isinstance(seq, (ast.Tuple, ast.List)) and idx is not None \
                    and 0 <= idx < len(seq.elts):
                return self.int_of(seq.elts[idx], depth + 1)
        return None

    def dims_of(self, node) -> Optional[List[Optional[int]]]:
        if isinstance(node, ast.Name):
            node = self.vals.get(node.id)
        if isinstance(node, (ast.Tuple, ast.List)):
            return [self.int_of(e) for e in node.elts]
        return None

    def list_of(self, node) -> Optional[List[ast.AST]]:
        if isinstance(node, (ast.List, ast.Tuple)):
            return list(node.elts)
        if isinstance(node, ast.Name):
            v = self.vals.get(node.id)
            if isinstance(v, (ast.List, ast.Tuple)):
                return list(v.elts)
        return None


def _dtype_last(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    name = _dotted(node)
    return name.split(".")[-1] if name else None


def _kernel_param_layouts(kfn: _Func) -> List[List[str]]:
    """Candidate positional-parameter name lists for a kernel: the
    literal signature, or — for ``*refs`` kernels — each tuple-unpack
    of the vararg found in the body (conditional unpacks yield several
    candidates; all are checked)."""
    a = kfn.node.args
    pos = [p.arg for p in (a.posonlyargs + a.args)]
    if a.vararg is None:
        return [pos]
    layouts: List[List[str]] = []
    for n in ast.walk(kfn.node):
        if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                and isinstance(n.value, ast.Name) \
                and n.value.id == a.vararg.arg \
                and isinstance(n.targets[0], (ast.Tuple, ast.List)) \
                and all(isinstance(e, ast.Name)
                        for e in n.targets[0].elts):
            layouts.append(pos + [e.id for e in n.targets[0].elts])
    return layouts or [pos]


def _mac_store_line(kfn: _Func, name: str) -> Optional[int]:
    """Line of an accumulating store into ref ``name``:
    ``name[...] += ...`` or ``name[...] = <expr reading name[...]>``."""
    for n in ast.walk(kfn.node):
        if isinstance(n, ast.AugAssign) \
                and isinstance(n.op, (ast.Add, ast.Sub)) \
                and isinstance(n.target, ast.Subscript) \
                and isinstance(n.target.value, ast.Name) \
                and n.target.value.id == name:
            return n.lineno
        if isinstance(n, ast.Assign):
            for t in n.targets:
                if isinstance(t, ast.Subscript) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id == name:
                    for sub in ast.walk(n.value):
                        if isinstance(sub, ast.Subscript) \
                                and isinstance(sub.value, ast.Name) \
                                and sub.value.id == name:
                            return n.lineno
    return None


def _check_pallas_site(site: _PallasSite, enabled: Set[str], emit,
                       vmem_budget_mib: float) -> None:
    """GL501/GL502/GL503 at one ``pallas_call`` site, from what is
    statically provable there — unknown dims/dtypes silently skip a
    check (this is a prover, not a guesser)."""
    node = site.node
    env = _ConstEnv(site.mod, site.fn)
    kws = {k.arg: k.value for k in node.keywords if k.arg}

    def as_list(x):
        if x is None:
            return []
        lst = env.list_of(x)
        return lst if lst is not None else [x]

    def sds(entry):
        if isinstance(entry, ast.Call):
            n = (_dotted(entry.func) or "").split(".")[-1]
            if n == "ShapeDtypeStruct" and entry.args:
                return env.dims_of(entry.args[0]), (
                    _dtype_last(entry.args[1])
                    if len(entry.args) > 1 else None
                )
        return None, None

    def block_dims(entry):
        if isinstance(entry, ast.Call):
            n = (_dotted(entry.func) or "").split(".")[-1]
            if n == "BlockSpec" and entry.args:
                return env.dims_of(entry.args[0])
        return None

    def scratch_info(entry):
        if isinstance(entry, ast.Call):
            n = (_dotted(entry.func) or "").split(".")[-1]
            if n in ("VMEM", "SMEM", "ANY") and entry.args:
                return env.dims_of(entry.args[0]), (
                    _dtype_last(entry.args[1])
                    if len(entry.args) > 1 else None
                )
            if n == "ShapeDtypeStruct":
                return sds(entry)
        return None, None

    shapes = as_list(kws.get("out_shape"))
    specs = as_list(kws.get("out_specs"))
    in_specs = env.list_of(kws.get("in_specs")) or []
    scratch = env.list_of(kws.get("scratch_shapes")) or []

    if "GL501" in enabled and shapes and len(shapes) == len(specs):
        for shp_e, spec_e in zip(shapes, specs):
            dims, _dt = sds(shp_e)
            block = block_dims(spec_e)
            if not dims or not block or len(dims) != len(block):
                continue
            for d, (n_, b_) in enumerate(zip(dims, block)):
                if isinstance(n_, int) and isinstance(b_, int) \
                        and b_ > 0 and n_ % b_:
                    emit(
                        "GL501", spec_e.lineno,
                        f"out_shape dim {d} = {n_} not divisible by "
                        f"BlockSpec block dim {b_} at this pallas_call "
                        "— the ragged tail tile reads/writes garbage",
                    )

    if "GL502" in enabled and scratch and site.kernels:
        sub32 = [
            (i, scratch_info(e)[1]) for i, e in enumerate(scratch)
            if scratch_info(e)[1] in _SUB_FP32_FLOATS
        ]
        for kfn in site.kernels:
            reported: Set[Tuple[int, str]] = set()
            for names in _kernel_param_layouts(kfn):
                if len(names) < len(scratch):
                    continue
                base = len(names) - len(scratch)
                for i, dt in sub32:
                    pname = names[base + i]
                    line = _mac_store_line(kfn, pname)
                    if line and (line, pname) not in reported:
                        reported.add((line, pname))
                        emit(
                            "GL502", line,
                            f"kernel `{kfn.qualname}` accumulates into "
                            f"sub-fp32 scratch `{pname}` ({dt}) — the "
                            "fp32-accumulation invariant every ops/ "
                            "kernel documents",
                        )

    if "GL503" in enabled:
        total = 0
        for e in in_specs:
            b = block_dims(e)
            if b and all(isinstance(x, int) for x in b):
                n = 1
                for x in b:
                    n *= x
                total += n * 4  # input dtypes unseen at the site
        for shp_e, spec_e in zip(shapes, specs):
            b = block_dims(spec_e)
            _dims, dt = sds(shp_e)
            if b and all(isinstance(x, int) for x in b):
                n = 1
                for x in b:
                    n *= x
                total += n * _DTYPE_BYTES.get(dt or "", 4)
        for e in scratch:
            dims, dt = scratch_info(e)
            if dims and all(isinstance(x, int) for x in dims):
                n = 1
                for x in dims:
                    n *= x
                total += n * _DTYPE_BYTES.get(dt or "", 4)
        budget = vmem_budget_mib * 1024 * 1024
        if total > budget:
            emit(
                "GL503", node.lineno,
                f"estimated VMEM footprint {total / (1024 * 1024):.1f} "
                f"MiB (statically-known blocks + scratch) exceeds the "
                f"{vmem_budget_mib:g} MiB budget",
            )


def _strong_taint_names(fn: _Func) -> Set[str]:
    """Names bound to array-op results in ``fn``'s own body (nested
    defs excluded) — what a kernel/index_map must not close over."""
    t = _Taint(fn.module)
    owner = fn.node

    class V(ast.NodeVisitor):
        def visit_FunctionDef(self, node):
            if node is owner:
                self.generic_visit(node)

        visit_AsyncFunctionDef = visit_FunctionDef

        def visit_Lambda(self, node):
            if node is owner:
                self.visit(node.body)

        def visit_Assign(self, node):
            self.generic_visit(node)
            v = t.expr(node.value)
            for tgt in node.targets:
                t.assign(tgt, v)

        def visit_AugAssign(self, node):
            self.generic_visit(node)
            if t.expr(node.value):
                t.assign(node.target, True)

        def visit_AnnAssign(self, node):
            self.generic_visit(node)
            if node.value is not None:
                t.assign(node.target, t.expr(node.value))

    V().visit(fn.node)
    return set(t.names)


def _free_loads(fn: _Func) -> Dict[str, int]:
    """Free variables of a function: names LOADED in its body that are
    neither parameters nor bound anywhere inside it."""
    bound: Set[str] = set()
    for n in ast.walk(fn.node):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            aa = n.args
            for p in aa.posonlyargs + aa.args + aa.kwonlyargs:
                bound.add(p.arg)
            for extra in (aa.vararg, aa.kwarg):
                if extra is not None:
                    bound.add(extra.arg)
            if not isinstance(n, ast.Lambda):
                bound.add(n.name)
        elif isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
            bound.add(n.id)
    loads: Dict[str, int] = {}
    for n in ast.walk(fn.node):
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) \
                and n.id not in bound:
            loads.setdefault(n.id, n.lineno)
    return loads


def _check_kernel_closures(kfn: _Func, enclosing: Optional[_Func],
                           enabled: Set[str], emit) -> None:
    """GL504's closure half: a kernel body or index_map referencing a
    traced value from the enclosing scope."""
    if "GL504" not in enabled or enclosing is None:
        return
    tainted = _strong_taint_names(enclosing)
    if not tainted:
        return
    kind = "index_map" if isinstance(kfn.node, ast.Lambda) else "kernel"
    for name, line in sorted(_free_loads(kfn).items()):
        if name in tainted:
            emit(
                "GL504", line,
                f"{kind} `{kfn.qualname}` closes over traced value "
                f"`{name}` from `{enclosing.qualname}` — pass it in as "
                "a ref or a partial-bound static",
            )


# -- GL601/GL602: lock-order graph + blocking-under-lock ----------------


def _self_attr(node: ast.AST) -> Optional[str]:
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


@dataclass
class _ClassInfo:
    mod: _Mod
    node: ast.ClassDef
    key: Tuple[str, str]  # (modname, ClassName)
    locks: Set[str] = field(default_factory=set)
    conds: Set[str] = field(default_factory=set)
    events: Set[str] = field(default_factory=set)
    queues: Set[str] = field(default_factory=set)
    attr_types: Dict[str, object] = field(default_factory=dict)
    methods: Dict[str, ast.AST] = field(default_factory=dict)


class _ConcurrencyChecker:
    """GL601/GL602 over every lock-owning class in the scanned tree.
    serving/ and tools/fleet.py are the motivating surfaces, but an
    inversion in train/ or obs/ deadlocks just the same, so the
    analysis is not directory-scoped (unlike GL301, whose shared-state
    heuristic is tuned to the serving threading model).

    The lock-order graph: one node per (class, lock attribute); while
    lock A is lexically held (``with self.A`` / ``self.A.acquire()``),
    an edge A→B is drawn for every lock B acquired inside — directly,
    through same-class method calls (transitive), or through methods
    of attributes whose class ``__init__`` makes resolvable
    (``self.x = SomeClass(...)``). A cycle means two threads can
    interleave the two paths and deadlock (GL601)."""

    def __init__(self, mods: Dict[str, _Mod], enabled: Set[str],
                 emit_for) -> None:
        self.mods = mods
        self.enabled = enabled
        self.emit_for = emit_for
        self.classes: Dict[Tuple[str, str], _ClassInfo] = {}
        self.edge_sites: Dict[Tuple, Tuple[_Mod, int]] = {}
        self.adj: Dict[Tuple, Set[Tuple]] = {}

    @staticmethod
    def _fmt(nodekey) -> str:
        (_mod, cls), attr = nodekey
        return f"{cls}.{attr}"

    def run(self) -> None:
        if not ({"GL601", "GL602"} & self.enabled):
            return
        for mod in self.mods.values():
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.ClassDef):
                    self._collect(mod, node)
        for ci in self.classes.values():
            self._resolve_attr_types(ci)
        for ci in sorted(
            self.classes.values(),
            key=lambda c: (c.mod.relpath, c.node.lineno),
        ):
            if ci.locks:
                for meth in ci.methods.values():
                    self._walk_method(ci, meth)
        if "GL601" in self.enabled:
            for (u, v), (mod, line) in sorted(
                self.edge_sites.items(),
                key=lambda kv: (kv[1][0].relpath, kv[1][1], str(kv[0])),
            ):
                if self._reaches(v, u):
                    self.emit_for(mod)(
                        "GL601", line,
                        f"lock-order inversion: {self._fmt(v)} acquired "
                        f"while holding {self._fmt(u)}, but another path "
                        f"acquires {self._fmt(u)} while holding "
                        f"{self._fmt(v)}",
                    )

    def _collect(self, mod: _Mod, cls: ast.ClassDef) -> None:
        key = (mod.modname, cls.name)
        ci = _ClassInfo(mod=mod, node=cls, key=key)
        for n in cls.body:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                ci.methods[n.name] = n
        for n in ast.walk(cls):
            if not isinstance(n, ast.Assign) or not isinstance(
                n.value, ast.Call
            ):
                continue
            vname = _dotted(n.value.func) or ""
            last = vname.split(".")[-1]
            for t in n.targets:
                attr = _self_attr(t)
                if attr is None:
                    continue
                if last in _LOCK_FACTORIES:
                    ci.locks.add(attr)
                    if last in _COND_FACTORIES:
                        ci.conds.add(attr)
                elif last in _EVENT_FACTORIES:
                    ci.events.add(attr)
                elif last in _QUEUE_FACTORIES:
                    ci.queues.add(attr)
                elif vname and last[:1].isupper():
                    ci.attr_types.setdefault(attr, vname)
        self.classes[key] = ci

    def _resolve_attr_types(self, ci: _ClassInfo) -> None:
        resolved: Dict[str, Tuple[str, str]] = {}
        for attr, vname in ci.attr_types.items():
            if "." not in vname and (ci.mod.modname, vname) in self.classes:
                resolved[attr] = (ci.mod.modname, vname)
                continue
            full = _call_dotted_resolved(ci.mod, vname)
            clsname = full.split(".")[-1]
            modpart = full.rsplit(".", 1)[0] if "." in full else ""
            m = _find_module(self.mods, modpart) if modpart else None
            if m is not None and (m.modname, clsname) in self.classes:
                resolved[attr] = (m.modname, clsname)
        ci.attr_types = resolved

    def _acquires(self, key, mname: str,
                  _seen: Optional[Set[Tuple]] = None) -> Set[Tuple]:
        """Locks a method acquires, transitively through resolvable
        calls. No memoization: a cache keyed on (class, method) gets
        permanently poisoned by cycle-guard placeholders, making GL601
        order-dependent on unrelated methods — the per-query `_seen`
        set bounds recursion instead, and the class method graphs here
        are small enough that recomputation is free."""
        if _seen is None:
            _seen = set()
        memo = (key, mname)
        if memo in _seen:
            return set()
        _seen.add(memo)
        ci = self.classes.get(key)
        out: Set[Tuple] = set()
        if ci is None or mname not in ci.methods:
            return out
        # own scope only: a callback DEFINED here acquires its locks
        # when it runs later, outside this method's lock context —
        # counting it would invent inversions (_walk_method skips
        # nested defs for the same reason)
        for n in _own_scope_nodes(ci.methods[mname]):
            if isinstance(n, (ast.With, ast.AsyncWith)):
                for item in n.items:
                    a = _self_attr(item.context_expr)
                    if a in ci.locks:
                        out.add((key, a))
            elif isinstance(n, ast.Call):
                nm = _dotted(n.func) or ""
                parts = nm.split(".")
                if len(parts) == 3 and parts[0] == "self" \
                        and parts[2] == "acquire" and parts[1] in ci.locks:
                    out.add((key, parts[1]))
                elif len(parts) == 2 and parts[0] == "self":
                    out |= self._acquires(key, parts[1], _seen)
                elif len(parts) == 3 and parts[0] == "self" \
                        and parts[1] in ci.attr_types:
                    out |= self._acquires(
                        ci.attr_types[parts[1]], parts[2], _seen
                    )
        return out

    def _edge(self, u, v, mod: _Mod, line: int) -> None:
        if (u, v) not in self.edge_sites:
            self.edge_sites[(u, v)] = (mod, line)
        self.adj.setdefault(u, set()).add(v)

    def _reaches(self, src, dst) -> bool:
        seen = {src}
        work = [src]
        while work:
            k = work.pop()
            if k == dst:
                return True
            for n in self.adj.get(k, ()):
                if n not in seen:
                    seen.add(n)
                    work.append(n)
        return False

    def _walk_method(self, ci: _ClassInfo, meth) -> None:
        checker = self
        emit = self.emit_for(ci.mod)
        held: List[Tuple] = []

        class V(ast.NodeVisitor):
            def visit_With(self, node):
                acquired = []
                for item in node.items:
                    self.visit(item.context_expr)
                    a = _self_attr(item.context_expr)
                    if a is not None and a in ci.locks:
                        tgt = (ci.key, a)
                        for h in held:
                            if h != tgt:
                                checker._edge(h, tgt, ci.mod, node.lineno)
                        held.append(tgt)
                        acquired.append(tgt)
                for b in node.body:
                    self.visit(b)
                for _ in acquired:
                    held.pop()

            visit_AsyncWith = visit_With

            def visit_FunctionDef(self, node):
                if node is meth:
                    self.generic_visit(node)
                # nested defs run later, outside this lock scope

            visit_AsyncFunctionDef = visit_FunctionDef

            def visit_Lambda(self, node):
                pass

            def visit_Call(self, node):
                self.generic_visit(node)
                if held:
                    checker._call_under_lock(ci, node, held, emit)

        V().visit(meth)

    def _call_under_lock(self, ci: _ClassInfo, node: ast.Call,
                         held: List[Tuple], emit) -> None:
        nm = _dotted(node.func) or ""
        resolved = _call_dotted_resolved(ci.mod, nm) if nm else ""
        parts = nm.split(".") if nm else []
        line = node.lineno
        # lock-order edges through calls
        acq: Set[Tuple] = set()
        if len(parts) == 3 and parts[0] == "self" \
                and parts[2] == "acquire" and parts[1] in ci.locks:
            acq = {(ci.key, parts[1])}
        elif len(parts) == 2 and parts[0] == "self":
            acq = self._acquires(ci.key, parts[1])
        elif len(parts) == 3 and parts[0] == "self" \
                and parts[1] in ci.attr_types:
            acq = self._acquires(ci.attr_types[parts[1]], parts[2])
        for tgt in acq:
            for h in held:
                if h != tgt:
                    self._edge(h, tgt, ci.mod, line)
        if "GL602" not in self.enabled:
            return
        held_names = ", ".join(self._fmt(h) for h in held)
        for cand in {nm, resolved}:
            if cand and any(
                cand.startswith(p) for p in _BLOCKING_PREFIXES
            ):
                emit(
                    "GL602", line,
                    f"blocking call {nm}() while holding {held_names}",
                )
                return
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr == "join" and not node.args:
            emit(
                "GL602", line,
                f".join() while holding {held_names}",
            )
            return
        if len(parts) == 3 and parts[0] == "self":
            attr, m = parts[1], parts[2]
            kwnames = {k.arg for k in node.keywords}
            # queue.get is non-blocking with block=False / get(False)
            nonblocking = any(
                kw.arg == "block" and isinstance(kw.value, ast.Constant)
                and kw.value.value is False
                for kw in node.keywords
            ) or (
                node.args and isinstance(node.args[0], ast.Constant)
                and node.args[0].value is False
            )
            if attr in ci.queues and m == "get" \
                    and "timeout" not in kwnames and len(node.args) < 2 \
                    and not nonblocking:
                emit(
                    "GL602", line,
                    f"self.{attr}.get() without timeout while holding "
                    f"{held_names}",
                )
            elif attr in ci.events and m == "wait" \
                    and not node.args and "timeout" not in kwnames:
                emit(
                    "GL602", line,
                    f"self.{attr}.wait() while holding {held_names}",
                )
            elif attr in ci.conds and m in ("wait", "wait_for"):
                others = [h for h in held if h != (ci.key, attr)]
                if others:
                    emit(
                        "GL602", line,
                        f"self.{attr}.{m}() releases only self.{attr} — "
                        "still holding "
                        + ", ".join(self._fmt(h) for h in others),
                    )


# -- GL301: serving lock discipline -------------------------------------


class _LockDisciplineChecker:
    """Per-class: find lock attributes created in __init__, then flag
    attribute mutations outside `with self.<lock>` when the attribute
    is shared across methods."""

    def __init__(self, mod: _Mod, enabled: Set[str], emit) -> None:
        self.mod = mod
        self.enabled = enabled
        self.emit = emit

    def run(self) -> None:
        if "GL301" not in self.enabled:
            return
        for node in ast.walk(self.mod.tree):
            if isinstance(node, ast.ClassDef):
                self._check_class(node)

    def _lock_attrs(self, cls: ast.ClassDef) -> Set[str]:
        locks: Set[str] = set()
        for node in ast.walk(cls):
            if not isinstance(node, ast.Assign):
                continue
            if not isinstance(node.value, ast.Call):
                continue
            vname = _dotted(node.value.func) or ""
            if vname.split(".")[-1] not in _LOCK_FACTORIES:
                continue
            for t in node.targets:
                if (
                    isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                ):
                    locks.add(t.attr)
        return locks

    @staticmethod
    def _self_attr(node: ast.AST) -> Optional[str]:
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"
        ):
            return node.attr
        return None

    def _check_class(self, cls: ast.ClassDef) -> None:
        locks = self._lock_attrs(cls)
        if not locks:
            return
        methods = [
            n for n in cls.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        # which methods touch which self attributes (read or write)
        touched_by: Dict[str, Set[str]] = {}
        writes: List[Tuple[str, ast.AST, int, bool]] = []
        for meth in methods:
            guarded_lines = self._guarded_lines(meth, locks)
            for node in ast.walk(meth):
                attr = None
                is_write = False
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        a = self._self_attr(t)
                        if a:
                            attr, is_write = a, True
                            break
                elif isinstance(node, ast.AugAssign):
                    a = self._self_attr(node.target)
                    if a:
                        attr, is_write = a, True
                elif isinstance(node, ast.Attribute):
                    attr = self._self_attr(node)
                if attr is None or attr in locks:
                    continue
                touched_by.setdefault(attr, set()).add(meth.name)
                if is_write and meth.name != "__init__":
                    writes.append((
                        attr, node, node.lineno,
                        node.lineno in guarded_lines,
                    ))
        for attr, _node, line, guarded in writes:
            if guarded:
                continue
            if len(touched_by.get(attr, ())) < 2:
                continue  # single-method private state: not shared
            lock_names = " / ".join(
                f"self.{name}" for name in sorted(locks)
            )
            self.emit(
                "GL301", line,
                f"`self.{attr}` mutated outside `with {lock_names}` in "
                f"{cls.name} (attribute is shared across "
                f"{len(touched_by[attr])} methods)",
            )

    def _guarded_lines(self, meth, locks: Set[str]) -> Set[int]:
        """Line numbers lexically inside `with self.<lock>:` blocks."""
        out: Set[int] = set()
        for node in ast.walk(meth):
            if not isinstance(node, ast.With):
                continue
            for item in node.items:
                a = self._self_attr(item.context_expr)
                if a in locks:
                    end = getattr(node, "end_lineno", node.lineno)
                    out.update(range(node.lineno, end + 1))
                    break
        return out


# -- driver -------------------------------------------------------------


def _iter_py_files(paths: Sequence[str]) -> List[Tuple[str, str, str]]:
    """(abspath, display_relpath, modname) for every .py under paths."""
    out = []
    for p in paths:
        p = os.path.abspath(p)
        if os.path.isfile(p) and p.endswith(".py"):
            # keep ONE parent component so directory-scoped rules
            # (GL301: serving/) apply identically when a file is
            # spot-linted (`graftlint pkg/serving/server.py`) — and
            # same-basename file args stay distinguishable
            parent = os.path.basename(os.path.dirname(p))
            rel = (
                os.path.join(parent, os.path.basename(p))
                if parent else os.path.basename(p)
            )
            out.append((p, rel, rel[:-3].replace(os.sep, ".")))
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = sorted(
                d for d in dirnames
                if d != "__pycache__" and not d.startswith(".")
            )
            for f in sorted(filenames):
                if not f.endswith(".py"):
                    continue
                full = os.path.join(dirpath, f)
                rel = os.path.relpath(full, os.path.dirname(p))
                out.append((full, rel, _modname_for(os.path.dirname(p), full)))
    return out


def _modname_for(root: str, path: str) -> str:
    rel = os.path.relpath(path, root)
    mod = rel[:-3].replace(os.sep, ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


@dataclass
class LintResult:
    findings: List[Finding]
    files_scanned: int
    jit_regions: int
    parse_errors: List[str] = field(default_factory=list)

    @property
    def active(self) -> List[Finding]:
        return [f for f in self.findings if not f.suppressed]

    @property
    def gating(self) -> List[Finding]:
        """Active findings that flip the exit code (warn-severity rules
        — GL503's VMEM estimate — are reported but never gate)."""
        return [f for f in self.active if f.severity == "error"]

    def as_dict(self) -> dict:
        return {
            "graftlint": 1,
            "files_scanned": self.files_scanned,
            "jit_regions": self.jit_regions,
            "parse_errors": list(self.parse_errors),
            "rules": sorted(RULES_BY_ID),
            "summary": {
                "total": len(self.findings),
                "active": len(self.active),
                "suppressed": len(self.findings) - len(self.active),
                "warnings": len(
                    [f for f in self.active if f.severity == "warning"]
                ),
            },
            "findings": [f.as_dict() for f in self.findings],
        }


_SARIF_SCHEMA = (
    "https://raw.githubusercontent.com/oasis-tcs/sarif-spec/master/"
    "Schemata/sarif-schema-2.1.0.json"
)


def to_sarif(result: LintResult) -> dict:
    """SARIF 2.1.0 document for CI annotation. Deterministic like the
    JSON report: rules sorted by id, results in finding order (already
    path/line/rule-sorted), suppressed findings carried with an
    ``inSource`` suppression instead of being dropped."""
    rules = [
        {
            "id": r.id,
            "name": r.name,
            "shortDescription": {"text": r.summary},
            "help": {"text": r.hint},
            "defaultConfiguration": {
                "level": "warning" if r.severity == "warning" else "error"
            },
        }
        for _id, r in sorted(RULES_BY_ID.items())
    ]
    results = []
    for f in result.findings:
        res = {
            "ruleId": f.rule,
            "level": "warning" if f.severity == "warning" else "error",
            "message": {"text": f"{f.message} (hint: {f.hint})"},
            "locations": [
                {
                    "physicalLocation": {
                        "artifactLocation": {
                            "uri": f.path.replace(os.sep, "/")
                        },
                        "region": {"startLine": f.line},
                    }
                }
            ],
        }
        if f.suppressed:
            res["suppressions"] = [{"kind": "inSource"}]
        results.append(res)
    for rel in result.parse_errors:
        results.append({
            "ruleId": "GL000",
            "level": "error",
            "message": {
                "text": "parse error — file silently exempt from every "
                        "rule"
            },
            "locations": [
                {
                    "physicalLocation": {
                        "artifactLocation": {
                            "uri": rel.replace(os.sep, "/")
                        },
                        "region": {"startLine": 1},
                    }
                }
            ],
        })
    return {
        "$schema": _SARIF_SCHEMA,
        "version": "2.1.0",
        "runs": [
            {
                "tool": {
                    "driver": {
                        "name": "graftlint",
                        # informationUri must be an ABSOLUTE URI per the
                        # SARIF schema; the repo doc lives in the help
                        # text instead
                        "fullDescription": {
                            "text": "JAX hazard linter — rule catalog "
                                    "and suppression syntax: ANALYSIS.md"
                        },
                        "rules": rules,
                    }
                },
                "results": results,
            }
        ],
    }


def lint_paths(
    paths: Sequence[str],
    rules: Optional[Sequence[str]] = None,
    *,
    files: Optional[Sequence[Tuple[str, str, str]]] = None,
    vmem_budget_mib: float = DEFAULT_VMEM_BUDGET_MIB,
) -> LintResult:
    """Lint every .py file under ``paths``; returns all findings
    (suppressed ones flagged, not dropped — the JSON output shows
    them so a suppression is an auditable decision, not a deletion).

    ``files`` (pre-enumerated ``_iter_py_files`` tuples) skips the
    directory walk — the CLI already walked each path for its
    empty-path guard and must not do the I/O twice.
    ``vmem_budget_mib`` parameterizes GL503's footprint estimate."""
    enabled: Set[str] = (
        {resolve_rule_token(r) for r in rules}
        if rules else set(RULES_BY_ID)
    )
    files = list(files) if files is not None else _iter_py_files(paths)
    mods: Dict[str, _Mod] = {}
    parse_errors: List[str] = []
    for full, rel, modname in files:
        m = _load_module(full, rel, modname)
        if m is not None:
            # same-basename spot-lint args must BOTH be scanned, not
            # last-writer-wins (an order-dependent silent lint gap);
            # disambiguated keys make cross-module resolution of the
            # colliding name ambiguous, which _find_module treats as
            # unresolvable — safe under-approximation
            key, i = modname, 2
            while key in mods:
                key, i = f"{modname}#{i}", i + 1
            m.modname = key
            mods[key] = m
        else:
            # an unparseable file would otherwise be SILENTLY exempt
            # from every rule — surface it (callers decide severity)
            parse_errors.append(rel)

    _mark_roots(mods)
    graph = _build_graph(mods)

    # Pallas kernel regions: each kernel/index_map plus every function
    # nested inside it (pl.when bodies execute within the kernel) plus
    # everything they call. A function reached ONLY through kernels
    # reports impure calls as GL504; one also reachable from ordinary
    # tracing roots keeps GL103.
    kernel_keys: Set[Tuple[str, str]] = set()
    kernel_enclosing: List[Tuple[_Func, Optional[_Func]]] = []
    seen_kernels: Set[Tuple[str, str]] = set()
    for (kf, enc) in graph.kernel_seeds:
        if kf.key not in seen_kernels:
            seen_kernels.add(kf.key)
            kernel_enclosing.append((kf, enc))
        kernel_keys.add(kf.key)
    for m in mods.values():
        for f in m.funcs:  # pre-order: parents precede children
            cur = f.parent
            while cur is not None:
                if cur.key in kernel_keys:
                    kernel_keys.add(f.key)
                    break
                cur = cur.parent

    kernelish = _closure(kernel_keys, graph.edges)
    root_keys = {
        f.key for m in mods.values() for f in m.funcs
        if f.is_root and f.key not in kernel_keys
    }
    # regular jit reachability STOPS at kernels: a jitted caller of a
    # pallas_call reaches the kernel, but the kernel (and helpers only
    # it calls) stay kernel regions — impure calls there are GL504,
    # not GL103, no matter where the call site sits
    regular = _closure(root_keys, graph.edges, stop=kernel_keys)
    regions = regular | kernelish
    kernel_only = kernelish - (regular - kernel_keys)
    envs = _env_closure(graph.binder_axes, graph.edges)
    arms = _closure(graph.arm_seeds, graph.edges)

    findings: List[Finding] = []

    def make_emit(mod: _Mod):
        def emit(rule: str, line: int, message: str) -> None:
            r = RULES_BY_ID[rule]
            # a suppression may sit on the reported line or anywhere in
            # the enclosing statement (multi-line calls)
            lines = _statement_lines(mod, line)
            findings.append(Finding(
                path=mod.relpath, line=line, rule=rule,
                message=message, hint=r.hint,
                suppressed=mod.suppressions.covers(rule, lines),
                severity=r.severity,
            ))
        return emit

    stmt_cache: Dict[str, List[Tuple[int, int]]] = {}

    def _statement_lines(mod: _Mod, line: int) -> List[int]:
        # keyed by ABSOLUTE path: two same-basename file args share a
        # display relpath (serving/x.py) but must not share spans, or
        # one file's suppression coverage silently applies the other's
        # statement extents
        spans = stmt_cache.get(mod.path)
        if spans is None:
            spans = []
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.stmt):
                    spans.append(
                        (node.lineno, getattr(node, "end_lineno", node.lineno))
                    )
            stmt_cache[mod.path] = spans
        best: Optional[Tuple[int, int]] = None
        for lo, hi in spans:
            if lo <= line <= hi and (
                best is None or (hi - lo) < (best[1] - best[0])
            ):
                best = (lo, hi)
        if best is None:
            return [line]
        return list(range(best[0], best[1] + 1))

    emit_by: Dict[int, object] = {}

    def emit_for(mod: _Mod):
        e = emit_by.get(id(mod))
        if e is None:
            e = make_emit(mod)
            emit_by[id(mod)] = e
        return e

    for mod in mods.values():
        emit = emit_for(mod)
        for fn in mod.funcs:
            if fn.key in regions:
                _JitRegionChecker(
                    fn, enabled, emit, kernel=fn.key in kernel_only
                ).visit(fn.node)
            else:
                _StepLoopChecker(fn, enabled, emit).visit(fn.node)
            _CollectiveChecker(
                fn, enabled, emit, envs.get(fn.key), fn.key in arms
            ).visit(fn.node)
        _DonateChecker(mod, enabled, emit).visit(mod.tree)
        # membership keyed on the lint-root-RELATIVE path (file args
        # keep one parent component, so spot-linting serving/server.py
        # still applies the rule) — never the absolute path, which
        # would drag a whole checkout under /home/serving/... into the
        # serving-only rules
        if "serving" in mod.relpath.split(os.sep):
            _LockDisciplineChecker(mod, enabled, emit).run()

    for site in graph.pallas_sites:
        _check_pallas_site(
            site, enabled, emit_for(site.mod), vmem_budget_mib
        )
    for (kf, enc) in kernel_enclosing:
        _check_kernel_closures(kf, enc, enabled, emit_for(kf.module))
    _ConcurrencyChecker(mods, enabled, emit_for).run()

    findings.sort(key=lambda f: (f.path, f.line, f.rule, f.message))
    return LintResult(
        findings=findings, files_scanned=len(mods),
        jit_regions=len(regions), parse_errors=sorted(parse_errors),
    )
