"""Trace-safety AST linter: JAX hazards inside jitted/traced code paths.

Pure-stdlib (ast + re): the linter code itself never touches jax.
(Reaching it through `lightgbm_tpu.analysis` still imports the parent
package, which does import jax — load this file directly, e.g. via
importlib from its path, for a truly jax-free environment.)

The analysis has three layers:

1. **Traced-scope discovery.** A function is *traced* when it is
   jit-decorated (`@jax.jit`, `@partial(jax.jit, ...)`), passed to a
   tracing combinator (`jax.jit(f)`, `lax.while_loop`, `lax.scan`,
   `lax.cond`/`switch`, `jax.vmap`, `shard_map`, `pl.pallas_call`,
   `jax.grad`, ...), nested inside a traced function, or reachable
   from a traced function through the package call graph (a traced
   caller makes its callees traced — `boosting.step` reaches the
   whole learner). Cross-module edges resolve through `from .x import
   f` style imports and `self.method` calls.

2. **Device-value taint.** Within a traced function, parameters are
   tracers unless the jit decorator marks them static
   (`static_argnames`) or their annotation is a plainly-host type;
   results of `jnp.*`/`lax.*`/`jax.random.*` calls are device values;
   taint propagates through arithmetic, indexing, tuple packing and
   helper calls. `.shape`/`.ndim`/`.dtype`/`len()` and `is`/`is not`
   comparisons are static and STOP taint — `if x is None` or
   `if a.ndim == 1` never fires a rule.

3. **Rules** (table below) fire on hazardous uses of tainted values.
   Intentional sites carry a suppression comment on the flagged line
   (or the line above):  `# lint: allow[rule-id]` — or file-wide in
   the first 10 lines:   `# lint: allow-file[rule-id]`.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, NamedTuple, Optional, Sequence, Set, Tuple


class Rule(NamedTuple):
    id: str
    summary: str


RULES: Dict[str, Rule] = {}


def _register(rule_id: str, summary: str) -> str:
    RULES[rule_id] = Rule(rule_id, summary)
    return rule_id


TRACER_CAST = _register(
    "tracer-cast",
    "float()/int()/bool() applied to a traced device value (forces a "
    "host sync / ConcretizationTypeError inside jit)",
)
NP_ON_TRACER = _register(
    "np-on-tracer",
    "numpy function applied to a traced device value (silently "
    "materializes the tracer or raises at trace time)",
)
TRACER_BRANCH = _register(
    "tracer-branch",
    "Python control flow (if/while/and/or/assert/ternary) on a traced "
    "device value — use lax.cond/jnp.where, or hoist the decision to "
    "trace time",
)
HOST_SYNC = _register(
    "host-sync",
    ".item()/.tolist()/block_until_ready()/device_get on a device "
    "value in traced or hot-loop code (a device->host round trip "
    "that stalls the dispatch pipeline)",
)
MUTABLE_DEFAULT = _register(
    "mutable-default",
    "mutable default argument — shared across calls, and a stale-state "
    "hazard when the function is traced more than once",
)
DEVICE_CLOSURE = _register(
    "device-closure",
    "jitted function closes over a device array — the value is baked "
    "into the compiled executable as a constant (stale across cache "
    "reuse, and bloats the serialized executable)",
)


class Finding(NamedTuple):
    rule: str
    path: str
    line: int
    col: int
    message: str
    suppressed: bool

    def format(self) -> str:
        sup = "  [suppressed]" if self.suppressed else ""
        return f"{self.path}:{self.line}:{self.col}: {self.rule}: " \
               f"{self.message}{sup}"


# attribute reads that yield STATIC (host) values even on a tracer
_STATIC_ATTRS = {
    "shape", "ndim", "dtype", "size", "sharding", "aval", "weak_type",
    "itemsize", "nbytes",
}
# method calls on a tracer that return device values (keep taint)
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
# parameter names that are static by package convention (specs/configs
# carried through traced helpers without annotations)
_STATIC_PARAM_NAMES = {
    "self", "cls", "spec", "config", "cfg", "axis_name", "ax",
    "num_slots", "num_bins", "num_out", "min_cap", "n_ranks",
}
# annotations that mark a parameter as a host value
_HOST_ANNOTATIONS = {
    "int", "str", "bool", "float", "bytes", "GrowerSpec", "Config",
    "BinnedDataset", "Mesh", "tuple", "Tuple", "dict", "Dict", "list",
    "List", "Path", "Callable", "type",
}
# jax combinators whose function-valued arguments become traced scopes;
# value = indices of function-valued positional args ("*" = all)
_TRACING_COMBINATORS = {
    "jit": (0,), "vmap": (0,), "pmap": (0,), "grad": (0,),
    "value_and_grad": (0,), "checkpoint": (0,), "remat": (0,),
    "custom_jvp": (0,), "custom_vjp": (0,), "named_call": (0,),
    "while_loop": (0, 1), "fori_loop": (2,), "scan": (0,),
    "cond": (1, 2, 3), "switch": "*", "associative_scan": (0,),
    "shard_map": (0,), "pallas_call": (0,), "map": (0,),
}

_ALLOW_RE = re.compile(r"#\s*lint:\s*allow\[([a-zA-Z0-9_,\- ]+)\]")
_ALLOW_FILE_RE = re.compile(r"#\s*lint:\s*allow-file\[([a-zA-Z0-9_,\- ]+)\]")


def scan_allow_comments(src: str) -> Tuple[Dict[int, Set[str]], Set[str]]:
    """`# lint: allow[rule]` / `# lint: allow-file[rule]` markers of a
    source text: ({line: rule ids}, file-wide rule ids). Shared by this
    linter and concurrency_lint.py so suppression syntax stays ONE
    thing."""
    allow_lines: Dict[int, Set[str]] = {}
    allow_file: Set[str] = set()
    for i, line in enumerate(src.splitlines(), start=1):
        m = _ALLOW_RE.search(line)
        if m:
            allow_lines[i] = {
                r.strip() for r in m.group(1).split(",") if r.strip()
            }
        if i <= 10:
            m = _ALLOW_FILE_RE.search(line)
            if m:
                allow_file |= {
                    r.strip() for r in m.group(1).split(",") if r.strip()
                }
    return allow_lines, allow_file


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _ModuleInfo:
    """Per-module symbol tables feeding the cross-module call graph."""

    def __init__(self, name: str, tree: ast.Module, src: str, path: str,
                 is_package: bool = False):
        self.name = name  # dotted module name inside the package
        # True for package __init__ modules: their dotted name has no
        # trailing module segment, so relative imports resolve one
        # level differently (from .x import f in pkg/__init__.py means
        # pkg.x, not pkg's parent .x)
        self.is_package = is_package
        self.tree = tree
        self.path = path
        self.lines = src.splitlines()
        # alias -> canonical root ("np", "jnp", "lax", "jax", "partial",
        # "shard_map", "pl", ...)
        self.aliases: Dict[str, str] = {}
        # imported function name -> (module, name) — cross-module edges
        self.imports: Dict[str, Tuple[str, str]] = {}
        # qualname -> ast.FunctionDef for every def in the module
        self.functions: Dict[str, ast.AST] = {}
        # class name -> {method name -> qualname}
        self.classes: Dict[str, Dict[str, str]] = {}
        # NamedTuple-ish classes holding jax.Array fields
        self.device_containers: Set[str] = set()
        self.allow_lines: Dict[int, Set[str]] = {}
        self.allow_file: Set[str] = set()
        self._scan_comments(src)
        self._scan_top(tree)

    def _scan_comments(self, src: str) -> None:
        self.allow_lines, self.allow_file = scan_allow_comments(src)

    def _scan_top(self, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    root = a.name.split(".")[0]
                    alias = a.asname or root
                    if a.name in ("jax.numpy",):
                        self.aliases[alias] = "jnp"
                    elif root == "numpy":
                        self.aliases[alias] = "np"
                    elif root == "jax":
                        self.aliases[alias] = "jax"
                    elif root == "functools":
                        self.aliases[alias] = "functools"
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                for a in node.names:
                    alias = a.asname or a.name
                    if mod == "jax" and a.name == "numpy":
                        self.aliases[alias] = "jnp"
                    elif mod == "jax" and a.name == "lax":
                        self.aliases[alias] = "lax"
                    elif mod == "jax" and a.name == "jit":
                        self.aliases[alias] = "jit"
                    elif mod == "functools" and a.name == "partial":
                        self.aliases[alias] = "partial"
                    elif mod.endswith("shard_map") and a.name == "shard_map":
                        self.aliases[alias] = "shard_map"
                    elif mod == "jax.experimental" and a.name == "pallas":
                        self.aliases[alias] = "pl"
                    elif a.name == "numpy":
                        self.aliases[alias] = "np"
                    elif node.level > 0 or mod.startswith("lightgbm_tpu"):
                        # package-relative import: record the edge target
                        self.imports[alias] = (self._resolve_rel(node), a.name)

    def _resolve_rel(self, node: ast.ImportFrom) -> str:
        """Absolute dotted module for a relative import. For a package
        __init__ the stripped '.__init__' segment counts as the level-1
        hop, so `from .x import f` stays inside the package."""
        mod = node.module or ""
        if node.level == 0:
            return mod
        parts = self.name.split(".")
        drop = node.level - (1 if self.is_package else 0)
        base = parts[: len(parts) - drop] if drop > 0 else parts
        return ".".join(base + ([mod] if mod else []))

    def root_of(self, node: ast.AST) -> Optional[str]:
        """Canonical root ('jnp', 'np', 'lax', 'jax', ...) of a dotted
        expression, through import aliases."""
        d = _dotted(node)
        if d is None:
            return None
        head = d.split(".")[0]
        canon = self.aliases.get(head)
        if canon == "jax" and d.startswith((f"{head}.numpy",)):
            return "jnp"
        return canon if canon is not None else None


class _FnInfo(NamedTuple):
    module: str
    qualname: str
    node: ast.AST  # FunctionDef | AsyncFunctionDef | Lambda
    cls: Optional[str]
    static_params: Tuple[str, ...]  # from jit static_argnames/nums


def _is_namedtuple_class(node: ast.ClassDef) -> bool:
    for b in node.bases:
        d = _dotted(b) or ""
        if d.split(".")[-1] == "NamedTuple":
            return True
    return False


def _ann_mentions_array(ann: ast.AST) -> bool:
    return "Array" in ast.unparse(ann) if ann is not None else False


class _Linter:
    """Package-wide analysis over a set of parsed modules."""

    def __init__(self, modules: Dict[str, _ModuleInfo]):
        self.modules = modules
        self.findings: List[Finding] = []
        # (module, qualname) -> _FnInfo
        self.fns: Dict[Tuple[str, str], _FnInfo] = {}
        self.traced: Set[Tuple[str, str]] = set()
        self.device_containers: Set[str] = set()
        for mi in modules.values():
            self._collect_fns(mi)
        self.device_containers |= {
            c for mi in modules.values() for c in mi.device_containers
        }

    # ------------------------------------------------------------------
    # collection
    def _collect_fns(self, mi: _ModuleInfo) -> None:
        def visit(node: ast.AST, prefix: str, cls: Optional[str]):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qn = f"{prefix}{child.name}"
                    mi.functions[qn] = child
                    static = self._jit_static_params(mi, child)
                    self.fns[(mi.name, qn)] = _FnInfo(
                        mi.name, qn, child, cls, static
                    )
                    if cls is not None:
                        mi.classes.setdefault(cls, {})[child.name] = qn
                    visit(child, qn + ".", cls)
                elif isinstance(child, ast.ClassDef):
                    if _is_namedtuple_class(child):
                        has_arr = any(
                            isinstance(s, ast.AnnAssign)
                            and _ann_mentions_array(s.annotation)
                            for s in child.body
                        )
                        if has_arr:
                            mi.device_containers.add(child.name)
                    visit(child, child.name + ".", child.name)

        visit(mi.tree, "", None)

    def _jit_decorators(self, mi: _ModuleInfo, fn: ast.AST) -> List[ast.AST]:
        out = []
        for dec in getattr(fn, "decorator_list", []):
            if self._is_jit_expr(mi, dec):
                out.append(dec)
        return out

    def _is_jit_expr(self, mi: _ModuleInfo, node: ast.AST) -> bool:
        """node is jax.jit / jit / partial(jax.jit, ...) / jax.jit(...)"""
        d = _dotted(node)
        if d is not None:
            root = mi.aliases.get(d.split(".")[0])
            return (root == "jit") or (root == "jax" and d.endswith(".jit"))
        if isinstance(node, ast.Call):
            fd = _dotted(node.func)
            if fd is not None:
                root = mi.aliases.get(fd.split(".")[0])
                if root == "partial" or fd.endswith("partial"):
                    return bool(node.args) and self._is_jit_expr(
                        mi, node.args[0]
                    )
                return self._is_jit_expr(mi, node.func)
        return False

    def _jit_static_params(self, mi: _ModuleInfo, fn: ast.AST) -> Tuple[str, ...]:
        """static_argnames/static_argnums named by a jit decorator."""
        names: List[str] = []
        for dec in self._jit_decorators(mi, fn):
            if not isinstance(dec, ast.Call):
                continue
            for kw in dec.keywords:
                if kw.arg == "static_argnames":
                    for c in ast.walk(kw.value):
                        if isinstance(c, ast.Constant) and isinstance(c.value, str):
                            names.append(c.value)
                elif kw.arg == "static_argnums":
                    idxs = [
                        c.value for c in ast.walk(kw.value)
                        if isinstance(c, ast.Constant)
                        and isinstance(c.value, int)
                    ]
                    params = [a.arg for a in fn.args.args]
                    for i in idxs:
                        if 0 <= i < len(params):
                            names.append(params[i])
        return tuple(names)

    # ------------------------------------------------------------------
    # traced-scope discovery
    def discover_traced(self) -> None:
        roots: Set[Tuple[str, str]] = set()
        for (mod, qn), fi in self.fns.items():
            mi = self.modules[mod]
            if self._jit_decorators(mi, fi.node):
                roots.add((mod, qn))
        # functions passed to tracing combinators anywhere in each module
        for mi in self.modules.values():
            for call in ast.walk(mi.tree):
                if not isinstance(call, ast.Call):
                    continue
                tgt = self._combinator_slots(mi, call)
                if tgt is None:
                    continue
                slots = range(len(call.args)) if tgt == "*" else tgt
                for i in slots:
                    if i >= len(call.args):
                        continue
                    for ref in self._fn_refs(mi, call.args[i]):
                        roots.add(ref)
        # propagate caller -> callee and outer -> nested to fixpoint
        traced = set(roots)
        changed = True
        while changed:
            changed = False
            for key in list(traced):
                fi = self.fns.get(key)
                if fi is None:
                    continue
                for callee in self._callees(fi):
                    if callee in self.fns and callee not in traced:
                        traced.add(callee)
                        changed = True
                for (mod, qn) in self.fns:
                    if mod == key[0] and qn.startswith(key[1] + ".") \
                            and (mod, qn) not in traced:
                        traced.add((mod, qn))
                        changed = True
        self.traced = traced

    def _combinator_slots(self, mi: _ModuleInfo, call: ast.Call):
        d = _dotted(call.func)
        if d is None:
            return None
        head, leaf = d.split(".")[0], d.split(".")[-1]
        root = mi.aliases.get(head)
        if leaf in _TRACING_COMBINATORS and (
            root in ("jax", "lax", "jit", "shard_map", "pl")
            or head == leaf  # direct `from x import while_loop` style
        ):
            # plain builtins named `map` must not count
            if leaf == "map" and root != "lax":
                return None
            return _TRACING_COMBINATORS[leaf]
        return None

    def _fn_refs(self, mi: _ModuleInfo, node: ast.AST):
        """(module, qualname) candidates a function-valued expression
        refers to — names, lists of names, partial(name, ...)."""
        out: List[Tuple[str, str]] = []
        for n in ast.walk(node):
            if isinstance(n, ast.Name):
                if n.id in mi.functions:
                    out.append((mi.name, n.id))
                elif n.id in mi.imports:
                    out.append(mi.imports[n.id])
                else:
                    # nested defs: qualname suffix match in this module
                    for qn in mi.functions:
                        if qn.split(".")[-1] == n.id:
                            out.append((mi.name, qn))
            elif isinstance(n, ast.Attribute) and isinstance(n.value, ast.Name):
                if n.value.id == "self":
                    for cls, meths in mi.classes.items():
                        if n.attr in meths:
                            out.append((mi.name, meths[n.attr]))
        return out

    def _callees(self, fi: _FnInfo):
        mi = self.modules[fi.module]
        out: Set[Tuple[str, str]] = set()
        for n in ast.walk(fi.node):
            if not isinstance(n, ast.Call):
                continue
            f = n.func
            if isinstance(f, ast.Name):
                if f.id in mi.imports:
                    out.add(mi.imports[f.id])
                elif f.id in mi.functions:
                    out.add((mi.name, f.id))
                else:
                    for qn in mi.functions:  # nested / sibling defs
                        if qn.split(".")[-1] == f.id:
                            out.add((mi.name, qn))
            elif isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
                if f.value.id == "self" and fi.cls is not None:
                    meths = mi.classes.get(fi.cls, {})
                    if f.attr in meths:
                        out.add((mi.name, meths[f.attr]))
                elif f.value.id in mi.imports:
                    # module-object import: from . import histogram
                    out.add((mi.imports[f.value.id][0] + "."
                             + mi.imports[f.value.id][1], f.attr))
        return out

    # ------------------------------------------------------------------
    # rules
    def run(self) -> List[Finding]:
        self.discover_traced()
        for mi in self.modules.values():
            module_env: Set[str] = set()
            self._scan_mutable_defaults(mi)
            # module-level device constants (rare; seed closure taint)
            for stmt in mi.tree.body:
                if isinstance(stmt, ast.Assign):
                    if self._expr_tainted(mi, stmt.value, module_env):
                        for t in stmt.targets:
                            module_env |= self._target_names(t)
            for (mod, qn), fi in sorted(self.fns.items()):
                if mod != mi.name:
                    continue
                # only analyze top-level-of-their-nesting functions here;
                # nested defs are analyzed inline with the parent env
                if "." in qn and self._parent_is_fn(mi, qn):
                    continue
                self._analyze_fn(mi, fi, dict.fromkeys(module_env, True))
        self.findings.sort(key=lambda f: (f.path, f.line, f.col))
        return self.findings

    def _parent_is_fn(self, mi: _ModuleInfo, qn: str) -> bool:
        parent = qn.rsplit(".", 1)[0]
        return parent in mi.functions

    def _scan_mutable_defaults(self, mi: _ModuleInfo) -> None:
        for qn, fn in mi.functions.items():
            for d in list(getattr(fn.args, "defaults", [])) + [
                k for k in getattr(fn.args, "kw_defaults", []) if k
            ]:
                if isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                    isinstance(d, ast.Call)
                    and isinstance(d.func, ast.Name)
                    and d.func.id in ("list", "dict", "set")
                ):
                    self._emit(mi, MUTABLE_DEFAULT, d,
                               f"function {qn!r} has a mutable default")

    # ---- taint -------------------------------------------------------
    def _param_tainted(self, fi: _FnInfo, arg: ast.arg,
                       has_literal_default: bool) -> bool:
        if arg.arg in _STATIC_PARAM_NAMES or arg.arg in fi.static_params:
            return False
        ann = arg.annotation
        if ann is not None:
            txt = ast.unparse(ann)
            leaf = txt.split("[")[0].split(".")[-1]
            if _ann_mentions_array(ann) or leaf in self.device_containers \
                    or leaf in ("SplitParams", "SplitRecord", "TreeArrays"):
                return True
            # any other annotation (QueryLayout, BundleInfo, ...) is a
            # named host type: the package convention is that tracer
            # params are annotated `jax.Array` or a device container
            return False
        # unannotated: literal defaults are static flags by convention
        return not has_literal_default

    def _seed_params(self, fi: _FnInfo, env: Dict[str, bool]) -> None:
        a = fi.node.args
        pos = list(a.posonlyargs) + list(a.args)
        n_def = len(a.defaults)
        for i, arg in enumerate(pos):
            has_def = i >= len(pos) - n_def
            d = a.defaults[i - (len(pos) - n_def)] if has_def else None
            lit = isinstance(d, ast.Constant)
            env[arg.arg] = self._param_tainted(fi, arg, lit)
        for arg, d in zip(a.kwonlyargs, a.kw_defaults):
            env[arg.arg] = self._param_tainted(
                fi, arg, isinstance(d, ast.Constant)
            )
        if a.vararg is not None:
            env[a.vararg.arg] = True
        if a.kwarg is not None:
            env[a.kwarg.arg] = True

    def _target_names(self, t: ast.AST) -> Set[str]:
        """Names BOUND by an assignment target: `self.x = v` binds no
        name (it mutates self), `a, (b, *c) = v` binds a, b, c."""
        out: Set[str] = set()
        stack = [t]
        while stack:
            n = stack.pop()
            if isinstance(n, ast.Name):
                out.add(n.id)
            elif isinstance(n, (ast.Tuple, ast.List)):
                stack.extend(n.elts)
            elif isinstance(n, ast.Starred):
                stack.append(n.value)
        return out

    def _expr_tainted(self, mi: _ModuleInfo, node: ast.AST,
                      env, record=None, traced: bool = False) -> bool:
        """Taint of an expression; `record` (a list) collects rule hits
        as (rule, node, message) while evaluating — only when inside a
        traced scope."""
        tainted = set(k for k, v in env.items() if v) \
            if isinstance(env, dict) else set(env)

        def is_t(n: ast.AST) -> bool:
            if n is None:
                return False
            if isinstance(n, ast.Name):
                return n.id in tainted
            if isinstance(n, ast.Attribute):
                if n.attr in _STATIC_ATTRS:
                    return False
                return is_t(n.value)
            if isinstance(n, ast.Subscript):
                return is_t(n.value) or is_t(n.slice)
            if isinstance(n, ast.Call):
                return self._call_tainted(mi, n, is_t, record, traced)
            if isinstance(n, ast.BinOp):
                return is_t(n.left) or is_t(n.right)
            if isinstance(n, ast.UnaryOp):
                if isinstance(n.op, ast.Not) and is_t(n.operand):
                    if record is not None and traced:
                        record.append((TRACER_BRANCH, n,
                                       "`not` on a device value calls "
                                       "__bool__ on a tracer"))
                return is_t(n.operand)
            if isinstance(n, ast.Compare):
                if all(isinstance(op, (ast.Is, ast.IsNot))
                       for op in n.ops):
                    return False  # identity checks are host-static
                return is_t(n.left) or any(is_t(c) for c in n.comparators)
            if isinstance(n, ast.BoolOp):
                hit = [v for v in n.values[:-1] if is_t(v)]
                if hit and record is not None and traced:
                    record.append((TRACER_BRANCH, hit[0],
                                   "and/or short-circuits on a device "
                                   "value (implicit __bool__); use & | "
                                   "or jnp.logical_*"))
                return any(is_t(v) for v in n.values)
            if isinstance(n, ast.IfExp):
                if is_t(n.test) and record is not None and traced:
                    record.append((TRACER_BRANCH, n.test,
                                   "ternary condition is a device value; "
                                   "use jnp.where / lax.cond"))
                return is_t(n.body) or is_t(n.orelse) or is_t(n.test)
            if isinstance(n, (ast.Tuple, ast.List, ast.Set)):
                return any(is_t(e) for e in n.elts)
            if isinstance(n, ast.Dict):
                return any(is_t(v) for v in n.values if v is not None)
            if isinstance(n, ast.Starred):
                return is_t(n.value)
            if isinstance(n, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
                return any(is_t(g.iter) for g in n.generators) \
                    or is_t(n.elt)
            if isinstance(n, ast.DictComp):
                return any(is_t(g.iter) for g in n.generators) \
                    or is_t(n.key) or is_t(n.value)
            if isinstance(n, ast.NamedExpr):
                return is_t(n.value)
            return False

        return is_t(node)

    def _call_tainted(self, mi: _ModuleInfo, n: ast.Call, is_t,
                      record, traced: bool) -> bool:
        args_tainted = any(is_t(a) for a in n.args) or any(
            is_t(k.value) for k in n.keywords
        )
        fd = _dotted(n.func)
        root = mi.root_of(n.func) if fd else None
        leaf = fd.split(".")[-1] if fd else None
        # device producers
        if root in ("jnp", "lax"):
            return True
        if root == "jax" and fd is not None and (
            ".random." in fd or ".nn." in fd
            or leaf in ("device_put", "fold_in")
        ):
            return True
        if root == "jax" and leaf in ("device_get",):
            if traced and args_tainted and record is not None:
                record.append((HOST_SYNC, n,
                               "jax.device_get inside traced code"))
            return False
        # casts
        if isinstance(n.func, ast.Name) and n.func.id in ("float", "int",
                                                          "bool", "complex"):
            if args_tainted:
                if traced and record is not None:
                    record.append((TRACER_CAST, n,
                                   f"{n.func.id}() on a device value"))
                return False
            return False
        if isinstance(n.func, ast.Name) and n.func.id in (
            "len", "isinstance", "hasattr", "getattr", "range", "print",
            "repr", "str", "type", "id",
        ):
            return False
        # numpy on tracers
        if root == "np":
            if args_tainted:
                if traced and record is not None:
                    record.append((NP_ON_TRACER, n,
                                   f"{fd}(...) applied to a device value"))
                return False
            return False
        # method calls on device values
        if isinstance(n.func, ast.Attribute):
            meth = n.func.attr
            recv_t = is_t(n.func.value)
            if meth in _SYNC_METHODS and (recv_t or traced):
                if record is not None and (traced or recv_t):
                    record.append((HOST_SYNC, n,
                                   f".{meth}() forces a device->host sync"))
                return False
            if recv_t:
                return True  # .astype/.sum/.reshape/... keep taint
        # everything else: taint-through on arguments
        return args_tainted

    # ---- per-function analysis --------------------------------------
    def _analyze_fn(self, mi: _ModuleInfo, fi: _FnInfo,
                    outer_env: Dict[str, bool]) -> None:
        traced = (fi.module, fi.qualname) in self.traced
        env: Dict[str, bool] = dict(outer_env)
        if traced:
            self._seed_params(fi, env)
        else:
            for a in list(fi.node.args.args) + list(fi.node.args.kwonlyargs):
                env[a.arg] = False
        body = list(fi.node.body)
        # fixpoint over assignments (loops may use later-assigned names)
        for _ in range(4):
            before = dict(env)
            self._collect_assign_taint(mi, fi, body, env, traced)
            if env == before:
                break
        # now walk statements firing rules
        self._walk_stmts(mi, fi, body, env, traced)
        # immediate nested defs analyzed with this env (they recurse)
        for n in self._walk_scope(fi.node):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qn = self._find_qn(mi, n)
                if qn is None:
                    continue
                sub = self.fns[(mi.name, qn)]
                self._analyze_fn(mi, sub, env)
        self._check_device_closures(mi, fi, env)

    def _find_qn(self, mi: _ModuleInfo, node: ast.AST) -> Optional[str]:
        for qn, f in mi.functions.items():
            if f is node:
                return qn
        return None

    @staticmethod
    def _walk_scope(fn_node: ast.AST):
        """ast.walk that does NOT descend into nested function/class
        scopes (their assignments must not leak into this scope)."""
        stack = list(ast.iter_child_nodes(fn_node))
        while stack:
            n = stack.pop()
            yield n
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
                continue
            stack.extend(ast.iter_child_nodes(n))

    def _collect_assign_taint(self, mi, fi, body, env, traced) -> None:
        fn_node = fi.node
        for n in self._walk_scope(fn_node):
            if isinstance(n, ast.Assign):
                t = self._expr_tainted(mi, n.value, env, None, traced)
                for tgt in n.targets:
                    self._assign_target(mi, tgt, n.value, t, env, traced)
            elif isinstance(n, ast.AnnAssign) and n.value is not None:
                t = self._expr_tainted(mi, n.value, env, None, traced)
                for name in self._target_names(n.target):
                    env[name] = env.get(name, False) or t
            elif isinstance(n, ast.AugAssign):
                t = self._expr_tainted(mi, n.value, env, None, traced)
                for name in self._target_names(n.target):
                    env[name] = env.get(name, False) or t
            elif isinstance(n, ast.For):
                t = self._expr_tainted(mi, n.iter, env, None, traced)
                for name in self._target_names(n.target):
                    env[name] = env.get(name, False) or t
            elif isinstance(n, ast.NamedExpr):
                t = self._expr_tainted(mi, n.value, env, None, traced)
                for name in self._target_names(n.target):
                    env[name] = env.get(name, False) or t
            elif isinstance(n, ast.withitem) and n.optional_vars is not None:
                for name in self._target_names(n.optional_vars):
                    env.setdefault(name, False)

    def _assign_target(self, mi, tgt, value, tainted, env, traced) -> None:
        """Tuple-unpack aware: `G, N = x.shape` stays host-static."""
        if isinstance(tgt, ast.Tuple) and isinstance(value, ast.Attribute) \
                and value.attr in _STATIC_ATTRS:
            for name in self._target_names(tgt):
                env[name] = env.get(name, False)
            return
        for name in self._target_names(tgt):
            env[name] = env.get(name, False) or tainted

    def _walk_stmts(self, mi, fi, body, env, traced) -> None:
        fn_node = fi.node

        def fire(hits):
            for rule, node, msg in hits:
                self._emit(mi, rule, node, msg)

        for n in self._walk_scope(fn_node):
            if not traced:
                continue
            hits: List[tuple] = []
            if isinstance(n, (ast.If, ast.While)):
                if self._expr_tainted(mi, n.test, env, hits, traced):
                    hits.append((
                        TRACER_BRANCH, n.test,
                        "Python branch on a device value; use jnp.where/"
                        "lax.cond or hoist to trace time",
                    ))
            elif isinstance(n, ast.Assert):
                if self._expr_tainted(mi, n.test, env, hits, traced):
                    hits.append((TRACER_BRANCH, n.test,
                                 "assert on a device value"))
            elif isinstance(n, (ast.Assign, ast.AugAssign, ast.Return,
                                ast.Expr, ast.AnnAssign)):
                val = getattr(n, "value", None)
                if val is not None:
                    self._expr_tainted(mi, val, env, hits, traced)
            # dedupe by (rule, line, col)
            seen = set()
            uniq = []
            for h in hits:
                k = (h[0], h[1].lineno, h[1].col_offset)
                if k not in seen:
                    seen.add(k)
                    uniq.append(h)
            fire(uniq)

    def _check_device_closures(self, mi, fi, env) -> None:
        """jax.jit(f) / @jit defs capturing tainted outer names."""
        for n in ast.walk(fi.node):
            target = None
            site = None
            if isinstance(n, ast.Call) and self._is_jit_expr(mi, n) \
                    and isinstance(n, ast.Call) and n.args:
                refs = self._fn_refs(mi, n.args[0])
                if refs:
                    target = refs[0]
                    site = n
            if target is None:
                continue
            t_fi = self.fns.get(target)
            if t_fi is None or t_fi.node is fi.node:
                continue
            free = self._free_names(t_fi.node)
            captured = sorted(name for name in free if env.get(name, False))
            if captured:
                self._emit(
                    mi, DEVICE_CLOSURE, site,
                    f"jitted {target[1].split('.')[-1]!r} closes over "
                    f"device value(s) {', '.join(captured)} — baked into "
                    "the executable as constants; pass them as arguments",
                )

    def _free_names(self, fn: ast.AST) -> Set[str]:
        bound: Set[str] = {a.arg for a in fn.args.args}
        bound |= {a.arg for a in fn.args.kwonlyargs}
        if fn.args.vararg:
            bound.add(fn.args.vararg.arg)
        if fn.args.kwarg:
            bound.add(fn.args.kwarg.arg)
        loads: Set[str] = set()
        for n in ast.walk(fn):
            if isinstance(n, ast.Name):
                if isinstance(n.ctx, (ast.Store, ast.Del)):
                    bound.add(n.id)
                else:
                    loads.add(n.id)
            elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and n is not fn:
                bound.add(n.name)
        import builtins

        return {x for x in loads - bound if not hasattr(builtins, x)}

    # ------------------------------------------------------------------
    def _emit(self, mi: _ModuleInfo, rule: str, node: ast.AST,
              message: str) -> None:
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        sup = rule in mi.allow_file or any(
            rule in mi.allow_lines.get(ln, ())
            for ln in (line, line - 1)
        )
        self.findings.append(
            Finding(rule, mi.path, line, col, message, sup)
        )


# ----------------------------------------------------------------------
# public API
def _module_name_for(path: Path, pkg_root: Path) -> str:
    rel = path.relative_to(pkg_root.parent).with_suffix("")
    return ".".join(rel.parts)


def lint_paths(paths: Sequence[Path], pkg_root: Path) -> List[Finding]:
    modules: Dict[str, _ModuleInfo] = {}
    for p in paths:
        src = p.read_text()
        tree = ast.parse(src, filename=str(p))
        name = _module_name_for(p, pkg_root)
        is_pkg = name.endswith(".__init__")
        if is_pkg:
            name = name[: -len(".__init__")]
        modules[name] = _ModuleInfo(name, tree, src, str(p),
                                    is_package=is_pkg)
    return _Linter(modules).run()


def iter_package_modules(pkg_root: Optional[str] = None,
                         exclude=("analysis",)) -> Tuple[List[Path], Path]:
    """(module files, package root) for a package-wide lint; `exclude`
    names subpackage or module stems skipped (the analyzers
    themselves, by default). With no pkg_root the INSTALLED
    lightgbm_tpu package is located — never a CWD-relative guess,
    which would lint nothing from another directory and report a
    vacuously clean result. Shared by this linter and
    concurrency_lint.py so the two --strict AST passes can never scan
    different file sets."""
    if pkg_root is None:
        import lightgbm_tpu

        root = Path(lightgbm_tpu.__file__).resolve().parent
    else:
        root = Path(pkg_root).resolve()
    files = [
        p for p in sorted(root.rglob("*.py"))
        if not any(part in exclude for part in
                   p.relative_to(root).parts)
    ]
    if not files:
        raise FileNotFoundError(
            f"no Python modules under {root} — wrong pkg_root? a clean "
            "lint over zero files would be meaningless"
        )
    return files, root


def lint_package(pkg_root: Optional[str] = None,
                 exclude=("analysis",)) -> List[Finding]:
    """Lint every module of the package (see iter_package_modules for
    root resolution and exclusion semantics)."""
    files, root = iter_package_modules(pkg_root, exclude)
    return lint_paths(files, root)


def lint_source(src: str, name: str = "fixture",
                module: str = "lightgbm_tpu._fixture") -> List[Finding]:
    """Lint a single in-memory module (test fixtures)."""
    tree = ast.parse(src, filename=name)
    mi = _ModuleInfo(module, tree, src, name)
    return _Linter({module: mi}).run()


def format_findings(findings: Sequence[Finding],
                    show_suppressed: bool = False,
                    label: str = "lint") -> str:
    lines = [
        f.format() for f in findings if show_suppressed or not f.suppressed
    ]
    active = sum(1 for f in findings if not f.suppressed)
    sup = len(findings) - active
    lines.append(
        f"{label}: {active} violation(s), {sup} suppressed"
    )
    return "\n".join(lines)
