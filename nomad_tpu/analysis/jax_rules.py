"""JAX purity rules (NLJ01–NLJ09).

A function is *traced* when it is jit-compiled, passed to
`jax.vmap`/`jax.pmap`/`jax.lax.scan`/`jax.lax.map`/`jax.checkpoint`
(directly or through a `functools.partial` alias), nested inside a
traced function, or reachable from one through same-module calls.
Inside a traced function every non-static parameter is *tainted*
(potentially a tracer), and taint flows through assignments — except
through `.shape`/`.ndim`/`.dtype`/`.size`, `len()`, `isinstance()` and
`type()`, which are static under trace (so `if p.cand_idx.shape[0]:`
stays clean, exactly like kernels/placement.py uses it).

NLJ06/NLJ07 are repo-native perf rules, not correctness rules: TPU
scatters and gathers serialize (see the comparison-einsum comments in
kernels/placement.py), so `.at[...]` updates and multi-array advanced
indexing inside a kernel are flagged in favor of the one-hot/einsum
idiom the placement kernel already uses.

NLJ05 (debug prints / host syncs) applies to the hot-path modules
whether or not the enclosing function is traced — `block_until_ready`
on the serving path stalls the dispatch pipeline even from host code.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, dotted as _dotted

JAX_RULES = {
    "NLJ01": ".item() inside a traced function forces a host-device "
             "sync per call",
    "NLJ02": "Python scalar conversion (float/int/bool/complex) of a "
             "traced value blocks on the device",
    "NLJ03": "numpy materialization (np.asarray/np.array) of a traced "
             "value breaks tracing",
    "NLJ04": "data-dependent Python control flow on a traced value "
             "(retrace per value / ConcretizationError)",
    "NLJ05": "host sync or debug output in a hot-path module",
    "NLJ06": "scatter (.at[...]) in a traced kernel — TPU scatters "
             "serialize",
    "NLJ07": "multi-array advanced indexing (gather) in a traced "
             "kernel — TPU gathers serialize",
    "NLJ08": "mutation of enclosing-scope state under trace (silently "
             "frozen at trace time)",
    "NLJ09": "traced/array expression passed to a static_argnums/"
             "static_argnames position (retrace per value)",
}

_HINTS = {
    "NLJ01": "keep values on device; convert after the dispatch "
             "boundary",
    "NLJ02": "use jnp ops / jnp.where; convert on the host side only",
    "NLJ03": "stay in jnp inside the kernel; np conversion belongs at "
             "the dispatch boundary",
    "NLJ04": "use jnp.where / lax.cond / lax.scan, or hoist the "
             "branch on a static shape",
    "NLJ05": "benchmarks may block; the serving path must not — move "
             "it behind the dispatch boundary",
    "NLJ06": "use a comparison one-hot + einsum (see "
             "kernels/placement.py _scatter_counts)",
    "NLJ07": "use a one-hot mask + einsum over the indexed axis",
    "NLJ08": "thread state through the function (scan carry / return "
             "values)",
    "NLJ09": "pass a Python int/str/bool; static args are hashed into "
             "the compile cache key",
}

#: hot-path scope for NLJ05, repo-relative prefixes/files
HOT_PATH_SCOPE = (
    "nomad_tpu/kernels/",
    "nomad_tpu/tensor/",
    "nomad_tpu/parallel/",
    "nomad_tpu/scheduler/",
    "nomad_tpu/server/select_batch.py",
)

_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}
_STATIC_CALLS = {"len", "isinstance", "type", "hasattr", "getattr",
                 "id", "repr", "str"}
_SCALAR_CASTS = {"float", "int", "bool", "complex"}
_TRANSFORMS = {"vmap", "pmap", "jit", "checkpoint", "scan", "map",
               "while_loop", "fori_loop", "grad", "value_and_grad"}
_MUTATORS = {"append", "extend", "update", "setdefault", "pop", "add",
             "remove", "clear", "insert", "discard"}


def _is_partial(call: ast.Call) -> bool:
    d = _dotted(call.func)
    return d in ("functools.partial", "partial")


def _const_tuple(node: ast.AST) -> Tuple:
    """Literal tuple/list/str/int contents, or () if not literal."""
    if isinstance(node, ast.Constant):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant):
                out.append(e.value)
        return tuple(out)
    return ()


class _FnInfo:
    __slots__ = ("node", "qualname", "parent", "traced", "static_names",
                 "static_nums", "calls")

    def __init__(self, node, qualname, parent):
        self.node = node
        self.qualname = qualname
        self.parent = parent          # enclosing _FnInfo or None
        self.traced = False
        self.static_names: Set[str] = set()
        self.static_nums: Set[int] = set()
        self.calls: Set[str] = set()  # bare names of local calls


def _collect_functions(tree: ast.Module) -> Dict[str, _FnInfo]:
    fns: Dict[str, _FnInfo] = {}

    def visit(node, parent: Optional[_FnInfo], prefix: str):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qn = f"{prefix}{child.name}"
                info = _FnInfo(child, qn, parent)
                fns[qn] = info
                visit(child, info, qn + ".")
            elif isinstance(child, ast.ClassDef):
                visit(child, parent, f"{prefix}{child.name}.")
            else:
                visit(child, parent, prefix)

    visit(tree, None, "")
    return fns


def _jit_static(call: ast.Call) -> Tuple[Set[str], Set[int]]:
    names: Set[str] = set()
    nums: Set[int] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            names |= {v for v in _const_tuple(kw.value)
                      if isinstance(v, str)}
        elif kw.arg == "static_argnums":
            nums |= {v for v in _const_tuple(kw.value)
                     if isinstance(v, int)}
    return names, nums


def _mark_traced(tree: ast.Module, fns: Dict[str, _FnInfo]) -> None:
    """Mark directly-traced functions, then close over local calls."""
    by_name: Dict[str, List[_FnInfo]] = {}
    for info in fns.values():
        by_name.setdefault(info.node.name, []).append(info)
    partial_alias: Dict[str, str] = {}

    def mark(name: str, static: Tuple[Set[str], Set[int]] = (set(), set())):
        name = partial_alias.get(name, name)
        for info in by_name.get(name, ()):
            info.traced = True
            info.static_names |= static[0]
            info.static_nums |= static[1]

    # decorators
    for info in fns.values():
        for dec in info.node.decorator_list:
            target = dec
            static: Tuple[Set[str], Set[int]] = (set(), set())
            if isinstance(dec, ast.Call):
                if _is_partial(dec) and dec.args:
                    target = dec.args[0]
                    if isinstance(target, ast.Call):
                        static = _jit_static(target)
                        target = target.func
                    elif (isinstance(dec, ast.Call)
                          and _dotted(target).endswith("jit")):
                        static = _jit_static(dec)
                else:
                    static = _jit_static(dec)
                    target = dec.func
            d = _dotted(target)
            if d.split(".")[-1] in ("jit", "checkpoint", "vmap", "pmap"):
                info.traced = True
                info.static_names |= static[0]
                info.static_nums |= static[1]

    # partial aliases and calls to transforms anywhere in the module
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            call = node.value
            if _is_partial(call) and call.args \
                    and isinstance(call.args[0], ast.Name) \
                    and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                partial_alias[node.targets[0].id] = call.args[0].id
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        leaf = d.split(".")[-1]
        if leaf not in _TRANSFORMS or not node.args:
            continue
        static = _jit_static(node) if leaf == "jit" else (set(), set())
        arg = node.args[0]
        if isinstance(arg, ast.Call) and _is_partial(arg) and arg.args:
            arg = arg.args[0]
        if isinstance(arg, ast.Name):
            mark(arg.id, static)

    # same-module call closure
    for info in fns.values():
        for node in ast.walk(info.node):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                info.calls.add(partial_alias.get(node.func.id,
                                                 node.func.id))
    # normalize static_argnums onto parameter names so they can flow
    # through the call closure below
    for info in fns.values():
        if info.static_nums:
            params = [a.arg for a in info.node.args.args]
            for i in info.static_nums:
                if 0 <= i < len(params):
                    info.static_names.add(params[i])
    changed = True
    while changed:
        changed = False
        for info in fns.values():
            if not info.traced:
                continue
            for callee in info.calls:
                for target in by_name.get(callee, ()):
                    if not target.traced:
                        target.traced = True
                        changed = True
                    # a static arg forwarded under the same name stays
                    # static in the callee (place_packed_chain's `spec`
                    # → _unpack_params' `spec`)
                    callee_params = {a.arg for a in target.node.args.args}
                    inherit = (info.static_names & callee_params) \
                        - target.static_names
                    if inherit:
                        target.static_names |= inherit
                        changed = True


def collect_jit_registry(tree: ast.Module, registry: Dict[str, object]
                         ) -> Dict[str, "_FnInfo"]:
    """Record jitted functions that declare static argnums/argnames —
    NLJ09 checks their call sites across the whole analyzed tree.
    registry: bare name -> (param order tuple, static name set,
    static num set). Returns the collected-and-marked function map so
    run_tree can hand it back to analyze_jax instead of paying the
    collect+mark walk twice per module."""
    fns = _collect_functions(tree)
    _mark_traced(tree, fns)
    for info in fns.values():
        if not info.traced or not (info.static_names or info.static_nums):
            continue
        params = tuple(a.arg for a in info.node.args.args)
        nums = set(info.static_nums)
        for n in info.static_names:
            if n in params:
                nums.add(params.index(n))
        registry[info.node.name] = (params, set(info.static_names), nums)
    return fns


def _arraylike(node: ast.AST) -> bool:
    """Syntactically an array expression: rooted at jnp/jax/np calls."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            root = _dotted(sub.func).split(".")[0]
            if root in ("jnp", "jax", "np", "numpy"):
                return True
    return False


class _TracedChecker:
    """Taint-based purity walk over one traced function."""

    def __init__(self, info: _FnInfo, rel: str, np_aliases: Set[str],
                 findings: List[Finding]):
        self.info = info
        self.rel = rel
        self.np_aliases = np_aliases
        self.findings = findings
        self.tainted: Set[str] = set()
        self.local: Set[str] = set()
        self.reported: Set[Tuple[int, str]] = set()

    def flag(self, node: ast.AST, rule: str, detail: str = "") -> None:
        line = getattr(node, "lineno", self.info.node.lineno)
        if (line, rule) in self.reported:
            return
        self.reported.add((line, rule))
        msg = JAX_RULES[rule] + (f": {detail}" if detail else "")
        self.findings.append(Finding(
            self.rel, line, rule, msg, _HINTS[rule],
            context=self.info.qualname))

    # -- taint --

    def _taint_params(self, node, static_names: Set[str],
                      static_nums: Set[int]) -> None:
        args = node.args
        ordered = list(args.posonlyargs) + list(args.args)
        for i, a in enumerate(ordered):
            if a.arg in static_names or i in static_nums \
                    or a.arg in ("self", "cls"):
                continue
            self.tainted.add(a.arg)
            self.local.add(a.arg)
        for a in list(args.kwonlyargs) + (
                [args.vararg] if args.vararg else []) + (
                [args.kwarg] if args.kwarg else []):
            if a.arg not in static_names:
                self.tainted.add(a.arg)
            self.local.add(a.arg)

    def is_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False
            return self.is_tainted(node.value)
        if isinstance(node, ast.Subscript):
            return self.is_tainted(node.value) or self.is_tainted(node.slice)
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            leaf = d.split(".")[-1]
            if leaf in _STATIC_CALLS:
                return False
            root = d.split(".")[0]
            if root in ("jnp", "jax"):
                return True  # returns a tracer under trace
            return any(self.is_tainted(a) for a in node.args) or any(
                self.is_tainted(k.value) for k in node.keywords)
        if isinstance(node, ast.Constant):
            return False
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.is_tainted(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return any(self.is_tainted(v) for v in node.values if v)
        if isinstance(node, (ast.BinOp,)):
            return self.is_tainted(node.left) or self.is_tainted(node.right)
        if isinstance(node, ast.BoolOp):
            return any(self.is_tainted(v) for v in node.values)
        if isinstance(node, ast.UnaryOp):
            return self.is_tainted(node.operand)
        if isinstance(node, ast.Compare):
            return self.is_tainted(node.left) or any(
                self.is_tainted(c) for c in node.comparators)
        if isinstance(node, ast.IfExp):
            return (self.is_tainted(node.test) or self.is_tainted(node.body)
                    or self.is_tainted(node.orelse))
        if isinstance(node, ast.Starred):
            return self.is_tainted(node.value)
        if isinstance(node, (ast.GeneratorExp, ast.ListComp, ast.SetComp,
                             ast.DictComp)):
            return any(self.is_tainted(g.iter) for g in node.generators)
        return False

    def _bind(self, target: ast.AST, tainted: bool) -> None:
        if isinstance(target, ast.Name):
            self.local.add(target.id)
            if tainted:
                self.tainted.add(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, tainted)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, tainted)

    # -- checks --

    def check_call(self, node: ast.Call) -> None:
        func = node.func
        if isinstance(func, ast.Attribute):
            if func.attr == "item" and not node.args:
                self.flag(node, "NLJ01")
            elif func.attr in _MUTATORS:
                base = func.value
                while isinstance(base, (ast.Attribute, ast.Subscript)):
                    base = base.value
                if isinstance(base, ast.Name) \
                        and base.id not in self.local \
                        and base.id not in self.np_aliases:
                    self.flag(node, "NLJ08",
                              f"{_dotted(func) or func.attr}() mutates "
                              "state captured by the trace")
        d = _dotted(func)
        leaf = d.split(".")[-1]
        root = d.split(".")[0]
        if leaf in _SCALAR_CASTS and isinstance(func, ast.Name) \
                and node.args and self.is_tainted(node.args[0]):
            self.flag(node, "NLJ02", f"{leaf}() on a traced value")
        if root in self.np_aliases and leaf in (
                "asarray", "array", "ascontiguousarray", "copy") \
                and node.args and self.is_tainted(node.args[0]):
            self.flag(node, "NLJ03", f"{d}() on a traced value")

    def check_subscript(self, node: ast.Subscript) -> None:
        if isinstance(node.value, ast.Attribute) \
                and node.value.attr == "at":
            self.flag(node, "NLJ06")
            return
        if isinstance(node.slice, ast.Tuple):
            arrays = sum(
                1 for e in node.slice.elts
                if not isinstance(e, (ast.Slice, ast.Constant))
                and self.is_tainted(e))
            if arrays >= 2:
                self.flag(node, "NLJ07")

    def run(self) -> None:
        self._taint_params(self.info.node, self.info.static_names,
                           self.info.static_nums)
        self._walk(self.info.node.body)

    def _walk(self, body) -> None:
        for stmt in body:
            self._stmt(stmt)

    def _stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested function: params traced too (closures over tracers)
            saved = set(self.tainted), set(self.local)
            self._taint_params(stmt, set(), set())
            self._walk(stmt.body)
            self.tainted, self.local = saved
            return
        if isinstance(stmt, (ast.Global, ast.Nonlocal)):
            self.flag(stmt, "NLJ08",
                      f"{'global' if isinstance(stmt, ast.Global) else 'nonlocal'}"
                      f" {', '.join(stmt.names)}")
        if isinstance(stmt, ast.Assign):
            self._exprs(stmt.value)
            tainted = self.is_tainted(stmt.value)
            for t in stmt.targets:
                if isinstance(t, ast.Attribute):
                    self.flag(stmt, "NLJ08",
                              f"assignment to {_dotted(t) or t.attr}")
                elif isinstance(t, ast.Subscript):
                    base = t.value
                    while isinstance(base, (ast.Attribute, ast.Subscript)):
                        base = base.value
                    if isinstance(base, ast.Name) \
                            and base.id not in self.local:
                        self.flag(stmt, "NLJ08",
                                  "subscript store to enclosing-scope "
                                  "object")
                else:
                    self._bind(t, tainted)
        elif isinstance(stmt, ast.AugAssign):
            self._exprs(stmt.value)
            t = stmt.target
            if isinstance(t, ast.Attribute):
                self.flag(stmt, "NLJ08",
                          f"augmented assignment to {_dotted(t) or t.attr}")
            elif isinstance(t, ast.Name):
                if self.is_tainted(stmt.value):
                    self.tainted.add(t.id)
                self.local.add(t.id)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self._exprs(stmt.value)
            if stmt.target and isinstance(stmt.target, ast.Name):
                self._bind(stmt.target, self.is_tainted(stmt.value))
        elif isinstance(stmt, (ast.If, ast.While)):
            self._exprs(stmt.test)
            if self.is_tainted(stmt.test):
                kind = "if" if isinstance(stmt, ast.If) else "while"
                self.flag(stmt, "NLJ04", f"`{kind}` on a traced value")
            self._walk(stmt.body)
            self._walk(stmt.orelse)
        elif isinstance(stmt, ast.For):
            self._exprs(stmt.iter)
            if self.is_tainted(stmt.iter):
                self.flag(stmt, "NLJ04", "`for` over a traced value")
            self._bind(stmt.target, False)
            self._walk(stmt.body)
            self._walk(stmt.orelse)
        elif isinstance(stmt, ast.Assert):
            if self.is_tainted(stmt.test):
                self.flag(stmt, "NLJ04", "`assert` on a traced value")
            self._exprs(stmt.test)
        elif isinstance(stmt, ast.Return) and stmt.value is not None:
            self._exprs(stmt.value)
        elif isinstance(stmt, (ast.With,)):
            for item in stmt.items:
                self._exprs(item.context_expr)
            self._walk(stmt.body)
        elif isinstance(stmt, ast.Try):
            self._walk(stmt.body)
            for h in stmt.handlers:
                self._walk(h.body)
            self._walk(stmt.orelse)
            self._walk(stmt.finalbody)
        elif isinstance(stmt, ast.Expr):
            self._exprs(stmt.value)

    def _exprs(self, node: ast.AST) -> None:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                self.check_call(sub)
            elif isinstance(sub, ast.Subscript):
                self.check_subscript(sub)
            elif isinstance(sub, ast.IfExp) and self.is_tainted(sub.test):
                self.flag(sub, "NLJ04", "ternary on a traced value")


def _np_aliases(tree: ast.Module) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name in ("numpy", "numpy.ma"):
                    out.add(a.asname or a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            if node.module == "numpy":
                for a in node.names:
                    out.add(a.asname or a.name)
    return out or {"np", "numpy"}


def _check_hot_path(tree: ast.Module, rel: str,
                    findings: List[Finding]) -> None:
    in_scope = any(
        rel.startswith(p) if p.endswith("/") else rel == p
        for p in HOT_PATH_SCOPE)
    if not in_scope:
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        leaf = d.split(".")[-1] if d else ""
        if d.startswith("jax.debug.") or leaf in ("block_until_ready",
                                                  "device_get"):
            findings.append(Finding(
                rel, node.lineno, "NLJ05",
                JAX_RULES["NLJ05"] + f": {d or leaf}()",
                _HINTS["NLJ05"]))


def _check_static_callsites(tree: ast.Module, rel: str,
                            registry: Dict[str, object],
                            findings: List[Finding]) -> None:
    if not registry:
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _dotted(node.func).split(".")[-1]
        ent = registry.get(name)
        if ent is None:
            continue
        params, static_names, static_nums = ent
        for i, arg in enumerate(node.args):
            if i in static_nums and _arraylike(arg):
                findings.append(Finding(
                    rel, node.lineno, "NLJ09",
                    JAX_RULES["NLJ09"]
                    + f": arg {i} of {name}() is an array expression",
                    _HINTS["NLJ09"]))
        for kw in node.keywords:
            if kw.arg in static_names and _arraylike(kw.value):
                findings.append(Finding(
                    rel, node.lineno, "NLJ09",
                    JAX_RULES["NLJ09"]
                    + f": {kw.arg}= of {name}() is an array expression",
                    _HINTS["NLJ09"]))


def analyze_jax(tree: ast.Module, rel: str,
                jit_registry: Optional[Dict[str, object]] = None,
                enable_traced: bool = True,
                fns: Optional[Dict[str, _FnInfo]] = None
                ) -> List[Finding]:
    """`enable_traced=False` skips the traced-function analysis — the
    expensive part — for modules that never mention jax (the hot-path
    and static-callsite scans still run: both are single walks and can
    fire in jax-free modules). `fns` is an already collected-and-marked
    function map from collect_jit_registry, so run_tree pays that walk
    once per module."""
    findings: List[Finding] = []
    _check_hot_path(tree, rel, findings)
    _check_static_callsites(tree, rel, jit_registry or {}, findings)
    if not enable_traced:
        return findings
    if fns is None:
        fns = _collect_functions(tree)
        if fns:
            _mark_traced(tree, fns)
    if fns:
        np_aliases = _np_aliases(tree)
        # only analyze OUTERMOST traced functions: nested ones are
        # covered by the enclosing walk (dedupe by line anyway)
        for info in fns.values():
            if not info.traced:
                continue
            p = info.parent
            covered = False
            while p is not None:
                if p.traced:
                    covered = True
                    break
                p = p.parent
            if covered:
                continue
            _TracedChecker(info, rel, np_aliases, findings).run()
    return findings
