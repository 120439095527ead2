"""JIT-hygiene checker.

Inside a ``jax.jit``-compiled function, the cheap-looking host idioms are the
expensive ones (accelerator guide: host/device boundary):

  * ``jit-host-sync`` — ``float(x)`` / ``x.item()`` / ``np.asarray(x)`` /
    ``np.array(x)`` / ``jax.device_get(x)`` on a traced value forces a
    device→host sync per call (or a ConcretizationError); one stray sync
    is a dispatch round trip per query.
  * ``jit-traced-branch`` — Python ``if``/``while`` on a traced parameter is
    a trace error; "fixing" it by making the value static retraces per
    distinct value. Shape/len/isinstance/`is None` tests are static and fine.
  * ``jit-mutable-closure`` — a jitted function reading module-level mutable
    state (list/dict/set) bakes the values seen at TRACE time into the
    compiled program; later mutations are silently ignored. Writing
    (``global``) from traced code never lands.
  * ``jit-static-args`` — a float-typed static argument retraces per distinct
    value (the silent 100x cliff); an unhashable static argument (list/dict/
    set/ndarray) raises at call time. Checked both at the decoration (float
    defaults on static params) and at same-module call sites.
  * ``jit-donation-unused`` — donation discipline on the flush path, both
    directions: (a) a ``donate_argnums``/``donate_argnames`` argument that
    never flows to the function's return is a donation with zero aliasing
    win — the input buffer is deleted (the caller may still hold it) and
    nothing is updated in place; (b) a jitted function that scatter-updates
    a parameter (``p.at[...].set/add``) and returns the result WITHOUT
    donating it allocates a full copy of the buffer per call — on the
    memstore flush path that is a store-sized allocation per staged-row
    commit (core/chunkstore.py's scatter jits donate for exactly this
    reason). Deliberate copies suppress with an inline
    ``filolint: ignore[jit-donation-unused]`` comment + reason.

Jitted functions are recognized by decorator (``@jax.jit``,
``@functools.partial(jax.jit, ...)``), by wrapping assignment
(``g = jax.jit(f, ...)``), and by factory return (``return jax.jit(f)``).
Cross-function flows (a jitted fn calling a helper that syncs) are out of
scope — keep helpers either pure or inline. Suppress deliberate host code
with an inline ``filolint: ignore[jit-host-sync]`` comment.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

from .findings import Finding

HOST_SYNC_ATTRS = {"item"}            # x.item()
JAX_SYNC_FUNCS = {"device_get"}       # jax.device_get(x)
NUMPY_SYNC_FUNCS = {"asarray", "array"}
UNHASHABLE_CTORS = {"list", "dict", "set", "bytearray"}
MUTABLE_CTORS = {"list", "dict", "set", "defaultdict", "OrderedDict",
                 "Counter", "deque", "bytearray"}
STATIC_TEST_CALLS = {"len", "isinstance", "getattr", "hasattr", "callable"}
STATIC_TEST_ATTRS = {"shape", "ndim", "dtype", "size", "nbytes"}


def _dotted(node: ast.expr) -> str | None:
    """'jax.jit' for Attribute chains / Names, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@dataclass
class _JitInfo:
    node: ast.FunctionDef
    qualname: str
    static_names: set = field(default_factory=set)
    static_nums: set = field(default_factory=set)   # positional indices
    donate_names: set = field(default_factory=set)
    donate_nums: set = field(default_factory=set)   # positional indices
    aliases: set = field(default_factory=set)       # names callable at sites

    def params(self) -> list[str]:
        a = self.node.args
        return ([p.arg for p in a.posonlyargs] + [p.arg for p in a.args]
                + [p.arg for p in a.kwonlyargs])

    def _resolve(self, names: set, nums: set) -> set:
        out = set(names)
        plist = self.params()
        for i in nums:
            if 0 <= i < len(plist):
                out.add(plist[i])
        return out

    def static_params(self) -> set:
        return self._resolve(self.static_names, self.static_nums)

    def donated_params(self) -> set:
        return self._resolve(self.donate_names, self.donate_nums)


class _ModuleIndex(ast.NodeVisitor):
    """First pass: numpy/jax import aliases, module-level mutable globals,
    and the set of jitted functions (with their static-arg info)."""

    def __init__(self):
        self.numpy_aliases: set[str] = set()
        self.jax_aliases: set[str] = {"jax"}
        self.jit_names: set[str] = set()       # bare names that mean jax.jit
        self.partial_names: set[str] = {"partial"}
        self.mutable_globals: dict[str, int] = {}
        self.module_names: set[str] = set()    # imports/defs/module assigns
        self._scope: list[str] = []
        self.by_name: dict[str, list[tuple[str, ast.FunctionDef]]] = {}

    def visit_Import(self, node: ast.Import):  # noqa: N802
        for a in node.names:
            as_ = a.asname or a.name.split(".")[0]
            self.module_names.add(as_)
            if a.name == "numpy":
                self.numpy_aliases.add(as_)
            elif a.name == "jax":
                self.jax_aliases.add(as_)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom):  # noqa: N802
        for a in node.names:
            self.module_names.add(a.asname or a.name)
        if node.module == "jax":
            for a in node.names:
                if a.name == "jit":
                    self.jit_names.add(a.asname or "jit")
        if node.module == "functools":
            for a in node.names:
                if a.name == "partial":
                    self.partial_names.add(a.asname or "partial")
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef):  # noqa: N802
        qual = ".".join(self._scope + [node.name]) or node.name
        self.by_name.setdefault(node.name, []).append((qual, node))
        if not self._scope:
            self.module_names.add(node.name)
        self._scope.append(node.name)
        self.generic_visit(node)
        self._scope.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node: ast.ClassDef):  # noqa: N802
        if not self._scope:
            self.module_names.add(node.name)
        self._scope.append(node.name)
        self.generic_visit(node)
        self._scope.pop()

    def visit_Assign(self, node: ast.Assign):  # noqa: N802
        if not self._scope:    # module level only
            val = node.value
            mutable = isinstance(val, (ast.List, ast.Dict, ast.Set,
                                       ast.ListComp, ast.DictComp,
                                       ast.SetComp))
            if isinstance(val, ast.Call):
                callee = _dotted(val.func)
                if callee and callee.split(".")[-1] in MUTABLE_CTORS:
                    mutable = True
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self.module_names.add(t.id)
                    if mutable:
                        self.mutable_globals[t.id] = node.lineno
        self.generic_visit(node)


SCATTER_UPDATE_ATTRS = {"set", "add", "subtract", "multiply", "divide",
                        "min", "max", "power", "apply"}


def _names_flowing_to_return(fn: ast.FunctionDef) -> set:
    """Over-approximate the set of names whose value can reach a ``return``
    expression: seed with the names read in return expressions, close
    backwards through (Ann/Aug)Assign statements, ``for``/``with`` target
    bindings, and mutating method calls on a name (``out.append(x)`` makes
    ``out`` depend on ``x``). Reassignment versions are not distinguished —
    over-approximation only ever SUPPRESSES findings."""
    deps: dict[str, set] = {}

    def _loads(expr: ast.expr | None) -> set:
        if expr is None:
            return set()
        return {n.id for n in ast.walk(expr)
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)}

    def _bind(targets, names: set) -> None:
        for t in targets:
            for tn in ast.walk(t):
                if isinstance(tn, ast.Name) and isinstance(tn.ctx,
                                                           (ast.Store,
                                                            ast.Load)):
                    deps.setdefault(tn.id, set()).update(names)

    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            _bind(node.targets, _loads(node.value))
        elif isinstance(node, ast.AugAssign):
            names = _loads(node.value)
            if isinstance(node.target, ast.Name):
                names.add(node.target.id)
            _bind([node.target], names)
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            _bind([node.target], _loads(node.value))
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            _bind([node.target], _loads(node.iter))
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                if item.optional_vars is not None:
                    _bind([item.optional_vars], _loads(item.context_expr))
        elif (isinstance(node, ast.Call)
              and isinstance(node.func, ast.Attribute)
              and isinstance(node.func.value, ast.Name)):
            # a method call may mutate its receiver with the args' values
            names = set()
            for a in node.args:
                names |= _loads(a)
            for kw in node.keywords:
                names |= _loads(kw.value)
            if names:
                deps.setdefault(node.func.value.id, set()).update(names)
    flowing: set = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Return) and node.value is not None:
            flowing |= {n.id for n in ast.walk(node.value)
                        if isinstance(n, ast.Name)
                        and isinstance(n.ctx, ast.Load)}
    changed = True
    while changed:
        changed = False
        for name in list(flowing):
            for s in deps.get(name, ()):
                if s not in flowing:
                    flowing.add(s)
                    changed = True
    return flowing


def _scatter_updated_params(fn: ast.FunctionDef, params: set) -> dict:
    """{param name: first lineno} of parameters used as the BASE of an
    in-place-eligible ``p.at[...].set/add/...`` update chain."""
    out: dict[str, int] = {}
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in SCATTER_UPDATE_ATTRS
                and isinstance(node.func.value, ast.Subscript)
                and isinstance(node.func.value.value, ast.Attribute)
                and node.func.value.value.attr == "at"
                and isinstance(node.func.value.value.value, ast.Name)):
            continue
        base = node.func.value.value.value.id
        if base in params:
            out.setdefault(base, node.lineno)
    return out


class JitChecker:
    rules = ("jit-host-sync", "jit-traced-branch", "jit-mutable-closure",
             "jit-static-args", "jit-donation-unused")

    def check_module(self, path: str, tree: ast.Module) -> list[Finding]:
        idx = _ModuleIndex()
        idx.visit(tree)
        jitted = self._find_jitted(tree, idx)
        findings: list[Finding] = []
        for info in jitted.values():
            findings += self._check_body(path, info, idx)
            findings += self._check_decoration(path, info)
            findings += self._check_donation(path, info)
        findings += self._check_call_sites(path, tree, jitted)
        return findings

    # -- donation discipline ----------------------------------------------

    def _check_donation(self, path: str, info: _JitInfo) -> list[Finding]:
        """jit-donation-unused, both directions: a donated argument that
        never flows to an output (the donation deletes an input for zero
        aliasing win), and a scatter-updated-and-returned parameter that is
        NOT donated (a full buffer copy per call on the flush path)."""
        findings: list[Finding] = []
        donated = info.donated_params()
        params = set(info.params()) - {"self"}
        flowing = _names_flowing_to_return(info.node)
        for name in sorted(donated):
            if name not in flowing:
                findings.append(Finding(
                    "jit-donation-unused", path, info.node.lineno,
                    info.qualname, f"donated-unread:{name}",
                    f"donated argument {name!r} never flows to the jitted "
                    "function's return — the donation deletes the caller's "
                    "buffer without any in-place update to alias into; "
                    "drop it from donate_argnums or update-and-return it"))
        scattered = _scatter_updated_params(info.node, params)
        for name, lineno in sorted(scattered.items()):
            if name in flowing and name not in donated:
                findings.append(Finding(
                    "jit-donation-unused", path, lineno, info.qualname,
                    f"undonated-scatter:{name}",
                    f"parameter {name!r} is scatter-updated and returned "
                    "but not donated — the update allocates a full copy of "
                    "the buffer per call; donate it (donate_argnums) so "
                    "the commit updates the array in place, or suppress "
                    "with a reason if the copy is deliberate"))
        return findings

    # -- recognizing jitted functions ------------------------------------

    def _is_jit_expr(self, node: ast.expr, idx: _ModuleIndex) -> bool:
        d = _dotted(node)
        if d is None:
            return False
        if d in idx.jit_names:
            return True
        parts = d.split(".")
        return len(parts) == 2 and parts[0] in idx.jax_aliases \
            and parts[1] == "jit"

    def _jit_call_static(self, call: ast.Call, info: _JitInfo) -> None:
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for v in ast.walk(kw.value):
                    if isinstance(v, ast.Constant) and isinstance(v.value, str):
                        info.static_names.add(v.value)
            elif kw.arg == "static_argnums":
                for v in ast.walk(kw.value):
                    if isinstance(v, ast.Constant) and isinstance(v.value, int):
                        info.static_nums.add(v.value)
            elif kw.arg == "donate_argnames":
                for v in ast.walk(kw.value):
                    if isinstance(v, ast.Constant) and isinstance(v.value, str):
                        info.donate_names.add(v.value)
            elif kw.arg == "donate_argnums":
                for v in ast.walk(kw.value):
                    if isinstance(v, ast.Constant) and isinstance(v.value, int):
                        info.donate_nums.add(v.value)

    def _find_jitted(self, tree: ast.Module,
                     idx: _ModuleIndex) -> dict[int, _JitInfo]:
        jitted: dict[int, _JitInfo] = {}

        def mark(fn: ast.FunctionDef, qual: str) -> _JitInfo:
            info = jitted.get(id(fn))
            if info is None:
                info = jitted[id(fn)] = _JitInfo(fn, qual)
                info.aliases.add(fn.name)
            return info

        # decorators
        for qual_list in idx.by_name.values():
            for qual, fn in qual_list:
                for dec in fn.decorator_list:
                    if self._is_jit_expr(dec, idx):
                        mark(fn, qual)
                    elif isinstance(dec, ast.Call):
                        callee = _dotted(dec.func)
                        if callee and (callee.split(".")[-1]
                                       in idx.partial_names) and dec.args \
                                and self._is_jit_expr(dec.args[0], idx):
                            info = mark(fn, qual)
                            self._jit_call_static(dec, info)
                        elif self._is_jit_expr(dec.func, idx):
                            info = mark(fn, qual)
                            self._jit_call_static(dec, info)

        # wrapping assignments / factory returns: jax.jit(f, ...)
        for node in ast.walk(tree):
            call = None
            alias = None
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                call = node.value
                if len(node.targets) == 1 and isinstance(node.targets[0],
                                                         ast.Name):
                    alias = node.targets[0].id
            elif isinstance(node, ast.Return) and isinstance(node.value,
                                                             ast.Call):
                call = node.value
            if call is None or not self._is_jit_expr(call.func, idx):
                continue
            if not call.args or not isinstance(call.args[0], ast.Name):
                continue   # jax.jit(partial(...)) — target not resolvable
            target = call.args[0].id
            for qual, fn in idx.by_name.get(target, ()):
                info = mark(fn, qual)
                self._jit_call_static(call, info)
                if alias:
                    info.aliases.add(alias)
        return jitted

    # -- body checks ------------------------------------------------------

    def _check_body(self, path: str, info: _JitInfo,
                    idx: _ModuleIndex) -> list[Finding]:
        findings: list[Finding] = []
        static = info.static_params()
        traced = set(info.params()) - static - {"self"}
        qual = info.qualname

        for node in ast.walk(info.node):
            if isinstance(node, ast.Call):
                findings += self._sync_call(path, qual, node, static, idx)
            elif isinstance(node, (ast.If, ast.While)):
                name = self._traced_test_name(node.test, traced)
                if name is not None:
                    findings.append(Finding(
                        "jit-traced-branch", path, node.lineno, qual,
                        f"branch:{name}",
                        f"Python branch on traced value {name!r} inside a "
                        "jitted function — traces fail (or retrace per value "
                        "if made static); use jnp.where/lax.cond"))
            elif isinstance(node, ast.Global):
                findings.append(Finding(
                    "jit-mutable-closure", path, node.lineno, qual,
                    f"global:{','.join(node.names)}",
                    "mutating module state from a jitted function never "
                    "lands in the compiled program"))
            elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
                if node.id in idx.mutable_globals and node.id not in traced \
                        and node.id not in static \
                        and not self._is_local(info.node, node.id):
                    findings.append(Finding(
                        "jit-mutable-closure", path, node.lineno, qual,
                        f"closure:{node.id}",
                        f"jitted function closes over mutable module global "
                        f"{node.id!r} (defined line "
                        f"{idx.mutable_globals[node.id]}); its value is "
                        "frozen at trace time — pass it as an argument"))
        return findings

    @staticmethod
    def _is_local(fn: ast.FunctionDef, name: str) -> bool:
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store) \
                    and node.id == name:
                return True
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn and name in [a.arg for a in
                                                    node.args.args]:
                return True
        return False

    @staticmethod
    def _maybe_traced(expr: ast.expr, static: set,
                      idx: _ModuleIndex) -> bool:
        """Could this expression carry a traced value? False when every Name
        it references is a module-level constant/import or a static param —
        then the call is a trace-time constant, the idiomatic way to bake
        host math into the program (e.g. float(np.log(GAMMA)))."""
        for n in ast.walk(expr):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
                if n.id not in idx.module_names and n.id not in static:
                    return True
        return False

    def _sync_call(self, path: str, qual: str, node: ast.Call,
                   static: set, idx: _ModuleIndex) -> list[Finding]:
        func = node.func
        # float(x) on a potentially-traced value (params/locals); float() of
        # module constants is trace-time host math and fine
        if isinstance(func, ast.Name) and func.id == "float" and node.args:
            if self._maybe_traced(node.args[0], static, idx):
                return [Finding(
                    "jit-host-sync", path, node.lineno, qual, "float()",
                    "float() on a traced value inside jit is a device→host "
                    "sync (ConcretizationError on abstract values) — keep it "
                    "as a 0-d array or make the argument static")]
        if isinstance(func, ast.Attribute):
            if func.attr in HOST_SYNC_ATTRS \
                    and self._maybe_traced(func.value, static, idx):
                return [Finding(
                    "jit-host-sync", path, node.lineno, qual, ".item()",
                    ".item() inside jit forces a device→host sync — return "
                    "the array and fetch outside the jitted function")]
            d = _dotted(func)
            if d:
                root, _, leaf = d.rpartition(".")
                if root in idx.numpy_aliases and leaf in NUMPY_SYNC_FUNCS \
                        and any(self._maybe_traced(a, static, idx)
                                for a in node.args):
                    return [Finding(
                        "jit-host-sync", path, node.lineno, qual, f"np.{leaf}",
                        f"{d}() inside jit materializes the traced value on "
                        "host — use jnp instead, or hoist out of the jitted "
                        "function")]
                if root in idx.jax_aliases and leaf in JAX_SYNC_FUNCS:
                    return [Finding(
                        "jit-host-sync", path, node.lineno, qual,
                        f"jax.{leaf}",
                        f"{d}() inside jit is a device→host transfer — fetch "
                        "outside the compiled function")]
        return []

    def _traced_test_name(self, test: ast.expr, traced: set) -> str | None:
        """The name of a traced parameter the branch condition depends on,
        or None when the test is statically evaluable (shape/len/isinstance/
        `is (not) None` forms)."""
        hits: list[str] = []

        def scan(node: ast.expr):
            if isinstance(node, ast.Attribute):
                if node.attr in STATIC_TEST_ATTRS:
                    return
                scan(node.value)
            elif isinstance(node, ast.Call):
                fname = _dotted(node.func)
                if fname and fname.split(".")[-1] in STATIC_TEST_CALLS:
                    return
                for a in node.args:
                    scan(a)
            elif isinstance(node, ast.Compare):
                if all(isinstance(op, (ast.Is, ast.IsNot))
                       for op in node.ops):
                    return
                scan(node.left)
                for c in node.comparators:
                    scan(c)
            elif isinstance(node, ast.BoolOp):
                for v in node.values:
                    scan(v)
            elif isinstance(node, ast.UnaryOp):
                scan(node.operand)
            elif isinstance(node, ast.BinOp):
                scan(node.left)
                scan(node.right)
            elif isinstance(node, ast.Subscript):
                scan(node.value)
            elif isinstance(node, ast.Name) and node.id in traced:
                hits.append(node.id)

        scan(test)
        return hits[0] if hits else None

    # -- decoration + call-site checks ------------------------------------

    def _check_decoration(self, path: str, info: _JitInfo) -> list[Finding]:
        findings = []
        static = info.static_params()
        args = info.node.args
        defaults = dict(zip([a.arg for a in args.args][-len(args.defaults):]
                            if args.defaults else [], args.defaults))
        for name in sorted(static):
            d = defaults.get(name)
            if isinstance(d, ast.Constant) and isinstance(d.value, float):
                findings.append(Finding(
                    "jit-static-args", path, info.node.lineno, info.qualname,
                    f"static-float:{name}",
                    f"static arg {name!r} defaults to a float — each "
                    "distinct value retraces the whole program; pass floats "
                    "as traced 0-d arrays"))
        return findings

    def _check_call_sites(self, path: str, tree: ast.Module,
                          jitted: dict[int, _JitInfo]) -> list[Finding]:
        by_alias: dict[str, _JitInfo] = {}
        for info in jitted.values():
            for alias in info.aliases:
                by_alias[alias] = info
        findings: list[Finding] = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)):
                continue
            info = by_alias.get(node.func.id)
            if info is None or node.func.id == info.node.name and \
                    node.lineno == info.node.lineno:
                continue
            plist = info.params()
            static = info.static_params()
            for i, arg in enumerate(node.args):
                if i < len(plist) and plist[i] in static:
                    findings += self._static_arg_value(
                        path, node.func.id, plist[i], arg)
            for kw in node.keywords:
                if kw.arg in static:
                    findings += self._static_arg_value(
                        path, node.func.id, kw.arg, kw.value)
        return findings

    def _static_arg_value(self, path: str, callee: str, pname: str,
                          arg: ast.expr) -> list[Finding]:
        sym = f"<call:{callee}>"
        if isinstance(arg, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                            ast.SetComp, ast.DictComp, ast.GeneratorExp)):
            return [Finding(
                "jit-static-args", path, arg.lineno, sym,
                f"unhashable:{pname}",
                f"unhashable value for static arg {pname!r} of jitted "
                f"{callee}() — static args are dict keys of the trace "
                "cache; pass a tuple")]
        if isinstance(arg, ast.Call):
            fname = _dotted(arg.func)
            leaf = fname.split(".")[-1] if fname else ""
            if leaf in UNHASHABLE_CTORS or (fname and leaf in ("asarray",
                                                               "array")):
                return [Finding(
                    "jit-static-args", path, arg.lineno, sym,
                    f"unhashable:{pname}",
                    f"unhashable {fname}(...) for static arg {pname!r} of "
                    f"jitted {callee}() — static args must be hashable")]
            if leaf == "float":
                return [Finding(
                    "jit-static-args", path, arg.lineno, sym,
                    f"float:{pname}",
                    f"float-typed static arg {pname!r} of jitted {callee}() "
                    "— retraces per distinct value")]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, float):
            return [Finding(
                "jit-static-args", path, arg.lineno, sym, f"float:{pname}",
                f"float literal for static arg {pname!r} of jitted "
                f"{callee}() — retraces per distinct value; hoist to a "
                "module constant or pass as a traced 0-d array")]
        return []
