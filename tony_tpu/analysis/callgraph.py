"""Cross-module call graph + jit-entry reachability for graft-lint.

Deliberately an under-approximation: names are resolved through explicit
imports, ``self.``/``cls.`` method access, module-level aliases
(``g = partial(f, ...)``), call arguments that are function references
(``lax.scan(block, ...)`` adds caller -> block), and ONE module idiom:
``m = pick(...)`` where every ``return`` of ``pick`` names an imported
module makes ``m.func(...)`` a call into each of them (how
serve/engine.py chooses a model family's steps). Other dynamic dispatch
through duck-typed attributes is NOT resolved — checkers that need it (GL004's
RPC-ish calls) match attribute patterns instead. Under-approximating keeps
the zero-findings tier-1 gate honest: every finding is explainable from
the source, so a clean tree stays clean without blanket suppressions.

Jit entry points ("roots"): functions decorated with / passed to
``jax.jit`` / ``jit`` / ``pjit`` (directly or through ``partial``). Every
function transitively callable from a root body is **traced** — code in it
runs under tracing, where a host sync or Python branch on a tracer is a
silent recompile/stall (GL001/GL002). Calling an already-jitted function
does not make the *caller* traced.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Iterable

from tony_tpu.analysis.core import SourceFile

_JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit", "jax.experimental.pjit.pjit"}
_PARTIAL_NAMES = {"partial", "functools.partial"}


def dotted(node: ast.expr) -> str | None:
    """Textual dotted name of a Name/Attribute chain (None otherwise)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def unwrap_partial(node: ast.expr) -> ast.expr:
    """``partial(f, ...)`` / ``functools.partial(f, ...)`` -> ``f``."""
    if isinstance(node, ast.Call) and dotted(node.func) in _PARTIAL_NAMES and node.args:
        return node.args[0]
    return node


@dataclass
class FuncInfo:
    module: str
    local: str          # "func", "Class.method", "outer.inner"
    node: ast.AST       # FunctionDef | AsyncFunctionDef | Lambda
    class_name: str = ""  # innermost enclosing class ("" for free functions)
    callees: set[str] = field(default_factory=set)  # resolved qualnames

    @property
    def qualname(self) -> str:
        return f"{self.module}:{self.local}"


@dataclass
class JitCall:
    """One ``jax.jit(...)`` call site (GL002/GL003 consume these)."""

    module: str
    func: "FuncInfo | None"   # enclosing function (None = module level)
    node: ast.Call
    target: "FuncInfo | None"  # the function being jitted, when resolvable
    donate: tuple[int, ...] = ()
    static_argnums: tuple[int, ...] = ()
    static_argnames: tuple[str, ...] = ()


class _ModuleIndex:
    def __init__(self, sf: SourceFile):
        self.sf = sf
        self.modname = sf.modname
        self.funcs: dict[str, FuncInfo] = {}
        # import name -> ("mod", dotted_module) | ("sym", module, symbol)
        self.imports: dict[str, tuple] = {}
        # module-level: alias name -> candidate function qualnames (an
        # alias assigned in both branches of an if keeps both candidates)
        self.aliases: dict[str, tuple[str, ...]] = {}


def _const_index_tuple(node: ast.expr | None) -> tuple[int, ...]:
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
        return tuple(out)
    return ()


def _const_str_tuple(node: ast.expr | None) -> tuple[str, ...]:
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(
            e.value for e in node.elts
            if isinstance(e, ast.Constant) and isinstance(e.value, str)
        )
    return ()


class Project:
    """Parsed modules + call graph + jit reachability (see module doc)."""

    def __init__(self, sources: Iterable[SourceFile]):
        self.sources = list(sources)
        self.by_path: dict[str, SourceFile] = {s.path: s for s in self.sources}
        self.modules: dict[str, _ModuleIndex] = {}
        self.funcs: dict[str, FuncInfo] = {}
        self.jit_calls: list[JitCall] = []
        self.jit_roots: dict[str, str] = {}  # qualname -> why
        # traced qualname -> one root it is reachable from
        self.traced_from: dict[str, str] = {}
        self._index_all()
        self._resolve_all()
        self._mark_traced()

    # --- pass 1: symbols ------------------------------------------------------

    def _index_all(self) -> None:
        for sf in self.sources:
            mi = _ModuleIndex(sf)
            self.modules[sf.modname] = mi
            self._collect(mi, sf.tree, prefix="", class_name="")
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.Import):
                    for a in node.names:
                        name = a.asname or a.name.split(".")[0]
                        mi.imports[name] = ("mod", a.name if a.asname else name)
                        if not a.asname:
                            # "import a.b.c" binds "a" but makes the full
                            # dotted path resolvable too
                            mi.imports.setdefault(a.name, ("mod", a.name))
                elif isinstance(node, ast.ImportFrom) and node.module and not node.level:
                    for a in node.names:
                        mi.imports[a.asname or a.name] = (
                            "sym", node.module, a.name
                        )
            for fi in mi.funcs.values():
                self.funcs[fi.qualname] = fi

    def _collect(self, mi: _ModuleIndex, node: ast.AST, prefix: str,
                 class_name: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                local = f"{prefix}{child.name}"
                mi.funcs[local] = FuncInfo(mi.modname, local, child, class_name)
                self._collect(mi, child, prefix=f"{local}.", class_name=class_name)
            elif isinstance(child, ast.ClassDef):
                self._collect(mi, child, prefix=f"{prefix}{child.name}.",
                              class_name=child.name)

    # --- pass 2: resolution ---------------------------------------------------

    def resolve_candidates(self, mi: _ModuleIndex, caller: FuncInfo | None,
                           node: ast.expr,
                           local_aliases: dict[str, tuple[str, ...]] | None = None
                           ) -> tuple[FuncInfo, ...]:
        """All known functions a callee/argument expression may refer to
        (aliases assigned in different branches keep every candidate)."""
        node = unwrap_partial(node)
        name = dotted(node)
        if name is None:
            return ()
        parts = name.split(".")
        # self.method / cls.method -> same class (or any class up the chain)
        if parts[0] in ("self", "cls") and caller is not None and len(parts) == 2:
            if caller.class_name:
                fi = mi.funcs.get(f"{caller.class_name}.{parts[1]}")
                if fi is not None:
                    return (fi,)
            return ()
        if len(parts) == 1:
            for aliases in (local_aliases, mi.aliases):
                if aliases and name in aliases:
                    out = tuple(
                        self.funcs[q] for q in aliases[name] if q in self.funcs
                    )
                    if out:
                        return out
            # own nested function, then sibling nested, then module level
            if caller is not None:
                fi = mi.funcs.get(f"{caller.local}.{name}")
                if fi is not None:
                    return (fi,)
                scope = caller.local.rsplit(".", 1)[0] if "." in caller.local else ""
                if scope:
                    fi = mi.funcs.get(f"{scope}.{name}")
                    if fi is not None:
                        return (fi,)
            fi = mi.funcs.get(name)
            if fi is not None:
                return (fi,)
            imp = mi.imports.get(name)
            if imp is not None and imp[0] == "sym":
                target = self.modules.get(imp[1])
                if target is not None:
                    fi = target.funcs.get(imp[2])
                    if fi is not None:
                        return (fi,)
            return ()
        # head bound to the result of a module-returning function
        # (_scope_aliases keeps such a module as "<module>:")
        for aliases in (local_aliases, mi.aliases):
            mods = [q[:-1] for q in (aliases or {}).get(parts[0], ())
                    if q.endswith(":")]
            if mods:
                found = (self._resolve_in_module(m, ".".join(parts[1:]))
                         for m in mods)
                return tuple(fi for fi in found if fi is not None)
        fi = self._resolve_dotted(mi, parts)
        return (fi,) if fi is not None else ()

    def resolve_callable(self, mi: _ModuleIndex, caller: FuncInfo | None,
                         node: ast.expr,
                         local_aliases: dict[str, tuple[str, ...]] | None = None
                         ) -> FuncInfo | None:
        cands = self.resolve_candidates(mi, caller, node, local_aliases)
        return cands[0] if cands else None

    def _resolve_dotted(self, mi: _ModuleIndex, parts: list[str]
                        ) -> FuncInfo | None:
        # dotted: alias.func / package.module.func / Class.method
        head, rest = parts[0], ".".join(parts[1:])
        imp = mi.imports.get(head)
        if imp is not None:
            if imp[0] == "mod":
                return self._resolve_in_module(imp[1], rest)
            if imp[0] == "sym":
                # "from pkg import mod" then mod.func — or a class symbol
                target = self.modules.get(f"{imp[1]}.{imp[2]}")
                if target is not None:
                    return target.funcs.get(rest)
                target = self.modules.get(imp[1])
                if target is not None:
                    return target.funcs.get(f"{imp[2]}.{rest}")
                return None
        # full dotted path to an analyzed module ("import a.b.c" style)
        for split in range(len(parts) - 1, 0, -1):
            modname = ".".join(parts[:split])
            if modname in self.modules:
                return self.modules[modname].funcs.get(".".join(parts[split:]))
        # same-module Class.method
        return mi.funcs.get(".".join(parts))

    def _resolve_in_module(self, modname: str, local: str) -> FuncInfo | None:
        target = self.modules.get(modname)
        if target is None:
            # "import a.b" + "a.b.c.func": c may be a submodule
            head, _, rest = local.partition(".")
            if rest:
                return self._resolve_in_module(f"{modname}.{head}", rest)
            return None
        fi = target.funcs.get(local)
        if fi is not None:
            return fi
        head, _, rest = local.partition(".")
        if rest:
            return self._resolve_in_module(f"{modname}.{head}", rest)
        return None

    def dotted_resolved(self, mi: _ModuleIndex, node: ast.expr) -> str | None:
        """Dotted callee text with the first segment expanded through the
        import map (``from jax import jit`` -> ``jax.jit``)."""
        name = dotted(node)
        if name is None:
            return None
        head, _, rest = name.partition(".")
        imp = mi.imports.get(head)
        if imp is None:
            return name
        if imp[0] == "mod":
            return f"{imp[1]}.{rest}" if rest else imp[1]
        full = f"{imp[1]}.{imp[2]}"
        return f"{full}.{rest}" if rest else full

    def _scope_aliases(self, mi: _ModuleIndex, caller: FuncInfo | None,
                       root: ast.AST,
                       inherited: dict[str, tuple[str, ...]] | None = None
                       ) -> dict[str, tuple[str, ...]]:
        """Alias assignments anywhere in ``root``'s own body (not nested
        defs): ``g = f`` / ``g = partial(f, ...)`` / ``g = f1 if c else f2``.
        Two passes so alias-of-alias chains resolve."""
        aliases: dict[str, tuple[str, ...]] = dict(inherited or {})
        assigns = sorted(
            (n for n in self._own_nodes(root)
             if isinstance(n, ast.Assign) and len(n.targets) == 1
             and isinstance(n.targets[0], ast.Name)),
            key=lambda n: (n.lineno, n.col_offset),
        )
        for _ in range(2):
            for stmt in assigns:
                values = (
                    [stmt.value.body, stmt.value.orelse]
                    if isinstance(stmt.value, ast.IfExp) else [stmt.value]
                )
                quals: list[str] = []
                for v in values:
                    for fi in self.resolve_candidates(mi, caller, v, aliases):
                        if fi.qualname not in quals:
                            quals.append(fi.qualname)
                    if isinstance(v, ast.Call):
                        for fi in self.resolve_candidates(mi, caller, v.func, aliases):
                            quals += [m + ":" for m in self._returned_modules(fi)
                                      if m + ":" not in quals]
                if quals:
                    name = stmt.targets[0].id
                    merged = list(aliases.get(name, ()))
                    for q in quals:
                        if q not in merged:
                            merged.append(q)
                    aliases[name] = tuple(merged)
        return aliases

    def _returned_modules(self, fi: FuncInfo) -> list[str]:
        """Analyzed modules that ``fi``'s own ``return <name>`` statements
        name through its module's imports."""
        imports = self.modules[fi.module].imports
        out: list[str] = []
        for node in self._own_nodes(fi.node):
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            imp = imports.get(dotted(node.value) or "")
            if imp is None:
                continue
            mod = imp[1] if imp[0] == "mod" else f"{imp[1]}.{imp[2]}"
            if mod in self.modules and mod not in out:
                out.append(mod)
        return out

    def _own_nodes(self, root: ast.AST):
        stack = list(ast.iter_child_nodes(root))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef, ast.Lambda)):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))

    def _resolve_all(self) -> None:
        for mi in self.modules.values():
            mi.aliases = self._scope_aliases(mi, None, mi.sf.tree)
        for mi in self.modules.values():
            # parents before children so nested defs inherit aliases
            func_aliases: dict[str, dict[str, tuple[str, ...]]] = {}
            for local in sorted(mi.funcs, key=lambda q: q.count(".")):
                fi = mi.funcs[local]
                inherited = dict(mi.aliases)
                parent = local
                chain = []
                while "." in parent:
                    parent = parent.rsplit(".", 1)[0]
                    chain.append(parent)
                for anc in reversed(chain):
                    inherited.update(func_aliases.get(anc, {}))
                local_aliases = self._scope_aliases(mi, fi, fi.node, inherited)
                func_aliases[local] = local_aliases
                for node in self._own_calls(fi.node):
                    self._record_call(mi, fi, node, local_aliases)
            # module-level calls (jit roots defined at import time)
            for node in self._own_calls(mi.sf.tree, top=True):
                self._record_call(mi, None, node, mi.aliases)

    def _own_calls(self, root: ast.AST, top: bool = False):
        """Call nodes in ``root``'s body, not descending into nested
        function/class definitions (those index their own calls)."""
        stack = list(ast.iter_child_nodes(root))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                if not top:
                    continue
                # at module level, descend into classes but not functions
                if isinstance(node, ast.ClassDef):
                    stack.extend(ast.iter_child_nodes(node))
                continue
            if isinstance(node, ast.Call):
                yield node
            stack.extend(ast.iter_child_nodes(node))

    def _record_call(self, mi: _ModuleIndex, fi: FuncInfo | None,
                     node: ast.Call, aliases: dict[str, tuple[str, ...]]) -> None:
        callee_dotted = self.dotted_resolved(mi, node.func)
        if fi is not None:
            for target in self.resolve_candidates(mi, fi, node.func, aliases):
                fi.callees.add(target.qualname)
        if callee_dotted in _JIT_NAMES:
            self._record_jit(mi, fi, node, aliases)
            return
        # higher-order propagation: function references passed as args are
        # (likely) called by the callee in the caller's dynamic context —
        # lax.scan(block, ...), vmap(write), value_and_grad(loss_fn), hooks
        if fi is not None:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                for ref in self.resolve_candidates(mi, fi, arg, aliases):
                    fi.callees.add(ref.qualname)

    def _record_jit(self, mi: _ModuleIndex, fi: FuncInfo | None,
                    node: ast.Call, aliases: dict[str, str]) -> None:
        fn_node = node.args[0] if node.args else None
        for kw in node.keywords:
            if kw.arg in ("fun", "fn", "f") and fn_node is None:
                fn_node = kw.value
        target = (
            self.resolve_callable(mi, fi, fn_node, aliases)
            if fn_node is not None else None
        )
        kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
        jc = JitCall(
            module=mi.modname, func=fi, node=node, target=target,
            donate=_const_index_tuple(kwargs.get("donate_argnums")),
            static_argnums=_const_index_tuple(kwargs.get("static_argnums")),
            static_argnames=_const_str_tuple(kwargs.get("static_argnames")),
        )
        self.jit_calls.append(jc)
        if target is not None:
            self.jit_roots.setdefault(
                target.qualname,
                f"passed to {dotted(node.func)} at {mi.sf.path}:{node.lineno}",
            )

    # --- pass 3: reachability -------------------------------------------------

    def _mark_traced(self) -> None:
        # decorator roots
        for fi in self.funcs.values():
            deco_list = getattr(fi.node, "decorator_list", [])
            mi = self.modules[fi.module]
            for deco in deco_list:
                expr = deco.func if isinstance(deco, ast.Call) else deco
                expr = unwrap_partial(expr) if isinstance(deco, ast.Call) else expr
                name = self.dotted_resolved(mi, expr)
                if name in _JIT_NAMES or (
                    isinstance(deco, ast.Call)
                    and self.dotted_resolved(mi, deco.func) in _PARTIAL_NAMES
                    and deco.args
                    and self.dotted_resolved(mi, deco.args[0]) in _JIT_NAMES
                ):
                    self.jit_roots.setdefault(
                        fi.qualname, f"decorated @{name or 'jit'}"
                    )
        # closure
        for root in sorted(self.jit_roots):
            stack = [root]
            while stack:
                q = stack.pop()
                if q in self.traced_from:
                    continue
                self.traced_from[q] = root
                fi = self.funcs.get(q)
                if fi is None:
                    continue
                stack.extend(fi.callees - self.traced_from.keys())

    def is_traced(self, qualname: str) -> bool:
        return qualname in self.traced_from
