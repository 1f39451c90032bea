"""Whole-program index + resolved call graph (the interprocedural
substrate under the NATIVE5xx/LOCK4xx families and the transitive
DEVICE/ASYNC upgrades).

PR 5 made the dispatch hot path depend on invariants that live ACROSS
functions: a cached ``native_views`` pointer must die before any arena
growth, a host sync two helper calls deep inside a ``@jax.jit`` region
still destroys the perf story, and a lock-order inversion split across
modules hangs the broker just as dead as one in a single function.
The PR-2 analyzer is intra-function, so all of those are invisible to
it.  This module builds what the rules need to see them:

  * a per-file **ModuleIndex** — every function/method by dotted
    qualname, classes with their methods/bases, import aliases
    (``import x as y`` / ``from . import z``), module-level aliases
    (``g = f``, ``g = functools.partial(f, ...)``), instance-attribute
    types (``self.router = Router(...)``), and parameter/variable type
    annotations — cached by file (mtime, size) so repeated whole-tree
    runs re-parse nothing that didn't change;
  * a **Program** over the indexed files with ``resolve_call``:
    direct calls, ``self.``/``cls.`` methods (own class, one level of
    base classes, ``self.x = self._m`` attribute aliasing), calls
    through import aliases, one-level local aliasing
    (``fn = self._m; fn()``), ``functools.partial``, and
    attribute/annotation-typed receivers
    (``enc: "C.DispatchEncoder"`` → ``enc.slot_for`` resolves).

Resolution is deliberately an UNDER-approximation: a name the index
cannot pin to exactly one function yields no edge.  Rules built on top
stay quiet rather than spam — the same contract as the staticness
classifier in devicerules.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .engine import call_tail, dotted_name

# known GIL-released native entry points: the C ABI symbol prefixes of
# native/*.cpp (da_=dispatchasm, ht_=hosttrie, td_=tokdict,
# su_=sortutil, dslog_=dslog, sw_=sockwriter).  A call whose tail matches is a "native
# call" base fact; wrappers (ops.dispatchasm.assemble_run, ...) pick
# it up transitively through their summaries.
NATIVE_ENTRY_PREFIXES: Tuple[str, ...] = (
    "da_", "ht_", "td_", "su_", "dslog_", "sw_",
)


def is_native_entry(tail: str) -> bool:
    return tail.startswith(NATIVE_ENTRY_PREFIXES)


def module_dotted(path: str) -> str:
    """'emqx_tpu/broker/session.py' -> 'emqx_tpu.broker.session';
    '__init__.py' names the package itself."""
    p = path[:-3] if path.endswith(".py") else path
    parts = [x for x in p.split("/") if x]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


class FuncInfo:
    """One function/method in the program (identity: path, qualname)."""

    __slots__ = ("module", "qualname", "node", "is_async", "cls",
                 "name", "_locals")

    def __init__(self, module: "ModuleIndex", qualname: str,
                 node: ast.AST, cls: Optional[str]) -> None:
        self.module = module
        self.qualname = qualname
        self.node = node
        self.is_async = isinstance(node, ast.AsyncFunctionDef)
        self.cls = cls               # enclosing class name (or None)
        self.name = node.name        # bare name
        self._locals = None          # lazy per-function alias/type maps

    @property
    def key(self) -> Tuple[str, str]:
        return (self.module.path, self.qualname)

    def __repr__(self) -> str:  # debugging aid only
        return f"<FuncInfo {self.module.path}:{self.qualname}>"


class _ClassInfo:
    __slots__ = ("name", "methods", "bases", "attr_aliases",
                 "attr_types")

    def __init__(self, name: str) -> None:
        self.name = name
        self.methods: Dict[str, str] = {}     # bare -> qualname
        self.bases: List[ast.expr] = []       # base class expressions
        # self.x = self._m  ->  attr_aliases['x'] = '_m'
        self.attr_aliases: Dict[str, str] = {}
        # self.x = Router(...)  ->  attr_types['x'] = <ctor expr>
        self.attr_types: Dict[str, ast.expr] = {}


class ModuleIndex:
    """Parse + index of one source file (shared with ModuleContext:
    the tree is parsed once per (mtime, size) and reused by both the
    per-file rule families and the program passes)."""

    def __init__(self, path: str, source: str) -> None:
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree: ast.Module = ast.parse(source, filename=path)
        self.dotted = module_dotted(path)
        self.funcs: Dict[str, FuncInfo] = {}       # qualname -> info
        self.classes: Dict[str, _ClassInfo] = {}
        self.import_mods: Dict[str, str] = {}      # alias -> module
        self.from_imports: Dict[str, Tuple[str, str]] = {}
        self.mod_aliases: Dict[str, str] = {}      # g = f (top level)
        self.mod_types: Dict[str, ast.expr] = {}   # x = Cls() (top)
        # run-to-run caches (valid for this (mtime, size) index):
        self.file_cache = None     # (findings, io_methods, fp_methods)
        self.wrapped_cache = None  # devicerules._wrapped_names result
        # per-file PROGRAM findings cache: (dep_digest, findings).
        # NOT keyed by this file's identity alone — the digest covers
        # the dependency summaries, so editing ONLY a callee
        # invalidates the caller's entry (engine._dep_digest)
        self.program_cache = None
        self.from_cache = False    # did index_file serve this warm?
        self._index()

    # ------------------------------------------------------- indexing

    def _pkg_parts(self) -> List[str]:
        parts = self.dotted.split(".") if self.dotted else []
        if self.path.endswith("__init__.py"):
            return parts
        return parts[:-1]

    def _rel_base(self, level: int) -> Optional[str]:
        pkg = self._pkg_parts()
        if level - 1 > len(pkg):
            return None
        base = pkg[: len(pkg) - (level - 1)] if level > 1 else pkg
        return ".".join(base)

    def _index(self) -> None:
        stack: List[str] = []

        def walk(node: ast.AST, cls: Optional[_ClassInfo]) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    stack.append(child.name)
                    ci = self.classes.setdefault(
                        child.name, _ClassInfo(child.name)
                    )
                    ci.bases = list(child.bases)
                    walk(child, ci)
                    stack.pop()
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    stack.append(child.name)
                    qual = ".".join(stack)
                    fi = FuncInfo(self, qual, child,
                                  cls.name if cls else None)
                    self.funcs[qual] = fi
                    if cls is not None and len(stack) == 2:
                        cls.methods[child.name] = qual
                    if cls is not None:
                        self._scan_self_assigns(child, cls)
                    # nested defs index under their parent's qualname
                    walk(child, None)
                    stack.pop()
                else:
                    if not stack:
                        self._index_toplevel(child)
                    elif isinstance(child, (ast.Import,
                                            ast.ImportFrom)):
                        # function-level imports (the lazy-import
                        # idiom) index too; top-level entries win on
                        # a name conflict
                        self._index_import(child, top=False)
                    walk(child, cls)

        walk(self.tree, None)

    def _index_import(self, node: ast.AST, top: bool) -> None:
        if isinstance(node, ast.Import):
            for a in node.names:
                key = a.asname or a.name.split(".")[0]
                if top:
                    self.import_mods[key] = a.name
                else:
                    self.import_mods.setdefault(key, a.name)
        elif isinstance(node, ast.ImportFrom):
            base = (self._rel_base(node.level) if node.level
                    else node.module)
            if node.level and node.module:
                base = f"{base}.{node.module}" if base else node.module
            if base is None:
                return
            for a in node.names:
                if a.name == "*":
                    continue
                key = a.asname or a.name
                if top:
                    self.from_imports[key] = (base, a.name)
                else:
                    self.from_imports.setdefault(key, (base, a.name))

    def _index_toplevel(self, node: ast.AST) -> None:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            self._index_import(node, top=True)
        elif isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if isinstance(t, ast.Name):
                v = _alias_target(node.value)
                if v is not None:
                    self.mod_aliases[t.id] = v
                elif isinstance(node.value, ast.Call):
                    self.mod_types[t.id] = node.value.func

    def _scan_self_assigns(self, fn: ast.AST, cls: _ClassInfo) -> None:
        """Record ``self.x = self._m`` aliases and
        ``self.x = Router(...)`` instance-attribute types (one level:
        the constructor expression resolves at lookup time)."""
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1):
                continue
            t = node.targets[0]
            if not (isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"):
                continue
            v = node.value
            if (isinstance(v, ast.Attribute)
                    and isinstance(v.value, ast.Name)
                    and v.value.id == "self"):
                cls.attr_aliases.setdefault(t.attr, v.attr)
            elif isinstance(v, ast.Call) and not isinstance(
                v.func, ast.Lambda
            ):
                cls.attr_types.setdefault(t.attr, v.func)

    # ---------------------------------------------------- suppression

    def suppressed(self, line: int, rule: str) -> bool:
        """Same contract as ModuleContext (delegates to the ONE
        shared matcher) — base facts (e.g. a justified blocking call
        in a loader) respect inline ignores so they don't propagate
        through summaries either."""
        from .engine import site_suppressed

        return site_suppressed(self.lines, line, rule)


def _alias_target(value: ast.expr) -> Optional[str]:
    """The aliased NAME for ``g = f`` / ``g = functools.partial(f,..)``
    (None when the rhs is not an alias shape)."""
    if isinstance(value, ast.Name):
        return value.id
    if isinstance(value, ast.Call) and dotted_name(
        value.func
    ).endswith("partial") and value.args:
        a = value.args[0]
        if isinstance(a, ast.Name):
            return a.id
        if isinstance(a, ast.Attribute):
            return dotted_name(a)
    return None


# per-file index cache: abspath -> (mtime_ns, size, ModuleIndex).
# run_lint hits this once per file per run; editing a file (new mtime
# or size) invalidates exactly that entry.
_INDEX_CACHE: Dict[str, Tuple[int, int, ModuleIndex]] = {}


def index_file(abspath: str, rel: str) -> ModuleIndex:
    st = os.stat(abspath)
    key = (st.st_mtime_ns, st.st_size)
    hit = _INDEX_CACHE.get(abspath)
    if hit is not None and (hit[0], hit[1]) == key and \
            hit[2].path == rel:
        hit[2].from_cache = True
        return hit[2]
    with open(abspath, "r") as f:
        source = f.read()
    idx = ModuleIndex(rel, source)  # may raise SyntaxError (caller)
    _INDEX_CACHE[abspath] = (key[0], key[1], idx)
    return idx


class Program:
    """The indexed modules plus cross-module call resolution."""

    def __init__(self, modules: Dict[str, ModuleIndex]) -> None:
        self.modules = modules                       # rel path -> idx
        self.by_dotted: Dict[str, ModuleIndex] = {
            m.dotted: m for m in modules.values()
        }
        self._edges: Optional[Dict[Tuple[str, str],
                                   List[Tuple[ast.Call, FuncInfo]]]] \
            = None

    # ------------------------------------------------------ iteration

    def functions(self) -> List[FuncInfo]:
        out: List[FuncInfo] = []
        for m in self.modules.values():
            out.extend(m.funcs.values())
        return out

    # ------------------------------------------------------- lookups

    def _module_for(self, dotted: str) -> Optional[ModuleIndex]:
        return self.by_dotted.get(dotted)

    def lookup_toplevel(self, mod: ModuleIndex,
                        name: str) -> Optional[FuncInfo]:
        fi = mod.funcs.get(name)
        if fi is not None:
            return fi
        alias = mod.mod_aliases.get(name)
        if alias is not None and alias != name:
            return self.resolve_name(mod, alias)
        return None

    def lookup_class(self, mod: ModuleIndex,
                     name: str) -> Optional[Tuple[ModuleIndex,
                                                  _ClassInfo]]:
        ci = mod.classes.get(name)
        if ci is not None:
            return (mod, ci)
        imp = mod.from_imports.get(name)
        if imp is not None:
            base, orig = imp
            target = self._module_for(base)
            if target is not None and orig in target.classes:
                return (target, target.classes[orig])
            # `from x import y` where y is a submodule holding nothing
            # by this name: give up
        return None

    def _class_ref(self, mod: ModuleIndex,
                   expr: ast.expr) -> Optional[Tuple[ModuleIndex,
                                                     _ClassInfo]]:
        """Resolve a class-naming expression (``Router``, ``C.Foo``,
        ``Optional[Session]``, a string annotation's parsed body) to
        its _ClassInfo."""
        if isinstance(expr, ast.Constant) and isinstance(
            expr.value, str
        ):
            try:
                expr = ast.parse(expr.value, mode="eval").body
            except SyntaxError:
                return None
        # unwrap Optional[X] / typing wrappers one level
        if isinstance(expr, ast.Subscript) and dotted_name(
            expr.value
        ).rpartition(".")[2] in ("Optional",):
            expr = expr.slice
        if isinstance(expr, ast.Name):
            return self.lookup_class(mod, expr.id)
        if isinstance(expr, ast.Attribute) and isinstance(
            expr.value, ast.Name
        ):
            base = expr.value.id
            target_mod = None
            if base in mod.import_mods:
                target_mod = self._module_for(mod.import_mods[base])
            elif base in mod.from_imports:
                b, orig = mod.from_imports[base]
                target_mod = self._module_for(f"{b}.{orig}") or \
                    self._module_for(b)
            if target_mod is not None:
                ci = target_mod.classes.get(expr.attr)
                if ci is not None:
                    return (target_mod, ci)
        return None

    def _method_in(self, mod: ModuleIndex, ci: _ClassInfo, name: str,
                   depth: int = 0) -> Optional[FuncInfo]:
        qual = ci.methods.get(name)
        if qual is not None:
            return mod.funcs.get(qual)
        alias = ci.attr_aliases.get(name)
        if alias is not None and alias != name:
            qual = ci.methods.get(alias)
            if qual is not None:
                return mod.funcs.get(qual)
        if depth < 1:  # one level of base classes
            for b in ci.bases:
                ref = self._class_ref(mod, b)
                if ref is not None:
                    hit = self._method_in(ref[0], ref[1], name,
                                          depth + 1)
                    if hit is not None:
                        return hit
        return None

    def resolve_name(self, mod: ModuleIndex,
                     name: str) -> Optional[FuncInfo]:
        """A bare NAME in module scope: local function, alias chain,
        constructor (``Cls()`` resolves to ``Cls.__init__``), or
        from-import of a function in an indexed module."""
        fi = mod.funcs.get(name)
        if fi is not None:
            return fi
        alias = mod.mod_aliases.get(name)
        if alias is not None and alias != name:
            return self.resolve_name(mod, alias)
        ref = self.lookup_class(mod, name)
        if ref is not None:
            return self._method_in(ref[0], ref[1], "__init__")
        imp = mod.from_imports.get(name)
        if imp is not None:
            base, orig = imp
            target = self._module_for(base)
            if target is not None:
                return self.lookup_toplevel(target, orig)
        return None

    # ------------------------------------------- per-function locals

    def _fn_locals(self, fn: FuncInfo) -> Tuple[Dict[str, str],
                                                Dict[str, str],
                                                Dict[str, ast.AST]]:
        """(local one-level aliases, self-attr aliases, local var
        types) for `fn`: ``g = self._m`` / ``g = partial(f, ..)``
        aliases, ``nat = self._native`` self-attribute aliases, plus
        ``x = Router(...)`` / ``x = self.cm.lookup(...)`` (typed by
        constructor or the callee's return annotation) / annotated
        params & AnnAssigns."""
        if fn._locals is not None:
            return fn._locals
        aliases: Dict[str, str] = {}
        self_aliases: Dict[str, str] = {}
        types: Dict[str, ast.AST] = {}
        node = fn.node
        args = node.args
        for a in (args.posonlyargs + args.args + args.kwonlyargs):
            if a.annotation is not None:
                types[a.arg] = a.annotation
        for sub in ast.walk(node):
            if isinstance(sub, ast.Assign) and len(sub.targets) == 1 \
                    and isinstance(sub.targets[0], ast.Name):
                t = sub.targets[0].id
                v = _alias_target(sub.value)
                if v is not None:
                    aliases.setdefault(t, v)
                elif isinstance(sub.value, ast.Attribute) and \
                        isinstance(sub.value.value, ast.Name) and \
                        sub.value.value.id in ("self", "cls"):
                    self_aliases.setdefault(t, sub.value.attr)
                elif isinstance(sub.value, ast.Call):
                    # store the whole Call: the type may come from
                    # the constructor OR the callee's return
                    # annotation
                    types.setdefault(t, sub.value)
            elif isinstance(sub, ast.AnnAssign) and isinstance(
                sub.target, ast.Name
            ):
                types.setdefault(sub.target.id, sub.annotation)
        fn._locals = (aliases, self_aliases, types)
        return fn._locals

    def _type_of_local(self, fn: FuncInfo, name: str,
                       _depth: int = 0
                       ) -> Optional[Tuple[ModuleIndex, _ClassInfo]]:
        """The class a local/param resolves to: annotation,
        constructor call, self-attr alias through the class's
        attr_types, or the return annotation of the call that bound
        it (``session = self.cm.lookup(cid)`` with
        ``lookup() -> Optional[Session]``)."""
        if _depth > 3:
            return None
        mod = fn.module
        _aliases, self_aliases, types = self._fn_locals(fn)
        attr = self_aliases.get(name)
        if attr is not None and fn.cls is not None:
            ci = mod.classes.get(fn.cls)
            if ci is not None:
                ctor = ci.attr_types.get(attr)
                if ctor is not None:
                    return self._class_ref(mod, ctor)
            return None
        ann = types.get(name)
        if ann is None:
            return None
        if isinstance(ann, ast.Call):
            ref = self._class_ref(mod, ann.func)
            if ref is not None:
                return ref
            callee = self._resolve_expr(ann.func, fn, depth=_depth + 1)
            if callee is not None and getattr(
                callee.node, "returns", None
            ) is not None:
                return self._class_ref(callee.module,
                                       callee.node.returns)
            return None
        return self._class_ref(mod, ann)

    # -------------------------------------------------- call resolve

    def resolve_call(self, call: ast.Call,
                     fn: FuncInfo) -> Optional[FuncInfo]:
        return self._resolve_expr(call.func, fn, depth=0)

    def _resolve_expr(self, f: ast.expr, fn: FuncInfo,
                      depth: int) -> Optional[FuncInfo]:
        if depth > 4:
            return None
        mod = fn.module
        if isinstance(f, ast.Name):
            aliases, self_aliases, _types = self._fn_locals(fn)
            tgt = self_aliases.get(f.id)
            if tgt is not None:
                # `h = self._m; h()` resolves as the aliased method
                return self._resolve_self_attr(fn, tgt)
            tgt = aliases.get(f.id)
            if tgt is not None and tgt != f.id:
                hit = self._resolve_self_attr(fn, tgt)
                if hit is not None:
                    return hit
                return self.resolve_name(mod, tgt)
            return self.resolve_name(mod, f.id)
        if isinstance(f, ast.Attribute):
            base = f.value
            # self.m() / cls.m()
            if isinstance(base, ast.Name) and base.id in (
                "self", "cls"
            ):
                return self._resolve_self_attr(fn, f.attr)
            # self.attr.m(): typed instance attribute receiver
            if (isinstance(base, ast.Attribute)
                    and isinstance(base.value, ast.Name)
                    and base.value.id in ("self", "cls")
                    and fn.cls is not None):
                ci = mod.classes.get(fn.cls)
                if ci is not None:
                    ctor = ci.attr_types.get(base.attr)
                    if ctor is not None:
                        ref = self._class_ref(mod, ctor)
                        if ref is not None:
                            return self._method_in(ref[0], ref[1],
                                                   f.attr)
                return None
            if isinstance(base, ast.Name):
                # import alias: mod.f() / pkg-level from-import
                if base.id in mod.import_mods:
                    target = self._module_for(mod.import_mods[base.id])
                    if target is not None:
                        return self.lookup_toplevel(target, f.attr)
                    return None
                if base.id in mod.from_imports:
                    b, orig = mod.from_imports[base.id]
                    target = self._module_for(f"{b}.{orig}")
                    if target is not None:
                        return self.lookup_toplevel(target, f.attr)
                    target = self._module_for(b)
                    if target is not None:
                        # `from x import y` where y is a class
                        ci = target.classes.get(orig)
                        if ci is not None:
                            return self._method_in(target, ci, f.attr)
                    return None
                # ClassName.method(...)
                ref = self.lookup_class(mod, base.id)
                if ref is not None:
                    return self._method_in(ref[0], ref[1], f.attr)
                # typed local/param receiver: enc.slot_for() — via
                # annotation, constructor, self-attr alias, or the
                # binding call's return annotation
                ref = self._type_of_local(fn, base.id, depth + 1)
                if ref is not None:
                    return self._method_in(ref[0], ref[1], f.attr)
            return None
        return None

    def _resolve_self_attr(self, fn: FuncInfo,
                           attr: str) -> Optional[FuncInfo]:
        if fn.cls is None:
            return None
        mod = fn.module
        ci = mod.classes.get(fn.cls)
        if ci is None:
            return None
        return self._method_in(mod, ci, attr)

    # ------------------------------------------------------- edges

    def callees(self, fn: FuncInfo) -> List[Tuple[ast.Call, FuncInfo]]:
        """Resolved (call node, callee) pairs lexically in `fn`
        (nested defs pruned — they are their own FuncInfos)."""
        edges = self._edges
        if edges is None:
            edges = self._edges = {}
        hit = edges.get(fn.key)
        if hit is not None:
            return hit
        out: List[Tuple[ast.Call, FuncInfo]] = []

        def walk(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef,
                                      ast.Lambda)) and child is not \
                        fn.node:
                    continue
                if isinstance(child, ast.Call):
                    callee = self.resolve_call(child, fn)
                    if callee is not None and callee is not fn:
                        out.append((child, callee))
                walk(child)

        walk(fn.node)
        edges[fn.key] = out
        return out


def build_program(modules: Dict[str, ModuleIndex]) -> Program:
    return Program(modules)


__all__ = [
    "FuncInfo", "ModuleIndex", "NATIVE_ENTRY_PREFIXES", "Program",
    "build_program", "index_file", "is_native_entry", "module_dotted",
]
