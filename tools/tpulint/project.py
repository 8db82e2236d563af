"""tpulint pass 1: repo-wide symbol table, call graph, device-context propagation.

The file-local engine (PR 1) missed hazards hidden one call away: a helper that
returns a `jnp` value branched on by its caller, a closure-append leak in a module
imported by the jitted root, a collective in a function only *reachable* from a
`shard_map`ed program. This pass builds the project-wide context every rule needs:

- **symbol table** — every function/method in the linted file set, keyed by
  (module, name); module names derive from repo-relative paths, with a
  basename fallback so explicit fixture files can import each other.
- **import resolution** — `from .mod import f` / `import pkg.mod as m` aliases
  per module, so Name and dotted calls resolve across files.
- **call graph** — per-function resolved callees (by-name within the module
  first, then through imports; unresolved names are kept for escape analysis).
- **traced closure** — functions reachable from jit/shard_map roots through the
  call graph, ACROSS modules (the "device context" that flows through helper
  calls; TPU003/TPU009 consume this, TPU001 extends its checks into it).
- **device-returning fixpoint** — functions whose return value is produced by a
  `jnp.*`/`lax.*` call, directly or via another device-returning function
  (TPU001's branch rule follows assignments through these).
- **shard_map coverage + mesh axes** — which functions execute inside a
  `shard_map` region (roots passed by name, their transitive callees, and
  escaping closures, which get the benefit of the doubt for factory patterns
  like mesh_search._mesh_score_program), plus every literal mesh axis name from
  `Mesh(...)` constructions (TPU006/TPU007 validate collective axes against
  these).

Resolution is intentionally static and conservative: anything dynamic (getattr,
decorators that rewrap) stays unresolved and never creates findings by itself.
The one dispatch that resolves is a module-level TABLE of functions (a dict,
tuple or list literal naming them, as execute.GROUP_KINDS does): a function of
that module that reads the table may call every function it names.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field

from .engine import SourceFile

_JIT_NAMES = {"jit"}
_SHARD_MAP_NAMES = {"shard_map", "pjit", "xmap"}
_DEVICE_MODULES = {"jnp", "lax"}
# jnp methods that produce HOST values, not device arrays
_HOST_RESULTS = {"tolist", "item"}


def _dotted(node: ast.AST) -> tuple[str, ...] | None:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


def _is_jit_name(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr in _JIT_NAMES) or \
        (isinstance(node, ast.Name) and node.id in _JIT_NAMES)


def _is_shard_map_name(node: ast.AST) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr in _SHARD_MAP_NAMES) or \
        (isinstance(node, ast.Name) and node.id in _SHARD_MAP_NAMES)


def module_name(relpath: str) -> str:
    """elasticsearch_tpu/ops/scoring.py -> elasticsearch_tpu.ops.scoring."""
    mod = relpath[:-3] if relpath.endswith(".py") else relpath
    mod = mod.replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[: -len(".__init__")]
    return mod


@dataclass
class FuncInfo:
    """One def in the project, with everything pass 2 asks about it."""

    fid: int
    module: str
    name: str
    qualname: str
    node: ast.AST
    sf: SourceFile
    nested: bool = False
    calls: set = field(default_factory=set)  # resolved fids
    called_names: set = field(default_factory=set)  # unresolved raw names
    escapes: bool = False  # referenced as a value (returned/stored/passed)
    returns_device_direct: bool = False  # a return expr is a jnp/lax call
    return_calls: set = field(default_factory=set)  # fids returned as f() results


class Project:
    """The interprocedural context, built once per lint run (pass 1)."""

    def __init__(self, files: list[SourceFile]):
        self.files = files
        self.functions: list[FuncInfo] = []
        self._by_module_name: dict[tuple[str, str], list[int]] = {}
        self._basename: dict[str, str] = {}  # short module name -> full
        self._imports: dict[str, dict[str, str]] = {}  # module -> alias -> target
        self.mesh_axes: set[str] = set()
        self.traced: set[int] = set()  # fids inside jit/shard_map tracing
        self.shard_map_covered: set[int] = set()  # fids inside a shard_map region
        self.device_returning: set[int] = set()
        self._fid_of_node: dict[int, int] = {}  # id(ast node) -> fid
        self._tables: dict[tuple[str, str], set[str]] = {}  # (module, name) -> names held

        for sf in files:
            self._index_file(sf)
        self._resolve_calls()
        self._propagate_device_returns()
        self._propagate_traced()

    # -- pass 1a: symbols, imports, meshes ----------------------------------
    def _index_file(self, sf: SourceFile) -> None:
        mod = module_name(sf.relpath)
        self._basename.setdefault(mod.rsplit(".", 1)[-1], mod)
        imports: dict[str, str] = {}
        self._imports[mod] = imports
        pkg_parts = mod.split(".")[:-1]

        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    imports[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom):
                if node.level:  # relative: climb from the containing package
                    base = pkg_parts[: len(pkg_parts) - (node.level - 1)]
                    src = ".".join(base + (node.module.split(".") if node.module
                                           else []))
                else:
                    src = node.module or ""
                for a in node.names:
                    imports[a.asname or a.name] = f"{src}.{a.name}" if src else a.name
            elif isinstance(node, ast.Call):
                self._note_mesh_axes(node)

        # functions, with class/nesting context
        def walk(scope, parents: list[str], nested: bool):
            for child in ast.iter_child_nodes(scope):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qual = ".".join(parents + [child.name]) if parents else child.name
                    fi = FuncInfo(fid=len(self.functions), module=mod,
                                  name=child.name, qualname=qual, node=child,
                                  sf=sf, nested=nested)
                    self.functions.append(fi)
                    self._fid_of_node[id(child)] = fi.fid
                    self._by_module_name.setdefault((mod, child.name), []).append(fi.fid)
                    walk(child, parents + [child.name], True)
                elif isinstance(child, ast.ClassDef):
                    walk(child, parents + [child.name], nested)
                else:
                    walk(child, parents, nested)

        walk(sf.tree, [], False)

        # module-level tables of functions: name -> the names its literal holds
        for stmt in sf.tree.body:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name) \
                    and isinstance(stmt.value, (ast.Dict, ast.Tuple, ast.List)):
                self._tables[(mod, stmt.targets[0].id)] = {
                    n.id for n in ast.walk(stmt.value)
                    if isinstance(n, ast.Name)}

    def _note_mesh_axes(self, call: ast.Call) -> None:
        """Mesh(devices, ("a", "b")) / Mesh(..., axis_names=...) literal axes."""
        f = call.func
        name = f.attr if isinstance(f, ast.Attribute) else \
            f.id if isinstance(f, ast.Name) else None
        if name != "Mesh":
            return
        axis_arg = None
        if len(call.args) >= 2:
            axis_arg = call.args[1]
        for kw in call.keywords:
            if kw.arg == "axis_names":
                axis_arg = kw.value
        if axis_arg is None:
            return
        if isinstance(axis_arg, ast.Constant) and isinstance(axis_arg.value, str):
            self.mesh_axes.add(axis_arg.value)
        elif isinstance(axis_arg, (ast.Tuple, ast.List)):
            for el in axis_arg.elts:
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    self.mesh_axes.add(el.value)

    # -- name resolution ----------------------------------------------------
    def resolve(self, mod: str, name_parts: tuple[str, ...]) -> list[int]:
        """Resolve a (possibly dotted) reference in `mod` to FuncInfo fids.

        Name: module-local defs first (by-name, every def sharing the name —
        the TPU003 idiom), then from-imports. Dotted `alias.f`: through
        import aliases to (target_module, f). Unresolvable -> []."""
        if len(name_parts) == 1:
            n = name_parts[0]
            local = self._by_module_name.get((mod, n))
            if local:
                return list(local)
            target = self._imports.get(mod, {}).get(n)
            if target and "." in target:
                tmod, tname = target.rsplit(".", 1)
                return self._lookup(tmod, tname)
            return []
        alias, fname = name_parts[0], name_parts[-1]
        target = self._imports.get(mod, {}).get(alias)
        if target:
            return self._lookup(target, fname)
        return []

    def _lookup(self, tmod: str, tname: str) -> list[int]:
        hit = self._by_module_name.get((tmod, tname))
        if hit:
            return list(hit)
        # basename fallback: explicit fixture files import each other by stem
        full = self._basename.get(tmod.rsplit(".", 1)[-1])
        if full and full != tmod:
            return list(self._by_module_name.get((full, tname), []))
        return []

    def func_at(self, node: ast.AST) -> FuncInfo | None:
        fid = self._fid_of_node.get(id(node))
        return self.functions[fid] if fid is not None else None

    # -- pass 1b: call graph + escapes + device returns ---------------------
    def _resolve_calls(self) -> None:
        for fi in self.functions:
            # nested defs have their own FuncInfo — their bodies must NOT be
            # attributed to the parent (a factory returning `def inner():
            # return jnp.zeros(3)` is not itself device-returning, and the
            # parent does not "call" whatever inner calls)
            nested_ids: set[int] = set()
            for n in ast.walk(fi.node):
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and n is not fi.node:
                    nested_ids.update(id(x) for x in ast.walk(n))
            for node in ast.walk(fi.node):
                if node is fi.node or id(node) in nested_ids:
                    continue
                if isinstance(node, ast.Call):
                    d = _dotted(node.func)
                    if d:
                        fi.called_names.add(d[-1])
                        for fid in self.resolve(fi.module, d):
                            fi.calls.add(fid)
                    # bare-name args passed to calls are escaping references
                    for a in list(node.args) + [kw.value for kw in node.keywords]:
                        if isinstance(a, ast.Name):
                            self._mark_escape(fi.module, a.id)
                elif isinstance(node, ast.Name):
                    # a read of a module-level table of functions
                    for name in self._tables.get((fi.module, node.id), ()):
                        fi.calls.update(self.resolve(fi.module, (name,)))
                elif isinstance(node, ast.Return) and node.value is not None:
                    self._note_return(fi, node.value)
                elif isinstance(node, (ast.Assign, ast.AnnAssign)):
                    v = getattr(node, "value", None)
                    if isinstance(v, ast.Name):
                        self._mark_escape(fi.module, v.id)

    def _mark_escape(self, mod: str, name: str) -> None:
        for fid in self.resolve(mod, (name,)):
            self.functions[fid].escapes = True

    def _note_return(self, fi: FuncInfo, value: ast.AST) -> None:
        """Classify `return <expr>`: device-producing call, call into another
        function (fixpoint edge), or an escaping function reference."""
        if isinstance(value, ast.Name):
            for fid in self.resolve(fi.module, (value.id,)):
                self.functions[fid].escapes = True
            return
        if not isinstance(value, ast.Call):
            return
        d = _dotted(value.func)
        if d is None:
            return
        if d[0] in _DEVICE_MODULES and d[-1] not in _HOST_RESULTS:
            fi.returns_device_direct = True
            return
        for fid in self.resolve(fi.module, d):
            fi.return_calls.add(fid)

    def _propagate_device_returns(self) -> None:
        self.device_returning = {fi.fid for fi in self.functions
                                 if fi.returns_device_direct}
        changed = True
        while changed:
            changed = False
            for fi in self.functions:
                if fi.fid in self.device_returning:
                    continue
                if fi.return_calls & self.device_returning:
                    self.device_returning.add(fi.fid)
                    changed = True

    # -- pass 1c: traced closure + shard_map coverage -----------------------
    def _traced_roots(self) -> tuple[set[int], set[int]]:
        jit_roots: set[int] = set()
        sm_roots: set[int] = set()
        for fi in self.functions:
            for deco in fi.node.decorator_list:
                if _is_jit_name(deco) or _is_shard_map_name(deco):
                    jit_roots.add(fi.fid)
                elif isinstance(deco, ast.Call) and (
                        _is_jit_name(deco.func) or _is_shard_map_name(deco.func)
                        or any(_is_jit_name(a) for a in deco.args)):
                    jit_roots.add(fi.fid)
        for sf in self.files:
            mod = module_name(sf.relpath)
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.Call):
                    continue
                is_sm = _is_shard_map_name(node.func)
                if not (_is_jit_name(node.func) or is_sm):
                    continue
                fn_args = [a for a in node.args[:1]] + \
                    [kw.value for kw in node.keywords if kw.arg in ("fun", "f")]
                for a in fn_args:
                    if isinstance(a, ast.Name):
                        for fid in self.resolve(mod, (a.id,)):
                            (sm_roots if is_sm else jit_roots).add(fid)
        return jit_roots, sm_roots

    def _closure(self, roots: set[int]) -> set[int]:
        seen: set[int] = set()
        pending = list(roots)
        while pending:
            fid = pending.pop()
            if fid in seen:
                continue
            seen.add(fid)
            pending.extend(self.functions[fid].calls - seen)
        return seen

    def _propagate_traced(self) -> None:
        jit_roots, sm_roots = self._traced_roots()
        self.shard_map_covered = self._closure(sm_roots)
        # factory pattern: a nested closure that escapes its builder may be the
        # function some caller shard_maps later — benefit of the doubt
        doubt = {fi.fid for fi in self.functions if fi.nested and fi.escapes}
        self.shard_map_covered |= self._closure(doubt)
        self.traced = self._closure(jit_roots | sm_roots)

    # -- queries used by rules ----------------------------------------------
    def traced_functions_in(self, sf: SourceFile) -> list[FuncInfo]:
        return [fi for fi in self.functions
                if fi.sf is sf and fi.fid in self.traced]

    def device_returning_names(self, sf: SourceFile) -> set[str]:
        """Names in `sf`'s module that resolve to device-returning functions —
        callers treat `x = helper(...)` as producing a device value."""
        mod = module_name(sf.relpath)
        out = set()
        for fi in self.functions:
            if fi.fid in self.device_returning:
                if fi.module == mod:
                    out.add(fi.name)
        imports = self._imports.get(mod, {})
        for alias, target in imports.items():
            if "." in target:
                tmod, tname = target.rsplit(".", 1)
                if any(fid in self.device_returning
                       for fid in self._lookup(tmod, tname)):
                    out.add(alias)
        return out
