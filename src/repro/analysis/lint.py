"""Repo-specific AST lint rules for trace-time discipline (DESIGN.md §2.11).

Rules
-----
HS01  host-sync op (``.item()``, ``.tolist()``, ``float()``/``int()``/
      ``bool()``, ``np.asarray``/``np.array``) inside a jitted body of a
      hot-path module (``sim/``, ``core/ils_jax.py``, ``kernels/``).
RNG01 wall-clock or host RNG (``time.time``, ``np.random.*``,
      ``random.*``) inside a jitted body, anywhere in ``src/repro``.
DEP01 call to a deprecated ``repro.compat`` shim (a function whose body
      calls ``warn_deprecated``) outside ``compat.py`` itself.
KRN01 a public kernel entry point in ``kernels/<k>/ops.py`` without a
      matching ``<name>_ref`` oracle symbol in ``kernels/<k>/ref.py``.
STA01 a ``static_argnames``/``static_argnums`` parameter whose
      annotation is missing or not a hashable type (int/str/bool/...,
      or a frozen dataclass defined in the tree).

"Jitted body" is decided statically per module: a function is a jit
scope if it is decorated with ``jax.jit`` (directly or through
``functools.partial``), wrapped by a ``jax.jit(fn, ...)`` call, passed
as a branch/body/cond callable to ``lax`` control flow, nested inside a
jit scope, or called (by local name) from one — the transitive closure
matters because trace-time helpers execute inside the trace.
"""
from __future__ import annotations

import ast
import dataclasses
import os
from typing import Iterable, Sequence

__all__ = ["Violation", "lint_paths", "lint_source", "RULES"]

RULES = {
    "HS01": "host-sync op on a traced value inside a jitted hot-path body",
    "RNG01": "wall-clock or host RNG inside a jitted body",
    "DEP01": "call to a deprecated repro.compat shim outside compat.py",
    "KRN01": "Pallas kernel entry point without a ref.py oracle symbol",
    "STA01": "static jit argument without a hashable type annotation",
}

# Modules whose jitted bodies are the measured hot paths (HS01 scope).
_HOT_PREFIXES = ("sim/", "kernels/")
_HOT_FILES = ("core/ils_jax.py",)

# lax control-flow primitives whose callable arguments run under trace.
_LAX_HOFS = {
    "while_loop", "scan", "cond", "switch", "fori_loop", "map",
    "associative_scan", "custom_root", "custom_linear_solve",
}

_HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_HOST_SYNC_BUILTINS = {"float", "int", "bool", "complex"}
_NP_SYNC_FUNCS = {"asarray", "array", "frombuffer", "copyto"}

_HASHABLE_NAMES = {
    "int", "str", "bool", "float", "bytes", "tuple", "frozenset",
    "None", "NoneType", "type", "Callable", "callable", "Mesh",
}


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    path: str
    line: int
    message: str

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> str:
    """Best-effort dotted-name string for Name/Attribute chains."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _add_parents(tree: ast.AST) -> None:
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._lint_parent = node  # type: ignore[attr-defined]


def _enclosing_function(node: ast.AST) -> ast.AST | None:
    cur = getattr(node, "_lint_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return cur
        cur = getattr(cur, "_lint_parent", None)
    return None


def _is_jit_expr(node: ast.AST) -> bool:
    """``jax.jit`` / ``jit`` / ``functools.partial(jax.jit, ...)``."""
    name = _dotted(node)
    if name in ("jax.jit", "jit"):
        return True
    if isinstance(node, ast.Call) and _dotted(node.func) in (
            "functools.partial", "partial"):
        return bool(node.args) and _is_jit_expr(node.args[0])
    return False


@dataclasses.dataclass
class _Module:
    path: str          # posix path relative to the repo root
    rel: str           # posix path relative to src/repro (or path if outside)
    tree: ast.Module
    funcs: dict[int, ast.AST] = dataclasses.field(default_factory=dict)
    jit_scopes: set[int] = dataclasses.field(default_factory=set)


def _collect_functions(tree: ast.Module) -> dict[int, ast.AST]:
    return {id(n): n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda))}


def _jit_roots(mod: _Module) -> set[int]:
    """Functions directly marked as traced: jit-decorated, jax.jit(f)
    wrapped, or passed to a lax control-flow primitive."""
    roots: set[int] = set()
    # name -> list of defs (module/class/function level; last wins per scope
    # is overkill — collect all, linting is conservative).
    defs_by_name: dict[str, list[ast.AST]] = {}
    for fn in mod.funcs.values():
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(fn.name, []).append(fn)

    for fn in mod.funcs.values():
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_jit_expr(d) for d in fn.decorator_list):
                roots.add(id(fn))

    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        func_name = _dotted(node.func)
        # jax.jit(fn, ...) wrapping a local def by name
        if _is_jit_expr(node.func) and node.args:
            tgt = node.args[0]
            if isinstance(tgt, ast.Name):
                for d in defs_by_name.get(tgt.id, ()):
                    roots.add(id(d))
            elif isinstance(tgt, ast.Lambda):
                roots.add(id(tgt))
        # lax.while_loop(cond, body, ...), lax.scan(f, ...), lax.cond(p, t, f)
        leaf = func_name.rsplit(".", 1)[-1]
        if leaf in _LAX_HOFS:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    for d in defs_by_name.get(arg.id, ()):
                        roots.add(id(d))
                elif isinstance(arg, ast.Lambda):
                    roots.add(id(arg))
    return roots


def _mark_jit_scopes(mod: _Module) -> None:
    """Transitive closure: nested defs inside jit scopes, plus local
    functions *called* from a jit scope (trace-time helpers)."""
    scopes = _jit_roots(mod)
    defs_by_name: dict[str, list[ast.AST]] = {}
    for fn in mod.funcs.values():
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs_by_name.setdefault(fn.name, []).append(fn)

    changed = True
    while changed:
        changed = False
        for fn in mod.funcs.values():
            if id(fn) in scopes:
                continue
            enc = _enclosing_function(fn)
            if enc is not None and id(enc) in scopes:
                scopes.add(id(fn))
                changed = True
        # calls from jit scopes to module-local names
        for fid in list(scopes):
            fn = mod.funcs[fid]
            body = fn.body if not isinstance(fn, ast.Lambda) else [fn.body]
            for stmt in body:
                for node in ast.walk(stmt if isinstance(stmt, ast.AST) else fn):
                    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                        for d in defs_by_name.get(node.func.id, ()):
                            if id(d) not in scopes:
                                scopes.add(id(d))
                                changed = True
    mod.jit_scopes = scopes


def _in_jit_scope(mod: _Module, node: ast.AST) -> bool:
    fn = _enclosing_function(node)
    while fn is not None:
        if id(fn) in mod.jit_scopes:
            return True
        fn = _enclosing_function(fn)
    return False


# ---------------------------------------------------------------------------
# per-rule passes
# ---------------------------------------------------------------------------

def _is_hot(rel: str) -> bool:
    return rel.startswith(_HOT_PREFIXES) or rel in _HOT_FILES


def _check_host_sync(mod: _Module) -> Iterable[Violation]:
    if not _is_hot(mod.rel):
        return
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or not _in_jit_scope(mod, node):
            continue
        name = _dotted(node.func)
        leaf = name.rsplit(".", 1)[-1]
        msg = None
        if isinstance(node.func, ast.Attribute) and node.func.attr in _HOST_SYNC_METHODS:
            msg = f".{node.func.attr}() forces a host sync on a traced value"
        elif name in _HOST_SYNC_BUILTINS:
            msg = f"{name}() materialises a traced value on the host"
        elif name.startswith(("np.", "numpy.")) and leaf in _NP_SYNC_FUNCS:
            msg = f"{name}() copies a traced value to host numpy"
        if msg:
            yield Violation("HS01", mod.path, node.lineno, msg)


def _check_host_rng(mod: _Module) -> Iterable[Violation]:
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or not _in_jit_scope(mod, node):
            continue
        name = _dotted(node.func)
        if name in ("time.time", "time.monotonic", "time.perf_counter",
                    "datetime.datetime.now", "datetime.now"):
            yield Violation("RNG01", mod.path, node.lineno,
                            f"wall-clock call {name}() inside a jitted body")
        elif name.startswith(("np.random.", "numpy.random.")) or name == "np.random":
            yield Violation("RNG01", mod.path, node.lineno,
                            f"host RNG {name}() inside a jitted body "
                            "(use jax.random with an explicit key)")
        elif name.startswith("random.") and not name.startswith(
                ("jax.random.", "jrandom.")):
            yield Violation("RNG01", mod.path, node.lineno,
                            f"host RNG {name}() inside a jitted body")


def _collect_shims(mods: Sequence[_Module]) -> set[str]:
    """Functions whose body calls ``warn_deprecated`` are shims."""
    shims: set[str] = set()
    for mod in mods:
        for fn in mod.funcs.values():
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and _dotted(
                        node.func).rsplit(".", 1)[-1] == "warn_deprecated":
                    shims.add(fn.name)
                    break
    return shims


def _check_deprecated(mod: _Module, shims: set[str]) -> Iterable[Violation]:
    if mod.rel == "compat.py" or not shims:
        return
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        leaf = _dotted(node.func).rsplit(".", 1)[-1]
        if leaf not in shims:
            continue
        # the shim's own definition (and siblings in its module) may
        # reference it; only flag call sites outside any shim body.
        enc = _enclosing_function(node)
        if isinstance(enc, (ast.FunctionDef, ast.AsyncFunctionDef)) and enc.name in shims:
            continue
        yield Violation("DEP01", mod.path, node.lineno,
                        f"call to deprecated shim {leaf}() — use the "
                        "documented replacement (see repro.compat)")


def _check_kernel_refs(repo_src: str) -> Iterable[Violation]:
    kdir = os.path.join(repo_src, "repro", "kernels")
    if not os.path.isdir(kdir):
        return
    for entry in sorted(os.listdir(kdir)):
        ops_path = os.path.join(kdir, entry, "ops.py")
        ref_path = os.path.join(kdir, entry, "ref.py")
        if not os.path.isfile(ops_path):
            continue
        if not os.path.isfile(ref_path):
            yield Violation("KRN01", _posix_rel(ops_path, repo_src), 1,
                            f"kernel package {entry!r} has no ref.py oracle")
            continue
        with open(ref_path) as fh:
            ref_tree = ast.parse(fh.read())
        ref_syms = {n.name for n in ref_tree.body
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        for n in ref_tree.body:  # aliases: <entry>_ref = <other>_ref
            if isinstance(n, ast.Assign):
                ref_syms.update(t.id for t in n.targets if isinstance(t, ast.Name))
        with open(ops_path) as fh:
            ops_tree = ast.parse(fh.read())
        for n in ops_tree.body:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and not n.name.startswith("_"):
                want = n.name + "_ref"
                if want not in ref_syms:
                    yield Violation(
                        "KRN01", _posix_rel(ops_path, repo_src), n.lineno,
                        f"kernel entry point {n.name}() has no oracle "
                        f"{want}() in {entry}/ref.py")


def _annotation_hashable(ann: ast.AST | None,
                         frozen_classes: set[str]) -> bool:
    if ann is None:
        return False
    if isinstance(ann, ast.Constant):
        if ann.value is None:
            return True
        if isinstance(ann.value, str):  # quoted annotation
            try:
                return _annotation_hashable(
                    ast.parse(ann.value, mode="eval").body, frozen_classes)
            except SyntaxError:
                return False
    name = _dotted(ann)
    if name:
        leaf = name.rsplit(".", 1)[-1]
        return leaf in _HASHABLE_NAMES or leaf in frozen_classes
    if isinstance(ann, ast.BinOp) and isinstance(ann.op, ast.BitOr):
        return (_annotation_hashable(ann.left, frozen_classes)
                and _annotation_hashable(ann.right, frozen_classes))
    if isinstance(ann, ast.Subscript):  # Optional[...], tuple[int, ...]
        base = _dotted(ann.value).rsplit(".", 1)[-1]
        if base in ("Optional", "Union"):
            inner = ann.slice
            elts = inner.elts if isinstance(inner, ast.Tuple) else [inner]
            return all(_annotation_hashable(e, frozen_classes) for e in elts)
        return base in ("tuple", "Tuple", "frozenset", "FrozenSet", "type",
                        "Type", "Literal", "Callable")
    return False


def _collect_frozen_classes(mods: Sequence[_Module]) -> set[str]:
    out: set[str] = set()
    for mod in mods:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call) and _dotted(dec.func).rsplit(
                        ".", 1)[-1] == "dataclass":
                    if any(kw.arg == "frozen" and isinstance(kw.value, ast.Constant)
                           and kw.value.value is True for kw in dec.keywords):
                        out.add(node.name)
                # NamedTuple subclasses are hashable too
            for base in node.bases:
                if _dotted(base).rsplit(".", 1)[-1] in ("NamedTuple", "Enum",
                                                        "IntEnum", "StrEnum"):
                    out.add(node.name)
    return out


def _static_params(call: ast.Call) -> tuple[list[str], list[int]]:
    names: list[str] = []
    nums: list[int] = []
    for kw in call.keywords:
        val = kw.value
        elts = val.elts if isinstance(val, (ast.Tuple, ast.List)) else [val]
        if kw.arg == "static_argnames":
            names += [e.value for e in elts
                      if isinstance(e, ast.Constant) and isinstance(e.value, str)]
        elif kw.arg == "static_argnums":
            nums += [e.value for e in elts
                     if isinstance(e, ast.Constant) and isinstance(e.value, int)]
    return names, nums


def _check_static_args(mod: _Module, frozen: set[str]) -> Iterable[Violation]:
    defs_by_name: dict[str, list[ast.FunctionDef]] = {}
    for fn in mod.funcs.values():
        if isinstance(fn, ast.FunctionDef):
            defs_by_name.setdefault(fn.name, []).append(fn)

    def check(fn: ast.FunctionDef, names: list[str], nums: list[int],
              line: int) -> Iterable[Violation]:
        params = fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
        by_name = {p.arg: p for p in params}
        targets = [(n, by_name.get(n)) for n in names]
        targets += [(params[i].arg if i < len(params) else f"#{i}",
                     params[i] if i < len(params) else None) for i in nums]
        for pname, param in targets:
            if param is None:
                yield Violation("STA01", mod.path, line,
                                f"static arg {pname!r} not found on {fn.name}()")
            elif not _annotation_hashable(param.annotation, frozen):
                got = ast.unparse(param.annotation) if param.annotation else "missing"
                yield Violation(
                    "STA01", mod.path, param.lineno,
                    f"static arg {fn.name}({pname}) needs a hashable type "
                    f"annotation (got: {got}) — unhashable or untyped "
                    "statics churn the jit cache")

    for node in ast.walk(mod.tree):
        # decorator form: @partial(jax.jit, static_argnames=...)
        if isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call) and _is_jit_expr(dec):
                    names, nums = _static_params(dec)
                    if names or nums:
                        yield from check(node, names, nums, dec.lineno)
        # call form: jax.jit(fn, static_argnames=...)
        if isinstance(node, ast.Call) and _is_jit_expr(node.func) and node.args:
            tgt = node.args[0]
            if isinstance(tgt, ast.Name):
                names, nums = _static_params(node)
                if names or nums:
                    for d in defs_by_name.get(tgt.id, ()):
                        yield from check(d, names, nums, node.lineno)


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def _posix_rel(path: str, root: str) -> str:
    return os.path.relpath(path, root).replace(os.sep, "/")


def _parse_module(path: str, src_root: str, source: str | None = None) -> _Module:
    if source is None:
        with open(path) as fh:
            source = fh.read()
    tree = ast.parse(source)
    _add_parents(tree)
    rel = _posix_rel(path, os.path.join(src_root, "repro")) \
        if path.startswith(os.path.join(src_root, "repro")) else os.path.basename(path)
    mod = _Module(path=_posix_rel(path, os.path.dirname(src_root)), rel=rel,
                  tree=tree, funcs=_collect_functions(tree))
    _mark_jit_scopes(mod)
    return mod


def lint_source(source: str, *, rel: str = "sim/fixture.py",
                shims: set[str] | None = None,
                frozen_classes: set[str] | None = None) -> list[Violation]:
    """Lint a single source string — the test-fixture entry point.

    ``rel`` positions the fixture inside the package (hot-path rules key
    off it); ``shims``/``frozen_classes`` stand in for the repo-wide
    collection phases.
    """
    tree = ast.parse(source)
    _add_parents(tree)
    mod = _Module(path=rel, rel=rel, tree=tree, funcs=_collect_functions(tree))
    _mark_jit_scopes(mod)
    out: list[Violation] = []
    out += _check_host_sync(mod)
    out += _check_host_rng(mod)
    out += _check_deprecated(mod, shims or set())
    out += _check_static_args(mod, frozen_classes or set())
    return sorted(out, key=lambda v: (v.path, v.line, v.rule))


def lint_paths(src_root: str) -> list[Violation]:
    """Run every rule over ``src_root`` (the ``src/`` directory)."""
    mods: list[_Module] = []
    pkg = os.path.join(src_root, "repro")
    for dirpath, _dirnames, filenames in os.walk(pkg):
        for fname in sorted(filenames):
            if fname.endswith(".py"):
                mods.append(_parse_module(os.path.join(dirpath, fname), src_root))
    shims = _collect_shims(mods)
    frozen = _collect_frozen_classes(mods)
    out: list[Violation] = []
    for mod in mods:
        out += _check_host_sync(mod)
        out += _check_host_rng(mod)
        out += _check_deprecated(mod, shims)
        out += _check_static_args(mod, frozen)
    out += _check_kernel_refs(src_root)
    return sorted(out, key=lambda v: (v.path, v.line, v.rule))
