"""Kernel-contract passes over ops/ and parallel/.

The device layer's whole performance story rests on conventions no
runtime test can see breaking until a production trace does:

  * launch keys must be SHAPE-only -- operand values ride in as traced
    arrays (ops/filter's docstring is the contract) -- or every distinct
    query value recompiles its own XLA program (the compile storm the
    TempoKernelCompileStorm alert pages on, after the fact);
  * jitted bodies must not synchronize with the host: one `.item()` in
    a kernel turns an async dispatch into a blocking round trip per
    call, which erases the batching win;
  * jitted bodies trace with jnp; stray `np.` calls either break the
    trace or silently constant-fold a value that should be dynamic.

Scope is LEXICAL jit regions: a def decorated with @jax.jit (bare or
via functools.partial), plus local defs wrapped by a `jax.jit(...)`
call in the same function (chased through trivial assignments and
wrapper calls like shard_map(fn, ...)), plus everything nested inside
those. Module-level helpers invoked from traced code (ops/filter's
_cond_mask) are host functions that happen to run at trace time -- they
are out of region, the price of zero false positives on orchestration
code that legitimately calls np.asarray on fetched results.
"""

from __future__ import annotations

import ast
import builtins

from .core import Report, SourceModule, dotted_name, emit, register_rule

R_HOST_SYNC = register_rule(
    "jit-host-sync",
    "host synchronization inside a jitted body (.item/.tolist/"
    "block_until_ready/np.asarray/float(traced)) blocks the dispatch "
    "pipeline for a full link round trip")
R_NUMPY = register_rule(
    "jit-numpy",
    "np.* call inside a jitted body; traced math must use jnp or the "
    "value constant-folds at trace time")
R_CAPTURE = register_rule(
    "jit-nonstatic-capture",
    "jitted closure captures a name that varies across the enclosing "
    "scope (loop variable / rebound local): the first trace bakes one "
    "value, or every change silently retraces")
R_UNCACHED = register_rule(
    "jit-uncached-factory",
    "function builds a jax.jit wrapper on every call without lru_cache: "
    "every invocation retraces and recompiles")
R_VALUE_KEY = register_rule(
    "jit-value-key",
    "data-derived value (.item()/.max()/...) passed in a static "
    "launch-key position: every distinct data value compiles a fresh "
    "XLA program (compile storm)")

HOST_SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
NP_MATERIALIZE = {"asarray", "array", "frombuffer", "ascontiguousarray"}
# dtype constructors and trace-time metadata -- legitimate inside jit
NP_OK = {
    "int8", "int16", "int32", "int64", "uint8", "uint16", "uint32",
    "uint64", "float16", "float32", "float64", "bool_", "dtype",
    "iinfo", "finfo", "promote_types", "result_type",
}
# reductions whose result in a static position keys compiles on DATA
VALUE_EXTRACTORS = {"item", "max", "min", "sum", "mean", "argmax",
                    "argmin", "tolist"}
_BUILTINS = set(dir(builtins))
_CACHE_DECORATORS = ("lru_cache", "functools.lru_cache", "cache",
                     "functools.cache")


def _is_jax_jit(node: ast.AST) -> bool:
    return dotted_name(node) in ("jax.jit", "jit")


def _jit_decorator_info(fn: ast.FunctionDef) -> tuple[bool, set[str]]:
    """(is_jitted, static param names) from the decorator list."""
    for dec in fn.decorator_list:
        if _is_jax_jit(dec):
            return True, set()
        if isinstance(dec, ast.Call):
            # @jax.jit(...) or @partial(jax.jit, static_argnames=...)
            if dotted_name(dec.func) in ("partial", "functools.partial"):
                if not (dec.args and _is_jax_jit(dec.args[0])):
                    continue
            elif not _is_jax_jit(dec.func):
                continue
            return True, _static_names(dec, fn)
    return False, set()


def _static_names(call: ast.Call, fn: ast.FunctionDef) -> set[str]:
    params = [a.arg for a in fn.args.args]
    out: set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    out.add(el.value)
        elif kw.arg == "static_argnums":
            for el in ast.walk(kw.value):
                if (isinstance(el, ast.Constant) and isinstance(el.value, int)
                        and 0 <= el.value < len(params)):
                    out.add(params[el.value])
    return out


def _has_cache_decorator(fn: ast.FunctionDef) -> bool:
    return any(
        dotted_name(d if not isinstance(d, ast.Call) else d.func)
        in _CACHE_DECORATORS
        for d in fn.decorator_list)


def _chase_jit_wrapped(owner: ast.AST) -> set[int]:
    """ids of local defs inside `owner` that end up under a jax.jit(...)
    call: the argument itself, a name assigned from a def, or a def
    passed through a wrapper call (fn = smap(local, ...); jax.jit(fn))."""
    defs = {n.name: n for n in ast.iter_child_nodes(owner)
            if isinstance(n, ast.FunctionDef)}
    assigned: dict[str, ast.expr] = {}
    for n in ast.walk(owner):
        if (isinstance(n, ast.Assign) and len(n.targets) == 1
                and isinstance(n.targets[0], ast.Name)):
            assigned[n.targets[0].id] = n.value

    def defs_in(expr: ast.expr, depth: int) -> list[ast.FunctionDef]:
        if depth > 4:
            return []
        if isinstance(expr, ast.Name):
            if expr.id in defs:
                return [defs[expr.id]]
            if expr.id in assigned:
                return defs_in(assigned[expr.id], depth + 1)
            return []
        if isinstance(expr, ast.Call):
            out = []
            for a in list(expr.args) + [kw.value for kw in expr.keywords]:
                out.extend(defs_in(a, depth + 1))
            return out
        return []

    out: set[int] = set()
    for n in ast.walk(owner):
        if isinstance(n, ast.Call) and _is_jax_jit(n.func) and n.args:
            out.update(id(d) for d in defs_in(n.args[0], 0))
    return out


def _params_of(fn) -> set[str]:
    a = fn.args
    out = {arg.arg for arg in a.posonlyargs + a.args + a.kwonlyargs}
    if a.vararg:
        out.add(a.vararg.arg)
    if a.kwarg:
        out.add(a.kwarg.arg)
    return out


def _bound_names(fn: ast.FunctionDef) -> set[str]:
    """Every name bound anywhere within fn, including nested scopes --
    used to decide what the jit region could NOT have captured."""
    bound = _params_of(fn)
    for n in ast.walk(fn):
        if isinstance(n, ast.Name) and isinstance(n.ctx, (ast.Store, ast.Del)):
            bound.add(n.id)
        elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            bound |= _params_of(n)
            if not isinstance(n, ast.Lambda):
                bound.add(n.name)
        elif isinstance(n, ast.ClassDef):
            bound.add(n.name)
        elif isinstance(n, (ast.Import, ast.ImportFrom)):
            for al in n.names:
                bound.add((al.asname or al.name).split(".")[0])
    return bound


def _module_bindings(tree: ast.Module) -> set[str]:
    out: set[str] = set()
    for n in ast.walk(tree):
        if isinstance(n, (ast.Import, ast.ImportFrom)):
            for al in n.names:
                out.add((al.asname or al.name).split(".")[0])
    for n in tree.body:
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            out.add(n.name)
        elif isinstance(n, ast.Assign):
            for t in n.targets:
                for el in ast.walk(t):
                    if isinstance(el, ast.Name):
                        out.add(el.id)
        elif isinstance(n, (ast.AnnAssign, ast.AugAssign)) and isinstance(
                n.target, ast.Name):
            out.add(n.target.id)
    return out


class _EnclosingScope:
    """Classify one enclosing def's bindings for the capture rule:
    `params` and `once` (bound exactly once, outside any loop) are
    static per factory call; `varying` (loop targets, rebound names)
    change under the closure's feet."""

    def __init__(self, fn: ast.FunctionDef):
        self.params = _params_of(fn)
        counts: dict[str, int] = {}
        loop_bound: set[str] = set()

        def note_stores(node: ast.AST, in_loop: bool, cnt: dict) -> None:
            for el in ast.walk(node):
                if isinstance(el, ast.Name) and isinstance(
                        el.ctx, (ast.Store, ast.Del)):
                    cnt[el.id] = cnt.get(el.id, 0) + 1
                    if in_loop:
                        loop_bound.add(el.id)

        def scan(body: list, in_loop: bool, cnt: dict) -> None:
            for n in body:
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                    cnt[n.name] = cnt.get(n.name, 0) + 1
                    if in_loop:
                        loop_bound.add(n.name)
                elif isinstance(n, (ast.For, ast.AsyncFor)):
                    note_stores(n.target, True, cnt)
                    scan(n.body + n.orelse, True, cnt)
                elif isinstance(n, ast.While):
                    scan(n.body + n.orelse, True, cnt)
                elif isinstance(n, ast.If):
                    # disjoint branches: a name bound once in each arm is
                    # still bound once per call -- merge with max, not sum
                    note_stores(n.test, in_loop, cnt)
                    c_then: dict = {}
                    c_else: dict = {}
                    scan(n.body, in_loop, c_then)
                    scan(n.orelse, in_loop, c_else)
                    for k in set(c_then) | set(c_else):
                        cnt[k] = cnt.get(k, 0) + max(c_then.get(k, 0),
                                                     c_else.get(k, 0))
                elif isinstance(n, (ast.With, ast.AsyncWith)):
                    for item in n.items:
                        if item.optional_vars is not None:
                            note_stores(item.optional_vars, in_loop, cnt)
                    scan(n.body, in_loop, cnt)
                elif isinstance(n, ast.Try):
                    scan(n.body + n.orelse + n.finalbody, in_loop, cnt)
                    for h in n.handlers:
                        if h.name:
                            cnt[h.name] = cnt.get(h.name, 0) + 1
                        scan(h.body, in_loop, cnt)
                else:
                    note_stores(n, in_loop, cnt)

        scan(fn.body, False, counts)
        self.varying = loop_bound | {n for n, c in counts.items() if c > 1}
        self.once = {n for n in counts if n not in self.varying}


def _scan_jit_body(mod: SourceModule, report: Report, fn: ast.FunctionDef,
                   static_params: set[str], enclosing: list[ast.FunctionDef],
                   module_bound: set[str]) -> None:
    """jit-host-sync, jit-numpy and jit-nonstatic-capture over one
    lexical jit region (the wrapped def plus everything nested in it)."""
    traced_params = (_params_of(fn) - static_params)
    bound = _bound_names(fn)
    scopes = [_EnclosingScope(e) for e in enclosing]
    flagged_caps: set[str] = set()

    for n in ast.walk(fn):
        if isinstance(n, ast.Call):
            if isinstance(n.func, ast.Attribute):
                if n.func.attr in HOST_SYNC_ATTRS:
                    emit(mod, report, n.lineno, R_HOST_SYNC,
                         f".{n.func.attr}() inside jitted body",
                         "compute on device; fetch after the kernel returns")
                    continue
                root = n.func.value
                if isinstance(root, ast.Name) and root.id in ("np", "numpy"):
                    if n.func.attr in NP_MATERIALIZE:
                        emit(mod, report, n.lineno, R_HOST_SYNC,
                             f"np.{n.func.attr}() inside jitted body forces "
                             "a device->host transfer",
                             "keep the value a traced jnp array")
                    elif n.func.attr not in NP_OK:
                        emit(mod, report, n.lineno, R_NUMPY,
                             f"np.{n.func.attr}() inside jitted body",
                             f"use jnp.{n.func.attr} so the op traces")
                    continue
            if dotted_name(n.func) == "jax.device_get":
                emit(mod, report, n.lineno, R_HOST_SYNC,
                     "jax.device_get() inside jitted body",
                     "return the array and fetch outside the kernel")
                continue
            if (isinstance(n.func, ast.Name)
                    and n.func.id in ("float", "int", "bool")
                    and len(n.args) == 1 and isinstance(n.args[0], ast.Name)
                    and n.args[0].id in traced_params):
                emit(mod, report, n.lineno, R_HOST_SYNC,
                     f"{n.func.id}({n.args[0].id}) concretizes a traced "
                     "argument (host sync; fails under jit)",
                     "cast with .astype(...) on device, or mark the "
                     "argument static")
        elif isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
            name = n.id
            if (name in bound or name in module_bound or name in _BUILTINS
                    or name in flagged_caps):
                continue
            for sc in scopes:
                if name in sc.params or name in sc.once:
                    break
                if name in sc.varying:
                    flagged_caps.add(name)
                    emit(mod, report, n.lineno, R_CAPTURE,
                         f"jitted closure captures '{name}', which varies "
                         "in the enclosing scope",
                         "pass it as a static factory parameter so it "
                         "joins the compile key explicitly")
                    break


# value: (static positional indices, static keyword names); (None, None)
# means EVERY argument is static (an lru_cache'd compile factory)
StaticSpec = tuple


def _collect_static_key_callables(tree: ast.Module) -> dict[str, StaticSpec]:
    """Module-level callables whose arguments key XLA compiles."""
    out: dict[str, StaticSpec] = {}
    for n in tree.body:
        if not isinstance(n, ast.FunctionDef):
            continue
        contains_jit = any(
            (isinstance(w, ast.Call) and _is_jax_jit(w.func))
            or (isinstance(w, ast.FunctionDef) and w is not n
                and _jit_decorator_info(w)[0])
            for w in ast.walk(n))
        if _has_cache_decorator(n) and contains_jit:
            out[n.name] = (None, None)
            continue
        jitted, statics = _jit_decorator_info(n)
        if jitted and statics:
            params = [a.arg for a in n.args.args]
            out[n.name] = ({i for i, p in enumerate(params) if p in statics},
                           statics)
    return out


def _arg_extracts_value(expr: ast.expr) -> str | None:
    for n in ast.walk(expr):
        if (isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
                and n.func.attr in VALUE_EXTRACTORS):
            return n.func.attr
    return None


def run_jit_rules(mod: SourceModule, report: Report) -> None:
    tree = mod.tree
    module_bound = _module_bindings(tree)

    def visit(owner: ast.AST, enclosing: list[ast.FunctionDef]) -> None:
        """Locate lexical jit regions; flag uncached top-level factories."""
        # chase jax.jit(name) wrapping at module level too: the
        # `kernel = jax.jit(_impl)` definition style is a jit region
        # exactly like the decorator form
        wrapped_here: set[int] = set()
        if isinstance(owner, (ast.FunctionDef, ast.Module)):
            wrapped_here = _chase_jit_wrapped(owner)
        if isinstance(owner, ast.FunctionDef):
            # jit creation inside a nested @lru_cache'd def is that
            # def's responsibility (and it memoizes it): exclude those
            # subtrees so a plain wrapper around a cached factory does
            # not false-positive
            cached_subtrees: set[int] = set()
            for w in ast.walk(owner):
                if (isinstance(w, ast.FunctionDef) and w is not owner
                        and _has_cache_decorator(w)):
                    cached_subtrees.update(id(x) for x in ast.walk(w))
            creates_jit = bool(wrapped_here) or any(
                isinstance(w, ast.Call) and _is_jax_jit(w.func)
                and id(w) not in cached_subtrees
                for w in ast.walk(owner)) or any(
                isinstance(c, ast.FunctionDef) and _jit_decorator_info(c)[0]
                for c in ast.iter_child_nodes(owner))
            if (creates_jit and not enclosing
                    and not _has_cache_decorator(owner)):
                emit(mod, report, owner.lineno, R_UNCACHED,
                     f"'{owner.name}' builds a jax.jit wrapper on every "
                     "call without lru_cache",
                     "decorate the factory with @lru_cache so identical "
                     "shapes reuse the compiled program")
        next_enclosing = ([owner] + enclosing
                          if isinstance(owner, ast.FunctionDef) else enclosing)
        for child in ast.iter_child_nodes(owner):
            if isinstance(child, ast.FunctionDef):
                jitted, statics = _jit_decorator_info(child)
                if jitted or id(child) in wrapped_here:
                    _scan_jit_body(mod, report, child, statics,
                                   next_enclosing, module_bound)
                else:
                    visit(child, next_enclosing)
            elif isinstance(child, (ast.ClassDef, ast.If, ast.Try, ast.With,
                                    ast.For, ast.While)):
                visit(child, next_enclosing)

    visit(tree, [])

    _check_value_key_calls(mod, report, _collect_static_key_callables(tree))


def _check_value_key_calls(mod: SourceModule, report: Report,
                           static_callables: dict[str, StaticSpec]) -> None:
    if not static_callables:
        return

    def check(arg: ast.expr, label: str, fname: str, line: int) -> None:
        attr = _arg_extracts_value(arg)
        if attr:
            emit(mod, report, line, R_VALUE_KEY,
                 f"argument {label} of '{fname}' derives from data "
                 f"(.{attr}()) but keys the compiled program",
                 "key compiles on the padded shape bucket "
                 "(ops/device.bucket); ship values as traced operands")

    for n in ast.walk(mod.tree):
        if not (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)):
            continue
        if n.func.id not in static_callables:
            continue
        idxs, names = static_callables[n.func.id]
        for i, arg in enumerate(n.args):
            if idxs is not None and i not in idxs:
                continue
            check(arg, str(i), n.func.id, n.lineno)
        for kw in n.keywords:
            # static_argnames params are most naturally passed by
            # keyword: those key compiles exactly like positional ones
            if names is not None and kw.arg not in names:
                continue
            check(kw.value, f"'{kw.arg or '**'}'", n.func.id, n.lineno)


def run_value_key_cross(modules: dict[str, SourceModule],
                        report: Report) -> None:
    """Cross-module jit-value-key: the likeliest real compile storm is
    a db executor (or service) passing a data-derived value to an ops/
    compile factory it IMPORTED -- the per-module pass cannot see that.
    Phase 1 collects every kernel module's static-key callables under
    their fully-qualified names; phase 2 re-checks every module's calls
    to names imported from kernel modules."""
    from .callgraph import fq_module, resolve_import
    from .twinrules import KERNEL_PKGS
    from pathlib import Path

    fq_callables: dict[str, StaticSpec] = {}
    for rel, mod in modules.items():
        if rel.split("/")[0] not in KERNEL_PKGS:
            continue
        fq = fq_module(rel)
        for name, spec in _collect_static_key_callables(mod.tree).items():
            fq_callables[f"{fq}.{name}"] = spec

    if not fq_callables:
        return
    for rel, mod in modules.items():
        cur_pkg = "/".join(Path(rel).parts[:-1])
        cur_fq = fq_module(rel)
        local: dict[str, StaticSpec] = {}
        for n in ast.walk(mod.tree):
            if not isinstance(n, ast.ImportFrom):
                continue
            target = resolve_import(cur_pkg, n, KERNEL_PKGS)
            if target is None or target == cur_fq:
                continue  # same-module calls: per-module pass owns them
            for al in n.names:
                key = f"{target}.{al.name}"
                if key in fq_callables:
                    local[al.asname or al.name] = fq_callables[key]
        _check_value_key_calls(mod, report, local)
