"""jax-discipline passes: the accelerator substrate's three contracts.

The concurrency layers (locks/blocking/atomicity) make thread bugs
structurally impossible; nothing did the same for the jit/XLA substrate
the perf thesis rests on.  Three rules close that gap, each paired with
the dynamic tracer :mod:`dmlc_core_tpu.base.jitcheck`:

* ``recompile-hazard`` — a compiled program must be built once and
  keyed on stable values.  Flagged: ``jax.jit(f)(x)`` built fresh per
  call (jit's cache keys on function identity, which a fresh wrapper
  always misses); jit/AOT construction inside a loop unless the result
  is stored into a ``*cache*``-named table (the ``_AOT_EXEC_CACHE`` /
  ``_ROUND_FN_CACHE`` idiom); dict/list/set literals or per-call
  f-strings/``.format`` at ``static_argnums`` positions (unhashable →
  TypeError, fresh strings → silent cache miss); and ``os.environ``
  reads inside ``*cache_key*`` functions (a mid-run env mutation flips
  the key and recompiles — route through ``base/knobs.py``).

* ``donation-discipline`` — an argument passed at a donated position
  is DEAD after the call (XLA reuses the buffer the moment the call
  dispatches): any later read of that name (before a rebinding store)
  is flagged.

* ``transfer-discipline`` — host↔device traffic belongs at ingest and
  result boundaries, not inside traced code or round loops.  Flagged:
  ``np.*`` / ``.item()`` / ``.tolist()`` / ``float()/int()/bool()`` of
  traced parameters inside jit-traced functions (host round-trip baked
  at trace, or ConcretizationTypeError); ``.item()`` / ``.tolist()``
  and loop-invariant ``device_put`` inside a round loop — a loop that
  dispatches a compiled executable — where every coercion is a device
  sync per round (``device_put`` feeding the executable call itself is
  ingest and exempt).

Jit-root discovery and same-module transitive following are shared
with :mod:`~dmlc_core_tpu.analysis.jitpure` (decorators,
``partial(jax.jit, ...)``, ``jax.jit(f)`` call sites); executable
*handles* additionally include names / ``self.*`` attributes assigned
from ``jax.jit(...)`` or ``.lower(...).compile()``.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from dmlc_core_tpu.analysis.engine import AnalysisContext, ParsedFile
from dmlc_core_tpu.analysis.jitpure import (_FuncIndex, _is_jit_expr,
                                            _jit_roots, _partial_jit)

__all__ = ["run", "EXPLAIN"]

RULES = ("recompile-hazard", "donation-discipline", "transfer-discipline")

_MAX_DEPTH = 24

EXPLAIN = {
    "recompile-hazard": {
        "doc": "Call path that defeats jax's compile cache: a fresh "
               "jax.jit wrapper built per call (cache keys on function "
               "identity), jit/AOT construction inside a loop without "
               "storing into a *cache*-named table, an unhashable or "
               "per-call-fresh value (dict/list/set literal, f-string, "
               ".format) at a static_argnums position, or os.environ "
               "read inside a *cache_key* function (env mutation flips "
               "the key mid-run; route through base/knobs.py).  The "
               "dynamic companion is base/jitcheck.py, which fails "
               "drills on any steady-state compile.",
        "flagged": (
            "def step(self, x):\n"
            "    return jax.jit(self._kernel)(x)   # fresh wrapper = "
            "recompile\n"
            "\n"
            "def _cache_key(self):\n"
            "    return (self.depth,\n"
            "            os.environ.get('DMLC_HIST_BLOCKS', '0'))\n"),
        "clean": (
            "def __init__(self):\n"
            "    self._kernel_jit = jax.jit(self._kernel)  # built once\n"
            "\n"
            "def step(self, x):\n"
            "    return self._kernel_jit(x)\n"
            "\n"
            "def _cache_key(self):\n"
            "    return (self.depth, knobs.value('DMLC_HIST_BLOCKS'))\n"),
    },
    "donation-discipline": {
        "doc": "Donated buffers are freed for reuse by XLA the moment "
               "the call dispatches: a name passed at a donated "
               "position must not be read again before it is rebound.",
        "flagged": (
            "step = jax.jit(update, donate_argnums=(0,))\n"
            "new = step(state, grads)\n"
            "log(state.mean())      # read after donation: garbage\n"),
        "clean": (
            "step = jax.jit(update, donate_argnums=(0,))\n"
            "state = step(state, grads)   # rebinding kills the name\n"),
    },
    "transfer-discipline": {
        "doc": "Implicit host<->device traffic on a hot path: np.* / "
               ".item() / .tolist() / float()-of-parameter inside a "
               "jit-traced function (the transfer happens at trace and "
               "bakes a constant, or raises ConcretizationTypeError), "
               "or .item()/.tolist()/loop-invariant device_put inside "
               "a round loop — the loop that dispatches a compiled "
               "executable — where each is a per-round device sync.  "
               "device_put feeding the executable call itself is "
               "ingest and exempt.",
        "flagged": (
            "while done < n_trees:\n"
            "    cfg = jax.device_put(table)   # re-uploaded per round\n"
            "    preds = round_fn(preds, cfg)\n"
            "    total += preds.item()          # device sync per round\n"),
        "clean": (
            "cfg = jax.device_put(table)        # ingest: once\n"
            "while done < n_trees:\n"
            "    preds = round_fn(preds, cfg)\n"
            "total = float(preds.sum())         # one sync at the end\n"),
    },
}


# -- shared module model -----------------------------------------------------

def _call_name(func: ast.expr) -> str:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


def _const_nums(node: Optional[ast.expr]) -> Optional[Tuple[int, ...]]:
    """donate/static argnums as a tuple of ints when statically known:
    a literal int or a literal tuple of ints; None when unknowable (a
    variable, ...)."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant)
                    and isinstance(elt.value, int)):
                return None
            out.append(elt.value)
        return tuple(out)
    return None


def _kwarg(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _jit_call_info(call: ast.Call) -> Optional[Dict[str, object]]:
    """For ``jax.jit(f, ...)`` / ``partial(jax.jit, ...)`` calls: the
    statically-known donate/static argnums."""
    if not (_is_jit_expr(call.func) or _partial_jit(call)):
        return None
    return {
        "donate": _const_nums(_kwarg(call, "donate_argnums")),
        "static": _const_nums(_kwarg(call, "static_argnums")),
    }


def _compile_chain(call: ast.Call) -> bool:
    """``f.lower(...).compile()`` — AOT construction (same per-call /
    in-loop hazards as jax.jit)."""
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr == "compile"
            and isinstance(f.value, ast.Call)
            and isinstance(f.value.func, ast.Attribute)
            and f.value.func.attr == "lower")


def _cache_store_target(target: ast.expr) -> bool:
    """Assignment target that parks the executable in a cache table:
    a subscript whose base name mentions "cache" (``_AOT_EXEC_CACHE[k]``,
    ``self._multi_cache[K]``)."""
    if not isinstance(target, ast.Subscript):
        return False
    base = target.value
    name = (base.id if isinstance(base, ast.Name)
            else base.attr if isinstance(base, ast.Attribute) else "")
    return "cache" in name.lower()


class _Module:
    """Per-file model: jitted defs (with argnums), executable handles
    (names / self-attrs bound to compiled callables), function index."""

    def __init__(self, tree: ast.AST) -> None:
        iv = _FuncIndex()
        iv.visit(tree)
        self.index: Dict[str, ast.FunctionDef] = iv.defs
        #: callable ref ("name" or "self.attr") -> info dict
        self.jitted: Dict[str, Dict[str, object]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call):
                        info = _jit_call_info(dec)
                        if info is not None:
                            self.jitted[node.name] = info
                    elif _is_jit_expr(dec):
                        self.jitted[node.name] = {
                            "donate": None, "static": None}
            elif isinstance(node, ast.Assign) and len(node.targets) == 1:
                v = node.value
                if not isinstance(v, ast.Call):
                    continue
                info = _jit_call_info(v)
                if info is None and _compile_chain(v):
                    info = {"donate": None, "static": None}
                if info is None:
                    continue
                t = node.targets[0]
                if isinstance(t, ast.Name):
                    self.jitted[t.id] = info
                elif (isinstance(t, ast.Attribute)
                      and isinstance(t.value, ast.Name)
                      and t.value.id == "self"):
                    self.jitted[f"self.{t.attr}"] = info

    def handle_ref(self, func: ast.expr) -> Optional[str]:
        """The jitted-handle key a call dispatches through, or None."""
        if isinstance(func, ast.Name) and func.id in self.jitted:
            return func.id
        if (isinstance(func, ast.Attribute)
                and isinstance(func.value, ast.Name)
                and func.value.id == "self"
                and f"self.{func.attr}" in self.jitted):
            return f"self.{func.attr}"
        return None

    def is_executable_call(self, call: ast.Call) -> bool:
        """A dispatch of a compiled program: a known jitted handle, or
        a subscript of a *cache* table (``execs[label](...)``)."""
        if self.handle_ref(call.func) is not None:
            return True
        return _cache_store_target(call.func)  # Subscript of *cache*


def _enclosing_functions(tree: ast.AST) -> List[ast.FunctionDef]:
    return [n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]


# -- recompile-hazard --------------------------------------------------------

_UNSTABLE_STATIC = (ast.Dict, ast.List, ast.Set, ast.JoinedStr)


def _check_recompile(ctx: AnalysisContext, pf: ParsedFile,
                     mod: _Module) -> None:
    for fn in _enclosing_functions(pf.tree):
        for node in ast.walk(fn):
            # (a) jax.jit(f)(x): fresh wrapper per call
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Call)
                    and (_is_jit_expr(node.func.func)
                         or _partial_jit(node.func))):
                ctx.add(pf, node.lineno, "recompile-hazard",
                        f"{fn.name} builds a fresh jax.jit wrapper per "
                        "call — jit's cache keys on function identity, "
                        "so every call recompiles; build the wrapper "
                        "once (module/__init__ scope or a *cache* table)",
                        key=f"{fn.name}:jit-per-call")
            # (b) jit/AOT construction inside a loop without cache store
            if isinstance(node, (ast.For, ast.While)):
                for stmt in node.body:
                    for sub in ast.walk(stmt):
                        if not isinstance(sub, ast.Call):
                            continue
                        is_ctor = (_is_jit_expr(sub.func)
                                   or _partial_jit(sub)
                                   or _compile_chain(sub))
                        if not is_ctor:
                            continue
                        cached = (isinstance(stmt, ast.Assign) and any(
                            _cache_store_target(t) for t in stmt.targets))
                        if not cached:
                            ctx.add(
                                pf, sub.lineno, "recompile-hazard",
                                f"{fn.name} constructs a jit/AOT "
                                "executable inside a loop without "
                                "storing it in a *cache* table — every "
                                "iteration recompiles",
                                key=f"{fn.name}:jit-in-loop")
            # (c) unstable values at static_argnums positions
            if isinstance(node, ast.Call):
                ref = mod.handle_ref(node.func)
                info = mod.jitted.get(ref) if ref else None
                static = info.get("static") if info else None
                if static:
                    for pos in static:
                        if pos >= len(node.args):
                            continue
                        arg = node.args[pos]
                        bad = isinstance(arg, _UNSTABLE_STATIC) or (
                            isinstance(arg, ast.Call)
                            and isinstance(arg.func, ast.Attribute)
                            and arg.func.attr == "format")
                        if bad:
                            what = ("an f-string/.format key built "
                                    "per call" if not isinstance(
                                        arg, (ast.Dict, ast.List,
                                              ast.Set))
                                    else "an unhashable literal")
                            ctx.add(
                                pf, arg.lineno, "recompile-hazard",
                                f"{fn.name} passes {what} at static "
                                f"position {pos} of jitted {ref} — "
                                "unhashable statics raise, fresh "
                                "strings miss the compile cache every "
                                "call",
                                key=f"{fn.name}:unstable-static:{ref}")
        # (d) os.environ reads inside cache-key builders
        if "cache_key" in fn.name:
            for node in ast.walk(fn):
                hit = None
                if (isinstance(node, ast.Attribute)
                        and isinstance(node.value, ast.Name)
                        and node.value.id == "os"
                        and node.attr in ("environ", "getenv")):
                    hit = f"os.{node.attr}"
                if hit:
                    ctx.add(pf, node.lineno, "recompile-hazard",
                            f"{fn.name} reads {hit} while building a "
                            "compile-cache key — an env mutation "
                            "mid-run silently flips the key and "
                            "recompiles; read through "
                            "base/knobs.value() instead",
                            key=f"{fn.name}:env-cache-key")


# -- donation-discipline -----------------------------------------------------

def _name_events(fn: ast.AST, name: str) -> List[Tuple[int, str, int]]:
    """(lineno, 'load'|'store', node id) for every use of ``name``."""
    out = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and node.id == name:
            kind = "store" if isinstance(
                node.ctx, (ast.Store, ast.Del)) else "load"
            out.append((node.lineno, kind, id(node)))
    out.sort()
    return out


def _check_donation(ctx: AnalysisContext, pf: ParsedFile,
                    mod: _Module) -> None:
    """Donated argument read after the call."""
    for fn in _enclosing_functions(pf.tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            ref = mod.handle_ref(node.func)
            info = mod.jitted.get(ref) if ref else None
            donate = info.get("donate") if info else None
            if not donate:
                continue
            for pos in donate:
                if pos >= len(node.args):
                    continue
                arg = node.args[pos]
                if not isinstance(arg, ast.Name):
                    continue
                for lineno, kind, nid in _name_events(fn, arg.id):
                    if lineno < node.lineno or nid == id(arg):
                        continue
                    if kind == "store":
                        break              # rebound: name is dead
                    ctx.add(pf, lineno, "donation-discipline",
                            f"{fn.name} reads {arg.id!r} after donating "
                            f"it to {ref} (argnum {pos}) — the buffer "
                            "is already reused by XLA; rebind the name "
                            "from the call's result or copy before "
                            "donating",
                            key=f"{fn.name}:use-after-donate:{arg.id}")
                    break


# -- transfer-discipline -----------------------------------------------------

def _static_param_names(fn: ast.AST,
                        static: Optional[Tuple[int, ...]]) -> Set[str]:
    if not static or not isinstance(
            fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        return set()
    params = [p.arg for p in (list(fn.args.posonlyargs)
                              + list(fn.args.args))]
    return {params[i] for i in static if 0 <= i < len(params)}


def _check_traced_transfers(ctx: AnalysisContext, pf: ParsedFile,
                            mod: _Module) -> None:
    """np/.item/.tolist/float-of-parameter inside jit-traced code
    (root + transitive same-module callees, as in jitpure)."""
    roots = _jit_roots(pf.tree, mod.index)
    for root_name, root_fn in roots:
        static_names = _static_param_names(
            root_fn, (mod.jitted.get(root_name) or {}).get("static"))
        visited: Set[str] = set()
        frontier: List[Tuple[str, ast.AST]] = [(root_name, root_fn)]
        depth = 0
        reported: Set[Tuple[str, int]] = set()
        while frontier and depth < _MAX_DEPTH:
            depth += 1
            nxt: List[Tuple[str, ast.AST]] = []
            for fname, fnode in frontier:
                if fname in visited:
                    continue
                visited.add(fname)
                if fnode is root_fn and isinstance(
                        fnode, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                    params = {p.arg for p in
                              (list(fnode.args.posonlyargs)
                               + list(fnode.args.args)
                               + list(fnode.args.kwonlyargs))}
                else:
                    params = set()
                body = fnode.body if isinstance(fnode.body, list) \
                    else [fnode.body]
                for stmt in body:
                    for node in ast.walk(stmt):
                        if not isinstance(node, ast.Call):
                            continue
                        f = node.func
                        if isinstance(f, ast.Name):
                            if (f.id in ("float", "int", "bool")
                                    and len(node.args) == 1
                                    and isinstance(node.args[0],
                                                   ast.Name)
                                    and node.args[0].id in params
                                    and node.args[0].id
                                    not in static_names
                                    and fnode is root_fn):
                                key = (f"{fname}:coerce", node.lineno)
                                if key not in reported:
                                    reported.add(key)
                                    ctx.add(
                                        pf, node.lineno,
                                        "transfer-discipline",
                                        f"jitted {root_name} coerces "
                                        f"traced parameter "
                                        f"{node.args[0].id!r} with "
                                        f"{f.id}() — a device sync "
                                        "baked at trace time (or "
                                        "ConcretizationTypeError)",
                                        key=f"{root_name}:coerce:"
                                            f"{node.args[0].id}")
                            elif f.id in mod.index \
                                    and f.id not in visited:
                                nxt.append((f.id, mod.index[f.id]))
                        elif isinstance(f, ast.Attribute):
                            base = f.value
                            if (isinstance(base, ast.Name)
                                    and base.id in ("np", "numpy")):
                                key = (f"{fname}:np", node.lineno)
                                if key not in reported:
                                    reported.add(key)
                                    via = "" if fname == root_name \
                                        else f" (via {fname})"
                                    ctx.add(
                                        pf, node.lineno,
                                        "transfer-discipline",
                                        f"jitted {root_name}{via} "
                                        f"calls np.{f.attr} — numpy "
                                        "forces a host transfer of "
                                        "traced values (or raises); "
                                        "use jnp inside traced code",
                                        key=f"{root_name}:np:{f.attr}")
                            elif f.attr in ("item", "tolist"):
                                key = (f"{fname}:sync", node.lineno)
                                if key not in reported:
                                    reported.add(key)
                                    via = "" if fname == root_name \
                                        else f" (via {fname})"
                                    ctx.add(
                                        pf, node.lineno,
                                        "transfer-discipline",
                                        f"jitted {root_name}{via} "
                                        f"calls .{f.attr}() — host "
                                        "materialization inside "
                                        "traced code",
                                        key=f"{root_name}:sync:{f.attr}")
            frontier = nxt


def _check_round_loops(ctx: AnalysisContext, pf: ParsedFile,
                       mod: _Module) -> None:
    """.item()/.tolist()/loop-invariant device_put inside loops that
    dispatch a compiled executable."""
    for fn in _enclosing_functions(pf.tree):
        for loop in ast.walk(fn):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            exec_calls = [n for n in ast.walk(loop)
                          if isinstance(n, ast.Call)
                          and mod.is_executable_call(n)]
            if not exec_calls:
                continue
            #: nodes feeding the executable call = ingest, exempt
            fed: Set[int] = set()
            for c in exec_calls:
                for a in list(c.args) + [kw.value for kw in c.keywords]:
                    fed.update(id(n) for n in ast.walk(a))
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call) or id(node) in fed:
                    continue
                f = node.func
                if _call_name(f) == "device_put":
                    ctx.add(pf, node.lineno, "transfer-discipline",
                            f"{fn.name} calls device_put inside its "
                            "round loop (the loop dispatching a "
                            "compiled executable) — a host->device "
                            "upload per round; hoist to ingest",
                            key=f"{fn.name}:roundloop-device-put")
                elif (isinstance(f, ast.Attribute)
                        and f.attr in ("item", "tolist")):
                    ctx.add(pf, node.lineno, "transfer-discipline",
                            f"{fn.name} calls .{f.attr}() inside its "
                            "round loop — a blocking device sync per "
                            "round; accumulate on device and fetch "
                            "once after the loop",
                            key=f"{fn.name}:roundloop-sync:{f.attr}")


# -- driver ------------------------------------------------------------------

def _in_scope(rel: str) -> bool:
    return (rel.startswith("dmlc_core_tpu/")
            or rel.startswith("scripts/")
            or rel == "bench.py")


def run(ctx: AnalysisContext, selected: Set[str]) -> None:
    """Run the selected jax-discipline rules over every in-scope
    Python file (dmlc_core_tpu/, scripts/, bench.py — tests and
    examples build throwaway programs and are exempt)."""
    for pf in ctx.files:
        if pf.kind != "py" or pf.tree is None or not _in_scope(pf.rel):
            continue
        mod = _Module(pf.tree)
        if "recompile-hazard" in selected:
            _check_recompile(ctx, pf, mod)
        if "donation-discipline" in selected:
            _check_donation(ctx, pf, mod)
        if "transfer-discipline" in selected:
            _check_traced_transfers(ctx, pf, mod)
            _check_round_loops(ctx, pf, mod)
