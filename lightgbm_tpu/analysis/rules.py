"""jaxlint built-in rules R1-R21 (R18 retired with the booster fleet).

Each rule is a generator over the :class:`~.core.PackageIndex`; see
``docs/ANALYSIS.md`` for the catalogue with examples and the pragma format.
Scope vocabulary used below:

* *hot function* — jit-decorated, reachable from a jit-decorated function
  through the package call graph, or nested inside one (its body is traced);
* *host driver* — a non-traced function whose ``for``/``while`` loop calls a
  jit-decorated function (the boosting/growth round loops).
"""

from __future__ import annotations

import ast
import re
from typing import Iterator, Optional

from .core import (Finding, FuncInfo, PackageIndex, dotted_name,
                   has_cache_decorator, jit_info_from_call, register_rule)

_NUMPY_ALIASES = ("np", "numpy", "onp")
_SYNC_ATTRS = ("item", "tolist")
_NP_SYNC_FUNCS = ("asarray", "array")
_CAST_BUILTINS = ("float", "int", "bool")
_SHAPE_ATTRS = ("shape", "ndim", "size", "dtype")
_COLLECTIVES = ("psum", "pmax", "pmin", "pmean", "all_gather", "psum_scatter",
                "all_to_all", "ppermute", "pshuffle", "axis_index")
_PY_IMPURE_MODULES = ("time", "random")


def _own_body(fi: FuncInfo, include_nested: bool = False
              ) -> Iterator[ast.AST]:
    """Walk fi's body.  With include_nested=False (the default), nested
    function defs are skipped — each nested def is its own FuncInfo, so
    per-function iteration visits every node exactly ONCE (no duplicate
    findings) while lambdas, which have no FuncInfo, stay with the
    enclosing function.  include_nested=True additionally descends into
    nested defs; use it only when iterating top-level functions exclusively
    (R3 does, to see closure reads)."""

    def rec(node: ast.AST) -> Iterator[ast.AST]:
        for child in ast.iter_child_nodes(node):
            if (not include_nested and isinstance(
                    child, (ast.FunctionDef, ast.AsyncFunctionDef))):
                continue
            yield child
            yield from rec(child)

    def top() -> Iterator[ast.AST]:
        # statement body only — decorators/defaults/annotations are the
        # ENCLOSING scope's code (a @partial(jax.jit, ...) decorator is not
        # a jit constructed "inside" the function it decorates)
        for stmt in fi.node.body:
            yield stmt
            if (not include_nested and isinstance(
                    stmt, (ast.FunctionDef, ast.AsyncFunctionDef))):
                continue  # direct nested def: own FuncInfo covers its body
            yield from rec(stmt)

    return top()


def _is_np_attr(node: ast.AST, attrs) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr in attrs
            and isinstance(node.value, ast.Name)
            and node.value.id in _NUMPY_ALIASES)


def _mentions_param(node: ast.AST, params) -> bool:
    return any(isinstance(n, ast.Name) and n.id in params
               for n in ast.walk(node))


def _is_shape_like(node: ast.AST) -> bool:
    """Expressions like x.shape[0] / len(x) / x.ndim are Python ints at
    trace time — casting them is NOT a host sync."""
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in _SHAPE_ATTRS:
            return True
        if (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                and n.func.id == "len"):
            return True
    return False


def _finding(fi: FuncInfo, node: ast.AST, rule: str, msg: str, hint: str
             ) -> Finding:
    return Finding(str(fi.module.path), getattr(node, "lineno", fi.node.lineno),
                   rule, msg, hint)


# ---------------------------------------------------------------------------
# R1 — host-sync-in-hot-path
# ---------------------------------------------------------------------------

@register_rule("R1", "host-sync-in-hot-path")
def r1_host_sync(pkg: PackageIndex) -> Iterator[Finding]:
    """``np.asarray``/``np.array``/``.item()``/``.tolist()`` force a device
    pull (or break the trace outright inside jit); builtin ``float``/``int``/
    ``bool`` applied to a traced parameter concretize it.  In a hot function
    any of these is a trace error or a silent sync; in a host driver loop it
    is a per-round blocking pull that stalls the device queue
    (docs/NEXT.md)."""
    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            hot = pkg.is_hot(fi)
            driver = pkg.is_host_driver(fi)
            if not hot and not driver:
                continue
            where = "jit-traced code" if hot else "a jit-dispatching host loop"
            # in a host driver only the LOOP body is hot: a pull before/after
            # the loop is a once-per-call cost (e.g. a numpy-returning API
            # boundary), not the per-round sync class this rule hunts
            loop_nodes = PackageIndex._loop_body_walk(fi) if driver else None
            for node in _own_body(fi):
                if not isinstance(node, ast.Call):
                    continue
                if loop_nodes is not None and node not in loop_nodes:
                    continue
                if _is_np_attr(node.func, _NP_SYNC_FUNCS):
                    name = dotted_name(node.func)
                    yield _finding(
                        fi, node, "R1",
                        f"{name}(...) in {where} ({fi.qualname})",
                        "use jnp inside traces; hoist host pulls out of the "
                        "round loop or batch them into one sync")
                elif (isinstance(node.func, ast.Attribute)
                        and node.func.attr in _SYNC_ATTRS and not node.args):
                    yield _finding(
                        fi, node, "R1",
                        f".{node.func.attr}() device pull in {where} "
                        f"({fi.qualname})",
                        "keep scalars on device (0-d arrays) until the host "
                        "actually needs them")
                elif (hot and isinstance(node.func, ast.Name)
                        and node.func.id in _CAST_BUILTINS
                        and len(node.args) == 1
                        and _mentions_param(node.args[0], fi.params)
                        and not _is_shape_like(node.args[0])):
                    yield _finding(
                        fi, node, "R1",
                        f"{node.func.id}() concretizes a traced argument in "
                        f"{fi.qualname}",
                        "operate on the traced value with jnp, or mark the "
                        "argument static if it is genuinely a Python scalar")


# ---------------------------------------------------------------------------
# R2 — recompile-hazard
# ---------------------------------------------------------------------------

def _enclosing_is_cached(fi: FuncInfo) -> bool:
    cur: Optional[FuncInfo] = fi
    while cur is not None:
        if has_cache_decorator(cur.node):
            return True
        cur = cur.parent
    return False


@register_rule("R2", "recompile-hazard")
def r2_recompile(pkg: PackageIndex) -> Iterator[Finding]:
    """Two statically-detectable recompile classes: (a) a ``jax.jit`` created
    inside a function body keys a FRESH trace cache per call — every
    invocation of the enclosing function retraces (and leaks compiled
    executables), unless the enclosing function is memoized; (b) a
    list/dict/set literal passed for a ``static_argnames``/``static_argnums``
    parameter is unhashable and raises at call time.  Per-round retraces
    from *varying* static values are a runtime property — the compile
    counter in ``utils/sanitizer.py`` is the matching runtime check."""
    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            if _enclosing_is_cached(fi):
                continue
            # (a) nested jit: decorated nested defs...
            if fi.jit is not None and fi.parent is not None:
                if not _enclosing_is_cached(fi.parent):
                    yield _finding(
                        fi, fi.node, "R2",
                        f"jit-decorated {fi.qualname} is created per call of "
                        f"{fi.parent.qualname} (fresh trace cache each time)",
                        "hoist the jit to module level, or memoize the "
                        "factory (functools.lru_cache / an explicit cache)")
            # ...and jax.jit(...) call expressions in the body
            for node in _own_body(fi):
                if isinstance(node, ast.Call) and \
                        jit_info_from_call(node) is not None:
                    yield _finding(
                        fi, node, "R2",
                        f"jax.jit(...) constructed inside {fi.qualname} "
                        "(fresh trace cache per call)",
                        "hoist to module level or memoize the factory "
                        "(functools.lru_cache) keyed by the static config")

        # (b) unhashable static args at resolved jitted call sites
        for fi in mod.functions.values():
            for node in _own_body(fi):
                if not isinstance(node, ast.Call):
                    continue
                target = pkg.resolve_call(mod, node.func)
                callee = pkg.lookup(target) if target else None
                if callee is None or callee.jit is None:
                    continue
                static_idx = set(callee.jit.static_argnums)
                static_names = set(callee.jit.static_argnames)
                pos_params = callee.params
                for i, arg in enumerate(node.args):
                    name = pos_params[i] if i < len(pos_params) else None
                    if (i in static_idx or name in static_names) and \
                            isinstance(arg, (ast.List, ast.Dict, ast.Set)):
                        yield _finding(
                            fi, arg, "R2",
                            f"unhashable literal for static arg "
                            f"{name or i} of {callee.qualname}",
                            "pass a tuple/frozenset — static args are "
                            "hashed into the jit cache key")
                for kw in node.keywords:
                    by_num = (kw.arg in pos_params
                              and pos_params.index(kw.arg) in static_idx)
                    if (kw.arg in static_names or by_num) and isinstance(
                            kw.value, (ast.List, ast.Dict, ast.Set)):
                        yield _finding(
                            fi, kw.value, "R2",
                            f"unhashable literal for static arg {kw.arg} of "
                            f"{callee.qualname}",
                            "pass a tuple/frozenset — static args are "
                            "hashed into the jit cache key")


# ---------------------------------------------------------------------------
# R3 — use-after-donate
# ---------------------------------------------------------------------------

def _donated_arg_names(callee: FuncInfo, call: ast.Call):
    """Names of simple variables the call site passes in donated positions."""
    jit = callee.jit
    donated_idx = set(jit.donate_argnums)
    donated_names = set(jit.donate_argnames)
    pos_params = callee.params
    for i, arg in enumerate(call.args):
        pname = pos_params[i] if i < len(pos_params) else None
        if i in donated_idx or pname in donated_names:
            dn = dotted_name(arg)
            if dn:
                yield dn
    for kw in call.keywords:
        if kw.arg in donated_names or (
                kw.arg in pos_params and pos_params.index(kw.arg) in donated_idx):
            dn = dotted_name(kw.value)
            if dn:
                yield dn


@register_rule("R3", "use-after-donate")
def r3_use_after_donate(pkg: PackageIndex) -> Iterator[Finding]:
    """A buffer passed through a ``donate_argnums`` position is DEAD after
    the call — XLA may have reused its memory for the output.  Reading the
    old variable afterwards raises at best (deleted-buffer error) and
    corrupts silently at worst (sharded aliasing edge cases).  The windowed
    grower donates its 1.5 GB-at-Epsilon hist state, so its host loop must
    thread the state linearly: always rebind (``state = f(state, ...)``),
    never touch the pre-call name again."""
    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            if fi.parent is not None:
                # nested defs are covered by their top-level ancestor's
                # include_nested walk (closure reads of a donated name must
                # be visible); iterating them again would double-report
                continue
            calls = []  # (lineno, donated-name)
            rebinds = {}  # name -> sorted lines where it is (re)assigned
            loads = {}  # name -> lines where it is read
            for node in _own_body(fi, include_nested=True):
                if isinstance(node, ast.Call):
                    target = pkg.resolve_call(mod, node.func)
                    callee = pkg.lookup(target) if target else None
                    if callee is not None and callee.jit is not None and (
                            callee.jit.donate_argnums
                            or callee.jit.donate_argnames):
                        for dn in _donated_arg_names(callee, node):
                            calls.append((node.lineno, dn, callee.qualname))
                if isinstance(node, (ast.Name, ast.Attribute)):
                    dn = dotted_name(node)
                    if dn is None:
                        continue
                    ctx = getattr(node, "ctx", None)
                    if isinstance(ctx, ast.Store):
                        rebinds.setdefault(dn, []).append(node.lineno)
                    elif isinstance(ctx, ast.Load):
                        loads.setdefault(dn, []).append(node.lineno)
            for call_line, dn, callee_name in calls:
                # first rebind at/after the call line kills the old binding
                # (x = f(x) rebinds on the call line itself)
                rebind_line = min(
                    (ln for ln in rebinds.get(dn, []) if ln >= call_line),
                    default=None)
                for load_line in loads.get(dn, []):
                    if load_line <= call_line:
                        continue
                    if rebind_line is not None and load_line >= rebind_line:
                        continue
                    yield Finding(
                        str(mod.path), load_line, "R3",
                        f"{dn} read after being donated to {callee_name} "
                        f"(line {call_line}) in {fi.qualname}",
                        "rebind the donated variable to the call result "
                        "(state = f(state, ...)) and only use the new value")


# ---------------------------------------------------------------------------
# R4 — collective-axis-name
# ---------------------------------------------------------------------------

@register_rule("R4", "collective-axis-name")
def r4_axis_names(pkg: PackageIndex) -> Iterator[Finding]:
    """Every string-literal axis name fed to a collective must be one of the
    axis constants the mesh module declares (``DATA_AXIS``/``FEATURE_AXIS``
    in ``parallel/mesh.py``): a typo'd axis name fails only when that code
    path finally runs under ``shard_map``, usually on real hardware.  Names
    that flow in as function parameters are dynamic and skipped."""
    declared = pkg.axis_names
    if not declared:
        return
    for mod in pkg.modules.values():
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = dotted_name(node.func)
            if fn is None:
                continue
            parts = fn.split(".")
            if parts[-1] not in _COLLECTIVES:
                continue
            if not (len(parts) == 1 or parts[-2] == "lax"):
                continue
            axis_arg = None
            for kw in node.keywords:
                if kw.arg in ("axis_name", "axis"):
                    axis_arg = kw.value
            if axis_arg is None:
                want = 0 if parts[-1] == "axis_index" else 1
                if len(node.args) > want:
                    axis_arg = node.args[want]
            if axis_arg is None:
                continue
            if isinstance(axis_arg, ast.Constant) and isinstance(
                    axis_arg.value, str):
                if axis_arg.value not in declared:
                    yield Finding(
                        str(mod.path), axis_arg.lineno, "R4",
                        f"collective axis name {axis_arg.value!r} is not a "
                        f"declared mesh axis {sorted(declared)}",
                        "use the axis constants from parallel/mesh.py "
                        "(DATA_AXIS / FEATURE_AXIS), not ad-hoc strings")
            elif isinstance(axis_arg, ast.Name):
                # resolve the name to a module-level string constant (local
                # or imported); unresolvable names (parameters, locals) are
                # dynamic and out of static reach
                nm = axis_arg.id
                value = mod.str_constants.get(nm)
                if value is None:
                    imp = mod.imports.get(nm)
                    if imp is not None and imp[0] == "func":
                        src = pkg.modules.get(imp[1][0])
                        if src is not None:
                            value = src.str_constants.get(imp[1][1])
                if value is not None and value not in declared:
                    yield Finding(
                        str(mod.path), axis_arg.lineno, "R4",
                        f"collective axis name {nm}={value!r} is not a "
                        f"declared mesh axis {sorted(declared)}",
                        "use the axis constants from parallel/mesh.py "
                        "(DATA_AXIS / FEATURE_AXIS), not ad-hoc strings")


# ---------------------------------------------------------------------------
# R5 — impure-under-jit
# ---------------------------------------------------------------------------

@register_rule("R5", "impure-under-jit")
def r5_impure(pkg: PackageIndex) -> Iterator[Finding]:
    """Python-level side effects inside traced code run ONCE at trace time
    and never again: ``time.*`` / stdlib ``random`` / ``np.random`` calls
    bake a single host value into the compiled program, and ``global``/
    ``nonlocal`` writes mutate host state from inside a trace (executed at
    trace time, silently skipped on cached calls).  Use ``jax.random`` with
    threaded keys, pass times in as arguments, and carry state through
    function returns."""
    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            if not pkg.is_hot(fi):
                continue
            for node in _own_body(fi):
                if isinstance(node, (ast.Global, ast.Nonlocal)):
                    kind = ("global" if isinstance(node, ast.Global)
                            else "nonlocal")
                    yield _finding(
                        fi, node, "R5",
                        f"{kind} write ({', '.join(node.names)}) inside "
                        f"traced {fi.qualname} runs at trace time only",
                        "thread state through arguments/returns instead of "
                        "mutating host scope under jit")
                if not isinstance(node, ast.Call):
                    continue
                fn = dotted_name(node.func)
                if fn is None:
                    continue
                parts = fn.split(".")
                if parts[0] in _PY_IMPURE_MODULES and len(parts) > 1:
                    yield _finding(
                        fi, node, "R5",
                        f"{fn}() inside traced {fi.qualname} is evaluated "
                        "once at trace time",
                        "pass host values in as arguments; use jax.random "
                        "for in-trace randomness")
                elif (len(parts) >= 3 and parts[0] in _NUMPY_ALIASES
                        and parts[1] == "random"):
                    yield _finding(
                        fi, node, "R5",
                        f"{fn}() host RNG inside traced {fi.qualname} "
                        "(one sample baked into the trace)",
                        "use jax.random with an explicitly threaded key")


# ---------------------------------------------------------------------------
# R6 — fusable-round-loop
# ---------------------------------------------------------------------------

_HOST_CONSUMER_ATTRS = ("item", "tolist")


def _call_names(node: ast.AST) -> set:
    """Simple names mentioned anywhere in `node`."""
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _statement_branch_contexts(root: ast.AST) -> dict:
    """Map each statement under `root` to its chain of enclosing if-arms
    ((id(if_node), arm), ...) — statements in the body vs orelse of the
    same ``if`` are mutually exclusive within one iteration."""
    out: dict = {}

    def rec(stmts, ctx) -> None:
        for st in stmts:
            out[st] = ctx
            if isinstance(st, ast.If):
                rec(st.body, ctx + ((id(st), 0),))
                rec(st.orelse, ctx + ((id(st), 1),))
            elif isinstance(st, ast.Match):
                for arm, case in enumerate(st.cases):
                    rec(case.body, ctx + ((id(st), arm),))
            elif isinstance(st, (ast.For, ast.While)):
                rec(st.body, ctx)
                rec(st.orelse, ctx)
            elif isinstance(st, ast.With):
                rec(st.body, ctx)
            elif isinstance(st, ast.Try):
                rec(st.body, ctx)
                rec(st.orelse, ctx)
                rec(st.finalbody, ctx)
                for h in st.handlers:
                    rec(h.body, ctx)

    rec(getattr(root, "body", []), ())
    return out


def _mutually_exclusive(ctx_a, ctx_b) -> bool:
    """True when the two branch contexts share an ``if`` with different
    arms — at most one of the statements runs per iteration."""
    arms_a = dict(ctx_a)
    return any(if_id in arms_a and arms_a[if_id] != arm
               for if_id, arm in ctx_b)


@register_rule("R6", "fusable-round-loop")
def r6_fusable_round_loop(pkg: PackageIndex) -> Iterator[Finding]:
    """Two consecutive jitted dispatches on the same DONATED state inside
    a host round loop, with no host consumer of the first call's results
    between them, are one fused dispatch waiting to happen: each extra
    dispatch costs host time and splits the round
    into separately scheduled XLA programs (the windowed grower's round-6
    admit/pass split — fused in round 7, docs/PERF_NOTES.md).  A host
    read (``np.asarray``/``.item()``/``float()`` of the first call's
    output) between the two is a REAL data dependency the host consumes
    — the loop genuinely needs the sync (or an async-read protocol) and
    is not flagged."""
    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            if not pkg.is_host_driver(fi):
                continue
            # pair dispatches PER LOOP: two single-dispatch loops in
            # sequence share nothing per-iteration and must not pair
            # (nested loops revisit their nodes under the outer loop too
            # — `seen` dedups the identical finding)
            loops = [node for node in _own_body(fi)
                     if isinstance(node, (ast.For, ast.While))]
            seen = set()
            for loop in loops:
                loop_nodes = set(ast.walk(loop)) - {loop}
                branch_ctx = _statement_branch_contexts(loop)
                donated_calls = []  # (line, assigned, donated, qualname, ctx)
                dispatch_nodes = set()  # AST nodes inside dispatch assigns
                for node in _own_body(fi):
                    if node not in loop_nodes:
                        continue
                    if isinstance(node, ast.Assign) and isinstance(
                            node.value, ast.Call):
                        call = node.value
                        target = pkg.resolve_call(mod, call.func)
                        callee = pkg.lookup(target) if target else None
                        if callee is not None and callee.jit is not None and (
                                callee.jit.donate_argnums
                                or callee.jit.donate_argnames):
                            assigned = set()
                            for t in node.targets:
                                assigned |= _call_names(t)
                            donated_calls.append((
                                node.lineno, assigned,
                                set(_donated_arg_names(callee, call)),
                                callee.qualname, branch_ctx.get(node, ())))
                            dispatch_nodes.update(ast.walk(node))
                consumers = []  # (lineno, mentioned-names) — sync calls
                loads = []  # (lineno, name) — bare reads OUTSIDE dispatches
                for node in _own_body(fi):
                    if node not in loop_nodes:
                        continue
                    if isinstance(node, ast.Call):
                        is_sync = _is_np_attr(node.func, _NP_SYNC_FUNCS) or (
                            isinstance(node.func, ast.Attribute)
                            and node.func.attr in _HOST_CONSUMER_ATTRS) or (
                            isinstance(node.func, ast.Name)
                            and node.func.id in _CAST_BUILTINS)
                        if is_sync:
                            consumers.append((node.lineno, _call_names(node)))
                    if (isinstance(node, ast.Name)
                            and isinstance(getattr(node, "ctx", None), ast.Load)
                            and node not in dispatch_nodes):
                        # reads INSIDE a dispatch are device arguments, not
                        # host consumption (run_pass(state, info) is still
                        # fusable); a sync call inside a dispatch argument
                        # (int(np.asarray(info)[0])) is caught above
                        loads.append((node.lineno, node.id))
                donated_calls.sort(key=lambda e: e[0])
                for (la, assigned, _d_a, name_a, ctx_a), (
                        lb, _as_b, donated_b, name_b, ctx_b) in zip(
                        donated_calls, donated_calls[1:]):
                    threaded = assigned & donated_b
                    if not threaded:
                        continue
                    if _mutually_exclusive(ctx_a, ctx_b):
                        # if/else arms: only one dispatch runs per
                        # iteration — nothing to fuse
                        continue
                    # a host consumer suppresses the finding — either an
                    # explicit sync call touching the first dispatch's
                    # outputs (lc <= lb: a consumer ON the second
                    # dispatch's line still counts), or a bare read of a
                    # non-threaded output outside any dispatch
                    # (`if info[0]: break` implies a real host data
                    # dependency even without a recognizable sync call)
                    side_outputs = assigned - donated_b
                    consumed = any(
                        la < lc <= lb and (names & assigned)
                        for lc, names in consumers) or any(
                        la < ll <= lb and nm in side_outputs
                        for ll, nm in loads)
                    if consumed:
                        continue
                    key = (la, lb, name_a, name_b)
                    if key in seen:
                        continue
                    seen.add(key)
                    yield Finding(
                        str(mod.path), lb, "R6",
                        f"{name_b} re-dispatches donated state "
                        f"{sorted(threaded)} produced by {name_a} (line {la}) "
                        f"in {fi.qualname}'s round loop with no host consumer "
                        "between them",
                        "fuse both phases into one jitted round body (one "
                        "dispatch/round); if the host truly needs a value "
                        "between them, read it asynchronously one round behind "
                        "(utils/sanitizer.py async_pull_*)")


# ---------------------------------------------------------------------------
# R7 — host-nonfinite-guard
# ---------------------------------------------------------------------------

_NONFINITE_FUNCS = ("isnan", "isfinite", "isinf")
_NONFINITE_HOST_MODULES = _NUMPY_ALIASES + ("math",)
_DEVICE_NP_ALIASES = ("jnp", "jax")


@register_rule("R7", "host-nonfinite-guard")
def r7_host_nonfinite_guard(pkg: PackageIndex) -> Iterator[Finding]:
    """The NaN-guard anti-pattern: checking per-round tensors for
    non-finite values FROM THE HOST inside a grower/boosting loop.  A
    ``np.isnan(...)``/``math.isnan(...)`` on a device value forces a
    blocking device pull every round (the sync class R1 hunts), and
    ``float()``/``bool()``/``int()`` wrapped around a
    device-side ``jnp.isnan(...)``/``jnp.isfinite(...)`` result is the
    same sync wearing a jnp costume.  The supported pattern costs
    nothing: fold the finite flag into the round's device info vector and
    read it asynchronously one round behind (the windowed grower's guard,
    utils/guards.py + utils/sanitizer.py async_pull_*), or accumulate a
    device-side first-bad-iteration scalar checked at existing sync
    points (models/gbdt.py _guard_accumulate/_guard_check)."""
    hint = ("keep the finite check ON DEVICE: fold it into the round's "
            "info vector and resolve it one round behind "
            "(utils/sanitizer.py async_pull_*), or accumulate a device "
            "flag checked at existing sync points — see "
            "docs/ROBUSTNESS.md and models/gbdt.py::_guard_accumulate")
    def _device_nonfinite_call(node: ast.AST) -> Optional[str]:
        """Dotted name of a jnp/jax is{nan,finite,inf} call inside node."""
        for inner in ast.walk(node):
            if not isinstance(inner, ast.Call):
                continue
            ifn = dotted_name(inner.func)
            if ifn is None:
                continue
            iparts = ifn.split(".")
            if (iparts[-1] in _NONFINITE_FUNCS
                    and iparts[0] in _DEVICE_NP_ALIASES):
                return ifn
        return None

    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            if not pkg.is_host_driver(fi):
                continue
            loop_nodes = PackageIndex._loop_body_walk(fi)
            flagged = set()  # nodes already reported via an if/while test
            for node in _own_body(fi):
                if node not in loop_nodes:
                    continue
                # if/while/assert on a jnp.is* result: __bool__ on a
                # device array — the implicit form of the same sync
                if isinstance(node, (ast.If, ast.While, ast.Assert)):
                    cond = node.test
                    ifn = _device_nonfinite_call(cond)
                    if ifn is not None:
                        flagged.update(ast.walk(cond))
                        yield _finding(
                            fi, cond, "R7",
                            f"branching on {ifn}(...) forces a blocking "
                            f"device pull (implicit bool) in "
                            f"{fi.qualname}'s round loop", hint)
                    continue
                if not isinstance(node, ast.Call) or node in flagged:
                    continue
                fn = dotted_name(node.func)
                if fn is not None:
                    parts = fn.split(".")
                    if (len(parts) >= 2 and parts[-1] in _NONFINITE_FUNCS
                            and parts[0] in _NONFINITE_HOST_MODULES):
                        yield _finding(
                            fi, node, "R7",
                            f"host-side {fn}() non-finite check on a "
                            f"per-round tensor in {fi.qualname}'s round loop "
                            "(one blocking device pull per round)", hint)
                        continue
                if (isinstance(node.func, ast.Name)
                        and node.func.id in _CAST_BUILTINS and node.args):
                    ifn = _device_nonfinite_call(node.args[0])
                    if ifn is not None:
                        yield _finding(
                            fi, node, "R7",
                            f"{node.func.id}({ifn}(...)) pulls a "
                            f"device-side finite flag synchronously in "
                            f"{fi.qualname}'s round loop", hint)


# ---------------------------------------------------------------------------
# R8 — unbucketed-predict-entry
# ---------------------------------------------------------------------------

_MASK_PRODUCING_FNS = ("nonzero", "flatnonzero", "where", "isnan",
                       "isfinite", "isinf")


def _masklike_names(fi: FuncInfo) -> set:
    """Names assigned (anywhere in ``fi``) from a boolean-mask-shaped
    expression — a comparison, a bitwise mask combination (&, |, ~), or a
    ``np.nonzero``/``np.where``/``np.isnan``-class call.  Subscripting a
    batch with one of these produces a DATA-dependent row count, the shape
    class that defeats jit caching."""
    def masky(expr: ast.AST) -> bool:
        for node in ast.walk(expr):
            if isinstance(node, ast.Compare):
                return True
            if isinstance(node, ast.BinOp) and isinstance(
                    node.op, (ast.BitAnd, ast.BitOr)):
                return True
            if isinstance(node, ast.UnaryOp) and isinstance(
                    node.op, ast.Invert):
                return True
            if isinstance(node, ast.Call):
                fn = dotted_name(node.func)
                if fn is not None and fn.split(".")[-1] in _MASK_PRODUCING_FNS:
                    return True
        return False

    out = set()
    for node in _own_body(fi):
        if isinstance(node, ast.Assign) and masky(node.value):
            for t in node.targets:
                out |= _call_names(t)
        elif isinstance(node, ast.AugAssign) and (
                masky(node.value)
                or isinstance(node.op, (ast.BitAnd, ast.BitOr))):
            out |= _call_names(node.target)
    return out


@register_rule("R8", "unbucketed-predict-entry")
def r8_unbucketed_predict_entry(pkg: PackageIndex) -> Iterator[Finding]:
    """A jitted entry point dispatched in a host loop with a DATA-dependent
    leading dimension — the ``X[active]`` anti-pattern the round-9 serving
    rework removed from prediction early-stopping: every distinct mask
    count is a new shape, so the entry RETRACES AND RECOMPILES once per
    distinct active-set size (O(chunks) compiles for one predict call).
    The supported pattern keeps every row in a bucket-padded batch and
    masks inactive rows ON DEVICE (ops/predict.py ``active=`` +
    models/gbdt.py ``_predict_bucket``), so the loop reuses one compiled
    executable."""
    hint = ("pad the batch to a shape bucket and pass the mask to the "
            "device (ops/predict.py active=); shrinking the array "
            "host-side recompiles per distinct mask count — see "
            "docs/ANALYSIS.md (R8) and models/gbdt.py "
            "_predict_raw_early_stop")
    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            if not pkg.is_host_driver(fi):
                continue
            loop_nodes = PackageIndex._loop_body_walk(fi)
            masky = _masklike_names(fi)
            for node in _own_body(fi):
                if node not in loop_nodes or not isinstance(node, ast.Call):
                    continue
                target = pkg.resolve_call(mod, node.func)
                callee = pkg.lookup(target) if target else None
                if callee is None or callee.jit is None:
                    continue
                args = list(node.args) + [kw.value for kw in node.keywords]
                for arg in args:
                    if not isinstance(arg, ast.Subscript):
                        continue
                    idx = arg.slice
                    if isinstance(idx, ast.Name) and idx.id in masky:
                        why = f"boolean-mask subscript [{idx.id}]"
                    elif isinstance(idx, ast.Compare):
                        why = "inline comparison-mask subscript"
                    else:
                        continue
                    yield _finding(
                        fi, node, "R8",
                        f"{callee.qualname} dispatched in {fi.qualname}'s "
                        f"loop with a data-dependent leading dimension "
                        f"({why}): one retrace + compile per distinct mask "
                        "count", hint)


# ---------------------------------------------------------------------------
# R9 — untimed-device-section
# ---------------------------------------------------------------------------

_TIMER_ATTRS = ("perf_counter", "monotonic", "perf_counter_ns",
                "monotonic_ns")
# calls that prove the device queue drained (or a host pull resolved)
# between a dispatch and the timer read: the wall-clock delta then covers
# the device work it claims to measure
_R9_SYNC_ATTRS = ("asarray", "array", "item", "tolist", "block_until_ready",
                  "sync_pull", "async_pull_result")


def _is_timer_call(node: ast.AST) -> bool:
    """``time.perf_counter()`` / ``time.time()`` / ``time.monotonic()``
    (any module alias whose name contains "time"; bare ``perf_counter``
    from a ``from time import`` also counts)."""
    if not isinstance(node, ast.Call):
        return False
    fn = dotted_name(node.func)
    if fn is None:
        return False
    parts = fn.split(".")
    if parts[-1] in _TIMER_ATTRS:
        return True
    return len(parts) >= 2 and parts[-1] == "time" and "time" in parts[0]


def _r9_sync_lines(fi: FuncInfo) -> list:
    out = []
    for node in _own_body(fi):
        if not isinstance(node, ast.Call):
            continue
        fn = dotted_name(node.func)
        if fn is not None and fn.split(".")[-1] in _R9_SYNC_ATTRS:
            out.append(node.lineno)
        elif (isinstance(node.func, ast.Name)
                and node.func.id in _CAST_BUILTINS and node.args):
            # int()/float()/bool() of a device value is itself a blocking
            # pull — as a SUPPRESSOR, over-matching is the safe direction
            out.append(node.lineno)
    return out


@register_rule("R9", "untimed-device-section")
def r9_untimed_device_section(pkg: PackageIndex) -> Iterator[Finding]:
    """The async-dispatch mistiming anti-pattern: a ``time.perf_counter()``
    / ``time.time()`` delta taken around a jitted dispatch with no
    accounted sync between the dispatch and the second timer read.  JAX
    dispatch is ASYNCHRONOUS — the jitted call returns as soon as the
    work is enqueued, so the delta measures
    enqueue time, not device compute, and every benchmark built on it is
    fiction (the round-4 ``block_until_ready``-returns-early episode in
    docs/PERF_NOTES.md is the companion failure on the sync side).  A host
    pull (``np.asarray``/``.item()``/``sync_pull``) or an
    ``async_pull_result`` between the dispatch and the read makes the
    delta honest and suppresses the finding — as does routing the section
    through ``utils/profiling.py::timed_section(sync=True)``, which drains
    the queue with the documented host-pull sync."""
    hint = ("resolve a host pull of the dispatched work before reading the "
            "timer (np.asarray of a tiny slice, utils/sanitizer.py "
            "sync_pull/async_pull_result), or use utils/profiling.py "
            "timed_section(sync=True) — raw perf_counter around an async "
            "dispatch times the enqueue, not the device")
    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            if pkg.is_hot(fi):
                continue  # time.* under trace is R5's business
            timer_starts: dict = {}  # var -> [assignment lines]
            subs = []  # (line, names in the Sub expr, has inline timer call)
            dispatch_lines = []
            for node in _own_body(fi):
                if (isinstance(node, ast.Assign) and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and _is_timer_call(node.value)):
                    timer_starts.setdefault(
                        node.targets[0].id, []).append(node.lineno)
                    continue
                if isinstance(node, ast.BinOp) and isinstance(
                        node.op, ast.Sub):
                    has_timer_call = any(_is_timer_call(x)
                                         for x in ast.walk(node))
                    names = {x.id for x in ast.walk(node)
                             if isinstance(x, ast.Name)}
                    if names:
                        subs.append((node.lineno, names, has_timer_call))
                if isinstance(node, ast.Call):
                    target = pkg.resolve_call(mod, node.func)
                    callee = pkg.lookup(target) if target else None
                    if callee is not None and callee.jit is not None:
                        dispatch_lines.append(node.lineno)
            # a delta reads a timer var against a second timer value —
            # either an inline timer call (perf_counter() - t0) or another
            # timer var (t1 - t0, the stored-second-read spelling); decided
            # after the walk, when timer_starts is complete
            deltas = [(ln, names) for ln, names, inline in subs
                      if (names & set(timer_starts))
                      and (inline
                           or len(names & set(timer_starts)) >= 2)]
            if not dispatch_lines or not deltas:
                continue
            sync_lines = _r9_sync_lines(fi)
            for dline, names in deltas:
                for var in names & set(timer_starts):
                    starts = [ln for ln in timer_starts[var] if ln < dline]
                    if not starts:
                        continue
                    s = max(starts)  # the binding this delta reads
                    disp = [d for d in dispatch_lines if s < d < dline]
                    if not disp:
                        continue
                    last_d = max(disp)
                    # a blocking pull at-or-after the last dispatch drains
                    # the queue — earlier dispatches retired with it.
                    # `<=` on the left: np.asarray(step(x)) puts the pull
                    # on the dispatch's own line, and over-matching is the
                    # safe direction for a suppressor
                    if any(last_d <= sl <= dline for sl in sync_lines):
                        continue
                    yield Finding(
                        str(mod.path), dline, "R9",
                        f"wall-clock delta (started line {s}) read around "
                        f"a jitted dispatch (line {last_d}) with no "
                        f"accounted sync before the read in {fi.qualname}",
                        hint)


# ---------------------------------------------------------------------------
# R10 — sync-in-span-close
# ---------------------------------------------------------------------------

# calls that PULL a device value to the host (fresh blocking syncs when the
# value lives on device).  Narrower than R9's suppressor list on purpose:
# here matching is a POSITIVE finding, so the sanitizer-routed accounted
# reads (sync_pull / async_pull_result) are explicitly allowed — closing a
# span AT an accounted sync is the correct pattern, adding a fresh pull to
# "drain for the timer" is the bug.
_R10_FRESH_PULL_ATTRS = ("asarray", "array", "item", "tolist",
                         "block_until_ready", "device_get")
_R10_ACCOUNTED = ("sync_pull", "async_pull_result")
_R10_CLOSE_NAMES = ("__exit__", "close", "end", "finish")


def _is_contextmanager(node: ast.FunctionDef) -> bool:
    return any((dotted_name(d) or "").split(".")[-1] == "contextmanager"
               for d in node.decorator_list)


def _r10_close_paths(mod) -> Iterator:
    """(FuncInfo, first_line) pairs whose body (from first_line on, or all
    of it for None) is a span CLOSE path: the ``__exit__``/``close`` of a
    *Span-named* class, or the after-``yield`` tail of a
    ``@contextmanager`` generator named like a span."""
    for fi in mod.functions.values():
        parts = fi.qualname.split(".")
        if (len(parts) >= 2 and parts[-1] in _R10_CLOSE_NAMES
                and any("span" in p.lower() for p in parts[:-1])):
            yield fi, None
            continue
        if "span" in parts[-1].lower() and _is_contextmanager(fi.node):
            ylines = [n.lineno for n in ast.walk(fi.node)
                      if isinstance(n, (ast.Yield, ast.YieldFrom))]
            if ylines:
                yield fi, min(ylines)


@register_rule("R10", "sync-in-span-close")
def r10_sync_in_span_close(pkg: PackageIndex) -> Iterator[Finding]:
    """The tracing twin of R9's mistiming class: a span ``__exit__`` /
    ``close`` (or the after-yield tail of a ``@contextmanager`` span) that
    performs a FRESH device pull (``np.asarray``/``.item()``/
    ``block_until_ready``/a host cast) to make its duration "honest".
    Spans are opened around device work everywhere the round loops run, so
    a pull in the close path reintroduces exactly the per-round blocking
    sync the round-7 protocol removed — one hidden blocking pull
    per span, and the DispatchCounter budget pins fail with
    tracing on.  The correct pattern is the inverse: close the span AT an
    existing accounted sync (the async info resolve, the predict entry's
    ``sync_pull``) via ``obs.trace.record_span`` — the accounted readers
    (``sync_pull``/``async_pull_result``) are therefore allowed here."""
    hint = ("span closes must not pull: record device-inclusive intervals "
            "retroactively at an existing accounted sync "
            "(obs/trace.py record_span after the async info resolve or the "
            "entry's sync_pull) and let context-manager spans stay "
            "host-causal — see docs/OBSERVABILITY.md 'Span tracing'")
    for mod in pkg.modules.values():
        for fi, after_line in _r10_close_paths(mod):
            for node in _own_body(fi):
                if not isinstance(node, ast.Call):
                    continue
                if after_line is not None and node.lineno <= after_line:
                    continue
                fn = dotted_name(node.func)
                last = fn.split(".")[-1] if fn else None
                if last in _R10_ACCOUNTED:
                    continue
                if last in _R10_FRESH_PULL_ATTRS:
                    yield _finding(
                        fi, node, "R10",
                        f"span close path {fi.qualname} performs a fresh "
                        f"device pull ({last}) — a hidden blocking sync "
                        "per span", hint)


# ---------------------------------------------------------------------------
# R11 — whole-array-vmem-staging
# ---------------------------------------------------------------------------

def _r11_imports_pallas(mod) -> bool:
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ImportFrom):
            src = node.module or ""
            if "pallas" in src or any("pallas" in (a.name or "")
                                      for a in node.names):
                return True
        elif isinstance(node, ast.Import):
            if any("pallas" in a.name for a in node.names):
                return True
    return False


def _r11_variable_dim(shape_node: ast.AST) -> bool:
    """A block shape with a NON-literal dimension — a runtime-dependent
    size (``n``, ``n_pad``, ``x.shape[0]``...), the signature of a block
    sized by the data rather than a fixed tile."""
    if not isinstance(shape_node, ast.Tuple):
        return False
    return any(not isinstance(e, ast.Constant) for e in shape_node.elts)


def _r11_const_index_map(node: ast.AST) -> bool:
    """True when an index_map lambda sends EVERY grid step to the same
    block (body is a literal, or a tuple of literals, ignoring the grid
    args) — with a constant map the block IS the whole array."""
    if not isinstance(node, ast.Lambda):
        return False
    body = node.body
    elts = body.elts if isinstance(body, ast.Tuple) else [body]
    return all(isinstance(e, ast.Constant) for e in elts)


def _r11_module_int_consts(mod) -> set:
    """Module-level ``NAME = <int literal>`` assignments — fixed tile
    constants (``_CHUNK = 512``) that are fine in scratch shapes."""
    out = set()
    for node in mod.tree.body:
        if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Constant) and isinstance(
                node.value.value, int):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
    return out


_R11_CONST_NAME = re.compile(r"^_?[A-Z][A-Z0-9_]*$")


def _r11_scratch_dim_ok(e: ast.AST, consts: set) -> bool:
    """A scratch dimension is fine when it is a literal, a module-level
    int constant, or an ALL-CAPS identifier (the config-tile convention —
    ``_CHUNK``, ``FB``, a budget-derived feature block); a lowercase
    name (``n``, ``n_pad``, ``rows``) is the data-sized signature."""
    if isinstance(e, ast.Constant):
        return True
    name = dotted_name(e)
    if name:
        last = name.split(".")[-1]
        return last in consts or bool(_R11_CONST_NAME.match(last))
    return False


@register_rule("R11", "whole-array-vmem-staging")
def r11_whole_array_vmem_staging(pkg: PackageIndex) -> Iterator[Finding]:
    """A Pallas ``BlockSpec`` whose block shape carries a variable (data-
    dependent) dimension AND whose index map sends every grid step to the
    same block stages the ENTIRE array through VMEM: staging traffic is
    O(N) however little the kernel touches, and the scoped-VMEM budget
    turns into a hard row cap (the v1 partition kernel's deleted
    ``_MAX_VMEM_ROWS = 650_000`` was exactly this).  The fix pattern is
    an HBM ref + chunked DMA: keep the operand un-staged
    (``memory_space=pl.ANY``) and stream fixed-size chunks through a
    small double-buffered VMEM scratch via ``pltpu.make_async_copy``
    (ops/partition_pallas.py v2).  Grid-blocked specs (index map uses a
    grid arg) and fixed-size tiles are the NORMAL Pallas idiom and are
    not flagged; an intentionally staged small variable-size block (an
    O(S) per-segment table) takes a pragma with its reason.

    Round 16 (the megakernel's discipline): ``pltpu.VMEM(...)`` SCRATCH
    allocations are held to the same standard — a scratch buffer sized
    by a data-dependent dimension is whole-array staging by another
    name.  Literal dims, module-level int constants (``_CHUNK``), and
    ALL-CAPS config-tile names (a budget-derived feature block like
    ``FB``) are the normal idiom; a lowercase data name (``n``,
    ``n_pad``) is flagged."""
    hint = ("stage per-chunk, not per-array: give the operand "
            "memory_space=pl.ANY (HBM ref) and DMA fixed-size chunks "
            "into a VMEM scratch with pltpu.make_async_copy, double-"
            "buffered (copy chunk k+1 in while computing chunk k) — see "
            "ops/partition_pallas.py and docs/ANALYSIS.md R11")
    for mod in pkg.modules.values():
        if not _r11_imports_pallas(mod):
            continue
        consts = _r11_module_int_consts(mod)
        for fi in mod.functions.values():
            for node in _own_body(fi):
                if not isinstance(node, ast.Call):
                    continue
                fn = dotted_name(node.func)
                if fn and fn.split(".")[-1] == "VMEM" and node.args:
                    shape = node.args[0]
                    if isinstance(shape, ast.Tuple) and any(
                            not _r11_scratch_dim_ok(e, consts)
                            for e in shape.elts):
                        yield _finding(
                            fi, node, "R11",
                            f"VMEM scratch in {fi.qualname} is sized by a "
                            "data-dependent dimension: scratch residency "
                            "scales with the data and the VMEM budget "
                            "becomes a row cap", hint)
                    continue
                if not fn or fn.split(".")[-1] != "BlockSpec":
                    continue
                block_shape = node.args[0] if node.args else None
                index_map = node.args[1] if len(node.args) > 1 else None
                is_hbm_ref = False
                for kw in node.keywords:
                    if kw.arg == "block_shape":
                        block_shape = kw.value
                    if kw.arg == "index_map":
                        index_map = kw.value
                    if kw.arg == "memory_space" and (
                            dotted_name(kw.value) or "").endswith("ANY"):
                        is_hbm_ref = True  # nothing is staged
                if block_shape is None or not _r11_variable_dim(block_shape):
                    continue
                if is_hbm_ref:
                    continue
                if index_map is not None and not _r11_const_index_map(
                        index_map):
                    continue
                yield _finding(
                    fi, node, "R11",
                    f"BlockSpec in {fi.qualname} stages a variable-size "
                    "array whole in VMEM (non-literal block dimension, "
                    "constant index map): staging is O(N) and the VMEM "
                    "budget becomes a row cap", hint)


# ---------------------------------------------------------------------------
# R12 — raw-model-write
# ---------------------------------------------------------------------------

# name fragments marking an expression as a model/snapshot artifact path —
# matched case-insensitively against identifiers, attribute names, and
# string literals inside the written-path expression
_R12_ARTIFACT_TOKENS = ("model", "snapshot", "manifest", "checkpoint",
                        "ckpt")


def _r12_mentions_artifact(node: ast.AST) -> bool:
    for n in ast.walk(node):
        name = None
        if isinstance(n, ast.Name):
            name = n.id
        elif isinstance(n, ast.Attribute):
            name = n.attr
        elif isinstance(n, ast.Constant) and isinstance(n.value, str):
            name = n.value
        if name is not None:
            low = name.lower()
            if any(t in low for t in _R12_ARTIFACT_TOKENS):
                return True
    return False


@register_rule("R12", "raw-model-write")
def r12_raw_model_write(pkg: PackageIndex) -> Iterator[Finding]:
    """A durable write of a model/snapshot artifact OUTSIDE
    utils/checkpoint.py: ``open(path, "w"/"wb")``, ``np.save``/
    ``np.savez[_compressed]``, or a hand-rolled ``os.replace`` whose
    target expression names a model/snapshot/manifest path.  Every
    durable model write must go through the atomic sha256-trailed helper
    (``checkpoint.atomic_write_text`` / ``save_snapshot``): a raw
    ``open(..., "w")`` torn by a crash leaves a half-file a restart
    happily parses into a half-model — the silent-corruption class the
    round-8 checkpoint layer exists to exclude — and a raw ``os.replace``
    without the fsync'd temp protocol can still publish an incompletely
    flushed file.  Writes of non-artifact paths (logs, predictions,
    metrics, data caches with their own CRC trailers) are not flagged;
    an intentional raw artifact write (e.g. generated source code whose
    name merely contains 'model') takes a pragma with its reason."""
    hint = ("route durable model writes through utils/checkpoint.py: "
            "atomic_write_text(path, text) for plain models, "
            "save_snapshot(path, text, iteration) for trailer-stamped "
            "snapshots, write_fleet_checkpoint for fleet rounds — see "
            "docs/ROBUSTNESS.md and docs/ANALYSIS.md R12")
    for mod in pkg.modules.values():
        if str(mod.path).endswith("checkpoint.py"):
            continue  # the sanctioned writer itself
        for fi in mod.functions.values():
            for node in _own_body(fi):
                if not isinstance(node, ast.Call):
                    continue
                fn = dotted_name(node.func) or ""
                last = fn.split(".")[-1]
                how = None
                if last == "open" and "." not in fn and node.args:
                    mode = None
                    if (len(node.args) > 1
                            and isinstance(node.args[1], ast.Constant)):
                        mode = node.args[1].value
                    for kw in node.keywords:
                        if (kw.arg == "mode"
                                and isinstance(kw.value, ast.Constant)):
                            mode = kw.value.value
                    if (isinstance(mode, str) and "w" in mode
                            and _r12_mentions_artifact(node.args[0])):
                        how = f"open(..., {mode!r})"
                elif (_is_np_attr(node.func,
                                  ("save", "savez", "savez_compressed"))
                      and any(_r12_mentions_artifact(a)
                              for a in node.args)):
                    how = f"np.{last}"
                elif (fn == "os.replace" and len(node.args) > 1
                      and _r12_mentions_artifact(node.args[1])):
                    how = "os.replace"
                if how is not None:
                    yield _finding(
                        fi, node, "R12",
                        f"{fi.qualname} writes a model/snapshot artifact "
                        f"via raw {how} — outside the atomic "
                        "sha256-trailed checkpoint helper, a crash can "
                        "leave a torn file a restart will trust", hint)


# ---------------------------------------------------------------------------
# R13 — collective-outside-fused-round
# ---------------------------------------------------------------------------

_R13_COLLECTIVES = ("psum", "psum_scatter", "all_gather", "pmax", "pmin",
                    "pmean", "all_to_all", "ppermute")


def _r13_body_has_collective(fi: FuncInfo) -> bool:
    for node in _own_body(fi, include_nested=True):
        if isinstance(node, ast.Call):
            fn = dotted_name(node.func)
            if fn and fn.split(".")[-1] in _R13_COLLECTIVES:
                return True
    return False


@register_rule("R13", "collective-outside-fused-round")
def r13_collective_outside_fused_round(pkg: PackageIndex) -> Iterator[Finding]:
    """A cross-device collective issued from a HOST round loop that also
    dispatches donated (fused-round) state — either an eager
    ``jax.lax.psum``/``psum_scatter``/``all_gather`` call, or a second
    jitted dispatch whose body performs the collective.  Either form
    reintroduces the per-round collective round-trip LightGBM's Network
    layer pays (a ReduceScatter per split): one extra dispatch per round
    plus a device-queue barrier at exactly the cadence the fused round
    exists to remove.  On the sharded path the merge belongs INSIDE the
    donated round body — one dispatch, the collective in-trace
    (ops/treegrow_windowed.py::_round_fused under shard_map,
    docs/DISTRIBUTED.md "Sharded fused rounds").  Collectives inside the
    donated callee itself are the FIX, not a finding; loops with no
    donated dispatch (setup/eval phases) are out of scope."""
    hint = ("fold the collective into the donated round body (psum/"
            "psum_scatter inside the shard_mapped fused round — see "
            "docs/ANALYSIS.md R13); if the host truly needs the "
            "reduced value, return it in the round's async info vector")
    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            if pkg.is_hot(fi):
                continue
            loops = [node for node in _own_body(fi)
                     if isinstance(node, (ast.For, ast.While))]
            for loop in loops:
                loop_nodes = set(ast.walk(loop)) - {loop}
                donated_lines = set()
                for node in _own_body(fi):
                    if node not in loop_nodes or not isinstance(
                            node, ast.Call):
                        continue
                    target = pkg.resolve_call(mod, node.func)
                    callee = pkg.lookup(target) if target else None
                    if callee is not None and callee.jit is not None and (
                            callee.jit.donate_argnums
                            or callee.jit.donate_argnames):
                        donated_lines.add(node.lineno)
                if not donated_lines:
                    continue  # not a fused-round loop
                for node in _own_body(fi):
                    if node not in loop_nodes or not isinstance(
                            node, ast.Call):
                        continue
                    if node.lineno in donated_lines:
                        continue  # the fused round itself
                    fn = dotted_name(node.func) or ""
                    last = fn.split(".")[-1]
                    if last in _R13_COLLECTIVES:
                        yield _finding(
                            fi, node, "R13",
                            f"host-issued collective {fn}() in "
                            f"{fi.qualname}'s fused round loop — a "
                            "per-round collective dispatch OUTSIDE the "
                            "donated round body", hint)
                        continue
                    target = pkg.resolve_call(mod, node.func)
                    callee = pkg.lookup(target) if target else None
                    if (callee is not None and callee.jit is not None
                            and not (callee.jit.donate_argnums
                                     or callee.jit.donate_argnames)
                            and _r13_body_has_collective(callee)):
                        yield _finding(
                            fi, node, "R13",
                            f"{callee.qualname} (jitted, collective-"
                            f"bearing) dispatched per round in "
                            f"{fi.qualname}'s fused round loop — the "
                            "merge pays a second dispatch instead of "
                            "riding the donated round", hint)


# ---------------------------------------------------------------------------
# R14 — metadata-via-device-pull
# ---------------------------------------------------------------------------

_R14_META_ATTRS = ("shape", "ndim", "size", "dtype")


def _r14_np_convert_call(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and _is_np_attr(
        node.func, _NP_SYNC_FUNCS)


@register_rule("R14", "metadata-via-device-pull")
def r14_metadata_via_device_pull(pkg: PackageIndex) -> Iterator[Finding]:
    """Reading METADATA through a whole-array host conversion:
    ``np.asarray(x).shape`` / ``np.asarray(x).dtype`` /
    ``len(np.asarray(x))`` / ``x.shape[0].item()``.  On a jitted output
    the ``np.asarray`` is a BLOCKING device pull of the entire buffer —
    paid to read a property (``.shape``/``.dtype``/``len``) the array
    object already exposes for free, device or host (the exact class the
    round-14 review caught in a sharded grower, which read
    ``num_bins_pf``'s length via ``np.asarray`` once per tree).
    Unlike R1 this fires EVERYWHERE, not just hot paths: a metadata read
    never needs the conversion, so the pull is pure waste wherever it
    sits — and on host inputs it is still a gratuitous O(N) copy."""
    hint = ("read .shape/.dtype/len() directly off the array (device "
            "arrays expose them without a transfer), or np.shape(x) for "
            "maybe-list inputs; convert once and bind the result if the "
            "DATA is genuinely needed too")
    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            for node in _own_body(fi):
                if (isinstance(node, ast.Attribute)
                        and node.attr in _R14_META_ATTRS
                        and _r14_np_convert_call(node.value)):
                    yield _finding(
                        fi, node, "R14",
                        f"np.asarray(...).{node.attr} in {fi.qualname}: "
                        "a whole-array pull/copy to read metadata the "
                        "array already exposes", hint)
                elif (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id == "len" and len(node.args) == 1
                        and _r14_np_convert_call(node.args[0])):
                    yield _finding(
                        fi, node, "R14",
                        f"len(np.asarray(...)) in {fi.qualname}: a "
                        "whole-array pull/copy to read a length "
                        ".shape already exposes", hint)
                elif (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "item" and not node.args
                        and isinstance(node.func.value, ast.Subscript)
                        and isinstance(node.func.value.value, ast.Attribute)
                        and node.func.value.value.attr == "shape"):
                    yield _finding(
                        fi, node, "R14",
                        f".shape[...].item() in {fi.qualname}: shape "
                        "entries are Python ints already — .item() here "
                        "signals a device round-trip habit", hint)


# ---------------------------------------------------------------------------
# R15 — staging-alloc-in-serve-loop
# ---------------------------------------------------------------------------

_R15_FRESH_ALLOCS = ("empty", "zeros", "ones", "full")
_R15_HOST_SOURCES = _R15_FRESH_ALLOCS + ("asarray", "array", "empty_like",
                                         "zeros_like", "ones_like",
                                         "full_like")
_R15_UPLOADS = ("asarray", "array", "device_put")
_R15_JNP_ALIASES = ("jnp", "jax")


def _r15_is_fresh_alloc(node: ast.AST) -> bool:
    """np.empty/zeros/ones/full — a fresh host buffer per call."""
    return isinstance(node, ast.Call) and _is_np_attr(node.func,
                                                      _R15_FRESH_ALLOCS)


def _r15_is_upload_of_fresh_host(node: ast.AST) -> bool:
    """jnp.asarray / jnp.array / jax.device_put whose operand is itself a
    fresh host-array construction (np.zeros(...)/np.asarray(...)/...): a
    per-call allocate-then-upload.  Uploads of a NAMED buffer are clean —
    reusing a pinned buffer is exactly the sanctioned pattern."""
    if not (isinstance(node, ast.Call) and node.args
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _R15_UPLOADS
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id in _R15_JNP_ALIASES):
        return False
    arg = node.args[0]
    return (isinstance(arg, ast.Call)
            and _is_np_attr(arg.func, _R15_HOST_SOURCES))


def _r15_is_predict_entry(node: ast.AST) -> bool:
    """An accounted serving dispatch: a call whose final name is a
    predict entry (predict / predict_raw / predict_coalesced / the
    predict_ops kernels) or the accounted ``sync_pull`` itself."""
    if not isinstance(node, ast.Call):
        return False
    fn = dotted_name(node.func) or ""
    last = fn.split(".")[-1]
    return last.startswith("predict") or last == "sync_pull"


@register_rule("R15", "staging-alloc-in-serve-loop")
def r15_staging_alloc_in_serve_loop(pkg: PackageIndex) -> Iterator[Finding]:
    """A fresh host staging allocation INSIDE a loop that also drives an
    accounted predict entry: per-iteration ``np.empty``/``np.zeros`` (a
    new batch buffer every request) or ``jnp.asarray``/``jax.device_put``
    of a freshly constructed host array (allocate-then-upload per call).
    A serving loop runs forever at request cadence, so a per-iteration
    staging buffer is allocator pressure + a page-faulting copy on every
    batch — the exact cost the pinned double-buffered staging in
    lightgbm_tpu/serve/runtime.py exists to remove (one buffer pair per
    bucket rung, one ``readinto``-style copy per request, reused across
    batches; the round-12 out-of-core reused-buffer discipline applied to
    serving).  Uploading a NAMED (hoisted, reused) buffer inside the loop
    is clean — that upload is the design.  Loops with no predict entry
    (setup, training drivers) are out of scope: R1/R14 own those."""
    hint = ("hoist the staging buffer out of the loop and reuse it "
            "(lightgbm_tpu/serve/runtime.py::_next_staging is the "
            "pattern: one pinned pair per bucket rung, filled per "
            "request, uploaded by name); see docs/ANALYSIS.md R15")
    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            if pkg.is_hot(fi):
                continue  # traced bodies: allocation is R1/R11's domain
            for loop in _own_body(fi):
                if not isinstance(loop, (ast.For, ast.While)):
                    continue
                nodes = list(ast.walk(loop))
                if not any(_r15_is_predict_entry(n) for n in nodes):
                    continue
                # an alloc wrapped directly in a flagged upload reports
                # ONCE (as the allocate-then-upload form), not twice
                wrapped = {id(n.args[0]) for n in nodes
                           if _r15_is_upload_of_fresh_host(n)}
                for n in nodes:
                    if _r15_is_fresh_alloc(n) and id(n) not in wrapped:
                        yield _finding(
                            fi, n, "R15",
                            f"per-iteration host staging allocation "
                            f"np.{n.func.attr}(...) in {fi.qualname}'s "
                            "serving loop — a fresh batch buffer every "
                            "request instead of a pinned reused one",
                            hint)
                    elif _r15_is_upload_of_fresh_host(n):
                        yield _finding(
                            fi, n, "R15",
                            f"{dotted_name(n.func)}(np.{n.args[0].func.attr}"
                            f"(...)) in {fi.qualname}'s serving loop — "
                            "allocate-then-upload of a fresh host array "
                            "per iteration", hint)


# ---------------------------------------------------------------------------
# R16 — mutation-outside-version-bump
# ---------------------------------------------------------------------------

# the ensemble state whose mutation MUST route through the versioned
# pack invalidation: the tree list and the per-tree leaf tables
_R16_ENSEMBLE_ATTRS = ("models", "_models", "leaf_value")
_R16_LIST_MUTATORS = ("append", "extend", "insert", "pop", "remove",
                      "clear", "sort", "reverse")
_R16_BUMP = "_invalidate_pred_cache"
# only serve/continual code paths are in scope: they run BESIDE live
# serving readers, where an unbumped mutation hands an in-flight predict
# a pack that no longer matches the trees (docs/ANALYSIS.md static-limits
# note covers the rest of the tree)
_R16_SCOPED_DIRS = ("serve", "continual")


def _r16_ensemble_attr(node: ast.AST) -> Optional[str]:
    """The ensemble attribute an expression touches: ``x.models`` /
    ``x._models`` / ``tree.leaf_value`` (as an Attribute), or a Subscript
    over one (``x.models[i]``, ``tree.leaf_value[k]``)."""
    if isinstance(node, ast.Subscript):
        node = node.value
    if isinstance(node, ast.Attribute) and node.attr in _R16_ENSEMBLE_ATTRS:
        return node.attr
    return None


def _r16_has_bump(fi: FuncInfo) -> bool:
    for node in _own_body(fi):
        if isinstance(node, ast.Call):
            fn = dotted_name(node.func) or ""
            if fn.split(".")[-1] == _R16_BUMP:
                return True
    return False


@register_rule("R16", "mutation-outside-version-bump")
def r16_mutation_outside_version_bump(pkg: PackageIndex) -> Iterator[Finding]:
    """An ensemble-mutating write in serve/continual code that does not
    route through ``_invalidate_pred_cache``: assigning to ``.models`` /
    ``._models`` / ``.leaf_value`` (whole, element, or slice) or calling
    a list mutator on them, in a function whose own body never bumps the
    pack version.  The round-18 ``_packed`` cache is keyed on
    ``_pack_version``; a mutation that skips the bump leaves the CURRENT
    version's device pack describing trees that no longer exist — a
    live serving reader then returns predictions from the pre-mutation
    ensemble indefinitely (stale, not just racy), and the round-19 lock
    making bump+lookup atomic cannot help a bump that never happens.
    Scoped to modules under ``serve/`` and ``continual/`` directories —
    the code that runs beside live serving readers; trainer-side
    mutations elsewhere are covered by the versioned key's belt-and-
    braces components and the runtime budget pins (static-limits note in
    docs/ANALYSIS.md)."""
    hint = ("mutate, then call gbdt._invalidate_pred_cache('<reason>') in "
            "the SAME function (continual/refit.py::refit_leaves is the "
            "pattern) — or mutate a private clone and publish it through "
            "ServingRuntime.swap_model")
    for mod in pkg.modules.values():
        parts = getattr(mod.path, "parts", ())
        if not any(d in parts for d in _R16_SCOPED_DIRS):
            continue
        for fi in mod.functions.values():
            if _r16_has_bump(fi):
                continue
            for node in _own_body(fi):
                if isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = (node.targets
                               if isinstance(node, ast.Assign)
                               else [node.target])
                    for t in targets:
                        attr = _r16_ensemble_attr(t)
                        if attr is not None:
                            yield _finding(
                                fi, node, "R16",
                                f"write to .{attr} in {fi.qualname} "
                                "without a _pack_version bump — the "
                                "serving pack cache now describes trees "
                                "that no longer exist", hint)
                elif (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in _R16_LIST_MUTATORS):
                    attr = _r16_ensemble_attr(node.func.value)
                    if attr is not None:
                        yield _finding(
                            fi, node, "R16",
                            f".{attr}.{node.func.attr}(...) in "
                            f"{fi.qualname} without a _pack_version bump "
                            "— an in-place ensemble edit invisible to "
                            "the versioned pack cache", hint)


# ---------------------------------------------------------------------------
# R17 — full-histogram-over-dcn
# ---------------------------------------------------------------------------

_R17_COLLECTIVES = ("psum", "psum_scatter", "all_gather", "pmean",
                    "all_to_all", "ppermute", "pmax", "pmin")
# gather-style calls whose result is top-k-shaped by construction: an
# operand assigned from one of these is an elected subset, not the
# full-F plane
_R17_TOPK_GATHERS = ("take_along_axis", "top_k", "dynamic_slice",
                     "dynamic_slice_in_dim")


def _r17_axis_mentions_dcn(axis_arg: ast.AST) -> bool:
    """The collective's axis expression references the DCN axis: the
    'dcn' string literal, the DCN_AXIS constant, or any dcn-named
    variable — including tuple axes like (ICI_AXIS, DCN_AXIS)."""
    for sub in ast.walk(axis_arg):
        if (isinstance(sub, ast.Constant) and isinstance(sub.value, str)
                and "dcn" in sub.value.lower()):
            return True
        if isinstance(sub, ast.Name) and "dcn" in sub.id.lower():
            return True
        if isinstance(sub, ast.Attribute) and "dcn" in sub.attr.lower():
            return True
    return False


def _r17_hist_name(expr: ast.AST) -> Optional[str]:
    """The operand's name when it reads as a histogram buffer."""
    if isinstance(expr, ast.Subscript):
        return _r17_hist_name(expr.value)
    if isinstance(expr, ast.Name):
        nm = expr.id
    elif isinstance(expr, ast.Attribute):
        nm = expr.attr
    else:
        return None
    return nm if "hist" in nm.lower() else None


def _r17_topk_shaped(fi: FuncInfo, name: str) -> bool:
    """True when ``name`` is assigned (anywhere in the function) from a
    top-k gather — take_along_axis / top_k / dynamic_slice family — so a
    hist-named operand is actually an elected feature subset."""
    for node in _own_body(fi, include_nested=True):
        if not isinstance(node, ast.Assign):
            continue
        targets = {t.id for t in node.targets if isinstance(t, ast.Name)}
        if name not in targets:
            continue
        for sub in ast.walk(node.value):
            if isinstance(sub, ast.Call):
                fn = dotted_name(sub.func) or ""
                if fn.split(".")[-1] in _R17_TOPK_GATHERS:
                    return True
    return False


@register_rule("R17", "full-histogram-over-dcn")
def r17_full_histogram_over_dcn(pkg: PackageIndex) -> Iterator[Finding]:
    """A collective whose axis set includes the DCN axis moving a FULL
    histogram operand.  The hierarchical two-level merge's contract
    (docs/DISTRIBUTED.md "Hierarchical merge") is that full (…, F, B)
    histogram planes merge only INSIDE a slice's ICI axis — crossing
    DCN is reserved for top-k-shaped payloads (elected feature columns,
    gathered by the vote's indices) and scalars, because DCN bandwidth
    is an order of magnitude below ICI and a full-F merge there erases
    the multi-slice speedup at exactly the scale it was bought for.
    Statically: any ``jax.lax`` collective whose axis expression
    references the dcn axis and whose operand NAMES a histogram
    (``*hist*``) is flagged, unless that operand is assigned from a
    top-k gather (``take_along_axis``/``top_k``/``dynamic_slice``) in
    the same function — the elected-subset shape
    ``parallel/hierarchy.py::dcn_topk_best`` ships.  Name-heuristic by
    necessity (the AST has no avals); the jaxpr-audit ``dcn_max_bytes``
    contract pin is the sound byte-level half (docs/ANALYSIS.md)."""
    hint = ("merge full histograms over the ici axis only; cross dcn "
            "with the elected top-k feature columns "
            "(parallel/hierarchy.py::dcn_topk_best) or scalars — see "
            "docs/DISTRIBUTED.md 'Hierarchical merge' and the "
            "jaxpr-audit dcn_max_bytes pin")
    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            if fi.parent is not None:
                # nested defs are walked through their ENCLOSING function
                # (include_nested below) — visiting them again would both
                # duplicate findings and lose sight of a top-k gather
                # assigned in the enclosing scope (the R3 discipline)
                continue
            for node in _own_body(fi, include_nested=True):
                if not isinstance(node, ast.Call):
                    continue
                fn = dotted_name(node.func)
                if fn is None or fn.split(".")[-1] not in _R17_COLLECTIVES:
                    continue
                if not node.args:
                    continue
                axis_arg = None
                for kw in node.keywords:
                    if kw.arg in ("axis_name", "axis"):
                        axis_arg = kw.value
                if axis_arg is None and len(node.args) > 1:
                    axis_arg = node.args[1]
                if axis_arg is None or not _r17_axis_mentions_dcn(axis_arg):
                    continue
                hist_nm = _r17_hist_name(node.args[0])
                if hist_nm is None:
                    continue
                if _r17_topk_shaped(fi, hist_nm):
                    continue
                yield _finding(
                    fi, node, "R17",
                    f"{fn}({hist_nm}, …) in {fi.qualname} moves a full "
                    "histogram operand across the dcn axis — the "
                    "cross-slice merge must be top-k-shaped or scalar",
                    hint)


def _walk_no_defs(node: ast.AST) -> Iterator[ast.AST]:
    """ast.walk minus nested function defs — their bodies are their own
    FuncInfo's territory (the _own_body discipline)."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield child
        yield from _walk_no_defs(child)


# ---------------------------------------------------------------------------
# R19 — unbounded-retry
# ---------------------------------------------------------------------------

# IO/dispatch-ish call spellings worth retry discipline: a failure here is
# transient-by-nature (network, device runtime, filesystem), which is what
# tempts the swallow-and-spin loop this rule exists to catch
_R19_IO_RE = re.compile(
    r"(request|urlopen|fetch|download|upload|connect|send|recv|rpc|query"
    r"|dispatch|predict|submit|read|write|open|post|push|pull)",
    re.IGNORECASE)
# loop identifiers that evidence a retry BUDGET or DEADLINE — any of these
# appearing anywhere in the loop (test or body) means the author bounded it
_R19_BUDGET_RE = re.compile(
    r"(attempt|retr(y|ies)|budget|deadline|tries|remaining|give_up|giveup)",
    re.IGNORECASE)
# pacing call spellings: a loop that sleeps, backs off, or waits between
# attempts cannot hot-spin
_R19_PACING = ("sleep", "wait")
_R19_PACING_RE = re.compile(r"(backoff|jitter)", re.IGNORECASE)
# exception spellings broad enough to swallow EVERY transient failure —
# catching these without re-raising, bounding or pacing is the hallmark
_R19_BROAD = ("Exception", "BaseException", "OSError", "IOError",
              "EnvironmentError", "TimeoutError", "ConnectionError")


def _r19_is_broad_handler(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True  # bare except
    types = t.elts if isinstance(t, ast.Tuple) else [t]
    for e in types:
        nm = dotted_name(e)
        if nm is not None and nm.split(".")[-1] in _R19_BROAD:
            return True
    return False


def _r19_handler_escapes(handler: ast.ExceptHandler) -> bool:
    """True when the handler leaves the loop or re-raises — the failure
    is surfaced, not swallowed back into another attempt."""
    for node in _walk_no_defs(handler):
        if isinstance(node, (ast.Raise, ast.Break, ast.Return)):
            return True
    return False


def _r19_is_pacing_call(node: ast.Call) -> bool:
    fn = dotted_name(node.func)
    last = (fn.split(".")[-1] if fn is not None
            else getattr(node.func, "attr", ""))
    if last in _R19_PACING or _R19_PACING_RE.search(last or ""):
        return True
    # a bare `.get()` / `.get(timeout=...)` on some receiver is a BLOCKING
    # queue handoff — the worker-loop shape (the serve dispatcher): the
    # loop stalls for fresh WORK between iterations, so it cannot spin.
    # `dict.get(key)` passes positional args and does not count.
    return (isinstance(node.func, ast.Attribute)
            and node.func.attr == "get" and not node.args)


def _r19_loop_is_paced_or_bounded(loop: ast.While) -> bool:
    for node in _walk_no_defs(loop):
        if isinstance(node, ast.Call) and _r19_is_pacing_call(node):
            return True
        if isinstance(node, ast.Name) and _R19_BUDGET_RE.search(node.id):
            return True
        if (isinstance(node, ast.Attribute)
                and _R19_BUDGET_RE.search(node.attr)):
            return True
    # the loop TEST is not inside walk(loop)'s body-only iteration? it is —
    # ast.iter_child_nodes(While) yields test first; kept explicit anyway
    for node in ast.walk(loop.test):
        if isinstance(node, ast.Name) and _R19_BUDGET_RE.search(node.id):
            return True
    return False


@register_rule("R19", "unbounded-retry")
def r19_unbounded_retry(pkg: PackageIndex) -> Iterator[Finding]:
    """A ``while`` loop that swallows broad exceptions around an
    IO/dispatch-ish call and loops straight back into the next attempt —
    no sleep/backoff/jitter between tries, no attempt budget, no
    deadline.  Under a persistent failure (a device runtime wedged, an
    endpoint down, a full disk) the loop hot-spins: 100% host CPU,
    a log volcano, and — when the callee holds locks or device queues —
    a livelock that looks exactly like the hang it was written to
    survive.  The serve fleet's discipline is the counter-example
    (serve/fleet.py): every redispatch pays a retry-budget token, every
    restart backs off exponentially with jitter, and deadlines turn a
    sick fleet into typed shedding.  Statically: a ``while`` containing
    a ``try`` whose handler catches ``Exception``/``BaseException``/
    ``OSError``/``TimeoutError``/bare without raising or leaving the
    loop, whose try body makes an IO-ish call, in a loop with no pacing
    call (``sleep``/``wait``/``backoff``/``jitter``/blocking queue
    ``.get()``) and no budget/deadline identifier
    (``attempt``/``retry``/``budget``/``deadline``/``tries``/
    ``remaining``).  Narrow catches (``except Empty``) pass clean —
    they name the one expected failure instead of swallowing all of
    them."""
    hint = ("bound the loop: pace attempts (time.sleep with exponential "
            "backoff + jitter), spend a retry budget, or check a "
            "deadline — and re-raise or surface the error once the "
            "budget is gone (serve/fleet.py::_retry_or_fail_locked is "
            "the in-tree shape); narrow the except to the one expected "
            "failure where possible")
    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            for node in _own_body(fi):
                if not isinstance(node, ast.While):
                    continue
                if _r19_loop_is_paced_or_bounded(node):
                    continue
                for sub in _walk_no_defs(node):
                    if not isinstance(sub, ast.Try):
                        continue
                    broad = [h for h in sub.handlers
                             if _r19_is_broad_handler(h)
                             and not _r19_handler_escapes(h)]
                    if not broad:
                        continue
                    io_call = None
                    for b in sub.body:
                        for c in _walk_no_defs(b):
                            if isinstance(c, ast.Call):
                                fn = (dotted_name(c.func)
                                      or getattr(c.func, "attr", ""))
                                if fn and _R19_IO_RE.search(fn):
                                    io_call = fn.split(".")[-1]
                                    break
                        if io_call:
                            break
                    if io_call is None:
                        continue
                    yield _finding(
                        fi, sub, "R19",
                        f"retry loop in {fi.qualname} swallows broad "
                        f"exceptions around {io_call}(...) with no "
                        "backoff, budget or deadline — a persistent "
                        "failure hot-spins forever", hint)
                    break  # one finding per loop is enough


# ---------------------------------------------------------------------------
# R20 — feature-axis-hist-collective
# ---------------------------------------------------------------------------


def _r20_axis_mentions_feature(axis_arg: ast.AST) -> bool:
    """The axis expression references the feature mesh axis: the string
    literal, the FEATURE_AXIS mesh constant, or a *feature*-named
    variable/attribute (feature_axis_name) — including inside a tuple."""
    for sub in ast.walk(axis_arg):
        if (isinstance(sub, ast.Constant) and isinstance(sub.value, str)
                and "feature" in sub.value.lower()):
            return True
        if isinstance(sub, ast.Name) and "feature" in sub.id.lower():
            return True
        if isinstance(sub, ast.Attribute) and "feature" in sub.attr.lower():
            return True
    return False


@register_rule("R20", "feature-axis-hist-collective")
def r20_feature_axis_hist_collective(pkg: PackageIndex) -> Iterator[Finding]:
    """A collective whose axis set includes the FEATURE mesh axis moving a
    histogram operand.  The 2-D (feature x row) layout's entire point
    (docs/DISTRIBUTED.md "2-D sharding", parallel/feature2d.py) is that
    each device's ``(F/d_f, N/d_r)`` bin tile builds histograms that are
    already COMPLETE for the owned feature block — the merge is the row
    psum alone, and the feature axis carries only the winner's go/no-go
    row broadcast and election scalars.  A histogram collective over the
    feature axis re-replicates what the layout made local, paying d_f
    times the merge bytes to erase the axis the mesh was widened for.
    Statically: any ``jax.lax`` collective whose axis expression
    references the feature axis and whose first operand NAMES a
    histogram (``*hist*``) is flagged, unless that operand is assigned
    from a top-k gather in the same function (an elected subset, the R17
    escape).  Name-heuristic by necessity; the ``windowed_round_2d_*``
    jaxpr-audit contracts are the sound IR-level half — they pin ZERO
    feature-axis collectives in the histogram phase and bill every axis's
    bytes (docs/ANALYSIS.md)."""
    hint = ("histograms over the feature-sharded bin tile are complete "
            "for the owned block by layout — merge over the row axis "
            "only, and cross the feature axis with the winner's row "
            "decisions or election scalars "
            "(parallel/feature2d.py, docs/DISTRIBUTED.md '2-D sharding')")
    for mod in pkg.modules.values():
        for fi in mod.functions.values():
            if fi.parent is not None:
                # nested defs walk through their enclosing function (the
                # R17 discipline): one visit, enclosing-scope gathers seen
                continue
            for node in _own_body(fi, include_nested=True):
                if not isinstance(node, ast.Call):
                    continue
                fn = dotted_name(node.func)
                if fn is None or fn.split(".")[-1] not in _R17_COLLECTIVES:
                    continue
                if not node.args:
                    continue
                axis_arg = None
                for kw in node.keywords:
                    if kw.arg in ("axis_name", "axis"):
                        axis_arg = kw.value
                if axis_arg is None and len(node.args) > 1:
                    axis_arg = node.args[1]
                if axis_arg is None or not _r20_axis_mentions_feature(
                        axis_arg):
                    continue
                hist_nm = _r17_hist_name(node.args[0])
                if hist_nm is None:
                    continue
                if _r17_topk_shaped(fi, hist_nm):
                    continue
                yield _finding(
                    fi, node, "R20",
                    f"{fn}({hist_nm}, …) in {fi.qualname} moves a "
                    "histogram operand across the feature axis — the "
                    "feature-sharded tile's histograms are complete for "
                    "the owned block; merge over the row axis only",
                    hint)


# ---------------------------------------------------------------------------
# R21 — unlinked-cross-thread-span
# ---------------------------------------------------------------------------

# span-creation call names (last dotted component): the obs/trace.py API
# surface that records into the span ring
_R21_SPAN_CALLS = ("span", "record_span", "Span")
# a span call carrying any of these keywords names its causal identity
# explicitly and is immune to the thread-local-stack trap
_R21_LINK_KWARGS = ("ctx", "parent", "links")


def _r21_thread_entry_names(mod) -> set:
    """Names of functions this module hands to a worker thread: the
    ``target=`` of any ``*.Thread(...)`` ctor, or the first argument of
    any ``*.submit(...)`` call (executor dispatch).  Both ``self._fn``
    and bare ``fn`` references resolve to their last component — entry
    functions are matched per-module by unqualified name."""
    names: set = set()

    def ref_name(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Attribute):
            return node.attr
        if isinstance(node, ast.Name):
            return node.id
        return None

    for fi in mod.functions.values():
        for node in _own_body(fi):
            if not isinstance(node, ast.Call):
                continue
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "Thread"):
                for kw in node.keywords:
                    if kw.arg == "target":
                        nm = ref_name(kw.value)
                        if nm:
                            names.add(nm)
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "submit" and node.args):
                nm = ref_name(node.args[0])
                if nm:
                    names.add(nm)
    return names


@register_rule("R21", "unlinked-cross-thread-span")
def r21_unlinked_cross_thread_span(pkg: PackageIndex) -> Iterator[Finding]:
    """(round 24) a span created INSIDE a thread-entry function — one
    handed to ``threading.Thread(target=...)`` or ``executor.submit(...)``
    in the same module — without an explicit causal identity: no ``ctx=``,
    ``parent=`` or ``links=`` keyword on the ``span(``/``record_span(``/
    ``Span(`` call, and no ``.link(`` call in the function's own body.
    The span stack that supplies implicit parentage is THREAD-LOCAL
    (``obs/trace.py``): on a worker thread it is empty, so an implicit
    span silently roots a brand-new top-level trace instead of joining
    the request that crossed the thread boundary — the request's slice
    then reconstructs without its dispatch/leg spans and the flight
    recorder shows a broken story (the round-24 cross-thread bugfix).
    Scoped to ``serve/``/``continual/`` modules — where worker threads
    carry request/rollover contexts; own-body only (a helper the entry
    calls is that helper's finding when it, too, becomes an entry —
    static-limits note in docs/ANALYSIS.md)."""
    hint = ("carry the TraceContext across the boundary explicitly: mint "
            "or receive a ctx on the queued work item and pass ctx=/"
            "parent= to span()/record_span(), or adopt members via "
            "links=[...] (serve/runtime.py::_dispatch_loop is the "
            "pattern); an intentional rootless maintenance span takes a "
            "pragma with its reason")
    for mod in pkg.modules.values():
        parts = getattr(mod.path, "parts", ())
        if not any(d in parts for d in _R16_SCOPED_DIRS):
            continue
        entries = _r21_thread_entry_names(mod)
        if not entries:
            continue
        for fi in mod.functions.values():
            if fi.qualname.split(".")[-1] not in entries:
                continue
            linked_via_api = any(
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "link"
                for n in _own_body(fi))
            if linked_via_api:
                continue
            for node in _own_body(fi):
                if not isinstance(node, ast.Call):
                    continue
                fn = dotted_name(node.func)
                if fn is None or fn.split(".")[-1] not in _R21_SPAN_CALLS:
                    continue
                if any(kw.arg in _R21_LINK_KWARGS for kw in node.keywords):
                    continue
                yield _finding(
                    fi, node, "R21",
                    f"{fn}(...) in thread-entry {fi.qualname} without "
                    "ctx=/parent=/links= — the thread-local span stack is "
                    "empty on a worker thread, so this span roots a NEW "
                    "trace instead of joining the request that crossed "
                    "the boundary",
                    hint)
