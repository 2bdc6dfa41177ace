"""Jaxpr-level executable audit: verify the one-dispatch /
one-collective / all-donated contracts on the TRACED IR, not the Python
source (docs/ANALYSIS.md "Jaxpr audit layer").

The AST layer (rules.py R1-R14) reads source; this layer traces the
registered flagship executables (contracts.py) hermetically on the host
CPU and checks per-executable **J rules** on the jaxpr and the lowered
StableHLO:

====  ==========================  ========================================
J1    collective-count/axis-name  exactly the declared collectives, on
                                  declared mesh axes, in declared order;
                                  merge variants share the protocol spine
J2    donation-consumed           every live donated invar structurally
                                  matches an output buffer, and — where
                                  the platform lowers aliasing — is
                                  actually aliased (``tf.aliasing_output``)
J3    no-f64-promotion            no convert_element_type to f64, no f64
                                  aval anywhere in the body
J4    no-host-callback            no pure_callback / io_callback /
                                  debug_callback inside a budget-pinned
                                  executable
J5    transfer-free-body          no device_put inside the trace; no baked
                                  constant above the contract's byte
                                  threshold
J6    live-set bound              a conservative peak-live-bytes estimate
                                  over the jaxpr stays under the
                                  contract's HBM budget
====  ==========================  ========================================

This closes the closure-dispatch blind spot the AST rules document: the
shared ``_run_fused_rounds`` driver dispatches its round through a
closure parameter, so R1/R6/R13 cannot see INSIDE the round — but the
round's jaxpr can be audited directly, and the runtime DispatchCounter
budget is cross-checked against the auditor's collective count
(:func:`ledger_crosscheck`): one dispatch per round on the ledger means
every audited collective rode that single dispatch.

Findings render through the same :class:`~.core.Finding` reporter as the
lint layer; suppression is by **contract-level waiver** (contracts.py
``waivers={"J6": "reason"}``) with the same mandatory-reason hygiene
(P0 on a reasonless or unknown-rule waiver).

JAX is imported lazily — importing this module costs nothing; the CLI
(`python -m lightgbm_tpu.analysis --jaxpr`) arms the loopback-device env
before the first builder runs.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from .contracts import CONTRACTS, Contract, Target
from .core import Finding

# J-rule catalogue for --list-rules-style output
JAXPR_RULES: Dict[str, str] = {
    "J1": "collective-count/axis-name — exact declared sequence, declared "
          "mesh axes, family-consistent protocol spine, per-axis byte "
          "accounting (dcn_max_bytes pins the cross-slice bill)",
    "J2": "donation-consumed — every live donated invar aliasable (and "
          "aliased where the platform lowers aliasing)",
    "J3": "no-f64-promotion — no f64 cast or aval in the body",
    "J4": "no-host-callback — no pure/io/debug callback under the budget "
          "pin",
    "J5": "transfer-free-body — no in-trace device_put, no oversized "
          "baked constant",
    "J6": "live-set bound — conservative peak live bytes within the "
          "contract budget",
    "J7": "hbm-sweep-bound — statically estimated bin-matrix bytes read "
          "per round body within the contract's sweep budget",
}

# jax collective primitives -> the spelling contracts declare
_COLLECTIVE_PRIMS = {
    "psum": "psum", "psum2": "psum", "pmax": "pmax", "pmin": "pmin",
    "pmean": "pmean", "reduce_scatter": "psum_scatter",
    "all_gather": "all_gather", "all_to_all": "all_to_all",
    "ppermute": "ppermute", "axis_index": "axis_index",
}
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback")

# a collective moving at least this many operand bytes is a "large" merge
# (the histogram-class collective); everything below is scalar protocol
# traffic (info-vector merges, winner election).  The headline invariant
# — ONE large in-dispatch collective per merge strategy — is asserted on
# this split by tests/test_jaxpr_audit.py.
_LARGE_COLLECTIVE_BYTES = 4096


@dataclasses.dataclass
class ContractResult:
    name: str
    findings: List[Finding]
    waived: List[Tuple[Finding, str]]  # (finding, waiver reason)
    detail: Dict[str, object]

    @property
    def ok(self) -> bool:
        return not self.findings


@dataclasses.dataclass
class JaxprReport:
    results: List[ContractResult]
    ledger: Dict[str, dict]

    @property
    def findings(self) -> List[Finding]:
        return [f for r in self.results for f in r.findings]

    @property
    def waived(self) -> List[Tuple[Finding, str]]:
        return [w for r in self.results for w in r.waived]

    @property
    def ok(self) -> bool:
        return not self.findings


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _is_var(v) -> bool:
    """True for real jaxpr Vars (Literals are unhashable constants)."""
    import jax.extend.core as jc
    return isinstance(v, jc.Var)


def _sub_jaxprs(eqn):
    import jax.extend.core as jc
    for v in eqn.params.values():
        if isinstance(v, jc.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jc.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for vv in v:
                if isinstance(vv, jc.ClosedJaxpr):
                    yield vv.jaxpr
                elif isinstance(vv, jc.Jaxpr):
                    yield vv


def iter_eqns(jaxpr):
    """Every equation in the (open) jaxpr, recursing through call/pjit/
    shard_map/scan/cond sub-jaxprs, in trace order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    # a Pallas semaphore ref has a shape and a dtype that is no array
    # dtype (no itemsize): it occupies no bytes of the budget
    itemsize = getattr(getattr(aval, "dtype", None), "itemsize", None)
    if shape is None or itemsize is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * itemsize


def _eqn_axes(eqn) -> Tuple[str, ...]:
    ax = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(ax, (tuple, list)):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


def collect_collectives(jaxpr) -> List[Tuple[str, Tuple[str, ...], int]]:
    """Ordered (normalized-name, axis-names, max-operand-bytes) for every
    collective in the traced program."""
    out = []
    for eqn in iter_eqns(jaxpr):
        name = _COLLECTIVE_PRIMS.get(eqn.primitive.name)
        if name is None:
            continue
        nbytes = max((_aval_bytes(v.aval) for v in eqn.invars
                      if hasattr(v, "aval")), default=0)
        out.append((name, _eqn_axes(eqn), nbytes))
    return out


# ---------------------------------------------------------------------------
# J checks
# ---------------------------------------------------------------------------

def _finding(c: Contract, rule: str, msg: str, hint: str) -> Finding:
    return Finding(c.file, c.line, rule, f"[{c.name}] {msg}", hint)


def _declared_axes() -> set:
    from ..parallel.mesh import DATA_AXIS, DCN_AXIS, FEATURE_AXIS, ICI_AXIS
    return {DATA_AXIS, FEATURE_AXIS, ICI_AXIS, DCN_AXIS}


def dcn_axis_bytes(found) -> int:
    """Total operand bytes of every collective whose axes include the
    DCN axis — the per-round cross-slice byte bill the hierarchical
    contracts pin statically (``dcn_max_bytes``).  Scalar protocol
    merges that span both axes count too (they cross DCN); intra-slice
    merges on the ici axis alone do not."""
    from ..parallel.mesh import DCN_AXIS
    return sum(nb for _name, axes, nb in found if DCN_AXIS in axes)


def axis_bytes(found) -> Dict[str, int]:
    """Per-axis collective byte bill: total operand bytes of every
    collective whose axes include each mesh axis.  A both-axes scalar
    merge bills BOTH axes (it crosses both).  This is the generic form
    of ``dcn_axis_bytes`` — every contract's bill rides ``verdict()``
    into bench artifacts, so a chip row shows at a glance where a
    round's collective traffic lands on the (dcn, feature, row) grid."""
    out: Dict[str, int] = {}
    for _name, axes, nb in found:
        for ax in axes:
            out[ax] = out.get(ax, 0) + nb
    return out


def _check_dcn_bytes(c: Contract, found
                     ) -> Tuple[List[Finding], Dict[str, object]]:
    """The per-axis half of J1 (analogous to J7's sweep bound): the
    statically summed DCN-axis operand bytes per round body must stay
    under the contract's ``dcn_max_bytes`` — ≤ top-k histograms' worth.
    A full-F histogram merge smuggled onto the dcn axis fails here (and
    jaxlint R17 flags the source form)."""
    if c.dcn_max_bytes is None:
        return [], {}
    got = dcn_axis_bytes(found)
    findings = []
    if got > c.dcn_max_bytes:
        findings.append(_finding(
            c, "J1",
            f"{got} bytes of collective operands cross the dcn axis per "
            f"round, exceeding the {c.dcn_max_bytes}-byte contract pin",
            "the hierarchical merge's whole point is that only "
            "top-k-shaped or scalar operands cross DCN — route new "
            "cross-slice traffic through the top-k election "
            "(parallel/hierarchy.py::dcn_topk_best) or raise the budget "
            "consciously (docs/ANALYSIS.md, jaxlint R17)"))
    return findings, {"dcn_bytes": got}


def _check_feature_bytes(c: Contract, found
                         ) -> Tuple[List[Finding], Dict[str, object]]:
    """The 2-D layout's axis-bill pin (the feature-axis twin of
    ``_check_dcn_bytes``): collective operand bytes crossing the feature
    axis per round must stay under ``feature_max_bytes`` — the winner's
    go/no-go row broadcast plus election scalars.  A histogram merge
    smuggled onto the feature axis fails here (jaxlint R20 flags the
    source form; the exact J1 sequence pin is the ordering half)."""
    if c.feature_max_bytes is None:
        return [], {}
    from ..parallel.mesh import FEATURE_AXIS
    got = sum(nb for _name, axes, nb in found if FEATURE_AXIS in axes)
    findings = []
    if got > c.feature_max_bytes:
        findings.append(_finding(
            c, "J1",
            f"{got} bytes of collective operands cross the feature axis "
            f"per round, exceeding the {c.feature_max_bytes}-byte "
            "contract pin",
            "the 2-D layout makes the owned feature block's histograms "
            "complete locally — only the winner's row decisions and "
            "election scalars may cross the feature axis "
            "(parallel/feature2d.py, jaxlint R20); route new traffic "
            "through the election or raise the budget consciously"))
    return findings, {"feature_bytes": got}


def _check_j1(c: Contract, found) -> Tuple[List[Finding], List[str]]:
    """``found`` is the ``collect_collectives`` result — walked once by
    the caller and shared with the large-collective detail."""
    tokens = []
    findings = []
    declared_axes = _declared_axes()
    for name, axes, _nb in found:
        for ax in axes:
            if ax not in declared_axes:
                findings.append(_finding(
                    c, "J1",
                    f"collective {name} uses undeclared axis {ax!r}",
                    "collectives must ride the mesh axes parallel/mesh.py "
                    "declares (DATA_AXIS / FEATURE_AXIS)"))
        tokens.append(f"{name}@{','.join(axes) if axes else '?'}")
    if tuple(tokens) != c.collectives:
        findings.append(_finding(
            c, "J1",
            f"collective sequence mismatch: traced {len(tokens)} "
            f"({' '.join(tokens) or 'none'}), declared "
            f"{len(c.collectives)} ({' '.join(c.collectives) or 'none'})",
            "a collective entered or left the traced round body — if "
            "intentional, update the contract declaration next to the "
            "code (analysis/contracts.py); a SECOND large merge or a "
            "host-loop collective is the regression class R13 cannot see "
            "through the closure dispatch"))
    return findings, tokens


def _check_family_spine(results: Dict[str, "ContractResult"]) -> List[Finding]:
    """Merge variants of one family must share the declared protocol
    spine (prefix/suffix of the collective sequence) — the 'same order
    across merge variants' half of J1."""
    by_family: Dict[str, List[Contract]] = {}
    for name, c in CONTRACTS.items():
        if c.family and c.spine != (0, 0) and name in results:
            by_family.setdefault(c.family, []).append(c)
    findings = []
    for family, members in by_family.items():
        if len(members) < 2:
            continue
        pre = min(c.spine[0] for c in members)
        suf = min(c.spine[1] for c in members)
        ref = members[0]
        for c in members[1:]:
            if (c.collectives[:pre] != ref.collectives[:pre]
                    or (suf and c.collectives[-suf:]
                        != ref.collectives[-suf:])):
                findings.append(_finding(
                    c, "J1",
                    f"family {family!r}: protocol spine diverges from "
                    f"{ref.name} (shared prefix {pre} / suffix {suf})",
                    "merge variants must keep the round protocol's "
                    "collective order identical — only the declared "
                    "merge/election block may differ"))
    return findings


def _flat_arg_leaves(target: Target):
    """Flatten the positional args the way jax.jit does, returning
    (leaf avals, per-arg leaf index ranges)."""
    import jax.tree_util as jtu
    leaves = []
    ranges = []
    for a in target.args:
        ls = jtu.tree_leaves(a)
        ranges.append((len(leaves), len(leaves) + len(ls)))
        leaves.extend(ls)
    return leaves, ranges


def _check_j2(c: Contract, target: Target, jaxpr, lowered_text: str
              ) -> Tuple[List[Finding], Dict[str, object]]:
    import jax.tree_util as jtu
    findings: List[Finding] = []
    if not c.donated_args:
        return findings, {"donated_leaves": 0}
    _leaves, ranges = _flat_arg_leaves(target)
    donated_idx = set()
    for ai in c.donated_args:
        lo, hi = ranges[ai]
        donated_idx.update(range(lo, hi))
    jx = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    invars = jx.invars
    used = set()
    for eqn in jx.eqns:
        used.update(v for v in eqn.invars if _is_var(v))
    used.update(v for v in jx.outvars if _is_var(v))
    live_donated = [i for i in donated_idx
                    if i < len(invars) and invars[i] in used]

    # donated leaf -> (owning arg position, human path) for the message
    paths = []
    for ai, a in enumerate(target.args):
        paths.extend((ai, jtu.keystr(p)) for p, _ in
                     jtu.tree_flatten_with_path(a)[0])

    # structural consumability: every live donated invar must claim an
    # output buffer of identical aval.  Duplicate outvars count ONCE (a
    # dup output is forwarded, not a second buffer) — the class XLA
    # "drops with a warning" and the runtime CPU tier can never observe.
    avail: Dict[Tuple, int] = {}
    seen_out = set()
    for v in jx.outvars:
        if not _is_var(v) or id(v) in seen_out:
            continue
        seen_out.add(id(v))
        key = (getattr(v.aval, "shape", None),
               str(getattr(v.aval, "dtype", None)))
        avail[key] = avail.get(key, 0) + 1
    unmatched = []
    for i in live_donated:
        key = (getattr(invars[i].aval, "shape", None),
               str(getattr(invars[i].aval, "dtype", None)))
        if avail.get(key, 0) > 0:
            avail[key] -= 1
        else:
            unmatched.append(i)
    for i in unmatched:
        arg_pos, leaf_path = paths[i]
        findings.append(_finding(
            c, "J2",
            f"donated buffer arg{arg_pos}{leaf_path} "
            f"{invars[i].aval.str_short()} matches no free output buffer "
            "— XLA will warn once and silently copy every call",
            "thread the donated state linearly (same pytree structure/"
            "avals out as in) so every donated buffer can be reused in "
            "place; see docs/ANALYSIS.md J2"))

    # lowered-aliasing confirmation: where the platform lowering carries
    # tf.aliasing_output (single-device CPU/TPU), every live donated
    # buffer that SURVIVES lowering must carry the attr.  Two sanctioned
    # gaps, both measured on the flagship round: (a) the multi-device CPU
    # lowering drops aliasing wholesale (attrs == 0) — the structural
    # check above is the platform-independent half there; (b) lowering
    # DCE drops dead args entirely (keep_unused=False), and a donor the
    # executable never reads costs nothing — so the bound allows exactly
    # as much slack as the number of args lowering dropped.
    aliased = len(re.findall(r"tf\.aliasing_output", lowered_text))
    total_leaves = len(_leaves)
    m = re.search(r"func\.func public @main\((.*?)\)\s*->", lowered_text,
                  re.S)
    lowered_args = (len(re.findall(r"%arg\d+:", m.group(1)))
                    if m else total_leaves)
    dce_slack = max(total_leaves - lowered_args, 0)
    detail = {"donated_leaves": len(donated_idx),
              "live_donated_leaves": len(live_donated),
              "aliased_in_lowering": aliased,
              "lowering_dce_slack": dce_slack}
    if aliased and not unmatched and aliased < len(live_donated) - dce_slack:
        missing = len(live_donated) - dce_slack - aliased
        findings.append(_finding(
            c, "J2",
            f"{missing} live donated buffer(s) lost their aliasing in "
            f"lowering ({aliased}/{len(live_donated)} aliased, "
            f"{dce_slack} dropped by lowering DCE)",
            "a donation the jaxpr could consume was dropped at lowering "
            "— check for output forwarding or sharding mismatches"))
    return findings, detail


def _check_j3(c: Contract, jaxpr) -> List[Finding]:
    """Report f64 only where it ENTERS the trace (an f64 input, or an
    equation producing f64 from non-f64 operands — which includes every
    cast).  One leak flows through most of the downstream body, so
    flagging every f64-touching equation would flood the report and bury
    other findings; the entry points are also where the fix lives."""
    import numpy as np
    findings = []
    f64 = np.dtype("float64")

    def _is_f64(v) -> bool:
        return getattr(getattr(v, "aval", None), "dtype", None) == f64

    jx = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    for v in list(jx.constvars) + list(jx.invars):
        if _is_f64(v):
            findings.append(_finding(
                c, "J3",
                f"f64 input/const to the traced body ({v.aval.str_short()})",
                "cast at the host API boundary; the TPU round/predict "
                "bodies are f32/int programs"))
    for eqn in iter_eqns(jx):
        if any(_is_f64(v) for v in eqn.outvars) and not any(
                _is_f64(v) for v in eqn.invars):
            what = ("convert_element_type to float64"
                    if eqn.primitive.name == "convert_element_type"
                    else f"{eqn.primitive.name} producing f64 from "
                         "non-f64 operands")
            findings.append(_finding(
                c, "J3", f"{what} inside the traced body",
                "a f64 promotion entered the trace (x64 constant or "
                "cast) — keep f64 on the host API boundary; doubles "
                "bytes and falls off the MXU"))
    return findings


def _check_j4(c: Contract, jaxpr) -> List[Finding]:
    findings = []
    for eqn in iter_eqns(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr):
        if eqn.primitive.name in _CALLBACK_PRIMS:
            findings.append(_finding(
                c, "J4",
                f"{eqn.primitive.name} inside a budget-pinned executable",
                "host callbacks serialize the device queue at every call "
                "— the 1-dispatch/0-sync budget cannot hold; move the "
                "host work to the async info protocol"))
    return findings


def _check_j5(c: Contract, jaxpr) -> Tuple[List[Finding], Dict[str, object]]:
    findings = []
    for eqn in iter_eqns(jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr):
        if eqn.primitive.name == "device_put":
            findings.append(_finding(
                c, "J5",
                "device_put inside the traced body",
                "transfers belong outside the executable; pass the value "
                "as an argument"))
    const_bytes = 0
    biggest = 0
    for const in getattr(jaxpr, "consts", ()):
        nb = getattr(const, "nbytes", 0) or 0
        const_bytes += nb
        biggest = max(biggest, nb)
        if nb > c.max_const_bytes:
            shape = getattr(const, "shape", "?")
            findings.append(_finding(
                c, "J5",
                f"baked constant of {nb} bytes (shape {shape}) exceeds "
                f"the {c.max_const_bytes}-byte contract threshold",
                "a closure captured a concrete array into the trace — "
                "every dispatch re-uploads it; thread it as an argument"))
    return findings, {"const_bytes": const_bytes, "largest_const": biggest}


def peak_live_bytes(jaxpr) -> int:
    """Conservative peak-live-bytes estimate over the jaxpr: classic
    linear-scan liveness (a var is live from its defining equation to its
    last use; invars from entry; outvars to exit) plus, at each call-like
    equation, the recursive peak of its sub-jaxprs (an overestimate —
    outer operands are counted again inside — which is the safe
    direction for a budget gate)."""
    jx = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    n = len(jx.eqns)
    last_use: Dict[object, int] = {}
    def_idx: Dict[object, int] = {}
    for v in jx.invars:
        def_idx[v] = 0
    for i, eqn in enumerate(jx.eqns):
        for v in eqn.invars:
            if _is_var(v):
                last_use[v] = i
        for v in eqn.outvars:
            if _is_var(v):
                def_idx[v] = i
    for v in jx.outvars:
        if _is_var(v):
            last_use[v] = n
    base = sum(_aval_bytes(cv.aval) for cv in jx.constvars)
    # event sweep
    add_at: Dict[int, int] = {}
    del_after: Dict[int, int] = {}
    for v, d in def_idx.items():
        b = _aval_bytes(getattr(v, "aval", None))
        if not b or v not in last_use:
            continue
        add_at[d] = add_at.get(d, 0) + b
        del_after[last_use[v]] = del_after.get(last_use[v], 0) + b
    live = base + add_at.get(0, 0)
    # vars defined at 0 == invars; eqn 0's outvars also say def 0 — fold
    # them in before the sweep step for i=0 (conservative)
    peak = live
    for i, eqn in enumerate(jx.eqns):
        if i > 0:
            live += add_at.get(i, 0)
        inner = max((peak_live_bytes(s) for s in _sub_jaxprs(eqn)),
                    default=0)
        peak = max(peak, live + inner)
        live -= del_after.get(i, 0)
    return peak


# ---------------------------------------------------------------------------
# J7: bin-matrix sweep estimate
# ---------------------------------------------------------------------------

# layout-movement primitives: reading a tracked array through these is a
# bin-matrix read, and their matrix-scale outputs stay tracked (the
# materialized window copy the three-pass round re-reads).  Compute
# primitives (arithmetic, convert_element_type, the scatter itself) charge
# their tracked-operand read but do NOT propagate: the first compute
# consumer is the chain's final charged read — the rule that makes the
# estimate the ROADMAP's "three passes over the bins" (gather + transpose
# + the histogram's int cast), not a count of every downstream artifact.
_J7_GATHER_PRIMS = {"gather", "dynamic_slice", "slice"}
_J7_MOVE_PRIMS = {"transpose", "reshape", "copy", "squeeze", "rev",
                  "broadcast_in_dim"}
_J7_CALL_PRIMS = {"pjit", "closed_call", "core_call", "shard_map"}


def _j7_sub_jaxpr(eqn):
    import jax.extend.core as jc
    sub = eqn.params.get("jaxpr")
    if isinstance(sub, jc.ClosedJaxpr):
        return sub.jaxpr
    return sub


def bin_sweep_bytes(jaxpr, seed_vars, matrix_elems: int,
                    matrix_bytes: int) -> int:
    """Walk the jaxpr charging every read of the bin matrix or a
    matrix-scale array derived from it by pure layout movement.

    Charges: gather-family reads cost ``out_elems x src_itemsize`` (you
    read what you fetch — a W-column window gather reads W*F elements
    however large N is); movement/compute reads cost the tracked
    operand's bytes; a ``pallas_call`` consuming the matrix is charged
    exactly ONE sweep — the kernel contract (HBM-resident ``ANY`` refs,
    per-chunk DMA, every window column fetched once) is what jaxlint R11
    and the kernel's own parity tests verify, and the single charge is
    what makes the FUSION count visible next to the three separate
    charges the three-pass body accrues.  Control-flow bodies
    (scan/while/cond) are charged one conservative operand read without
    recursion — no audited round threads the matrix through them."""
    jx = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr

    def elems(v) -> int:
        n = 1
        for d in getattr(getattr(v, "aval", None), "shape", ()):
            n *= int(d)
        return n

    def walk(jxp, tracked) -> int:
        charged = 0
        for eqn in jxp.eqns:
            hit = [v for v in eqn.invars if _is_var(v) and v in tracked]
            if not hit:
                continue
            name = eqn.primitive.name
            if name in _J7_CALL_PRIMS:
                sub = _j7_sub_jaxpr(eqn)
                if sub is None:
                    charged += sum(_aval_bytes(v.aval) for v in hit)
                    continue
                inner = {iv for ov, iv in zip(eqn.invars, sub.invars)
                         if _is_var(ov) and ov in tracked}
                charged += walk(sub, inner)
                for sv, ov in zip(sub.outvars, eqn.outvars):
                    if _is_var(sv) and sv in inner:
                        tracked.add(ov)
                continue
            if name == "pallas_call":
                charged += matrix_bytes  # one sweep by kernel contract
                continue
            if name in _J7_GATHER_PRIMS:
                out_e = sum(elems(v) for v in eqn.outvars)
                charged += out_e * hit[0].aval.dtype.itemsize
            else:
                charged += sum(_aval_bytes(v.aval) for v in hit)
            if name in (_J7_GATHER_PRIMS | _J7_MOVE_PRIMS):
                for v in eqn.outvars:
                    if elems(v) >= matrix_elems:
                        tracked.add(v)
        return charged

    return walk(jx, set(seed_vars))


def _check_j7(c: Contract, target: Target, jaxpr
              ) -> Tuple[List[Finding], Dict[str, object]]:
    if c.bin_arg is None:
        return [], {}
    _leaves, ranges = _flat_arg_leaves(target)
    lo, hi = ranges[c.bin_arg]
    if hi - lo != 1:
        return [_finding(
            c, "J7", f"bin_arg={c.bin_arg} is not a single-leaf array arg",
            "declare the positional index of the bin matrix itself")], {}
    jx = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    seed = jx.invars[lo]
    m_elems = 1
    for d in seed.aval.shape:
        m_elems *= int(d)
    m_bytes = _aval_bytes(seed.aval)
    got = bin_sweep_bytes(jaxpr, [seed], m_elems, m_bytes)
    sweeps = got / max(m_bytes, 1)
    findings = []
    if c.max_bin_sweeps is not None and sweeps > c.max_bin_sweeps:
        findings.append(_finding(
            c, "J7",
            f"estimated {sweeps:.2f} bin-matrix sweeps per round exceeds "
            f"the {c.max_bin_sweeps}-sweep contract budget",
            "a new full read of the bin matrix (or a matrix-scale copy "
            "of it) entered the round body — the megakernel's whole "
            "point is ONE sweep; route new bin consumers through the "
            "kernel or raise the budget consciously (docs/ANALYSIS.md "
            "J7)"))
    return findings, {"bin_sweeps": round(sweeps, 3)}


def _check_j6(c: Contract, jaxpr) -> Tuple[List[Finding], Dict[str, object]]:
    peak = peak_live_bytes(jaxpr)
    findings = []
    if peak > c.max_live_bytes:
        findings.append(_finding(
            c, "J6",
            f"estimated peak live set {peak} bytes exceeds the "
            f"{c.max_live_bytes}-byte contract budget",
            "an O(L*F*B)-class buffer joined the round state — shrink it "
            "or raise the budget consciously (the budget is what keeps "
            "the blowup failing CI instead of a v5e)"))
    return findings, {"peak_live_bytes": peak}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def audit_contract(c: Contract) -> ContractResult:
    """Trace + lower one contract's executable and run J1-J6, applying
    the contract's waivers (mandatory reasons, like pragmas)."""
    target = c.build()
    traced = target.fn.trace(*target.args, **target.kwargs)
    jaxpr = traced.jaxpr
    # lower FROM the trace (AOT API) — fn.lower(...) would re-trace the
    # whole executable from scratch, doubling the audit's dominant cost
    lowered_text = traced.lower().as_text()

    raw: List[Finding] = []
    detail: Dict[str, object] = {"note": target.note}
    found = collect_collectives(jaxpr)
    j1, tokens = _check_j1(c, found)
    raw += j1
    detail["collectives"] = tokens
    detail["large_collectives"] = sum(
        1 for _n, _ax, nb in found if nb >= _LARGE_COLLECTIVE_BYTES)
    if found:
        detail["axis_bytes"] = axis_bytes(found)
    jdcn, ddcn = _check_dcn_bytes(c, found)
    raw += jdcn
    detail.update(ddcn)
    jfeat, dfeat = _check_feature_bytes(c, found)
    raw += jfeat
    detail.update(dfeat)
    j2, d2 = _check_j2(c, target, jaxpr, lowered_text)
    raw += j2
    detail.update(d2)
    raw += _check_j3(c, jaxpr)
    raw += _check_j4(c, jaxpr)
    j5, d5 = _check_j5(c, jaxpr)
    raw += j5
    detail.update(d5)
    j6, d6 = _check_j6(c, jaxpr)
    raw += j6
    detail.update(d6)
    j7, d7 = _check_j7(c, target, jaxpr)
    raw += j7
    detail.update(d7)

    # waiver hygiene first: unknown rules / missing reasons are P0 (never
    # waivable), mirroring the lint layer's pragma policy
    findings: List[Finding] = []
    waived: List[Tuple[Finding, str]] = []
    for rule, reason in c.waivers.items():
        if rule not in JAXPR_RULES:
            findings.append(_finding(
                c, "P0", f"waiver names unknown jaxpr rule {rule!r}",
                f"known rules: {', '.join(sorted(JAXPR_RULES))}"))
        elif not str(reason).strip():
            findings.append(_finding(
                c, "P0", f"waiver for {rule} has no reason",
                "every contract-level waiver must document why"))
    for f in raw:
        reason = c.waivers.get(f.rule, "")
        if f.rule in c.waivers and str(reason).strip():
            waived.append((f, str(reason)))
        else:
            findings.append(f)
    return ContractResult(c.name, findings, waived, detail)


def ledger_crosscheck(merges: Tuple[str, ...] = ("psum", "scatter")
                      ) -> Tuple[Dict[str, dict], List[Finding]]:
    """Run a tiny sharded windowed training per selected merge strategy
    and cross-check the runtime dispatch ledger against the auditor's
    collective count (utils/sanitizer.py::assert_ledger_agreement): one
    dispatch and zero blocking syncs per round on the ledger proves every
    audited collective rode INSIDE the donated round dispatch."""
    import numpy as np

    from ..binning import DatasetBinner
    from ..ops.split import SplitParams
    from ..parallel import data_parallel as dp
    from ..utils import sanitizer as _san
    from .contracts import _F, _L, _N, _TILE, audit_mesh

    rng = np.random.RandomState(0)
    X = rng.randn(_N, _F)
    y = X @ rng.randn(_F)
    binner = DatasetBinner.fit(X, max_bin=31)
    mesh = audit_mesh()
    sharded = dp.ShardedData(mesh, binner.transform(X).astype(np.int16),
                             np.asarray(binner.num_bins_per_feature),
                             np.asarray(binner.missing_bin_per_feature))
    grad = sharded.pad_rows(np.asarray(2 * y, np.float32))
    hess = sharded.pad_rows(np.ones(_N, np.float32))
    mask = sharded.pad_rows(np.ones(_N, bool), fill=False)
    sw = sharded.pad_rows(np.ones(_N, np.float32))
    fmask = np.ones(_F, bool)

    out: Dict[str, dict] = {}
    findings: List[Finding] = []
    for merge in merges:
        cname = f"windowed_round_sharded_{merge}"
        c = CONTRACTS[cname]
        stats: dict = {}
        tree, leaf = dp.grow_tree_windowed_data_parallel(
            sharded, grad, hess, mask, sw, fmask,
            num_leaves=_L, num_bins=32,
            params=SplitParams(min_data_in_leaf=5.0), leaf_tile=_TILE,
            use_pallas=False, merge=merge, stats=stats)
        import jax
        jax.block_until_ready(leaf)
        try:
            out[merge] = _san.assert_ledger_agreement(
                stats, collectives_per_round=len(c.collectives),
                what=f"sharded fused rounds (merge={merge})")
        except _san.BudgetError as e:
            findings.append(_finding(
                c, "J1", f"runtime ledger disagrees with the audited "
                         f"collective placement: {e}",
                "the collectives the auditor counted must all ride the "
                "single per-round dispatch — see docs/ANALYSIS.md "
                "'Jaxpr audit layer'"))
            out[merge] = {"error": str(e)}
    return out, findings


def run_jaxpr_audit(names: Optional[List[str]] = None,
                    runtime: bool = True) -> JaxprReport:
    """Audit the selected (default: all) registered contracts; with
    ``runtime`` also run the DispatchCounter ledger cross-check (executes
    a tiny sharded training — skipped automatically when the selection
    excludes the sharded contracts)."""
    selected = list(names) if names else sorted(CONTRACTS)
    unknown = [n for n in selected if n not in CONTRACTS]
    if unknown:
        raise ValueError(
            f"unknown contracts {unknown}; have {sorted(CONTRACTS)}")
    results = [audit_contract(CONTRACTS[n]) for n in selected]
    by_name = {r.name: r for r in results}
    fam = _check_family_spine(by_name)
    if fam:
        results.append(ContractResult("family-spine", fam, [], {}))
    ledger: Dict[str, dict] = {}
    # cross-check only the merge strategies the selection actually
    # audited — each one executes a tiny training
    merges = tuple(m for m in ("psum", "scatter")
                   if f"windowed_round_sharded_{m}" in selected)
    if runtime and merges:
        ledger, lf = ledger_crosscheck(merges)
        if lf:
            results.append(ContractResult("ledger-crosscheck", lf, [], {}))
    return JaxprReport(results=results, ledger=ledger)


def verdict(runtime: bool = False, exec_contracts: bool = True) -> dict:
    """Compact audit verdict for artifact embedding (bench.py): per-
    contract pass/fail/waiver summary — chip-session artifact rows carry
    proof the contracts held at trace time.  ``exec_contracts=False``
    additionally excludes contracts whose BUILDERS execute device code
    (the converted-predict toy booster) — on a chip those pay real
    remote compiles; the skipped names are listed so the verdict stays
    honest about its coverage."""
    try:
        names = sorted(CONTRACTS)
        skipped = []
        if not exec_contracts:
            skipped = [n for n in names if CONTRACTS[n].executes]
            names = [n for n in names if not CONTRACTS[n].executes]
        rep = run_jaxpr_audit(names, runtime=runtime)
    except Exception as e:  # noqa: BLE001 — artifact robustness first
        return {"ok": False, "error": f"{type(e).__name__}: {e}"[:300]}
    contracts = {}
    for r in rep.results:
        if r.findings:
            contracts[r.name] = f"FAILED:{len(r.findings)}"
        elif r.waived:
            contracts[r.name] = f"waived:{len(r.waived)}"
        else:
            contracts[r.name] = "ok"
    out = {
        "ok": rep.ok,
        "contracts": contracts,
        "findings": [f.format() for f in rep.findings][:20],
        "waivers": [[f.rule, f.message[:80], reason[:120]]
                    for f, reason in rep.waived],
        "ledger": rep.ledger,
    }
    # J7 sweep estimates ride the artifact next to the pass/fail rows —
    # a chip bench row carries the 3-vs-1 bin-sweep proof explicitly
    sweeps = {r.name: r.detail["bin_sweeps"] for r in rep.results
              if "bin_sweeps" in r.detail}
    if sweeps:
        out["bin_sweeps"] = sweeps
    # per-round DCN byte bills of the hierarchical contracts ride the
    # artifact too — a multislice bench row carries the cross-slice
    # budget proof next to the pass/fail rows
    dcn = {r.name: r.detail["dcn_bytes"] for r in rep.results
           if "dcn_bytes" in r.detail}
    if dcn:
        out["dcn_bytes"] = dcn
    # the full per-axis bills (row/feature/ici/dcn) of every collective-
    # bearing contract — a 2-D bench row shows where the round's traffic
    # lands on the mesh grid without re-running the audit
    per_axis = {r.name: r.detail["axis_bytes"] for r in rep.results
                if r.detail.get("axis_bytes")}
    if per_axis:
        out["axis_bytes"] = per_axis
    if skipped:
        out["skipped_exec_contracts"] = skipped
    return out
