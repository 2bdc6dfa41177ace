"""Executable contracts for the jaxpr audit layer (docs/ANALYSIS.md
"Jaxpr audit layer").

A *contract* pins the traced-IR invariants of one flagship executable —
the properties the AST rules structurally cannot see (a collective or a
dropped donation INSIDE a traced body is invisible to source lint).  Each
contract bundles:

* a **builder** that constructs the executable and hermetic example
  arguments (CPU, no chip, no network; ShapeDtypeStructs wherever the
  trace does not need data, so building mostly never executes device
  code — the one exception is the converted-predict contract, which
  trains a 2-iteration toy booster to audit the REAL fused entry);
* the **declared invariants** the auditor (jaxpr_audit.py) checks on the
  traced jaxpr and lowered StableHLO:

  - ``collectives``: the exact ordered ``prim@axis`` sequence the
    executable may contain (J1); ``()`` means the body must be
    collective-free.
  - ``donated_args``: positional args whose buffers are donated; J2
    asserts every live donated leaf is actually consumable (and, where
    the platform lowers aliasing, actually aliased).
  - ``max_const_bytes``: J5's baked-constant ceiling for this trace.
  - ``max_live_bytes``: J6's conservative peak-live-bytes budget — a
    state blowup in the body fails CI here before it fails allocation
    on a v5e.

Contracts are DECLARED NEXT TO the invariants they pin, in this module,
with contract-level **waivers** replacing line pragmas (a traced jaxpr
has no source line to hang a pragma on): ``waivers={"J6": "reason"}``
suppresses rule J6 for that contract, reason mandatory — a reasonless or
unknown-rule waiver is itself a P0 finding, exactly like the lint
layer's pragma hygiene.

Adding a contract::

    @contract(
        "my_executable",
        description="what it is and why its IR shape matters",
        collectives=("psum@data",),     # () = the body must be collective-free
        donated_args=(0,),
        max_live_bytes=1 << 22,
    )
    def _build_my_executable() -> Target:
        ...
        return Target(fn=jitted, args=(...), kwargs=dict(static=...))

JAX is imported only inside builders, so importing this module (and the
``lightgbm_tpu.analysis`` package) stays device-state-free; the CLI sets
the loopback-device env BEFORE any builder runs.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Callable, Dict, Mapping, Optional, Tuple

# shared hermetic shapes
_F, _BINS = 8, 32


@dataclasses.dataclass
class Target:
    """What a builder hands the auditor: the jitted callable plus the
    exact (positional args, static kwargs) to trace/lower it with."""

    fn: object
    args: tuple
    kwargs: dict
    note: str = ""


@dataclasses.dataclass(frozen=True)
class Contract:
    name: str
    description: str
    build: Callable[[], Target]
    collectives: Tuple[str, ...]
    donated_args: Tuple[int, ...]
    max_const_bytes: int
    max_live_bytes: int
    waivers: Mapping[str, str]
    file: str
    line: int
    # True when the BUILDER executes device code (not just trace/lower) —
    # e.g. trains a toy model.  Cost-sensitive callers (bench.py on chip,
    # where every compile is a remote Mosaic compile) can exclude these.
    executes: bool = False


CONTRACTS: Dict[str, Contract] = {}


def contract(name: str, *, description: str,
             collectives: Tuple[str, ...] = (),
             donated_args: Tuple[int, ...] = (),
             max_const_bytes: int = 1 << 16,
             max_live_bytes: int,
             waivers: Optional[Mapping[str, str]] = None,
             executes: bool = False):
    """Register a contract; the decorated function is its builder."""

    def deco(build: Callable[[], Target]) -> Callable[[], Target]:
        if name in CONTRACTS:
            raise ValueError(f"duplicate contract {name!r}")
        frame = inspect.stack()[1]
        CONTRACTS[name] = Contract(
            name=name, description=description, build=build,
            collectives=tuple(collectives),
            donated_args=tuple(donated_args),
            max_const_bytes=max_const_bytes,
            max_live_bytes=max_live_bytes,
            waivers=dict(waivers or {}), file=frame.filename,
            line=frame.lineno, executes=executes)
        return build

    return deco


# ---------------------------------------------------------------------------
# shared builders
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# warm predict entries (ops/predict.py, models/gbdt.py)
# ---------------------------------------------------------------------------

_PN, _PF, _PT, _PL = 128, 8, 8, 8  # bucket rows, features, trees, leaves


def _packed_sds():
    import jax.numpy as jnp
    m = _PL - 1
    return dict(
        split_feature=_sds((_PT, m), jnp.int32),
        threshold=_sds((_PT, m), jnp.float32),
        default_left=_sds((_PT, m), jnp.bool_),
        missing_type=_sds((_PT, m), jnp.int32),
        left_child=_sds((_PT, m), jnp.int32),
        right_child=_sds((_PT, m), jnp.int32),
        num_leaves=_sds((_PT,), jnp.int32),
        leaf_value=_sds((_PT, _PL), jnp.float32),
    )


@contract(
    "predict_warm_single",
    description="warm single-class predict traversal (predict_raw_values) "
                "on a bucket-padded batch with an active mask — the 1-"
                "dispatch serving entry tests/test_predict_budget.py pins",
    collectives=(),
    # measured peak ≈ 44 KB at the 128x8/T8 fixture; 1 MB bounds a
    # traversal that starts materializing per-(tree,row,node) temporaries
    max_live_bytes=1 << 20,
)
def _build_predict_warm_single() -> Target:
    import jax.numpy as jnp

    from ..ops import predict as predict_ops
    s = _packed_sds()
    args = (_sds((_PN, _PF), jnp.float32), s["split_feature"],
            s["threshold"], s["default_left"], s["missing_type"],
            s["left_child"], s["right_child"], s["num_leaves"],
            s["leaf_value"])
    return Target(predict_ops.predict_raw_values, args,
                  dict(active=_sds((_PN,), jnp.bool_)),
                  note="non-categorical pack (the cat variant adds bitset "
                       "gathers, same contract class)")


@contract(
    "predict_warm_multiclass",
    description="warm multiclass predict (predict_raw_multiclass, k=4): "
                "all classes reduced in the SAME single dispatch via the "
                "class-reshaped sum — no per-class loop may reappear",
    collectives=(),
    max_live_bytes=1 << 20,
)
def _build_predict_warm_multiclass() -> Target:
    import jax.numpy as jnp

    from ..ops import predict as predict_ops
    s = _packed_sds()
    args = (_sds((_PN, _PF), jnp.float32), s["split_feature"],
            s["threshold"], s["default_left"], s["missing_type"],
            s["left_child"], s["right_child"], s["num_leaves"],
            s["leaf_value"])
    return Target(predict_ops.predict_raw_multiclass, args,
                  dict(active=_sds((_PN,), jnp.bool_), k=4))


@functools.lru_cache(maxsize=1)
def _tiny_booster():
    """A 2-iteration toy binary booster: the ONLY contract builder that
    executes device code, because the fused converted-predict entry is an
    instance-cached jit closing over the model's real objective
    (models/gbdt.py::_get_convert_entry) — auditing a replica would let
    the real entry drift."""
    import numpy as np

    import lightgbm_tpu as lgb

    rng = np.random.RandomState(0)
    X = rng.randn(192, _PF)
    y = (X[:, 0] + 0.25 * X[:, 1] > 0).astype(np.float64)
    d = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 4,
                              "min_data_in_leaf": 5, "verbosity": -1},
                      train_set=d)
    for _ in range(2):
        bst.update()
    return bst._gbdt


@contract(
    "predict_warm_converted",
    description="fused converted predict (traversal + objective."
                "convert_output in ONE trace, models/gbdt.py::"
                "_get_convert_entry) — the round-12 single-dispatch entry; "
                "audited on a real 2-iteration binary booster",
    collectives=(),
    max_live_bytes=1 << 20,
    executes=True,  # the builder trains the toy booster
)
def _build_predict_warm_converted() -> Target:
    import jax.numpy as jnp

    g = _tiny_booster()
    s = g._packed(0, -1)
    run = g._get_convert_entry()
    args = (_sds((_PN, _PF), jnp.float32), s["split_feature"],
            s["threshold"], s["default_left"], s["missing_type"],
            s["left_child"], s["right_child"], s["num_leaves"],
            s["leaf_value"], s.get("is_cat"), s.get("cat_base"),
            s.get("cat_nwords"), s.get("cat_words"),
            _sds((_PN,), jnp.bool_))
    return Target(run, args, dict(k=1))


@contract(
    "predict_coalesced_bucket",
    description="the serving runtime's coalesced batch dispatch "
                "(lightgbm_tpu/serve/runtime.py -> GBDT.predict_coalesced): "
                "K concurrent requests packed into one bucket rung must "
                "dispatch the SAME traced executable family as warm "
                "single-caller predict — the fn is resolved through the "
                "runtime's own selector (serve.runtime.audit_dispatch_fn "
                "-> GBDT._coalesced_raw_fn), so a serve-owned second "
                "entry, a collective, or an in-trace transfer appearing "
                "in the serving loop fails the audit statically",
    collectives=(),
    max_live_bytes=1 << 20,
)
def _build_predict_coalesced_bucket() -> Target:
    import jax.numpy as jnp

    from ..serve.runtime import audit_dispatch_fn
    s = _packed_sds()
    fn = audit_dispatch_fn(1)
    args = (_sds((_PN, _PF), jnp.float32), s["split_feature"],
            s["threshold"], s["default_left"], s["missing_type"],
            s["left_child"], s["right_child"], s["num_leaves"],
            s["leaf_value"])
    return Target(fn, args, dict(active=_sds((_PN,), jnp.bool_)),
                  note="same fixture shape as predict_warm_single — the "
                       "coalesced dispatch IS that executable family by "
                       "construction, and this contract pins it")


@contract(
    "continual_refit_leaves",
    description="the continual runner's leaf-refit dispatch (lightgbm_tpu/"
                "continual/refit.py::make_refit_entry): the stacked leaf-"
                "index traversal + per-tree gradient/segment-sum/renewal "
                "scan + score accumulation, fused into ONE donated "
                "executable — the update that runs at ingest cadence "
                "beside live serving, so it must stay collective-free, "
                "transfer-free, and consume its donated leaf table (the "
                "caller uploads a FRESH table, never the serving pack's "
                "buffer).  Resolved through the runtime's own builder "
                "(continual.refit.audit_refit_fn), so a refit path that "
                "grew a second executable family fails here statically",
    collectives=(),
    donated_args=(0,),
    # the scan carries (N,) score + per-tree (L,) sums; measured peak is
    # well under 1 MB at the 128x8/T8/L8 fixture — 2 MB headroom catches
    # an accidental (T, N) or (N, L) materialization
    max_live_bytes=2 << 20,
)
def _build_continual_refit_leaves() -> Target:
    import jax.numpy as jnp

    from ..continual.refit import audit_refit_fn

    s = _packed_sds()
    fn = audit_refit_fn()
    args = (s["leaf_value"],                    # donated (T, L) leaf table
            _sds((_PT,), jnp.float32),          # per-tree shrinkage
            _sds((_PN, _PF), jnp.float32),      # bucket-padded window rows
            s["split_feature"], s["threshold"], s["default_left"],
            s["missing_type"], s["left_child"], s["right_child"],
            s["num_leaves"],
            None, None, None, None,             # non-categorical pack
            _sds((_PN,), jnp.float32),          # padded labels
            _sds((_PN,), jnp.bool_))            # active mask
    return Target(fn, args, {},
                  note="regression objective (the binary/other single-"
                       "output entries share the same trace shape: "
                       "gradients are elementwise over the score)")


# ---------------------------------------------------------------------------
# spill grower chunk steps (ops/treegrow_ooc.py)
# ---------------------------------------------------------------------------

_CN, _CC = 4096, 1024  # padded resident rows, chunk rows


@contract(
    "ooc_root_chunk",
    description="spill-grower root-pass chunk step (_root_chunk_step): the "
                "donated histogram fold plus in-jit mask/slice — the one "
                "accounted dispatch per chunk the OOC docstring promises",
    collectives=(),
    donated_args=(0,),
    # measured peak ≈ 0.5 MB (chunk payload broadcast); 2 MB headroom
    max_live_bytes=2 << 20,
)
def _build_ooc_root_chunk() -> Target:
    import jax.numpy as jnp

    from ..ops.treegrow_ooc import _root_chunk_step
    args = (_sds((3, _F, _BINS), jnp.float32), _sds((_CC, _F), jnp.int16),
            _sds((), jnp.int32), _sds((_CC,), jnp.bool_),
            _sds((_CN,), jnp.float32), _sds((_CN,), jnp.float32),
            _sds((_CN,), jnp.bool_))
    return Target(_root_chunk_step, args, dict(num_bins=_BINS))


@contract(
    "ooc_split_chunk",
    description="spill-grower split-sweep chunk step (_split_chunk_step): "
                "fused partition + small-child histogram fold, leaf ids "
                "AND the accumulator donated",
    collectives=(),
    donated_args=(0, 1),
    max_live_bytes=2 << 20,
)
def _build_ooc_split_chunk() -> Target:
    import jax.numpy as jnp

    from ..ops.treegrow_ooc import _split_chunk_step
    sel = dict(best_leaf=_sds((), jnp.int32), feature=_sds((), jnp.int32),
               threshold_bin=_sds((), jnp.int32),
               default_left=_sds((), jnp.bool_), is_cat=_sds((), jnp.bool_),
               cat_mask=_sds((_BINS,), jnp.bool_),
               new_leaf=_sds((), jnp.int32), small_leaf=_sds((), jnp.int32))
    args = (_sds((_CN,), jnp.int32), _sds((3, _F, _BINS), jnp.float32),
            _sds((_CC, _F), jnp.int16), _sds((), jnp.int32),
            _sds((_CC,), jnp.bool_), _sds((_CN,), jnp.float32),
            _sds((_CN,), jnp.float32), _sds((_CN,), jnp.bool_),
            _sds((_F,), jnp.int32), sel)
    return Target(_split_chunk_step, args, dict(num_bins=_BINS))
