"""The continual leaf-refit executable (round 19; README "Continuous
training").

``Booster.refit`` is the reference's continued-training primitive
(GBDT::RefitTree via LGBM_BoosterRefit): keep every tree's STRUCTURE,
renew its leaf values on fresh data as
``new = decay * old + (1 - decay) * (-G_leaf / (H_leaf + lambda_l2))``,
with the per-tree gradients taken at the score accumulated from the
already-renewed earlier trees.  The host implementation walks trees one
at a time — T host traversals, T gradient pulls, T bincounts.  A
continual runner refits at ingest cadence beside a live serving loop, so
the update must cost like a predict, not like a training epoch: this
module fuses the WHOLE refit — the stacked leaf-index traversal, the
per-tree gradient/segment-sum/renewal scan, and the score accumulation —
into ONE donated jitted dispatch (the ``continual_refit_leaves`` jaxpr
contract pins it: zero collectives, donation consumed, transfer-free).

Semantics notes (deliberate, documented deviations are none — this IS
``Booster.refit``'s recipe, in f32 on device):

* the score starts at 0 over the EXPORT-form trees (init score folded
  into the first tree per class), exactly as ``Booster.refit`` runs on
  a ``model_to_string`` round-trip;
* a leaf no fresh row reaches (``sum_h == 0``) keeps its old value;
* multiclass ensembles renew tree ``t`` against class ``t % k``'s
  gradient column of the (nb, k) score plane — the reference's
  iter-major, class-minor RefitTree order (round 21; previously
  refused);
* sample weights enter through ``objective.get_gradients`` when the
  caller passes them (round 21); the default stays ``weight=None``,
  which is also what ``Booster.refit`` does without a ``weight=``.

Round 20 adds the BATCHED twin :func:`make_fleet_refit_entry` /
:func:`fleet_refit_leaves`: B independent k=1 models (any same-config
model list) refresh their leaves in ONE donated dispatch — shared bucket-padded batch, per-lane
stacked packs, per-lane labels, the solo scan vmapped over the model
axis with the traversal input unmapped.

Envelope: non-linear leaves, no RF averaging — the same class of
eligibility the coalesced serving path checks.  Ineligible models
refuse loudly (``ContinualError``): silently refitting half a linear
model would be a correctness bug wearing a latency win.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..basic import LightGBMError
from ..ops import predict as predict_ops
from ..utils import sanitizer as _san


class ContinualError(LightGBMError):
    """An operation outside the continual runtime's envelope (linear
    leaves, multiclass device refit, missing mappers, ...)."""


@functools.lru_cache(maxsize=8)
def make_refit_entry(objective, decay: float, lam2: float, k: int = 1):
    """Build the jitted refit executable for one (objective, decay,
    lambda_l2, trees-per-iteration) configuration — memoized, so a
    runner (or repeated offline refits over the same objective instance)
    reuses ONE trace cache: every rollover reuses the compiled entry,
    zero retraces across rollovers, one compile per window bucket rung
    (the ``GBDT._get_convert_entry`` discipline, keyed on the factory
    args instead of the instance).

    Signature of the returned callable::

        new_leaf = run(leaf_value, shrinkage, x, sf, th, dl, mt, lc, rc,
                       nl, is_cat, cat_base, cat_nwords, cat_words,
                       label, active, weight=None)

    ``leaf_value`` (T, L) f32 is DONATED (callers pass a fresh upload,
    never the serving pack's cached buffer); ``x`` is a bucket-padded
    (nb, F) f32 batch with ``active`` masking the tail (None at exact
    fill), ``label`` the f32 targets padded alongside (class ids when
    ``k > 1``), ``weight`` optional padded f32 sample weights threaded
    to ``objective.get_gradients``.  Returns the renewed (T, L) f32
    leaf table.  ``k > 1`` runs the multiclass recipe: tree ``t``
    renews against class ``t % k``'s gradient column and accumulates
    into that class's score lane.
    """
    decay_f = jnp.float32(decay)
    keep_f = jnp.float32(1.0 - float(decay))
    lam2_f = jnp.float32(lam2)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(leaf_value, shrinkage, x, sf, th, dl, mt, lc, rc, nl,
            is_cat, cat_base, cat_nwords, cat_words, label, active,
            weight=None):
        # stacked leaf-index traversal: (N, T) -> (T, N), the same
        # vmapped walk the pred_leaf serving entry uses
        leaves = predict_ops.predict_leaf_values(
            x, sf, th, dl, mt, lc, rc, nl, is_cat=is_cat,
            cat_base=cat_base, cat_nwords=cat_nwords, cat_words=cat_words)
        leaves_t = leaves.T.astype(jnp.int32)  # (T, N)
        n_leaf = leaf_value.shape[1]
        actb = (jnp.ones(label.shape, jnp.bool_) if active is None
                else active)

        def renew(lv, leaf, shrink, g, h):
            g = jnp.where(actb, g.astype(jnp.float32), jnp.float32(0.0))
            h = jnp.where(actb, h.astype(jnp.float32), jnp.float32(0.0))
            sum_g = jnp.zeros((n_leaf,), jnp.float32).at[leaf].add(g)
            sum_h = jnp.zeros((n_leaf,), jnp.float32).at[leaf].add(h)
            new = -sum_g / (sum_h + lam2_f + jnp.float32(1e-15)) * shrink
            return jnp.where(sum_h > 0, decay_f * lv + keep_f * new, lv)

        if k == 1:
            def step(score, per_tree):
                lv, leaf, shrink = per_tree
                g, h = objective.get_gradients(score, label, weight)
                lv_new = renew(lv, leaf, shrink, g, h)
                # the renewed tree feeds the NEXT tree's gradients — the
                # reference's sequential RefitTree order, kept exactly
                score = score + jnp.where(actb, lv_new[leaf],
                                          jnp.float32(0.0))
                return score, lv_new

            score0 = jnp.zeros(label.shape, jnp.float32)
            _, new_leaf = jax.lax.scan(
                step, score0, (leaf_value, leaves_t, shrinkage))
            return new_leaf

        # multiclass: the (nb, k) score plane; tree t touches only its
        # class column c = t % k (the reference's iter-major order)
        cls = jnp.arange(leaf_value.shape[0], dtype=jnp.int32) % k

        def step_mc(score, per_tree):
            lv, leaf, shrink, c = per_tree
            g, h = objective.get_gradients(score, label, weight)
            lv_new = renew(lv, leaf, shrink,
                           jnp.take(g, c, axis=1), jnp.take(h, c, axis=1))
            score = score.at[:, c].add(
                jnp.where(actb, lv_new[leaf], jnp.float32(0.0)))
            return score, lv_new

        score0 = jnp.zeros((label.shape[0], k), jnp.float32)
        _, new_leaf = jax.lax.scan(
            step_mc, score0, (leaf_value, leaves_t, shrinkage, cls))
        return new_leaf

    return run


@functools.lru_cache(maxsize=8)
def make_fleet_refit_entry(objective, decay: float, lam2: float):
    """The BATCHED twin of :func:`make_refit_entry` for B independent
    k=1 models: the solo per-tree gradient/segment-sum/renewal scan
    vmapped over a leading model axis, with the bucket-padded traversal
    batch UNMAPPED (every lane walks the same rows through its OWN
    stacked pack).  One donated dispatch renews all B leaf tables.

    Signature of the returned callable::

        new_leaf = run(leaf_value, shrinkage, x, sf, th, dl, mt, lc, rc,
                       nl, label, active, weight=None)

    ``leaf_value`` (B, T, L) f32 is DONATED; the pack structure arrays
    are (B, T, m) stacked (lanes padded to the common T/m with
    single-leaf dummy trees whose shrinkage is 0 — their renewal and
    score contribution are exact zeros); ``label`` is (B, nb) per-lane
    targets over the SHARED (nb, F) batch; ``weight`` optionally
    (B, nb).  Categorical packs are outside the fleet envelope (the
    caller refuses them loudly).
    """
    decay_f = jnp.float32(decay)
    keep_f = jnp.float32(1.0 - float(decay))
    lam2_f = jnp.float32(lam2)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run(leaf_value, shrinkage, x, sf, th, dl, mt, lc, rc, nl,
            label, active, weight=None):
        n_leaf = leaf_value.shape[2]
        actb = (jnp.ones(x.shape[:1], jnp.bool_) if active is None
                else active)  # (nb,), shared across lanes

        def lane(lv_b, shr_b, sf_b, th_b, dl_b, mt_b, lc_b, rc_b, nl_b,
                 lab_b, w_b):
            leaves = predict_ops.predict_leaf_values(
                x, sf_b, th_b, dl_b, mt_b, lc_b, rc_b, nl_b)
            leaves_t = leaves.T.astype(jnp.int32)

            def step(score, per_tree):
                lv, leaf, shrink = per_tree
                g, h = objective.get_gradients(score, lab_b, w_b)
                g = jnp.where(actb, g.astype(jnp.float32), jnp.float32(0.0))
                h = jnp.where(actb, h.astype(jnp.float32), jnp.float32(0.0))
                sum_g = jnp.zeros((n_leaf,), jnp.float32).at[leaf].add(g)
                sum_h = jnp.zeros((n_leaf,), jnp.float32).at[leaf].add(h)
                new = -sum_g / (sum_h + lam2_f + jnp.float32(1e-15)) * shrink
                lv_new = jnp.where(sum_h > 0, decay_f * lv + keep_f * new, lv)
                score = score + jnp.where(actb, lv_new[leaf],
                                          jnp.float32(0.0))
                return score, lv_new

            score0 = jnp.zeros(lab_b.shape, jnp.float32)
            _, new_leaf = jax.lax.scan(
                step, score0, (lv_b, leaves_t, shr_b))
            return new_leaf

        if weight is None:
            return jax.vmap(
                lambda lv, sh, a, b, c, d, e, f, g, lab:
                lane(lv, sh, a, b, c, d, e, f, g, lab, None)
            )(leaf_value, shrinkage, sf, th, dl, mt, lc, rc, nl, label)
        return jax.vmap(lane)(leaf_value, shrinkage, sf, th, dl, mt,
                              lc, rc, nl, label, weight)

    return run


def refit_eligible(gbdt) -> Optional[str]:
    """None when the device refit applies, else the human reason it
    does not (the runner surfaces it in the ContinualError).  Round 20:
    multiclass ensembles are eligible — the scan renews tree ``t``
    against class ``t % k`` (make_refit_entry's ``k`` argument)."""
    if gbdt.average_output:
        return "RF-averaged ensembles renew against scaled scores"
    s = gbdt._packed(0, -1)
    if s is None:
        return "the ensemble is empty"
    if s["_linear"]:
        return ("linear leaves carry per-leaf linear terms a leaf-value "
                "refit would silently drop")
    return None


def refit_leaves(gbdt, X: np.ndarray, label: np.ndarray, *,
                 weight: Optional[np.ndarray] = None, entry=None) -> int:
    """Refit ``gbdt``'s leaf values on ``(X, label)`` in ONE donated
    dispatch + ONE accounted sync, writing the renewed values back into
    the host trees and version-bumping the packed cache.  Returns the
    number of rows used.

    ``weight`` optionally carries per-row sample weights into the
    gradient call (round 21 — ``Booster.refit(weight=...)`` parity).
    ``entry`` is a prebuilt :func:`make_refit_entry` executable (the
    runner's cached one); None builds a throwaway (tests, one-shot
    offline use).  The donated leaf table is a FRESH upload — the cached
    serving pack's buffer is never donated, so in-flight readers of the
    current pack version are untouched until the version bump."""
    from ..models.gbdt import _predict_bucket

    why = refit_eligible(gbdt)
    if why is not None:
        raise ContinualError(f"device refit does not apply: {why} "
                             "(lightgbm_tpu/continual/refit.py envelope)")
    k = gbdt.num_tree_per_iteration
    if entry is None:
        entry = make_refit_entry(
            gbdt.objective, float(gbdt.cfg.refit_decay_rate),
            float(gbdt.cfg.lambda_l2), k=k)
    s = gbdt._packed(0, -1)
    trees = s["_trees"]
    # structural-mutation guard: the renewed tables are computed from
    # THIS pack snapshot and written back positionally — any concurrent
    # mutation (shuffle/rollback/leaf edit, all of which bump the pack
    # version) would silently attach them to the wrong trees, so the
    # write-back below verifies the version is unchanged and aborts loudly
    v0 = gbdt._pack_version
    X = np.asarray(X, np.float64)
    label = np.asarray(label, np.float64).ravel()
    if X.shape[0] != len(label):
        raise ValueError(f"refit_leaves: {X.shape[0]} rows but "
                         f"{len(label)} labels")
    n = X.shape[0]
    nb = _predict_bucket(n)
    x = gbdt._pad_rows(X, nb)
    active = gbdt._active_mask(n, nb)
    yb = np.zeros(nb, np.float32)
    yb[:n] = label
    wb = None
    if weight is not None:
        weight = np.asarray(weight, np.float64).ravel()
        if len(weight) != n:
            raise ValueError(f"refit_leaves: {n} rows but "
                             f"{len(weight)} weights")
        wb = np.zeros(nb, np.float32)
        wb[:n] = weight
    # fresh donated leaf table + the tiny per-tree shrinkage vector; the
    # pack's structure arrays ride along read-only
    lv0 = jnp.asarray(np.stack(
        [np.pad(np.asarray(t.leaf_value, np.float32),
                (0, s["leaf_value"].shape[1] - t.num_leaves))
         for t in trees]))
    shrink = jnp.asarray(np.asarray([t.shrinkage for t in trees],
                                    np.float32))
    _san.record_dispatch()
    out = entry(lv0, shrink, x, s["split_feature"], s["threshold"],
                s["default_left"], s["missing_type"], s["left_child"],
                s["right_child"], s["num_leaves"], s.get("is_cat"),
                s.get("cat_base"), s.get("cat_nwords"), s.get("cat_words"),
                jnp.asarray(yb), active,
                None if wb is None else jnp.asarray(wb))
    new_lv = np.asarray(_san.sync_pull(out), np.float64)
    # write back; the export-form first tree per class carries the folded
    # init score, so a delta-form model (init_scores separate)
    # re-separates it here — predict (init + sum of deltas) stays exactly
    # the renewed folded sum.  Mutation + version bump in ONE pack-lock
    # section: a pack build racing this (the model may already be
    # serving) retries at insert time, never caching a half-renewed pack
    # under the old version
    inits = [float(v) for v in (gbdt.init_scores or [0.0])]
    with gbdt._plock():
        if gbdt._pack_version != v0:
            raise ContinualError(
                "the ensemble mutated while the refit dispatch ran "
                f"(pack version {v0} -> {gbdt._pack_version}) — the "
                "renewed leaf tables no longer map onto the current "
                "trees; the write-back was aborted and the model is "
                "unchanged.  Serialize mutations with refits (the "
                "ContinualRunner's update lock does)")
        for i, t in enumerate(gbdt.models):
            vals = new_lv[i, : t.num_leaves].copy()
            if i < k and inits[i % k]:
                vals -= inits[i % k]
            t.leaf_value = vals
        gbdt._invalidate_pred_cache("continual_refit")
    return n


def _unwrap_lane(model):
    gbdt = getattr(model, "_gbdt", model)
    if not hasattr(gbdt, "_packed"):
        raise ContinualError(
            f"fleet_refit_leaves: {type(model).__name__} is not a "
            "Booster/GBDT lane")
    return gbdt


def fleet_refit_leaves(models, X: np.ndarray, labels: np.ndarray, *,
                       weights: Optional[np.ndarray] = None,
                       entry=None) -> int:
    """Refresh B models' leaf values in ONE donated dispatch + ONE
    accounted sync — the batched twin of :func:`refit_leaves` for a list
    of same-config k=1 Boosters/GBDTs over the same feature space.

    ``labels`` is (B, n) per-lane targets over the SHARED ``X``;
    ``weights`` optionally (B, n).  Each lane's stacked pack is padded
    to the fleet's common (T, m) with zero-shrinkage single-leaf dummy
    trees (exact no-ops in the scan), the solo recipe runs vmapped over
    the model axis, and the renewed tables write back under each lane's
    pack lock with the solo version guard.  Returns the rows used."""
    from ..models.gbdt import _predict_bucket

    lanes = [_unwrap_lane(m) for m in models]
    if not lanes:
        raise ContinualError("fleet_refit_leaves: no models")
    for i, g in enumerate(lanes):
        why = refit_eligible(g)
        if why is None and g.num_tree_per_iteration != 1:
            why = ("the batched twin is k=1 only — refit multiclass "
                   "models one at a time through refit_leaves")
        if why is not None:
            raise ContinualError(f"device refit does not apply to fleet "
                                 f"lane {i}: {why} "
                                 "(lightgbm_tpu/continual/refit.py)")
    cfg0 = lanes[0].cfg
    if entry is None:
        entry = make_fleet_refit_entry(
            lanes[0].objective, float(cfg0.refit_decay_rate),
            float(cfg0.lambda_l2))
    X = np.asarray(X, np.float64)
    labels = np.asarray(labels, np.float64)
    n = X.shape[0]
    if labels.shape != (len(lanes), n):
        raise ValueError(f"fleet_refit_leaves: labels must be "
                         f"({len(lanes)}, {n}), got {labels.shape}")
    if weights is not None:
        weights = np.asarray(weights, np.float64)
        if weights.shape != labels.shape:
            raise ValueError(f"fleet_refit_leaves: weights must match "
                             f"labels {labels.shape}, got {weights.shape}")
    # per-lane pack snapshots; pad every lane to the fleet-wide (T, m, L)
    # with zero-shrinkage dummy trees — their traversal lands every row
    # in leaf 0 of a zero table and their renewal multiplies by 0
    packs, versions = [], []
    for g in lanes:
        s = g._packed(0, -1)
        if s.get("is_cat") is not None:
            raise ContinualError(
                "fleet_refit_leaves: categorical packs are outside the "
                "fleet envelope — refit those models through refit_leaves")
        packs.append(s)
        versions.append(g._pack_version)
    t_max = max(s["T"] for s in packs)
    m_max = max(s["split_feature"].shape[1] for s in packs)
    l_max = max(s["leaf_value"].shape[1] for s in packs)
    b = len(lanes)

    def stack(key, dtype, width, fill=0):
        out = np.full((b, t_max, width), fill, dtype=dtype)
        for i, s in enumerate(packs):
            a = np.asarray(s[key])
            out[i, : a.shape[0], : a.shape[1]] = a
        return jnp.asarray(out)

    nl = np.ones((b, t_max), np.int32)
    lv0 = np.zeros((b, t_max, l_max), np.float32)
    shr = np.zeros((b, t_max), np.float32)
    for i, s in enumerate(packs):
        nl[i, : s["T"]] = np.asarray(s["num_leaves"])
        for j, t in enumerate(s["_trees"]):
            lv0[i, j, : t.num_leaves] = np.asarray(t.leaf_value, np.float32)
            shr[i, j] = t.shrinkage
    nb = _predict_bucket(n)
    x = lanes[0]._pad_rows(X, nb)
    active = lanes[0]._active_mask(n, nb)
    yb = np.zeros((b, nb), np.float32)
    yb[:, :n] = labels
    wb = None
    if weights is not None:
        wb = np.zeros((b, nb), np.float32)
        wb[:, :n] = weights
    _san.record_dispatch()
    out = entry(jnp.asarray(lv0), jnp.asarray(shr), x,
                stack("split_feature", np.int32, m_max),
                stack("threshold", np.float32, m_max),
                stack("default_left", bool, m_max),
                stack("missing_type", np.int32, m_max),
                stack("left_child", np.int32, m_max, fill=-1),
                stack("right_child", np.int32, m_max, fill=-1),
                jnp.asarray(nl), jnp.asarray(yb), active,
                None if wb is None else jnp.asarray(wb))
    new_lv = np.asarray(_san.sync_pull(out), np.float64)
    for i, g in enumerate(lanes):
        inits = [float(v) for v in (g.init_scores or [0.0])]
        with g._plock():
            if g._pack_version != versions[i]:
                raise ContinualError(
                    f"fleet lane {i} mutated while the batched refit "
                    f"dispatch ran (pack version {versions[i]} -> "
                    f"{g._pack_version}); lanes 0..{i - 1} are renewed, "
                    f"lane {i} on are unchanged — serialize mutations "
                    "with refits")
            for j, t in enumerate(g.models):
                vals = new_lv[i, j, : t.num_leaves].copy()
                if j == 0 and inits[0]:
                    vals -= inits[0]
                t.leaf_value = vals
            g._invalidate_pred_cache("continual_refit")
    return n


def audit_refit_fn(objective=None):
    """The jitted callable one continual refit dispatches — the
    ``continual_refit_leaves`` jaxpr-audit contract traces THIS builder
    (analysis/contracts.py), so a refit path that grew a second
    executable, a collective, or an in-trace transfer fails the audit
    statically rather than burning a chip session."""
    if objective is None:
        from ..config import Config
        from ..objectives import create_objective

        objective = create_objective(Config.from_dict(
            {"objective": "regression"}))
    return make_refit_entry(objective, decay=0.9, lam2=0.0)
