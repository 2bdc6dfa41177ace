"""The ranking objectives on the bucketed query layout: the lambdas, the
XE-NDCG gradients and the position-bias carry equal what the dense layout
(every query padded to the longest, pairs as (Q, S, S) tensors) gave, which
is kept here as the oracle and nowhere in the program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import objectives
from lightgbm_tpu.config import Config
from lightgbm_tpu.metrics import dcg_at_k, pad_queries
from lightgbm_tpu.obs import metrics as obs
from lightgbm_tpu.utils import profiling
from lightgbm_tpu.utils.sanitizer import CompileCounter

LONGEST = 300


# ---------------------------------------------------------------------------
# the oracle: objectives.py's dense form as it stood before the buckets
# ---------------------------------------------------------------------------

def dense_lambdas(scores, labels, mask, label_gain, inv_mdcg, sigmoid,
                  truncation, norm):
    """(Q, S) in and out, every query padded to the longest."""
    masked = jnp.where(mask, scores, jnp.float32(-1e30))
    order = jnp.argsort(-masked, axis=1, stable=True)
    ranks = jnp.argsort(order, axis=1)
    lg = label_gain[jnp.clip(labels.astype(jnp.int32), 0,
                             label_gain.shape[0] - 1)]
    lg = jnp.where(mask, lg, 0.0)
    disc = 1.0 / jnp.log2(ranks.astype(jnp.float32) + 2.0)
    disc = jnp.where(ranks < truncation, disc, 0.0)
    in_window = ranks < truncation
    d_s = scores[:, :, None] - scores[:, None, :]
    d_gain = lg[:, :, None] - lg[:, None, :]
    d_disc = disc[:, :, None] - disc[:, None, :]
    delta = jnp.abs(d_gain) * jnp.abs(d_disc) * inv_mdcg[:, None, None]
    better = ((labels[:, :, None] > labels[:, None, :])
              & mask[:, :, None] & mask[:, None, :])
    better = better & (in_window[:, :, None] | in_window[:, None, :])
    rho = 1.0 / (1.0 + jnp.exp(sigmoid * d_s))
    lam = jnp.where(better, sigmoid * rho * delta, 0.0)
    hes = jnp.where(better, sigmoid * sigmoid * rho * (1.0 - rho) * delta,
                    0.0)
    grad = -jnp.sum(lam, axis=2) + jnp.sum(jnp.swapaxes(lam, 1, 2), axis=2)
    hess = jnp.sum(hes, axis=2) + jnp.sum(jnp.swapaxes(hes, 1, 2), axis=2)
    if norm:
        total = jnp.sum(jnp.abs(lam), axis=(1, 2))[:, None]
        scale = jnp.where(total > 0, jnp.log2(1.0 + total)
                          / jnp.maximum(total, 1e-20), 1.0)
        grad, hess = grad * scale, hess * scale
    return jnp.where(mask, grad, 0.0), jnp.where(mask, hess, 0.0)


class Dense:
    """The pad-to-longest layout and the loop a query that built it."""

    def __init__(self, qb, labels, obj):
        idx, self.mask = pad_queries(qb)
        self.idx, self.n = idx, len(labels)
        self.labels = jnp.asarray(np.asarray(labels, np.float32)[idx])
        inv = []
        for lo, hi in zip(qb[:-1], qb[1:]):
            best = np.sort(labels[lo:hi])[::-1]
            m = dcg_at_k(best, min(hi - lo, obj.truncation), obj.label_gain)
            inv.append(1.0 / m if m > 0 else 0.0)
        self.inv_mdcg = np.asarray(inv)
        self.obj = obj

    def padded(self, by_row):
        return jnp.asarray(np.asarray(by_row)[self.idx])

    def by_row(self, padded):
        out = np.zeros(self.n, np.float32)
        out[self.idx[self.mask]] = np.asarray(padded)[self.mask]
        return out

    def lambdas(self, score):
        o = self.obj
        g, h = dense_lambdas(
            self.padded(score), self.labels, jnp.asarray(self.mask),
            jnp.asarray(o.label_gain, jnp.float32),
            jnp.asarray(self.inv_mdcg, jnp.float32), o.sigmoid, o.truncation,
            o.norm)
        return g, h


def queries(seed=0):
    """Lengths 1 to 300 in no order, a query of one row first, one with a
    single grade, scores rounded so that most queries hold ties."""
    rng = np.random.RandomState(seed)
    lens = np.concatenate([[1, 2, LONGEST, 1, 7],
                           rng.randint(1, LONGEST, 40)])
    qb = np.concatenate([[0], np.cumsum(lens)])
    n = int(qb[-1])
    labels = rng.choice(5, n, p=[.6, .2, .1, .05, .05]).astype(np.float64)
    labels[qb[5]:qb[6]] = 2.0
    score = np.round(rng.randn(n), 1).astype(np.float32)
    return qb, labels, score


def close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-6 * max(np.abs(want).max(), 1e-30))


# ---------------------------------------------------------------------------
# the layout
# ---------------------------------------------------------------------------

def test_every_row_lies_in_one_lane_of_the_bucket_of_its_width():
    qb, labels, _ = queries()
    obj = objectives.LambdarankNDCG(Config(objective="lambdarank"))
    obj.set_query(qb, labels)
    lens = np.diff(qb)
    seen, lanes = [], 0
    for bucket, members in zip(obj._layout.buckets, obj._bucket_queries):
        rows = np.asarray(bucket.rows)
        w = rows.shape[1]
        assert w & (w - 1) == 0  # a power of two
        assert np.all((lens[members] <= w) & (2 * lens[members] > w))
        valid = np.arange(w)[None, :] < lens[members, None]
        np.testing.assert_array_equal(np.asarray(bucket.lens), lens[members])
        np.testing.assert_array_equal(
            rows[valid], np.concatenate([np.arange(qb[q], qb[q + 1])
                                         for q in members]))
        assert np.all(rows[~valid] == qb[-1])
        np.testing.assert_array_equal(np.asarray(bucket.label)[valid],
                                      labels[rows[valid]])
        seen.append(rows[valid])
        lanes += rows.size
    np.testing.assert_array_equal(np.sort(np.concatenate(seen)),
                                  np.arange(qb[-1]))
    # the way back: a row's lane holds the row
    flat = np.concatenate([np.asarray(b.rows).ravel()
                           for b in obj._layout.buckets])
    np.testing.assert_array_equal(flat[np.asarray(obj._layout.lane_of_row)],
                                  np.arange(qb[-1]))
    assert lanes < 2 * qb[-1]
    assert obj.rank_work == (qb[-1], lanes, sum(
        b.rows.shape[0] * min(30, b.rows.shape[1]) * b.rows.shape[1]
        for b in obj._layout.buckets))
    assert obj.max_query == LONGEST


def test_set_query_sets_the_gauges():
    qb, labels, _ = queries()
    obs.reset()
    obj = objectives.RankXENDCG(Config(objective="rank_xendcg"))
    obj.set_query(qb, labels)
    assert obs.gauge("rank_buckets").value == len(obj._layout.buckets) == 9
    assert obs.gauge("rank_longest_query").value == LONGEST
    assert obj.rank_work[2] == 0  # no pair is formed


def test_no_query_at_all_gives_no_gradient():
    obj = objectives.LambdarankNDCG(Config(objective="lambdarank"))
    obj.set_query(np.array([0]), np.zeros(0))
    g, h = obj.get_gradients(jnp.zeros((0,)), jnp.zeros((0,)), None)
    assert g.shape == h.shape == (0,)


# ---------------------------------------------------------------------------
# the lambdas
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("norm", [True, False])
@pytest.mark.parametrize("truncation", [1, 5, 30, LONGEST + 100])
def test_bucketed_lambdas_equal_the_dense_ones(truncation, norm):
    qb, labels, score = queries()
    obj = objectives.LambdarankNDCG(Config(
        objective="lambdarank", lambdarank_truncation_level=truncation,
        lambdarank_norm=norm))
    obj.set_query(qb, labels)
    dense = Dense(qb, labels, obj)
    np.testing.assert_allclose(obj.inverse_max_dcg, dense.inv_mdcg,
                               rtol=1e-12)
    # every score 0, as at the first round; then scores with ties
    for s in (np.zeros_like(score), score):
        g, h = obj.get_gradients(jnp.asarray(s),
                                 jnp.asarray(labels, jnp.float32), None)
        want_g, want_h = dense.lambdas(s)
        close(g, dense.by_row(want_g))
        close(h, dense.by_row(want_h))
        # a query of one row and a query of one grade form no pair
        for lo, hi in ((qb[0], qb[1]), (qb[5], qb[6])):
            assert np.all(np.asarray(g)[lo:hi] == 0)
            assert np.all(np.asarray(h)[lo:hi] == 0)
    assert np.abs(np.asarray(g)).max() > 0


def test_a_bucket_in_pieces_equals_the_bucket_whole(monkeypatch):
    qb, labels, score = queries(3)
    obj = objectives.LambdarankNDCG(Config(objective="lambdarank"))
    obj.set_query(qb, labels)
    whole = obj.get_gradients(jnp.asarray(score), None, None)
    # pieces of a few queries: every bucket of more takes the loop
    monkeypatch.setattr(objectives, "_PAIR_PIECE_LANES", 3 * 30 * 64)
    jax.clear_caches()
    pieces = obj.get_gradients(jnp.asarray(score), None, None)
    jax.clear_caches()
    for a, b in zip(pieces, whole):
        close(a, b)


def test_a_custom_gain_table_and_sigmoid():
    qb, labels, score = queries(5)
    obj = objectives.LambdarankNDCG(Config(
        objective="lambdarank", sigmoid=2.0, label_gain=[0, 1, 1, 7, 3]))
    obj.set_query(qb, labels)
    dense = Dense(qb, labels, obj)
    g, h = obj.get_gradients(jnp.asarray(score), None, None)
    want_g, want_h = dense.lambdas(score)
    close(g, dense.by_row(want_g))
    close(h, dense.by_row(want_h))


def test_position_bias_carry_equals_the_dense_one():
    qb, labels, score = queries(7)
    rng = np.random.RandomState(1)
    positions = rng.randint(0, 10, len(labels))
    reg = 0.5
    obj = objectives.LambdarankNDCG(Config(
        objective="lambdarank", lambdarank_position_bias_regularization=reg))
    obj.set_query(qb, labels)
    obj.set_positions(positions)
    dense = Dense(qb, labels, obj)
    pos_pad = positions[dense.idx]
    bias = np.zeros(10, np.float32)
    state = obj.fused_state()
    for _ in range(3):
        g, h = obj.get_gradients(jnp.asarray(score), None, None)
        # the dense form: bias added by lane, lambdas, Newton refit
        s_pad = dense.padded(score) + jnp.where(
            jnp.asarray(dense.mask), jnp.asarray(bias)[pos_pad], 0.0)
        want_g, want_h = dense_lambdas(
            s_pad, dense.labels, jnp.asarray(dense.mask),
            jnp.asarray(obj.label_gain, jnp.float32),
            jnp.asarray(dense.inv_mdcg, jnp.float32), obj.sigmoid,
            obj.truncation, obj.norm)
        gp = np.bincount(pos_pad.ravel(), np.asarray(want_g).ravel(), 10)
        hp = np.bincount(pos_pad.ravel(), np.asarray(want_h).ravel(), 10)
        bias = (bias - (gp + reg * bias) / (hp + reg + 1e-9)).astype(
            np.float32)
        close(g, dense.by_row(want_g))
        close(h, dense.by_row(want_h))
        np.testing.assert_allclose(np.asarray(obj.pos_bias), bias, rtol=1e-4,
                                   atol=1e-5)
        # the fused-state protocol: pure, the carry in and out
        fg, fh, state = obj.fused_gradients(jnp.asarray(score), None, None,
                                            state)
        np.testing.assert_array_equal(np.asarray(fg), np.asarray(g))
        np.testing.assert_array_equal(np.asarray(state),
                                      np.asarray(obj.pos_bias))
    assert np.abs(bias).max() > 0.1


def test_bucketed_xendcg_equals_the_dense_one():
    qb, labels, score = queries(9)
    obj = objectives.RankXENDCG(Config(objective="rank_xendcg",
                                       objective_seed=11))
    obj.set_query(qb, labels)
    dense = Dense(qb, labels, objectives.LambdarankNDCG(
        Config(objective="lambdarank")))
    for it in range(2):
        g, h = obj.get_gradients(jnp.asarray(score), None, None)
        # a uniform a row, whatever the layout
        u = jax.random.uniform(jax.random.PRNGKey(11 + it), score.shape,
                               dtype=jnp.float32)
        want_g, want_h = objectives._xendcg_query(
            dense.padded(score), dense.labels, jnp.asarray(dense.mask),
            dense.padded(u))
        close(g, dense.by_row(want_g))
        close(h, dense.by_row(want_h))
        # a query's lambdas sum to 0 and its hessians are a softmax's
        assert abs(float(np.asarray(g)[qb[2]:qb[3]].sum())) < 1e-5
    assert obj._iter == 2


# ---------------------------------------------------------------------------
# through the booster
# ---------------------------------------------------------------------------

def ranking_set(seed=0, n_queries=30):
    rng = np.random.RandomState(seed)
    group = rng.randint(1, 60, n_queries)
    n = int(group.sum())
    X = rng.randn(n, 5)
    y = np.clip(np.round(X[:, 0] + 0.5 * rng.randn(n)), 0, 4)
    return lgb.Dataset(X, label=y, group=group), n


@pytest.mark.parametrize("objective", ["lambdarank", "rank_xendcg"])
def test_the_gradient_step_compiles_once_over_ten_rounds(objective):
    ds, _ = ranking_set()
    bst = lgb.Booster({"objective": objective, "verbosity": -1,
                       "num_leaves": 7, "min_data_in_leaf": 2,
                       "tree_growth_mode": "rounds",
                       "fused_training": False}, ds)
    step = (objectives._lambdarank_step if objective == "lambdarank"
            else objectives._xendcg_step)
    bst.update()
    traced = step._cache_size()
    with CompileCounter() as cc:
        for _ in range(9):
            bst.update()
    assert step._cache_size() == traced
    assert cc.compiles - cc.cache_hits == 0
    assert bst.num_trees() == 10


def test_the_rounds_grower_counts_the_rank_work_a_tree():
    obs.reset()
    ds, n = ranking_set(1)
    bst = lgb.Booster({"objective": "lambdarank", "verbosity": -1,
                       "num_leaves": 7, "min_data_in_leaf": 2,
                       "tree_growth_mode": "rounds",
                       "fused_training": False}, ds)
    for _ in range(3):
        bst.update()
    assert len(bst._gbdt.models) == 3  # the flush counts
    rows, lanes, pairs = bst._gbdt.objective.rank_work
    assert rows == n and n <= lanes < 2 * n and pairs > lanes
    assert obs.counter("train_rank_rows_total").value == 3 * rows
    assert obs.counter("train_rank_lanes_total").value == 3 * lanes
    assert obs.counter("train_rank_pairs_total").value == 3 * pairs
    # another objective counts none
    obs.reset()
    rng = np.random.RandomState(0)
    X = rng.randn(300, 4)
    plain = lgb.Booster({"objective": "binary", "verbosity": -1,
                         "num_leaves": 4, "tree_growth_mode": "rounds"},
                        lgb.Dataset(X, label=(X[:, 0] > 0).astype(float)))
    plain.update()
    assert len(plain._gbdt.models) == 1
    assert obs.counter("train_rank_rows_total").value == 0


def test_the_rank_scopes_reach_the_step_s_hlo():
    qb, labels, score = queries()
    obj = objectives.LambdarankNDCG(Config(objective="lambdarank"))
    obj.set_query(qb, labels)
    text = objectives._lambdarank_step.lower(
        jnp.asarray(score), obj._layout, obj._inv_mdcg, obj._gain, None, None,
        sigmoid=1.0, truncation=30, norm=True,
        pos_reg=0.0).as_text(debug_info=True)
    for scope in ("rank.gather", "rank.sort", "rank.pairs", "rank.scatter"):
        assert scope in profiling.DEVICE_PHASES
        assert f"/{scope}/" in text, scope
    # no tensor of a query's rows squared: the widest pair block is the
    # truncation window against the bucket's width
    assert f"x{LONGEST}x" not in text and "x512x512x" not in text
    assert "x30x512x" in text
