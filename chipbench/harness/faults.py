"""The faults a training cell can have, planted in the timed path.  The tests
plant them at toy size and see ``correct`` come out false;
``tools/readings.py`` plants them on the chip at the cell's own size to read
what each moves."""

from __future__ import annotations


def state_unchanged(bst) -> None:
    """A step that returns its state unchanged: the tree is grown, the score
    is not moved."""
    import jax.numpy as jnp

    g = bst._gbdt
    step = g.train_one_iter

    def broken(*a, **k):
        before = jnp.array(g._score, copy=True)
        out = step(*a, **k)
        g._score = before
        return out

    g.train_one_iter = broken


def half_batch(bst) -> None:
    """Half of the rows left out of every tree, the sums taken over the
    rest."""
    import jax.numpy as jnp

    g = bst._gbdt
    n = int(g._score.shape[0])
    keep = jnp.arange(n) % 2 == 0
    ones = jnp.ones((n,), jnp.float32)
    g._bagging_mask = lambda: (keep, ones)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch}
