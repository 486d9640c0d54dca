"""Comparing the variables two different XLA programs trained from one start.

Program boundaries (segments, chunked staging) and layouts (packed residuals,
composed or folded kernels) change XLA's reduction order, and since PRs 27 and
29 the reference layout reassociates too, so bitwise equality ACROSS programs is
a property of a compiler version, not of the fold (ROADMAP D4). What the
reassociation does to a tree after Adam, by class of leaf:

- a conv bias that feeds straight into a BatchNorm has a true gradient of ~0
  (BN cancels an additive bias), and Adam, scale-invariant, turns the
  rounding noise of two programs into full steps of either sign: bounded by
  ``lr * steps``, not by a float tolerance;
- the BatchNorm running mean behind such a bias follows it, damped by
  ``1 - momentum`` a step;
- every other leaf moves by a few float32 ulps.
"""

import jax
import numpy as np


def _bn_shadowed_bias(key: str) -> bool:
    return key.endswith("'bias']") and any(
        s in key for s in ("stem_conv", "_sep", "_convT")
    )


def _running_mean(key: str) -> bool:
    return "'batch_stats'" in key and key.endswith("'mean']")


def _leaf_pairs(got, want):
    """``(key, got leaf, want leaf)`` of two trees of one structure."""
    gl = jax.tree_util.tree_leaves_with_path(got)
    wl = jax.tree_util.tree_leaves(want)
    assert len(gl) == len(wl)
    for (path, g), w in zip(gl, wl):
        yield jax.tree_util.keystr(path), np.asarray(g), np.asarray(w)


def assert_trees_match(
    got, want, atol=2e-5, *, shadowed_bias_atol=5e-3, running_mean_atol=None
):
    """``got`` against ``want`` leaf by leaf at ``atol``, except the two
    classes above, which get their own bounds (``running_mean_atol`` defaults
    to ``atol``)."""
    if running_mean_atol is None:
        running_mean_atol = atol
    for key, g, w in _leaf_pairs(got, want):
        if _bn_shadowed_bias(key):
            leaf_atol = shadowed_bias_atol
        elif _running_mean(key):
            leaf_atol = running_mean_atol
        else:
            leaf_atol = atol
        np.testing.assert_allclose(g, w, atol=leaf_atol, err_msg=key)


def assert_trees_equal(got, want):
    """Bitwise, leaf by leaf, naming the first leaf that differs."""
    for key, g, w in _leaf_pairs(got, want):
        np.testing.assert_array_equal(g, w, err_msg=key)
