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
  ``1 - momentum`` a step: channel by channel its gap is the bias's gap times
  ``(1 - momentum) * steps`` or less (read at half that over 4 steps);
- every other leaf moves by a few float32 ulps.
"""

import jax
import numpy as np

from fedcrack_tpu.models.resunet import _BN_MOMENTUM


def bn_shadowed_bias(key: str) -> bool:
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


def _followed_bias_gaps(got, want):
    """BatchNorm name -> the measured gap, a channel, of the shadowed bias
    before it (``stem_bn`` <- ``stem_conv``, ``x_bnK`` <- ``x_sepK`` or
    ``x_convTK``)."""
    gaps = {}
    for key, g, w in _leaf_pairs(got, want):
        if bn_shadowed_bias(key):
            conv = key.split("']['")[1]
            for stem in ("_convT", "_sep", "_conv"):
                conv = conv.replace(stem, "_bn")
            gaps[conv] = np.abs(g - w)
    return gaps


def assert_trees_match(
    got, want, atol=2e-5, *, shadowed_bias_atol=5e-3, running_mean_atol=None, steps=0
):
    """``got`` against ``want`` leaf by leaf at ``atol``, except the two
    classes above, which get their own bounds (``running_mean_atol`` defaults
    to ``atol``). Where the trees are ``steps`` optimizer steps from their
    common start, a running mean is allowed, beyond its bound, what the
    measured gap of the bias before it has moved it: ``(1 - momentum) *
    steps * gap``, channel by channel."""
    if running_mean_atol is None:
        running_mean_atol = atol
    followed = _followed_bias_gaps(got, want) if steps else {}
    for key, g, w in _leaf_pairs(got, want):
        if bn_shadowed_bias(key):
            leaf_atol = shadowed_bias_atol
        elif _running_mean(key):
            leaf_atol = running_mean_atol
            if steps:
                moved = (1 - _BN_MOMENTUM) * steps * followed[key.split("']['")[1]]
                g = w + np.sign(g - w) * np.maximum(np.abs(g - w) - moved, 0)
        else:
            leaf_atol = atol
        np.testing.assert_allclose(g, w, atol=leaf_atol, err_msg=key)


def assert_trees_equal(got, want):
    """Bitwise, leaf by leaf, naming the first leaf that differs."""
    for key, g, w in _leaf_pairs(got, want):
        np.testing.assert_array_equal(g, w, err_msg=key)
