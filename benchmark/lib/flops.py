"""Operations one SGD step of the crack U-Net needs, from its shapes.

The benchmark's own copy of the arithmetic in ``fedcrack_tpu/obs/flops.py``
(kept here so that no later PR can move the yardstick): 2 operations a
multiply-add over every convolution, where the published network runs it;
elementwise work is left out. The backward pass is two convolution-shaped
passes, so a training step is three forwards. Recomputed operations never
count.
"""

from __future__ import annotations


def _conv(out_hw: int, c_in: int, c_out: int, k: int) -> float:
    return 2.0 * out_hw * out_hw * c_out * (k * k * c_in)


def forward_flops(model: dict, batch: int) -> float:
    s = model["img_size"] // 2
    c = model["stem_features"]
    total = _conv(s, model["in_channels"], c, 3)
    for feat in model["encoder_features"]:
        total += 2.0 * s * s * c * 9 + _conv(s, c, feat, 1)
        total += 2.0 * s * s * feat * 9 + _conv(s, feat, feat, 1)
        s //= 2
        total += _conv(s, c, feat, 1)
        c = feat
    for feat in model["decoder_features"]:
        total += _conv(s, c, feat, 3) + _conv(s, feat, feat, 3)
        # The 1x1 residual and the head commute with nearest upsampling, so
        # they are needed at the lower resolution only.
        total += _conv(s, c, feat, 1)
        s *= 2
        c = feat
    total += _conv(s // 2, c, model["num_classes"], 1)
    return total * batch


def train_step_flops(model: dict, batch: int) -> float:
    return 3.0 * forward_flops(model, batch)
