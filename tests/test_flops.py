"""The analytic FLOPs model must agree with XLA's own HLO cost analysis —
otherwise every MFU number built on it is fiction."""

import jax
import jax.numpy as jnp
import pytest

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.models import ResUNet
from fedcrack_tpu.obs.flops import (
    TRAIN_STEP_FLOPS_MULTIPLIER,
    device_peak_flops,
    mfu,
    resunet_forward_flops,
    train_step_flops,
)


def _composed_separable_surplus(cfg: ModelConfig, batch: int) -> float:
    """FLOPs the model EXECUTES beyond the canonical count since PR 29: below
    128 input channels a `SeparableConv` runs as one composed conv
    (resunet.py, "The encoder's separable convolutions"). Multiply-adds a
    pixel: `enc0_sep1` a `[3,4,C,2F]` kernel over two pixels, `enc0_sep2` a
    `[3,3,2F,2F]` one over two pixels, `enc1_sep1` `[3,3,F,F1]`; each against
    the separable `9*Cin + Cin*Cout` that `resunet_forward_flops` charges."""
    c, (f, f1) = cfg.stem_features, cfg.encoder_features[:2]
    s = cfg.img_size // 2
    enc0 = (12 * c * f - (9 * c + c * f)) + (18 * f * f - (9 * f + f * f))
    enc1 = 9 * f * f1 - (9 * f + f * f1)
    return 2.0 * batch * (s * s * enc0 + (s // 2) ** 2 * enc1)


def _packed_tail_surplus(cfg: ModelConfig, batch: int) -> float:
    """FLOPs the model EXECUTES beyond the canonical count since PR 31: the
    last decoder block's `convT2` and the head read the packed `[N,h,w,4C]`
    (resunet.py, "The decoder's upsample") through a `[3,3,4C,4C]` kernel with
    36 of 144 blocks filled and a block-diagonal `[1,1,4C,4]` one: four times
    the multiply-adds `resunet_forward_flops` charges for each, on a quarter
    of the pixels at four times the columns."""
    f, s = cfg.decoder_features[-1], cfg.img_size // 2
    return 3.0 * 2.0 * batch * s * s * (9 * f * f + f * cfg.num_classes)


def test_forward_flops_match_xla_cost_analysis():
    # Flagship shape (convs dominate; at tiny shapes XLA's accounting of
    # padding/transpose-conv edges diverges more). XLA counts the program
    # that runs, the analytic model the network as published, so the known
    # surplus of the composed separable convolutions and of the packed decoder
    # tail is added before the two are compared; `resunet_forward_flops`
    # itself stays canonical (its numbers did not move in PRs 27, 29 or 31).
    cfg = ModelConfig()
    model = ResUNet(config=cfg)
    variables = model.init(
        jax.random.key(0), jnp.zeros((1, *cfg.input_shape)), train=False
    )
    batch = 4
    images = jnp.zeros((batch, *cfg.input_shape))

    def fwd(v, x):
        return model.apply(v, x, train=False)

    analysis = jax.jit(fwd).lower(variables, images).compile().cost_analysis()
    if isinstance(analysis, list):
        analysis = analysis[0]
    xla_flops = float(analysis["flops"])
    analytic = (
        resunet_forward_flops(cfg, batch)
        + _composed_separable_surplus(cfg, batch)
        + _packed_tail_surplus(cfg, batch)
    )
    assert 0.75 * xla_flops <= analytic <= 1.25 * xla_flops, (
        f"analytic {analytic:.3e} vs XLA {xla_flops:.3e}"
    )


def test_flops_scale_with_resolution_and_batch():
    f128 = resunet_forward_flops(ModelConfig(img_size=128))
    f256 = resunet_forward_flops(ModelConfig(img_size=256))
    # Fully convolutional: 4x the pixels is 4x the FLOPs, exactly.
    assert f256 == pytest.approx(4.0 * f128)
    assert resunet_forward_flops(ModelConfig(), batch_size=16) == pytest.approx(
        16.0 * f128
    )


def test_train_step_is_forward_times_multiplier():
    cfg = ModelConfig(img_size=32)
    assert train_step_flops(cfg, 8) == pytest.approx(
        TRAIN_STEP_FLOPS_MULTIPLIER * resunet_forward_flops(cfg, 8)
    )


def test_flops_are_canonical_across_layouts():
    """MFU-honesty invariant (round 6): the layout transforms re-express the
    same math with zero-extended kernels, and the FLOPs model must charge
    every layout the REFERENCE topology — an A/B whose transformed variant
    got billed its structural-zero MACs would report inflated MFU."""
    for img in (32, 128):
        ref = train_step_flops(ModelConfig(img_size=img), 4)
        for stem, res in (
            ("s2d", "reference"),
            ("s2d_full", "reference"),
            ("reference", "packed"),
            ("s2d", "packed"),
        ):
            cfg = ModelConfig(img_size=img, stem_layout=stem, res_layout=res)
            assert train_step_flops(cfg, 4) == ref


def test_decoder_flops_are_canonical_upsample_then_conv():
    """PR 27: the decoder executes `dec{i}_convT1` as one low-resolution conv
    into 4x the channels and `dec{i}_res` on a quarter of the pixels, but MFU's
    numerator stays the topology Keras states: every conv of a block at the
    block's own (upsampled) resolution. 256 px, a sample, forward; `dec3` is
    the 973 MFLOP of ISSUE 27 (604 M + 302 M + 67 M)."""
    cfg = ModelConfig(img_size=256)
    blocks = [  # convT1 + convT2 + res; dec0 reads the 16x16x256 bottleneck
        2.0 * s * s * (9 * cin * cout + 9 * cout * cout + cin * cout)
        for s, cin, cout in ((16, 256, 256), (32, 256, 128), (64, 128, 64), (128, 64, 32))
    ]
    assert blocks[3] == 973_078_528.0
    without_decoder = resunet_forward_flops(
        ModelConfig(img_size=256, decoder_features=())
    ) - 2.0 * 8 * 8 * 256  # that model's head: 1x1, 256 -> 1, on the 8x8 it defers to
    head = 2.0 * 128 * 128 * 32
    assert resunet_forward_flops(cfg) == without_decoder + sum(blocks) + head


def test_peak_flops_known_and_unknown_kind():
    class _V5e:
        device_kind = "TPU v5 lite"

    assert device_peak_flops(_V5e()) == pytest.approx(197e12)
    assert mfu(
        step_time_s=0.010, flops_per_step=197e12 * 0.010 * 0.5, device=_V5e()
    ) == pytest.approx(0.5)
    # The CPU test backend has no known MXU peak: MFU must be None, not a lie.
    assert device_peak_flops() is None
    assert mfu(0.010, 1e9) is None
