"""Model registry.

The reference advertises the (vestigial) model type string "mobilenet_v2"
(reference: fl_server.py:75) while server and client actually share one
architecture — the residual U-Net (reference: client_fit_model.py:92-150,
SURVEY.md §2.2(3)). The registry accepts the legacy alias so a reference
client's handshake still resolves to the real model.
"""

from __future__ import annotations

from fedcrack_tpu.configs import GdnMoeConfig, Lfm2MoeConfig, LoopedLmConfig, MlaMoeConfig, ModelConfig, SdarMoeConfig
from fedcrack_tpu.models.gdn_moe import GdnMoe
from fedcrack_tpu.models.lfm2_moe import Lfm2Moe
from fedcrack_tpu.models.looped_lm import LoopedLm
from fedcrack_tpu.models.mla_moe import MlaMoe
from fedcrack_tpu.models.resunet import ResUNet, depth_to_space, space_to_depth
from fedcrack_tpu.models.sdar_moe import SdarMoe

_ALIASES = {
    "resunet": "resunet",
    "unet": "resunet",
    # Legacy alias: the reference's advertised-but-vestigial model type string.
    "mobilenet_v2": "resunet",
    # The second family: a block-diffusion mixture-of-experts language model
    # (models/sdar_moe.py), under its published model_type.
    "sdar_moe": "sdar_moe",
    # The third: a latent-attention mixture-of-experts causal language model
    # (models/mla_moe.py), under its published model_type.
    "joyai_llm_flash": "joyai_llm_flash",
    # The fourth: a hybrid linear-attention mixture-of-experts causal language
    # model (models/gdn_moe.py), under its published model_type.
    "qwen3_next": "qwen3_next",
    # The fifth: a looped causal language model whose layers run several times
    # a token (models/looped_lm.py), under its published model_type.
    "ouro": "ouro",
    # The sixth: a hybrid of gated short convolutions and attention with
    # sparse experts (models/lfm2_moe.py), under its published model_type.
    "lfm2_moe": "lfm2_moe",
}


def get_model(
    name: str = "resunet",
    config: ModelConfig | SdarMoeConfig | MlaMoeConfig | GdnMoeConfig | LoopedLmConfig | Lfm2MoeConfig | None = None,
) -> ResUNet | SdarMoe | MlaMoe | GdnMoe | LoopedLm | Lfm2Moe:
    """Build a model by registry name (case-insensitive, legacy aliases ok)."""
    key = _ALIASES.get(name.lower())
    if key is None:
        raise KeyError(f"unknown model type {name!r}; known: {sorted(_ALIASES)}")
    if key == "sdar_moe":
        return SdarMoe(config=config or SdarMoeConfig())
    if key == "joyai_llm_flash":
        return MlaMoe(config=config or MlaMoeConfig())
    if key == "qwen3_next":
        return GdnMoe(config=config or GdnMoeConfig())
    if key == "ouro":
        return LoopedLm(config=config or LoopedLmConfig())
    if key == "lfm2_moe":
        return Lfm2Moe(config=config or Lfm2MoeConfig())
    return ResUNet(config=config or ModelConfig())


__all__ = ["GdnMoe", "Lfm2Moe", "LoopedLm", "MlaMoe", "ResUNet", "SdarMoe", "depth_to_space", "get_model", "space_to_depth"]
