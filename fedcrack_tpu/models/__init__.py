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

# The model families by their published ``model_type``: each one's
# configuration class and model class. ``get_model`` builds by name, and
# ``tasks`` finds a configuration's model and its task here.
FAMILIES = {
    # The crack U-Net (models/resunet.py).
    "resunet": (ModelConfig, ResUNet),
    # A block-diffusion mixture-of-experts language model (models/sdar_moe.py).
    "sdar_moe": (SdarMoeConfig, SdarMoe),
    # A latent-attention mixture-of-experts causal language model (models/mla_moe.py).
    "joyai_llm_flash": (MlaMoeConfig, MlaMoe),
    # A hybrid linear-attention mixture-of-experts causal language model (models/gdn_moe.py).
    "qwen3_next": (GdnMoeConfig, GdnMoe),
    # A looped causal language model whose layers run several times a token (models/looped_lm.py).
    "ouro": (LoopedLmConfig, LoopedLm),
    # A hybrid of gated short convolutions and attention with sparse experts (models/lfm2_moe.py).
    "lfm2_moe": (Lfm2MoeConfig, Lfm2Moe),
}
_ALIASES = {
    "unet": "resunet",
    # Legacy alias: the reference's advertised-but-vestigial model type string.
    "mobilenet_v2": "resunet",
}


def get_model(
    name: str = "resunet",
    config: ModelConfig | SdarMoeConfig | MlaMoeConfig | GdnMoeConfig | LoopedLmConfig | Lfm2MoeConfig | None = None,
) -> ResUNet | SdarMoe | MlaMoe | GdnMoe | LoopedLm | Lfm2Moe:
    """Build a model by registry name (case-insensitive, legacy aliases ok)."""
    key = _ALIASES.get(name.lower(), name.lower())
    if key not in FAMILIES:
        raise KeyError(f"unknown model type {name!r}; known: {sorted([*FAMILIES, *_ALIASES])}")
    config_class, model = FAMILIES[key]
    return model(config=config or config_class())


__all__ = [
    "FAMILIES", "GdnMoe", "Lfm2Moe", "LoopedLm", "MlaMoe", "ResUNet", "SdarMoe", "depth_to_space", "get_model",
    "space_to_depth",
]
