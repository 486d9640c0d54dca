"""Every model family's round program, pinned: the sha256 of its lowered
StableHLO on a (1,1) mesh at its tests' widths. A change that means to move
one of these programs replaces its pin here and says why below; one that does
not, and moves a pin, has changed a program it did not mean to."""

import hashlib

import jax
import jax.numpy as jnp
import pytest

from fedcrack_tpu.configs import MlaMoeConfig, ModelConfig
from fedcrack_tpu.parallel import build_federated_round, make_mesh

from test_gdn_moe import small_config as small_gdn_config
from test_lfm2_moe import small_config as small_lfm2_config
from test_looped_lm import small_config as small_looped_config
from test_mla_moe import small_config as small_mla_config
from test_sdar_moe import small_config as small_sdar_config

S = jax.ShapeDtypeStruct


def _tokens(seq_len):
    return S((1, 2, 2, seq_len), jnp.int32), S((1, 2, 2, seq_len), jnp.float32)


def _images(size):
    return S((1, 2, 2, size, size, 3), jnp.uint8), S((1, 2, 2, size, size, 1), jnp.uint8)


def _joyai():
    """The latent-attention model at its tests' widths, in its published bf16."""
    small = small_mla_config()
    return MlaMoeConfig(**{k: getattr(small, k) for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "intermediate_size", "moe_intermediate_size", "n_routed_experts",
        "num_experts_per_tok", "first_expert", "experts_held", "vocab_held", "seq_len",
    )})


# name: (configuration, the staged pair's shapes, learning rate, pin).
#
# The four expert models' pins (``sdar``, ``joyai``, ``qwen3next``, ``lfm2``)
# move with the held-expert layer they share (``models/moe_layers.py``). They
# were last replaced when the layer kept one XLA form for its row arrays of
# any length (at these widths the budget is every pair, and the layer's
# gather and float32 per-token sum are now the budget's) and returned its
# counters as one record (``EXPERT_COUNTERS``). The U-Nets' and the looped
# model's share nothing with it and hold.
PINNED = {
    "sdar": (
        small_sdar_config(compute_dtype="bfloat16"), _tokens(32), 1e-5,
        "f77dff473fdcfa44c1e3e0297fa902f7f698574bf21de339bf9e960e1e430b51",
    ),
    "joyai": (_joyai(), _tokens(32), 1e-5, "d836c8d64eb0178330a360d3bfbd4987babd8c4bfea3d746f02946eff89d17fd"),
    "qwen3next": (
        small_gdn_config(), _tokens(128), 1e-5, "9634eb5833b672913530ce50d3031a9bf433050a0306495a080012628ca0e2a2",
    ),
    "lfm2": (
        small_lfm2_config(), _tokens(128), 1e-5, "167b01cef8947608dab479a22d1ef7049f4d56932b8571a707a329221588225e",
    ),
    "ouro": (
        small_looped_config(), _tokens(128), 1e-5, "ad7544353caba43138e16a5f1fa2f61169942910eb941b4853f813d46f7842d7",
    ),
    "unet32": (
        ModelConfig(img_size=32, compute_dtype="bfloat16"), _images(32), 1e-3,
        "85fd6528a14e32005ee63bb8147cc1a47813e1eda931e5998a6ea6f59412cb02",
    ),
    "unet64": (
        ModelConfig(img_size=64, compute_dtype="bfloat16"), _images(64), 1e-3,
        "101a429fda5cfc4ea7ddb76ed46f9368af84872857cc01f3e027482d3f48fd82",
    ),
}


def _find_jitted(fn, depth=0):
    """The jitted program a round function closes over."""
    for cell in fn.__closure__ or ():
        v = cell.cell_contents
        if hasattr(v, "lower") and hasattr(v, "trace"):
            return v
        if callable(v) and getattr(v, "__closure__", None) and depth < 3:
            found = _find_jitted(v, depth + 1)
            if found is not None:
                return found
    return None


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_round_program_is_pinned(name):
    config, data, lr, pinned = PINNED[name]
    round_fn = build_federated_round(make_mesh(1, 1), config, learning_rate=lr, local_epochs=1)
    variables = jax.eval_shape(lambda: round_fn.task.init(jax.random.key(0)))
    one = S((1,), jnp.float32)
    text = _find_jitted(round_fn).lower(variables, *data, one, one).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == pinned
