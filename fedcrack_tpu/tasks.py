"""What a federated round trains: the task the round program takes.

The round program (``parallel/fedavg_mesh.py``) and the host step
(``train/local.py``) own the scan, the optimizer, FedProx, the fold and the
staging; a task owns what differs between model families: how a staged
batch unpacks, the train-mode forward, the loss with its statistics, how
those reduce over a client's shards, steps and epochs, and what the round
reports. A task is a frozen, hashable object chosen by the class of the
model configuration (``task_for``) and by nothing else.

- ``init(rng)`` -> ``{"params", "batch_stats"}`` (``batch_stats`` is the
  model's non-trained state: BatchNorm moments, or ``{}``).
- ``unpack(batch)`` -> ``(inputs, targets)`` from one step's staged pair.
- ``apply(params, state, inputs)`` -> ``(outputs, new_state)``, train mode.
- ``loss_and_metrics(outputs, targets, pos_weight)`` -> dict with ``"loss"``
  and every statistic ``metric_reductions`` names.
- ``metric_reductions``: ``((name, "mean" | "sum"), ...)`` in the order the
  step emits them; ``"mean"`` statistics average over a client's shards and
  over an epoch's steps, ``"sum"`` statistics add up.
- ``round_metrics(last)``: what a round reports, from the last epoch's
  reduced statistics (the builders add ``active`` and ``step_loss``).
- ``validate(data_a)``: host-side check of a staged slab's layout.
- ``step_flops(batch)``: operations one training step needs (forward and
  backward; recomputed operations never count).
- ``check_vma``: whether the round's ``shard_map`` may track which mesh axes
  every value varies over (``jax.shard_map(check_vma=...)``). Off, autodiff
  inside the program no longer sums a replicated parameter's gradient over
  the client's shards, and the step does it itself.
- ``donate_variables``: whether the monolithic round program takes over the
  buffers of the incoming global model for the outgoing one.
- ``block_scope``, ``model_scope``, ``program_name``: what
  ``obs/devtrace.py`` needs to name the round program's instructions: a
  regular expression that matches a part of an instruction's ``op_name``
  path where it is one of the model's blocks, the scope that encloses the
  blocks (``None``: anywhere), and what the compiled round program's module
  name holds.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from fedcrack_tpu.configs import GdnMoeConfig, Lfm2MoeConfig, LoopedLmConfig, MlaMoeConfig, ModelConfig, SdarMoeConfig


@dataclasses.dataclass(frozen=True)
class SegmentationTask:
    """The crack U-Net on ``(images, masks)``: weighted BCE, pixel accuracy
    and IoU counts; BatchNorm moments as state, synced over ``bn_axis_name``."""

    config: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    bn_axis_name: str | None = None

    metric_reductions = (("pixel_acc", "mean"), ("iou_inter", "sum"), ("iou_union", "sum"))
    check_vma = True
    donate_variables = False
    block_scope = r"^(stem|enc[0-9]+|dec[0-9]+|head)$"
    model_scope = "ResUNet"  # flax's own scope around the module's __call__
    program_name = "client_fit"

    @property
    def model(self):
        from fedcrack_tpu.models.resunet import ResUNet

        return ResUNet(config=self.config, bn_axis_name=self.bn_axis_name)

    def init(self, rng: jax.Array) -> dict:
        dummy = jnp.zeros((1, *self.config.input_shape), jnp.float32)
        variables = self.model.init(rng, dummy, train=False)
        return {"params": variables["params"], "batch_stats": variables["batch_stats"]}

    def unpack(self, batch):
        from fedcrack_tpu.data.pipeline import as_model_batch

        return as_model_batch(*batch)

    def apply(self, params, state, inputs):
        logits, mutated = self.model.apply(
            {"params": params, "batch_stats": state},
            inputs,
            train=True,
            mutable=["batch_stats"],
        )
        return logits, mutated["batch_stats"]

    def loss_and_metrics(self, outputs, targets, pos_weight=None) -> dict:
        from fedcrack_tpu.ops.pallas_bce import fused_segmentation_metrics

        # One fused pass for BCE + all statistics (Pallas kernel on TPU,
        # XLA reference elsewhere — ops/pallas_bce.py).
        return fused_segmentation_metrics(outputs, targets, pos_weight=pos_weight)

    def round_metrics(self, last: dict) -> dict:
        from fedcrack_tpu.ops.losses import iou_from_counts

        return {
            "loss": last["loss"],
            "pixel_acc": last["pixel_acc"],
            "iou": iou_from_counts(last["iou_inter"], last["iou_union"]),
        }

    def validate(self, images) -> None:
        in_ch = self.config.in_channels
        packed_ok = self.config.stem_layout != "reference"
        ch = images.shape[-1]
        allowed = (in_ch, 4 * in_ch) if packed_ok else (in_ch,)
        if ch not in allowed:
            raise ValueError(
                f"images carry {ch} channels; stem_layout="
                f"{self.config.stem_layout!r} accepts {allowed} "
                "(4x = space_to_depth-packed staging)"
            )

    def step_flops(self, batch: int) -> float:
        from fedcrack_tpu.obs.flops import train_step_flops

        return float(train_step_flops(self.config, batch))


@dataclasses.dataclass(frozen=True)
class TextDiffusionTask:
    """Block-diffusion training of one chip's share of the ``sdar_moe``
    model on ``(ids int32 [B, L], weight float32 [B, L])``: ``weight`` is 0
    where a token stays and ``1/t`` of its block where it is masked, so the
    noise is data. Loss ``sum_i weight_i CE(logits_i, ids_i) / (B L)``."""

    config: SdarMoeConfig = dataclasses.field(default_factory=SdarMoeConfig)
    kernels: str | None = None

    # JAX's splash-attention and megablox kernels declare no varying axes
    # for their results, which the tracking refuses.
    check_vma = False
    # 1.8 GB of float32 at the published widths: a second copy for the
    # outgoing model is what no longer fits beside Adam's state.
    donate_variables = True
    # The kinds of block, summed over the layers that hold them.
    block_scope = r"^(embed|attn_proj|blockdiff_attn|router|moe_dispatch|moe_experts|moe_combine|lm_head)$"
    model_scope = None  # plain functions: a block counts wherever it stands
    program_name = "client_fit"

    @property
    def model(self):
        from fedcrack_tpu.models.sdar_moe import SdarMoe

        return SdarMoe(config=self.config, kernels=self.kernels)

    @property
    def metric_reductions(self) -> tuple:
        return (("masked_tokens", "sum"), ("masked_hits", "sum"), *self.model.counters)

    def init(self, rng: jax.Array) -> dict:
        return {"params": self.model.init(rng), "batch_stats": {}}

    def unpack(self, batch):
        ids, weight = batch
        ids = ids.astype(jnp.int32)
        weight = weight.astype(jnp.float32)
        return (ids, weight > 0.0), (ids, weight)

    def apply(self, params, state, inputs):
        ids, masked = inputs
        return self.model.apply(params, ids, masked), state

    def loss_and_metrics(self, outputs, targets, pos_weight=None) -> dict:
        del pos_weight  # the segmentation loss's class weight
        _, weight = targets
        masked = (weight > 0.0).astype(jnp.float32)
        stats = {
            "loss": jnp.sum(weight * outputs["nll"]) / weight.size,
            "masked_tokens": jnp.sum(masked),
            "masked_hits": jnp.sum(masked * outputs["hit"]),
        }
        for name, _ in self.model.counters:
            stats[name] = outputs[name]
        return stats

    def round_metrics(self, last: dict) -> dict:
        metrics = {
            "loss": last["loss"],
            "masked_tokens": last["masked_tokens"],
            "masked_acc": last["masked_hits"] / jnp.maximum(last["masked_tokens"], 1.0),
        }
        for name, _ in self.model.counters:
            metrics[name] = last[name]
        return metrics

    def validate(self, ids) -> None:
        if ids.shape[-1] != self.config.seq_len:
            raise ValueError(
                f"sequences of {ids.shape[-1]} tokens; the configuration's are {self.config.seq_len}"
            )

    def step_flops(self, batch: int) -> float:
        """Matrix products of one step, 2 operations a multiply-add, forward
        times three; held experts at their expected ``top_k * experts_held /
        num_experts`` pairs a position, allowed scores only."""
        c = self.config
        positions = 2.0 * c.seq_len * batch
        q_out = c.num_attention_heads * c.head_dim
        kv_out = c.num_key_value_heads * c.head_dim
        proj = 2.0 * positions * c.hidden_size * (2 * q_out + 2 * kv_out)
        allowed = batch * (c.seq_len * (c.seq_len + c.block_length)) * 1.0
        scores = 2.0 * 2.0 * allowed * q_out
        router = 2.0 * positions * c.hidden_size * c.num_experts
        pairs = positions * c.num_experts_per_tok * c.experts_held / c.num_experts
        experts = 2.0 * pairs * 3 * c.hidden_size * c.moe_intermediate_size
        head = 2.0 * c.seq_len * batch * c.hidden_size * c.vocab_held
        return 3.0 * (c.num_hidden_layers * (proj + scores + router + experts) + head)


@dataclasses.dataclass(frozen=True)
class CausalLMTask:
    """Next-token training of one chip's share of a causal language model
    (``joyai_llm_flash``: ``models/mla_moe.py``; ``qwen3_next``:
    ``models/gdn_moe.py``; ``ouro``: ``models/looped_lm.py``; ``lfm2_moe``:
    ``models/lfm2_moe.py``; the class of ``config`` says which) on ``(ids
    int32 [B, L], weight float32 [B, L])``, the text task's staged pair
    without its noise: ``weight`` is what a token counts as a target (1; 0
    for padding). Position ``i`` is scored against
    token ``i + 1`` and, by a model that has a multi-token-prediction module,
    against token ``i + 2``: ``loss = CE_next [+ mtp_loss_weight x CE_mtp]``,
    each the weighted sum over a batch's positions over the positions that
    have such a target, ``B (L - 1)`` and ``B (L - 2)``; a model whose
    training objective a position is not its next token's cross-entropy
    returns its own (``objective``: the looped model's over its exits), and
    the loss weighs that in ``CE_next``'s place. What differs between the
    models comes from the model: its ``block_scope``, whether its ``apply``
    returns ``nll_mtp`` (``has_mtp_loss``; the second term and ``mtp_loss``
    exist only then), the statistics it reports beside the common ones
    (``counters``: the mixture-of-experts models' ``moe_layers.EXPERT_COUNTERS``
    among them; a statistic the model returns under ``per_position`` ``[..., B,
    L]`` is reported as its weighted mean over the positions that have a next
    token) and its ``step_flops``."""

    config: MlaMoeConfig | GdnMoeConfig | LoopedLmConfig | Lfm2MoeConfig = dataclasses.field(default_factory=MlaMoeConfig)
    kernels: str | None = None

    # As the text-diffusion task, and for its reasons: the same two library
    # kernels, and 2.0 GB of float32 at the published widths.
    check_vma = TextDiffusionTask.check_vma
    donate_variables = TextDiffusionTask.donate_variables
    model_scope = None
    program_name = "client_fit"

    @property
    def model(self):
        from fedcrack_tpu.models import FAMILIES

        (model,) = (model for config, model in FAMILIES.values() if isinstance(self.config, config))
        return model(config=self.config, kernels=self.kernels)

    @property
    def block_scope(self) -> str:
        return self.model.block_scope

    @property
    def metric_reductions(self) -> tuple:
        model = self.model
        return (
            ("next_loss", "mean"), *((("mtp_loss", "mean"),) if model.has_mtp_loss else ()),
            ("tokens", "sum"), ("next_hits", "sum"), *model.counters,
        )

    def init(self, rng: jax.Array) -> dict:
        return {"params": self.model.init(rng), "batch_stats": {}}

    def unpack(self, batch):
        ids, weight = batch
        ids = ids.astype(jnp.int32)
        return ids, (ids, weight.astype(jnp.float32))

    def apply(self, params, state, inputs):
        return self.model.apply(params, inputs), state

    def loss_and_metrics(self, outputs, targets, pos_weight=None) -> dict:
        del pos_weight  # the segmentation loss's class weight
        _, weight = targets
        batch, seq_len = weight.shape

        def shifted(k):
            """Position ``i``'s weight is its target's, token ``i + k``'s;
            the last ``k`` positions have none."""
            return jnp.concatenate([weight[:, k:], jnp.zeros((batch, k), weight.dtype)], axis=1)

        mtp = "nll_mtp" in outputs
        w_next, w_mtp = shifted(1), shifted(2) if mtp else None
        next_loss = jnp.sum(w_next * outputs["nll_next"]) / (batch * (seq_len - 1))
        base = next_loss
        if "objective" in outputs:
            base = jnp.sum(w_next * outputs["objective"]) / (batch * (seq_len - 1))
        terms = {"loss": base}
        if mtp:
            mtp_loss = jnp.sum(w_mtp * outputs["nll_mtp"]) / (batch * (seq_len - 2))
            terms = {"loss": base + self.config.mtp_loss_weight * mtp_loss, "mtp_loss": mtp_loss}
        tokens = jnp.sum(w_next)
        stats = dict(terms, next_loss=next_loss, tokens=tokens, next_hits=jnp.sum(w_next * outputs["hit_next"]))
        per_position = outputs.get("per_position", {})
        for name, _ in self.model.counters:
            if name in per_position:
                stats[name] = jnp.sum(w_next * per_position[name], axis=(-2, -1)) / jnp.maximum(tokens, 1.0)
            else:
                stats[name] = outputs[name]
        return stats

    def round_metrics(self, last: dict) -> dict:
        rest = {k: v for k, v in last.items() if k != "next_hits"}
        return dict(rest, next_acc=last["next_hits"] / jnp.maximum(last["tokens"], 1.0))

    def validate(self, ids) -> None:
        if ids.shape[-1] != self.config.seq_len:
            raise ValueError(
                f"sequences of {ids.shape[-1]} tokens; the configuration's are {self.config.seq_len}"
            )

    def step_flops(self, batch: int) -> float:
        return float(self.model.step_flops(batch))


def task_for(model_config: Any, bn_axis_name: str | None = None):
    """The task of a model configuration, by its class: every family of the
    models' table (``models.FAMILIES``) but the U-Net and the block-diffusion
    model trains by next-token prediction."""
    from fedcrack_tpu.models import FAMILIES

    if isinstance(model_config, SdarMoeConfig):
        return TextDiffusionTask(model_config)
    if isinstance(model_config, ModelConfig):
        return SegmentationTask(model_config, bn_axis_name=bn_axis_name)
    if isinstance(model_config, tuple(config for config, _ in FAMILIES.values())):
        return CausalLMTask(model_config)
    raise TypeError(f"no task for a model configuration of type {type(model_config).__name__}")
