"""Single dataclass-based configuration system.

The reference has no config system: constants are module globals
(``fl_server.py:17-18``), magic ctor args (``fl_client.py:102``,
``fl_server.py:230-231``), hardcoded dataset paths
(``client_fit_model.py:58-59``) and a hardcoded port (``fl_server.py:218``).
Here every knob lives in one serializable config that also travels in-band in
the protocol handshake config map (SURVEY.md §2.4), closing SURVEY.md §5.6.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Mapping


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Residual U-Net hyperparameters (reference: client_fit_model.py:92-150).

    The reference hardcodes 128x128x3 inputs, encoder filters [64, 128, 256],
    decoder filters [256, 128, 64, 32] and a single-sigmoid head.
    """

    img_size: int = 128
    in_channels: int = 3
    num_classes: int = 1
    stem_features: int = 32
    encoder_features: tuple[int, ...] = (64, 128, 256)
    decoder_features: tuple[int, ...] = (256, 128, 64, 32)
    # "bfloat16" compute with float32 params is the TPU-native default; the
    # reference trains in float32 throughout.
    compute_dtype: str = "float32"
    param_dtype: str = "float32"
    # Layout transforms (models/resunet.py): exact re-expressions of the same
    # math targeting the HBM-bound narrow-channel convs (BASELINE.md "The MFU
    # ceiling" / "layout levers"). Parameter shapes NEVER change — transformed
    # kernels are derived in-forward from the reference weights, so h5
    # imports, FedAvg, serialization and checkpoints are layout-blind.
    #
    # stem_layout:
    #   "reference" — the reference's Conv(3x3, stride 2) on [N,H,W,3].
    #   "s2d"       — space-to-depth input [N,H/2,W/2,4C]; the stem runs as a
    #                 width-folded (3,2) conv on 2C channels, stride (2,1) —
    #                 BIT-EXACT vs the reference layout (the fold preserves
    #                 XLA's (kh,kw,c) contraction order; test-pinned).
    #   "s2d_full"  — the fully folded stride-1 (2,2) conv on 4C channels.
    #                 Mathematically identical (same multiplies + exact zero
    #                 terms) but XLA reassociates the longer contraction, so
    #                 agreement is ~1 ulp, not bitwise (measured in
    #                 tests/test_model.py; never timed on a chip: ROADMAP D4).
    # res_layout:
    #   "reference" — encoder residual projections as strided 1x1 convs.
    #   "packed"    — encoder residual 1x1 stride-2 convs re-expressed as
    #                 stride-1 1x1 convs over the space-to-depth-packed block
    #                 input (zero-extended kernel; bit-exact, test-pinned).
    stem_layout: str = "reference"
    res_layout: str = "reference"

    def __post_init__(self) -> None:
        # stem /2 + three pools /2 then four x2 upsamples: output comes back to
        # img_size only when img_size is a multiple of 16; otherwise the head
        # would silently emit a larger map than the mask.
        if self.img_size % 16 != 0 or self.img_size <= 0:
            raise ValueError(
                f"img_size must be a positive multiple of 16, got {self.img_size}"
            )
        if self.stem_layout not in ("reference", "s2d", "s2d_full"):
            raise ValueError(
                "stem_layout must be one of 'reference', 's2d', 's2d_full'; "
                f"got {self.stem_layout!r}"
            )
        if self.res_layout not in ("reference", "packed"):
            raise ValueError(
                "res_layout must be 'reference' or 'packed'; "
                f"got {self.res_layout!r}"
            )

    @property
    def input_shape(self) -> tuple[int, int, int]:
        return (self.img_size, self.img_size, self.in_channels)


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    """One chip's share of a block-diffusion mixture-of-experts language
    model (``models/sdar_moe.py``; the ``sdar_moe`` family of
    JetLM/SDAR-30B-A3B-Chat). Widths carry the published names. The share:
    ``vocab_held`` rows of the embedding and of the head, and
    ``experts_held`` experts from ``first_expert`` on; the router keeps its
    ``num_experts`` outputs and its ``num_experts_per_tok`` choices. The
    round program picks its task from the class of the model configuration
    (``tasks.task_for``): this one trains by block diffusion."""

    hidden_size: int = 2048
    num_hidden_layers: int = 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    first_expert: int = 0
    experts_held: int = 16
    vocab_held: int = 18992
    # Block diffusion (BD3-LM): tokens a block; the mask token is the last
    # held row of the vocabulary.
    block_length: int = 4
    seq_len: int = 4096
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self) -> None:
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not group over "
                f"{self.num_key_value_heads} key/value heads"
            )
        if not 0 <= self.first_expert <= self.num_experts - self.experts_held:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.experts_held - 1} "
                f"are not among the router's {self.num_experts}"
            )
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("more experts a token than the router has")
        if self.seq_len % self.block_length or self.seq_len <= 0:
            raise ValueError(
                f"seq_len {self.seq_len} is not whole blocks of {self.block_length}"
            )
        if self.head_dim % 2:
            raise ValueError("rotary embedding needs an even head_dim")

    @property
    def mask_token(self) -> int:
        return self.vocab_held - 1


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
    """One chip's share of a latent-attention mixture-of-experts causal
    language model (``models/mla_moe.py``; the ``joyai_llm_flash`` family of
    jdopensource/JoyAI-LLM-Flash, DeepSeek-V3's shape key for key). Widths
    carry the published names. ``first_k_dense_replace`` leading layers have
    a dense SwiGLU of ``intermediate_size``; every later one a sigmoid router
    over ``n_routed_experts`` outputs with a selection bias,
    ``num_experts_per_tok`` choices scaled by ``routed_scaling_factor``, and
    ``n_shared_experts`` shared experts as one dense SwiGLU;
    ``num_nextn_predict_layers`` (0 or 1) multi-token-prediction modules
    follow the last layer. The share: ``vocab_held`` rows of the embedding
    and of the head, and ``experts_held`` routed experts from
    ``first_expert`` on. The round program picks its task from the class of
    the model configuration (``tasks.task_for``): this one trains by
    next-token prediction."""

    hidden_size: int = 2048
    num_hidden_layers: int = 5
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    first_k_dense_replace: int = 1
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-6
    first_expert: int = 0
    experts_held: int = 8
    vocab_held: int = 16160
    seq_len: int = 8192
    # L = CE_next + mtp_loss_weight x CE_mtp (the family's report: 0.3).
    mtp_loss_weight: float = 0.3
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self) -> None:
        if not 0 <= self.first_expert <= self.n_routed_experts - self.experts_held:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.experts_held - 1} "
                f"are not among the router's {self.n_routed_experts}"
            )
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("more experts a token than the router has")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary embedding rotates pairs: qk_rope_head_dim must be even")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace counts leading layers of num_hidden_layers")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("0 or 1 multi-token-prediction modules (the family publishes depth 1)")
        if self.seq_len <= 2:
            raise ValueError("a sequence needs a next token and the one after")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def sparse_layers(self) -> int:
        """Layers that hold experts, the multi-token-prediction module's included."""
        return self.num_hidden_layers - self.first_k_dense_replace + self.num_nextn_predict_layers


# Tokens a chunk of the gated delta rule (``models/gdn_moe.py``): a constant of
# that module's algebra, kept here because the configuration refuses a
# sequence it does not divide.
GDN_CHUNK = 64


@dataclasses.dataclass(frozen=True)
class GdnMoeConfig:
    """One chip's share of a hybrid linear-attention mixture-of-experts
    causal language model (``models/gdn_moe.py``; the ``qwen3_next`` family of
    Qwen/Qwen3-Next-80B-A3B-Instruct). Widths carry the published names. Layer
    ``i`` is gated full attention where ``(i + 1) % full_attention_interval
    == 0`` and a Gated DeltaNet layer otherwise; every layer has a softmax
    router over ``num_experts`` outputs with ``num_experts_per_tok`` choices
    and one shared expert of ``shared_expert_intermediate_size`` behind a
    sigmoid gate. The share: ``vocab_held`` rows of the embedding and of the
    head, and ``experts_held`` routed experts from ``first_expert`` on. The
    round program picks its task from the class of the model configuration
    (``tasks.task_for``): this one trains by next-token prediction."""

    hidden_size: int = 2048
    num_hidden_layers: int = 4
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    first_expert: int = 0
    experts_held: int = 16
    vocab_held: int = 18992
    seq_len: int = 8192
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self) -> None:
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not group over "
                f"{self.num_key_value_heads} key/value heads"
            )
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                f"{self.linear_num_value_heads} value heads do not group over "
                f"{self.linear_num_key_heads} key heads"
            )
        if not 0 <= self.first_expert <= self.num_experts - self.experts_held:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.experts_held - 1} "
                f"are not among the router's {self.num_experts}"
            )
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("more experts a token than the router has")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("rotary embedding rotates halves of an even share of head_dim")
        if self.seq_len <= 0 or self.seq_len % GDN_CHUNK:
            raise ValueError(f"seq_len {self.seq_len} is not whole chunks of {GDN_CHUNK} tokens")

    @property
    def rotary_dim(self) -> int:
        """Leading lanes of a head that the rotary embedding turns."""
        return int(self.head_dim * self.partial_rotary_factor)

    def is_linear(self, layer: int) -> bool:
        """Whether layer ``layer`` is a Gated DeltaNet layer."""
        return (layer + 1) % self.full_attention_interval != 0

    @property
    def linear_layers(self) -> int:
        return sum(self.is_linear(i) for i in range(self.num_hidden_layers))


@dataclasses.dataclass(frozen=True)
class LoopedLmConfig:
    """One chip's share of a looped causal language model
    (``models/looped_lm.py``; the ``ouro`` family of ByteDance/Ouro-2.6B): a
    stack of ``num_hidden_layers`` decoder layers run ``total_ut_steps`` times
    a token with the same weights, an exit (final norm, exit gate, head) after
    every pass. Widths carry the published names. The share: one pipeline
    stage's layers; the embedding, the final norm, the exit gate and the head
    whole. ``exit_entropy_beta`` weighs the exit distribution's entropy in
    the loss (the config has no key for it). The round program picks its task
    from the class of the model configuration (``tasks.task_for``): this one
    trains by next-token prediction over every exit."""

    hidden_size: int = 2048
    num_hidden_layers: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    vocab_size: int = 49152
    total_ut_steps: int = 4
    exit_entropy_beta: float = 0.05
    seq_len: int = 8192
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self) -> None:
        from fedcrack_tpu.models.moe_layers import ATTN_TILE

        if self.total_ut_steps < 1:
            raise ValueError(f"total_ut_steps {self.total_ut_steps}: the stack runs at least once")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("the attention kernel reads a key/value head for every query head")
        if self.head_dim % 2:
            raise ValueError("rotary embedding rotates halves of an even head_dim")
        tile = min(ATTN_TILE, self.seq_len)
        if self.seq_len <= 1 or self.seq_len % tile or tile % 128:
            raise ValueError(f"seq_len {self.seq_len} is not whole tiles of the attention kernel ({ATTN_TILE}, or 128 below it)")


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """One chip's share of a hybrid causal language model of gated short
    convolutions and attention with sparse experts (``models/lfm2_moe.py``;
    the ``lfm2_moe`` family of LiquidAI/LFM2-8B-A1B). Widths carry the
    published names. Layer ``i``'s operator is ``layer_types[i]`` (``"conv"``:
    a gated short convolution of ``conv_L_cache`` taps; ``"full_attention"``:
    grouped-query attention, ``hidden_size / num_attention_heads`` lanes a
    head); its feed-forward is a dense SwiGLU of ``intermediate_size`` for the
    first ``num_dense_layers`` layers and a sigmoid router over
    ``num_experts`` outputs with an expert bias, ``num_experts_per_tok``
    choices scaled by ``routed_scaling_factor``, after them. The head is the
    embedding's transpose. The share: ``vocab_held`` rows of the embedding,
    and ``experts_held`` routed experts from ``first_expert`` on. The round
    program picks its task from the class of the model configuration
    (``tasks.task_for``): this one trains by next-token prediction."""

    hidden_size: int = 2048
    num_hidden_layers: int = 5
    layer_types: tuple = ("conv", "full_attention", "conv", "conv", "conv")
    num_dense_layers: int = 1
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    first_expert: int = 0
    experts_held: int = 8
    vocab_held: int = 16384
    seq_len: int = 8192
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"

    def __post_init__(self) -> None:
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers or not set(self.layer_types) <= {"conv", "full_attention"}:
            raise ValueError(f"layer_types {self.layer_types} is not one of 'conv' or 'full_attention' a layer")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers counts leading layers of num_hidden_layers")
        if self.hidden_size % self.num_attention_heads or self.head_dim % 2:
            raise ValueError("the heads split hidden_size into even widths")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not group over "
                f"{self.num_key_value_heads} key/value heads"
            )
        if not 0 <= self.first_expert <= self.num_experts - self.experts_held:
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + self.experts_held - 1} "
                f"are not among the router's {self.num_experts}"
            )
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("more experts a token than the router has")
        if self.seq_len <= 1:
            raise ValueError("a sequence needs a next token")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def is_conv(self, layer: int) -> bool:
        """Whether layer ``layer``'s operator is the gated short convolution."""
        return self.layer_types[layer] == "conv"

    def is_sparse(self, layer: int) -> bool:
        """Whether layer ``layer``'s feed-forward is the expert layer."""
        return layer >= self.num_dense_layers

    @property
    def sparse_layers(self) -> int:
        return self.num_hidden_layers - self.num_dense_layers


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """Dataset layout + split semantics (reference: client_fit_model.py:54-90)."""

    image_dir: str = ""
    mask_dir: str = ""
    img_size: int = 128
    batch_size: int = 16          # reference: client_fit_model.py:55
    split_seed: int = 1337        # reference: client_fit_model.py:77-78
    train_samples: int = 6213     # reference: client_fit_model.py:76
    # "iid" or "skew" (per-client crack-density skew, SURVEY.md §7 step 2)
    partition: str = "iid"
    skew_alpha: float = 0.3       # Dirichlet concentration for non-IID shards
    prefetch: int = 2
    num_workers: int = 4


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving-plane configuration (round 10): the TPU-native batched
    inference endpoint that turns the federation's global model into a
    served workload (ROADMAP north star: "serves heavy traffic").

    The reference's inference path is a one-shot script
    (test/Segmentation2.py); here prediction is a resident service with
    pre-compiled per-bucket programs, dynamic micro-batching and live
    hot-swap of the federated weights.
    """

    # Compiled square input buckets (H == W == size); a request lands in the
    # smallest bucket that holds it (spatially zero-padded, output cropped),
    # and anything larger than the largest bucket runs tiled sliding-window
    # inference with the largest bucket as the tile.
    bucket_sizes: tuple[int, ...] = (128, 256)
    # Compiled batch per bucket: requests accumulate until max_batch or
    # max_delay_ms, then are padded to exactly max_batch lanes (inference-
    # mode BN is per-sample independent, so pad lanes cannot perturb real
    # lanes — test-pinned).
    max_batch: int = 8
    max_delay_ms: float = 5.0
    # Hot-swap poll period: how often the version manager checks the
    # federation's checkpoint/statefile outputs for a newer global model.
    swap_poll_s: float = 2.0
    # Tile overlap (pixels) for sliding-window inference; overlapping rows/
    # cols are blended with a deterministic separable ramp.
    tile_overlap: int = 32
    # Serving compute dtype (params stay float32, as in training).
    compute_dtype: str = "float32"
    # Data-parallel shard of a served batch over the mesh 'batch' axis;
    # max_batch must be divisible by it.
    mesh_batch: int = 1
    # Default per-request deadline for accounting (0 = none). Requests past
    # their deadline are still served (never dropped) but counted.
    deadline_ms: float = 0.0
    host: str = "127.0.0.1"
    port: int = 8890
    max_message_mb: int = 64
    # ---- Serve fleet + quantized predict (round 17) ----
    # Replica workers behind the fleet router (serve/fleet.py). 1 keeps the
    # round-10 single-replica topology (no router, no admission control).
    replicas: int = 1
    # Post-training quantized predict program (serve/quant.py): "int8"
    # builds a weight-only per-channel-symmetric int8 program per bucket
    # alongside the reference program. Installs are A/B-gated: a quantized
    # build whose probe-batch mask IoU vs the reference oracle falls below
    # quant_iou_floor is REFUSED loudly and the replica keeps serving the
    # unquantized program — never a silent accuracy cliff.
    quant: str = "none"
    quant_iou_floor: float = 0.98
    # ---- Low-precision kernel plane (round 20, fedcrack_tpu/kernels/) ----
    # Which program body the quantized predict path compiles:
    #   "reference"  — r17's dequantize-in-graph + model.apply (the default);
    #   "fused_int8" — Pallas fused dequant-matmul forward: int8 codes feed
    #                  the MXU directly, f32 accumulation, no f32 weight
    #                  tensor ever materialized;
    #   "fp8"        — same fused forward over fp8 e4m3 codes; a backend
    #                  without fp8 support degrades to "reference" (the r17
    #                  path) bit-exactly at engine build time.
    # Every non-reference plane still requires quant="int8" and installs
    # ONLY through the r17 quant_gate — a failing probe refuses loudly and
    # the fleet keeps serving the reference program.
    kernel_plane: str = "reference"
    # Optional activation fake-quant at the program boundary (dynamic
    # per-tensor symmetric int8 of the pre-sigmoid logits). Weight-only
    # quantization needs no calibration data; this flag measures the
    # activation-quant accuracy headroom on top of it.
    quant_act_fakequant: bool = False
    # Seeded probe batch for the install-time A/B gate (per bucket size).
    quant_probe_batch: int = 4
    quant_probe_seed: int = 0
    # Admission control (serve/router.py): shed load with a loud
    # RESOURCE_EXHAUSTED reject when the fleet's rolling p95 latency
    # breaches slo_p95_ms (0 = off) or when queued requests across all
    # replicas exceed queue_bound (0 = off). Shedding happens at ACCEPT
    # time only — a request already admitted is never dropped.
    slo_p95_ms: float = 0.0
    queue_bound: int = 0
    # ---- Frame-coherent video serving (round 19, serve/stream.py) ----
    # Per-stream tile cache bound (entries = tiles). A video session keys
    # cached per-tile probabilities on (model_version, tile content hash),
    # so a new frame only re-runs tiles whose bytes changed; 0 disables
    # caching entirely (every frame is a full re-run — the escape hatch).
    stream_cache_tiles: int = 4096
    # Open video sessions the serve process will hold at once; opening one
    # past the bound is REJECTED loudly (the assembly-cap idiom).
    stream_max_sessions: int = 64
    # Crack-track continuity (serve/stream.py CrackTracker): a contour in
    # frame t+1 continues the track whose last centroid lies within this
    # fraction of the frame diagonal; beyond it a new stable id is born.
    stream_track_match_frac: float = 0.05
    # ---- Elastic fleet (round 22, serve/autoscaler.py) ----
    # SLO-driven autoscaling between min_replicas and max_replicas: the
    # controller consumes the registry's own Prometheus exposition (rolling
    # p95, per-bucket queue depth, live replica count) and scales the fleet
    # — scale-up compiles + warms the new replica OFF the serving path,
    # scale-down drains via the kill/reroute machinery so zero accepted
    # requests drop. min_replicas=0 disarms the controller entirely (the
    # static round-17 fleet); armed, `replicas` is the boot size and must
    # sit inside [min_replicas, max_replicas].
    min_replicas: int = 0
    max_replicas: int = 0
    # Controller evaluation period and the cooldown after ANY scale action
    # (hysteresis against flap storms — a gust can trigger at most one
    # action per cooldown window).
    scale_interval_s: float = 1.0
    scale_cooldown_s: float = 5.0
    # Scale-up triggers: queued backlog per live replica reaching this, or
    # the rolling p95 reaching this fraction of slo_p95_ms (act BEFORE the
    # shed probe does — shed stays the loud backstop, never the steady
    # state).
    scale_up_queue_depth: int = 4
    scale_up_p95_frac: float = 0.8
    # Scale-down hysteresis: this many CONSECUTIVE calm evaluations (empty
    # queues, p95 comfortably under the trigger) before one replica drains.
    scale_down_idle_evals: int = 3
    # ---- Shadow-replica progressive delivery (round 22, serve/shadow.py) --
    # Fraction of admitted production traffic mirrored to the shadow
    # candidate (responses NEVER returned to clients); 0 disables staging —
    # published versions install directly, the round-17 behavior.
    shadow_fraction: float = 0.0
    # Mirrored completions required before a promote/rollback verdict.
    shadow_min_samples: int = 16
    # Verdict floors: candidate canary IoU vs the production reference,
    # max PSI delta between candidate and production probe profiles, and
    # the shadow-vs-production p95 latency ratio ceiling.
    shadow_iou_floor: float = 0.98
    shadow_psi_ceiling: float = 0.25
    shadow_latency_factor: float = 3.0

    def __post_init__(self) -> None:
        if not self.bucket_sizes:
            raise ValueError("bucket_sizes must not be empty")
        sizes = tuple(self.bucket_sizes)
        if list(sizes) != sorted(set(sizes)):
            raise ValueError(
                f"bucket_sizes must be strictly increasing, got {sizes}"
            )
        for s in sizes:
            if s <= 0 or s % 16 != 0:
                raise ValueError(
                    f"every bucket size must be a positive multiple of 16 "
                    f"(the U-Net's spatial contract), got {s}"
                )
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_delay_ms < 0:
            raise ValueError(
                f"max_delay_ms must be >= 0, got {self.max_delay_ms}"
            )
        if self.swap_poll_s <= 0:
            raise ValueError(
                f"swap_poll_s must be > 0, got {self.swap_poll_s}"
            )
        if self.tile_overlap < 0 or self.tile_overlap >= min(sizes):
            raise ValueError(
                f"tile_overlap must be in [0, smallest bucket), got "
                f"{self.tile_overlap} with buckets {sizes}"
            )
        if self.mesh_batch < 1 or self.max_batch % self.mesh_batch != 0:
            raise ValueError(
                f"mesh_batch={self.mesh_batch} must be >= 1 and divide "
                f"max_batch={self.max_batch}"
            )
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                "serve compute_dtype must be float32 or bfloat16, got "
                f"{self.compute_dtype!r}"
            )
        if self.replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {self.replicas}")
        if self.quant not in ("none", "int8"):
            raise ValueError(
                f"serve quant must be 'none' or 'int8', got {self.quant!r}"
            )
        if not 0.0 < self.quant_iou_floor <= 1.0:
            raise ValueError(
                f"quant_iou_floor must be in (0, 1], got {self.quant_iou_floor}"
            )
        if self.kernel_plane not in ("reference", "fused_int8", "fp8"):
            raise ValueError(
                "kernel_plane must be 'reference', 'fused_int8' or 'fp8', "
                f"got {self.kernel_plane!r}"
            )
        if self.kernel_plane != "reference" and self.quant != "int8":
            raise ValueError(
                f"kernel_plane={self.kernel_plane!r} requires quant='int8' — "
                "the fused planes consume the quantized tree and ride its "
                "install gate"
            )
        if self.quant_probe_batch < 1:
            raise ValueError(
                f"quant_probe_batch must be >= 1, got {self.quant_probe_batch}"
            )
        if self.slo_p95_ms < 0:
            raise ValueError(f"slo_p95_ms must be >= 0, got {self.slo_p95_ms}")
        if self.queue_bound < 0:
            raise ValueError(f"queue_bound must be >= 0, got {self.queue_bound}")
        if self.stream_cache_tiles < 0:
            raise ValueError(
                f"stream_cache_tiles must be >= 0, got {self.stream_cache_tiles}"
            )
        if self.stream_max_sessions < 1:
            raise ValueError(
                f"stream_max_sessions must be >= 1, got {self.stream_max_sessions}"
            )
        if not 0.0 < self.stream_track_match_frac <= 1.0:
            raise ValueError(
                f"stream_track_match_frac must be in (0, 1], got "
                f"{self.stream_track_match_frac}"
            )
        if self.min_replicas < 0 or self.max_replicas < 0:
            raise ValueError(
                f"min_replicas/max_replicas must be >= 0, got "
                f"{self.min_replicas}/{self.max_replicas}"
            )
        if self.min_replicas > 0:
            if self.max_replicas < self.min_replicas:
                raise ValueError(
                    f"max_replicas={self.max_replicas} must be >= "
                    f"min_replicas={self.min_replicas}"
                )
            if not self.min_replicas <= self.replicas <= self.max_replicas:
                raise ValueError(
                    f"replicas={self.replicas} (the boot size) must sit in "
                    f"[min_replicas={self.min_replicas}, "
                    f"max_replicas={self.max_replicas}]"
                )
        elif self.max_replicas > 0:
            raise ValueError(
                "max_replicas without min_replicas is a disarmed ceiling — "
                "set min_replicas >= 1 to arm the autoscaler"
            )
        if self.scale_interval_s <= 0:
            raise ValueError(
                f"scale_interval_s must be > 0, got {self.scale_interval_s}"
            )
        if self.scale_cooldown_s < 0:
            raise ValueError(
                f"scale_cooldown_s must be >= 0, got {self.scale_cooldown_s}"
            )
        if self.scale_up_queue_depth < 1:
            raise ValueError(
                f"scale_up_queue_depth must be >= 1, got "
                f"{self.scale_up_queue_depth}"
            )
        if not 0.0 < self.scale_up_p95_frac <= 1.0:
            raise ValueError(
                f"scale_up_p95_frac must be in (0, 1], got "
                f"{self.scale_up_p95_frac}"
            )
        if self.scale_down_idle_evals < 1:
            raise ValueError(
                f"scale_down_idle_evals must be >= 1, got "
                f"{self.scale_down_idle_evals}"
            )
        if not 0.0 <= self.shadow_fraction <= 1.0:
            raise ValueError(
                f"shadow_fraction must be in [0, 1], got {self.shadow_fraction}"
            )
        if self.shadow_min_samples < 1:
            raise ValueError(
                f"shadow_min_samples must be >= 1, got {self.shadow_min_samples}"
            )
        if not 0.0 < self.shadow_iou_floor <= 1.0:
            raise ValueError(
                f"shadow_iou_floor must be in (0, 1], got {self.shadow_iou_floor}"
            )
        if self.shadow_psi_ceiling <= 0:
            raise ValueError(
                f"shadow_psi_ceiling must be > 0, got {self.shadow_psi_ceiling}"
            )
        if self.shadow_latency_factor < 1.0:
            raise ValueError(
                f"shadow_latency_factor must be >= 1, got "
                f"{self.shadow_latency_factor}"
            )


@dataclasses.dataclass(frozen=True)
class FedConfig:
    """Federation round/protocol configuration.

    Reference values: MAX_NUM_ROUND=5 (fl_server.py:18), 10 s registration
    window (fl_server.py:42), 20 s version poll (fl_client.py:141), local
    epochs hardcoded to 10 (client_fit_model.py:166).
    """

    max_rounds: int = 5
    cohort_size: int = 2
    # Async federation (round 14, fedcrack_tpu/fed/buffered.py): "sync" is
    # the barrier round machine (reference semantics + all fixes); in
    # "buffered" mode the server runs FedBuff-style buffered aggregation
    # (Nguyen et al., 2022): updates are accepted AS THEY ARRIVE, each
    # weighted by the polynomial staleness decay (1 + staleness)^-alpha
    # (FedAsync, Xie et al., 2019), folded into a buffer of `buffer_k`
    # updates, and flushed to a new global version at K — no round barrier,
    # so one straggler never stalls the federation. Clients loop
    # pull→train→push continuously. `buffer_k = cohort_size` with
    # `staleness_alpha = 0` reproduces the sync FedAvg trajectory
    # bit-exactly (test-pinned).
    mode: str = "sync"
    # Buffered mode: how many accepted updates trigger a flush (FedBuff's
    # K). The round_deadline_s backstop flushes a non-empty partial buffer
    # so a dwindling cohort cannot stall the version counter forever.
    buffer_k: int = 2
    # Polynomial staleness-decay exponent: an update trained on a base
    # `s` versions behind the current global is weighted by
    # (1 + s)^-alpha (on top of its sample count). 0 disables decay
    # (every update weighs its plain sample count — the sync-degeneration
    # escape hatch).
    staleness_alpha: float = 0.5
    # Updates staler than this many versions are REJECTED into the round
    # history (like r8 sanitation rejects) and the sender is re-synced with
    # the current global. Also bounds the window of past broadcast blobs
    # the server retains for delta-frame decode (memory: max_staleness + 1
    # broadcast-sized blobs). 0 = only updates against the current version
    # are accepted.
    max_staleness: int = 4
    # Seeded per-round cohort sampling (round 13): the seed behind
    # fed.algorithms.sample_cohort — harnesses that sample `cohort_size`
    # clients per round from a larger population (the time-multiplexed
    # cohort plane, the hierarchical aggregation tree) derive every round's
    # cohort from (cohort_seed, round), so the whole multi-round trajectory
    # reproduces from this one number.
    cohort_seed: int = 0
    local_epochs: int = 10
    learning_rate: float = 1e-3
    registration_window_s: float = 10.0
    poll_period_s: float = 20.0
    # Per-round deadline; on expiry the cohort shrinks to the clients that
    # reported (fixes the reference's forever-hanging barrier, SURVEY.md §5.3).
    round_deadline_s: float = 0.0  # 0 = no deadline
    # Quorum aggregation (Bonawitz et al., MLSys 2019: over-provision the
    # cohort, aggregate at a goal count instead of the full barrier): the
    # round closes as soon as ceil(quorum_fraction * |cohort|) updates are
    # in. 1.0 keeps the full barrier (reference semantics); the deadline
    # stays as the backstop either way. Stragglers whose report lands after
    # the quorum closed the round are re-synced to the current round (their
    # late update is logged to history, never averaged).
    quorum_fraction: float = 1.0
    # Update sanitation before FedAvg: every TrainDone payload is checked
    # against the global template (decodable, leaf count, per-leaf shape,
    # finite values) and rejected — logged to the round's history entry —
    # instead of averaged. A single NaN client otherwise poisons the global
    # model for every client. Disable only for wire-format experiments.
    sanitize_updates: bool = True
    # Byzantine-robust aggregation (round 21, fed/aggregation.py): how the
    # server COMBINES the round's accepted updates. "fedavg" is the null
    # algebra — the sample-weighted mean, bitwise-pinned to every plane's
    # historical fold. "trimmed_mean" / "median" (alias "coordinate_median")
    # are the coordinate-wise robust estimators of Yin et al. (ICML 2018);
    # "krum" / "multi_krum" the distance-scored selection of Blanchard et
    # al. (NeurIPS 2017). Robust combines ignore client-reported sample
    # counts (a Byzantine client self-reports them) and run on the gRPC
    # rounds plane and the buffered root only — edge tiers refuse them
    # loudly (a trimmed partial of a partial is not a trimmed total).
    aggregation: str = "fedavg"
    # TrimmedMean's beta: drop floor(beta * n) per coordinate from each
    # tail. [0, 0.5) so at least one value survives per coordinate.
    trim_fraction: float = 0.1
    # Krum/Multi-Krum's f: the assumed Byzantine count. Scores sum the
    # n - f - 2 smallest squared distances (clamped to >= 1 neighbor);
    # Multi-Krum averages the n - f lowest-scoring updates.
    byzantine_f: int = 1
    # Ledger-coupled quarantine (round 21): a client whose flush-time
    # robust-z anomaly score (health/ledger.py observe_flush — the r18
    # detection plane) is >= this threshold is EXCLUDED from the fold,
    # logged in the history entry's "quarantined" map, and re-synced
    # NOT_WAIT like a sanitation reject. 0 disables (detection without
    # response — r18 behavior). Composable with any `aggregation`.
    quarantine_z: float = 0.0
    # ---- Privacy plane (round 23, fedcrack_tpu/privacy/) ----
    # DP-SGD (Abadi et al. 2016): per-client gradient clipping to this L2
    # norm inside the mesh plane's sgd_step (and, update-level, in the
    # gRPC client CLI — McMahan et al. 2018). 0 disables DP entirely; the
    # dp=off traced program is byte-identical to today's (test-pinned).
    dp_clip_norm: float = 0.0
    # Gaussian noise sigma, as a multiple of dp_clip_norm (noise stddev =
    # dp_noise_multiplier * dp_clip_norm). Requires dp_clip_norm > 0 —
    # unclipped noise has no sensitivity bound to calibrate against.
    dp_noise_multiplier: float = 0.0
    # Accountant parameters (privacy/accountant.py, the RDP/moments
    # accountant): per-step sampling rate q, the delta of the reported
    # eps(delta), and how many noise additions one round charges a client
    # (0 derives local_epochs — the mesh plane's one-noise-per-epoch-step
    # granularity collapses to epochs on the gRPC plane, where the server
    # cannot see client step counts).
    dp_sample_rate: float = 0.01
    dp_delta: float = 1e-5
    dp_steps_per_round: int = 0
    # Root of the (client, round, step, leaf) noise seed tree — the r12
    # codec-seed precedent, so chaos/retry replays are bit-identical.
    dp_seed: int = 0
    # eps(delta) budget: when any charged client's cumulative epsilon
    # reaches this, the federation REFUSES to open further rounds and
    # finishes (loud, recorded in history). 0 = unlimited.
    dp_epsilon_budget: float = 0.0
    # Pairwise-mask secure aggregation (round 23, privacy/secagg.py;
    # Bonawitz et al. 2017): clients upload fixed-point int64 updates
    # under pairwise PRG masks that cancel exactly in the ordered fold;
    # dropout is closed by a seed-recovery step under the r8 quorum
    # machinery. Masked updates are OPAQUE to the r18 ledger's norm/
    # cosine windows, so secagg composes only with the null combine:
    # aggregation must stay "fedavg", quarantine_z must stay 0, the
    # update codec must stay "null", and mode must stay "sync" — each
    # violation is a loud config error (the edge-tier-refuses-non-null
    # precedent), documented as the privacy/robustness trade-off.
    secagg: bool = False
    # Fixed-point fractional bits for the masked encoding (values are
    # round(x * 2^bits) in the 2^64 residue ring).
    secagg_bits: int = 24
    # Mid-round durable server state (msgpack via atomic write+fsync+rename;
    # empty disables): persists cohort/phase/received blobs on every
    # membership or upload change, so a server killed MID-round resumes the
    # same round with the already-received updates intact (the orbax
    # checkpoint only covers round boundaries). Restored in preference to
    # the orbax checkpoint when strictly newer.
    state_path: str = ""
    # FedProx proximal term; 0 disables (plain FedAvg).
    fedprox_mu: float = 0.0
    # Crack-pixel loss weight (1 + (pos_weight-1)*mask scales each pixel's
    # BCE): >1 counters the ~7% foreground imbalance of crack masks, which
    # under plain BCE converges to low-confidence maps that threshold poorly.
    # 1.0 is the reference's unweighted BCE (client_fit_model.py:157).
    # Travels in-band to every client like fedprox_mu.
    pos_weight: float = 1.0
    # FedOpt server optimizer on the round pseudo-gradient (Reddi et al.):
    # "avg" = plain FedAvg (the reference's behavior), "momentum"/"fedavgm",
    # "adam"/"fedadam", "yogi"/"fedyogi". Applied to params only; BN stats
    # are plain-averaged.
    server_optimizer: str = "avg"
    server_lr: float = 1.0
    server_momentum: float = 0.9
    # Advertised model type. The reference advertises the vestigial string
    # "mobilenet_v2" (fl_server.py:75) while actually sharing the U-Net; we
    # advertise honestly but accept the legacy alias (SURVEY.md §2.2(3)).
    model_type: str = "resunet"
    # Wire dtype for weight payloads on the control plane: "bfloat16" halves
    # upload + broadcast bytes (server math stays float32; the reference
    # shipped full float32 pickles, fl_client.py:63).
    wire_dtype: str = "float32"
    # Compressed update transport (round 12, fedcrack_tpu/compress): how
    # each client's upload is encoded. "null" ships today's msgpack bytes
    # unchanged (the bit-exactness escape hatch, test-pinned); "int8" ships
    # the per-leaf symmetric int8-quantized round delta with f32 scale
    # sidecars; "topk_delta" ships the top-k sparsified delta with a
    # client-side error-feedback accumulator (dropped mass re-enters next
    # round). Negotiated in-band at enroll like every other hyperparameter;
    # legacy clients that ignore it keep sending raw blobs, which the
    # server still accepts (mixed-codec cohorts decode to full trees before
    # FedAvg, so they aggregate correctly).
    update_codec: str = "null"
    # TopKDeltaCodec keep fraction: each leaf transmits ceil(fraction * n)
    # entries per round (8 bytes each vs 4 per dense f32 — 0.01 is ~50x
    # fewer bytes before framing/zlib).
    topk_fraction: float = 0.01
    host: str = "127.0.0.1"
    port: int = 8889              # reference: fl_server.py:218
    # Orbax checkpoint directory; empty disables. When the directory already
    # holds a checkpoint the federation resumes from the latest round
    # (SURVEY.md §5.4 — the reference server forgot rounds on restart).
    ckpt_dir: str = ""
    # PRNG seed for the initial global model.
    seed: int = 0
    # JSONL structured-metrics file (per-round records, SURVEY.md §5.5);
    # empty disables.
    metrics_path: str = ""
    # TensorBoard event-file directory: numeric per-round/epoch metrics are
    # teed as real TB scalars (obs/tb.py, no TF dependency) — the
    # reference's workflow of opening training logs in TensorBoard
    # (client_fit_model.py:153-154). Empty disables.
    tb_dir: str = ""
    # Server-side sink directory for client-uploaded log files (the
    # reference's 'L' chunk path wrote TensorBoard events under ./logs,
    # fl_server.py:84-89); empty keeps uploads in memory only.
    logs_dir: str = ""
    # In-memory log sink caps: chunks accumulate in server memory until the
    # uploader sends `last` (then they flush to logs_dir; with logs_dir
    # empty they are retained in memory for checkpointing), so uploads must
    # hit a ceiling. Per-upload and across-all-uploads, in MiB; over-cap
    # chunks are REJECTED; 0 = uncapped. Only cohort members may upload.
    log_max_mb_per_upload: int = 64
    log_max_mb_total: int = 256
    # jax.profiler trace directory for training spans; empty disables.
    profile_dir: str = ""
    # Msgpack pytree seeding the initial global model (e.g. from the Keras h5
    # importer, tools/h5_import.py); empty initializes from `seed`.
    init_weights: str = ""
    # When server-side eval runs (server --eval-*), the best global model by
    # eval loss is kept here as a msgpack pytree with a .json metrics sidecar
    # — the federated analog of the reference's best-val ModelCheckpoint
    # (test/Segmentation.py:177-179). Empty disables.
    best_path: str = ""
    # Control-plane security. The reference's channel was fully open — no
    # identity, no transport security; anyone reaching the port could
    # enroll or poison the cohort (fl_client.py:181, SURVEY.md §5.8).
    # auth_token: shared secret required on every client message when set
    # (constant-time compared server-side; unauthenticated messages are
    # REJECTED). Empty disables. Over a plaintext channel the token would
    # travel in cleartext on every message, so auth_token without TLS
    # (no tls_cert/tls_key on the server, no tls_ca on the client) is
    # refused unless allow_insecure_token is set explicitly.
    auth_token: str = ""
    # Escape hatch for loopback/test deployments that genuinely want a
    # shared token over plaintext. Anything crossing a real network should
    # configure TLS instead — with this on, anyone on the path reads the
    # secret off the first message.
    allow_insecure_token: bool = False
    # TLS: the server serves with ssl_server_credentials when tls_cert +
    # tls_key are both set (PEM file paths); a client connects over TLS
    # when tls_ca is set (PEM root to verify the server). When the server
    # also sets tls_ca, client certificates are required (mTLS) — clients
    # then present tls_cert/tls_key. All empty = plaintext.
    tls_cert: str = ""
    tls_key: str = ""
    tls_ca: str = ""
    max_message_mb: int = 512     # reference: fl_server.py:215 (both directions here)
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    # Serving plane (round 10): bucket/batching/hot-swap knobs for
    # `python -m fedcrack_tpu.serve`. Rides the same config object so one
    # preset describes a whole deployment (training + serving).
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)
    # Mesh shape for the TPU data plane: (#federated clients, per-client DP).
    mesh_clients: int = 8
    mesh_batch: int = 1
    # Epoch-segmented round execution (parallel.fedavg_mesh.SegmentedRound):
    # 0 runs the round as ONE compiled program (the monolithic
    # local_epochs x steps scan); K > 0 splits it into K device-resident-
    # carry segment programs (K must divide local_epochs; K = local_epochs
    # is one segment per epoch). Segmentation is bit-exact vs the monolith
    # and unlocks segment-grain staging overlap plus 1/K-sized compiles
    # (the 256 px reference-scale program only compiles chunked).
    segments: int = 0
    # With segments > 0: stream the next round's staging one step-range
    # chunk per in-flight segment (True, epoch-grain double buffering)
    # instead of one monolithic transfer per round (False). Peak staged
    # HBM is ~2 epoch slabs either way; streaming keeps any single
    # transfer 1/K the size and hides more of it under compute.
    segment_overlap: bool = True
    # Data plane for the mesh rounds (round 9): "streamed" re-stages each
    # round's shuffled epoch slab (the modes above); "resident" stages the
    # deduplicated per-client sample pool ONCE (data.pipeline.SamplePool,
    # sharded P('clients')) and ships only a [clients, epochs, steps,
    # batch] int32 gather plan per round — kilobytes instead of the epoch
    # slab, byte-identical trajectory (test-pinned). An HBM guard
    # (parallel.driver.resident_pool_fits) falls back to the streamed path
    # when the pool doesn't fit the device.
    data_placement: str = "streamed"

    def __post_init__(self) -> None:
        if self.data.img_size != self.model.img_size:
            raise ValueError(
                "data.img_size and model.img_size must match; got "
                f"{self.data.img_size} vs {self.model.img_size}"
            )
        if self.segments < 0:
            raise ValueError(f"segments must be >= 0, got {self.segments}")
        if self.segments > 0 and self.local_epochs % self.segments != 0:
            raise ValueError(
                f"segments={self.segments} must divide "
                f"local_epochs={self.local_epochs} (epoch-grain segmentation)"
            )
        if self.data_placement not in ("streamed", "resident"):
            raise ValueError(
                "data_placement must be 'streamed' or 'resident', got "
                f"{self.data_placement!r}"
            )
        if self.mode not in ("sync", "buffered"):
            raise ValueError(
                f"mode must be 'sync' or 'buffered', got {self.mode!r}"
            )
        if self.buffer_k < 1:
            raise ValueError(f"buffer_k must be >= 1, got {self.buffer_k}")
        if self.staleness_alpha < 0.0:
            raise ValueError(
                f"staleness_alpha must be >= 0, got {self.staleness_alpha}"
            )
        if self.max_staleness < 0:
            raise ValueError(
                f"max_staleness must be >= 0, got {self.max_staleness}"
            )
        if self.cohort_seed < 0:
            # SeedSequence entropy must be non-negative; fail at config
            # parse, not inside the first round's sample_cohort call.
            raise ValueError(
                f"cohort_seed must be >= 0, got {self.cohort_seed}"
            )
        if not 0.0 < self.quorum_fraction <= 1.0:
            raise ValueError(
                f"quorum_fraction must be in (0, 1], got {self.quorum_fraction}"
            )
        if self.aggregation not in (
            "fedavg", "trimmed_mean", "median", "coordinate_median",
            "krum", "multi_krum",
        ):
            raise ValueError(
                "aggregation must be one of 'fedavg', 'trimmed_mean', "
                "'median', 'coordinate_median', 'krum', 'multi_krum', got "
                f"{self.aggregation!r}"
            )
        if not 0.0 <= self.trim_fraction < 0.5:
            raise ValueError(
                f"trim_fraction must be in [0, 0.5), got {self.trim_fraction}"
            )
        if self.byzantine_f < 0:
            raise ValueError(
                f"byzantine_f must be >= 0, got {self.byzantine_f}"
            )
        if self.quarantine_z < 0.0:
            raise ValueError(
                f"quarantine_z must be >= 0 (0 disables), got "
                f"{self.quarantine_z}"
            )
        if self.dp_clip_norm < 0.0:
            raise ValueError(
                f"dp_clip_norm must be >= 0 (0 disables DP), got "
                f"{self.dp_clip_norm}"
            )
        if self.dp_noise_multiplier < 0.0:
            raise ValueError(
                f"dp_noise_multiplier must be >= 0, got "
                f"{self.dp_noise_multiplier}"
            )
        if self.dp_noise_multiplier > 0.0 and self.dp_clip_norm <= 0.0:
            raise ValueError(
                "dp_noise_multiplier > 0 requires dp_clip_norm > 0: noise "
                "is calibrated to the clip norm (stddev = multiplier * "
                "clip), and unclipped gradients have no sensitivity bound "
                "for the accountant to certify."
            )
        if not 0.0 < self.dp_sample_rate <= 1.0:
            raise ValueError(
                f"dp_sample_rate must be in (0, 1], got {self.dp_sample_rate}"
            )
        if not 0.0 < self.dp_delta < 1.0:
            raise ValueError(
                f"dp_delta must be in (0, 1), got {self.dp_delta}"
            )
        if self.dp_steps_per_round < 0:
            raise ValueError(
                f"dp_steps_per_round must be >= 0 (0 derives local_epochs), "
                f"got {self.dp_steps_per_round}"
            )
        if self.dp_epsilon_budget < 0.0:
            raise ValueError(
                f"dp_epsilon_budget must be >= 0 (0 = unlimited), got "
                f"{self.dp_epsilon_budget}"
            )
        if not 8 <= self.secagg_bits <= 52:
            raise ValueError(
                f"secagg_bits must be in [8, 52] (float64-exact fixed "
                f"point), got {self.secagg_bits}"
            )
        if self.secagg:
            # The privacy/robustness trade-off, stated loudly: masked
            # uploads are uniformly-random residues, opaque to the r18
            # ledger's norm/cosine windows and to every robust combine,
            # and only the sync plane carries the roster handshake. Refuse
            # the combination at config time (the edge-tier-refuses-
            # non-null precedent) rather than silently degrade either
            # property.
            if self.aggregation != "fedavg":
                raise ValueError(
                    "secagg composes only with the null combine: masked "
                    "updates are opaque to robust aggregation, so "
                    "aggregation must be 'fedavg', got "
                    f"{self.aggregation!r}. This is the privacy/robustness "
                    "trade-off — pick one per federation."
                )
            if self.quarantine_z != 0.0:
                raise ValueError(
                    "secagg requires quarantine_z=0: the r18 ledger cannot "
                    "window norms/cosines of masked uploads, so quarantine "
                    "would act on noise. Got quarantine_z="
                    f"{self.quarantine_z}."
                )
            if self.update_codec != "null":
                raise ValueError(
                    "secagg requires update_codec='null': the masked "
                    "fixed-point wire format replaces the codec stack, got "
                    f"{self.update_codec!r}"
                )
            if self.mode != "sync":
                raise ValueError(
                    "secagg requires mode='sync': the masking roster is a "
                    "closed cohort, and the buffered plane folds across "
                    f"cohort boundaries. Got mode={self.mode!r}."
                )
        if self.wire_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"wire_dtype must be float32 or bfloat16, got {self.wire_dtype!r}"
            )
        if self.update_codec not in ("null", "int8", "topk_delta"):
            raise ValueError(
                "update_codec must be 'null', 'int8' or 'topk_delta', got "
                f"{self.update_codec!r}"
            )
        if not 0.0 < self.topk_fraction <= 1.0:
            raise ValueError(
                f"topk_fraction must be in (0, 1], got {self.topk_fraction}"
            )
        if self.max_message_mb < 1:
            raise ValueError(
                f"max_message_mb must be >= 1, got {self.max_message_mb}"
            )
        if bool(self.tls_cert) != bool(self.tls_key):
            # Half a TLS identity must fail fast — otherwise the server
            # would silently fall back to a plaintext port (and a client
            # silently omit its mTLS certificate) while the operator
            # believes TLS is on.
            raise ValueError(
                "tls_cert and tls_key must be set together; got "
                f"tls_cert={self.tls_cert!r}, tls_key={self.tls_key!r}"
            )
        if (
            self.auth_token
            and not (self.tls_cert or self.tls_ca)
            and not self.allow_insecure_token
        ):
            # A shared secret over a plaintext channel is sent in cleartext
            # on EVERY message — an operator following a quickstart would
            # ship it to any on-path observer without noticing. Refuse the
            # combination unless it is opted into by name.
            raise ValueError(
                "auth_token is set but the channel is plaintext (no TLS "
                "config): the secret would travel in cleartext on every "
                "message. Configure tls_cert/tls_key (server) or tls_ca "
                "(client), or set allow_insecure_token=true to accept this "
                "for loopback/testing."
            )

    # ---- serialization (in-band config map + files) ----

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, blob: str | bytes) -> "FedConfig":
        raw = json.loads(blob)
        return cls.from_dict(raw)

    @classmethod
    def from_dict(cls, raw: Mapping[str, Any]) -> "FedConfig":
        raw = dict(raw)
        model = raw.pop("model", {})
        data = raw.pop("data", {})
        serve = raw.pop("serve", {})
        known = {f.name for f in dataclasses.fields(cls)}
        raw = {k: v for k, v in raw.items() if k in known}
        mknown = {f.name for f in dataclasses.fields(ModelConfig)}
        dknown = {f.name for f in dataclasses.fields(DataConfig)}
        sknown = {f.name for f in dataclasses.fields(ServeConfig)}
        mc = ModelConfig(**{k: _detuple(k, v) for k, v in model.items() if k in mknown})
        dc = DataConfig(**{k: v for k, v in data.items() if k in dknown})
        sc = ServeConfig(
            **{k: _detuple(k, v) for k, v in serve.items() if k in sknown}
        )
        return cls(model=mc, data=dc, serve=sc, **raw)


def _detuple(key: str, value: Any) -> Any:
    if key in ("encoder_features", "decoder_features", "bucket_sizes") and isinstance(
        value, list
    ):
        return tuple(value)
    return value
