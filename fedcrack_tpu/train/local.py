"""Local training engine: jitted train/eval steps and the per-round fit loop.

This is the TPU-native replacement for the reference's client ML engine
(reference: client_fit_model.py:152-174 ``train_model_tosave``): where the
reference rebuilds and re-compiles a Keras model every round and runs
``model.fit`` with a synchronous cv2 input loop, here the model is built once,
the train step is one jitted XLA program reused across all rounds (weights are
just pytree inputs), and batches stream through the prefetching pipeline.

FedProx (configs/c4_noniid_fedprox.json) is built into the step as a proximal term
``mu/2 * ||params - anchor||^2`` toward the round's global weights; ``mu=0``
recovers plain FedAvg local SGD and costs nothing at runtime. ``mu`` and the
anchor are traced inputs, so switching algorithms never recompiles.
"""

from __future__ import annotations

import functools
from typing import Any, Iterable, Mapping

import jax
import jax.numpy as jnp
import optax
from flax import core, struct

from fedcrack_tpu.configs import ModelConfig, SdarMoeConfig
from fedcrack_tpu.data.pipeline import as_model_batch, normalize_images
from fedcrack_tpu.fed.algorithms import fedprox_penalty
from fedcrack_tpu.models import ResUNet
from fedcrack_tpu.ops.losses import iou_from_counts
from fedcrack_tpu.ops.pallas_bce import fused_segmentation_metrics
from fedcrack_tpu.tasks import SegmentationTask, task_for


class TrainState(struct.PyTreeNode):
    """Carries params + optimizer state + BN batch_stats through jit."""

    step: jax.Array
    params: core.FrozenDict[str, Any]
    batch_stats: core.FrozenDict[str, Any]
    opt_state: optax.OptState
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    apply_fn: Any = struct.field(pytree_node=False)
    # What ``train_step`` trains (fedcrack_tpu.tasks): unpack, train-mode
    # forward, loss. ``apply_fn`` stays the model's own inference forward.
    task: Any = struct.field(pytree_node=False, default_factory=SegmentationTask)

    @property
    def variables(self) -> dict:
        return {"params": self.params, "batch_stats": self.batch_stats}

    def replace_variables(self, variables: Mapping[str, Any]) -> "TrainState":
        """Inject global weights (params + BN stats) received from the server."""
        return self.replace(
            params=variables["params"], batch_stats=variables["batch_stats"]
        )


def make_optimizer(learning_rate: float = 1e-3) -> optax.GradientTransformation:
    """Adam with Keras-default hyperparameters (the reference compiles with
    optimizer="Adam", client_fit_model.py:157). Single source of truth for
    BOTH execution planes — the host/gRPC path here and the one-program mesh
    round in ``fedcrack_tpu.parallel`` must train identically."""
    return optax.adam(learning_rate, b1=0.9, b2=0.999, eps=1e-7)


def create_train_state(
    rng: jax.Array,
    model_config: ModelConfig | SdarMoeConfig | None = None,
    learning_rate: float = 1e-3,
) -> TrainState:
    """Build the model once with the shared optimizer; the task follows
    from the model configuration's family (``tasks.task_for``)."""
    task = task_for(model_config or ModelConfig())
    variables = task.init(rng)
    tx = make_optimizer(learning_rate)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=variables["params"],
        batch_stats=variables["batch_stats"],
        opt_state=tx.init(variables["params"]),
        tx=tx,
        apply_fn=task.model.apply,
        task=task,
    )


# NB: no buffer donation — `anchor_params` aliases `state.params` in the
# plain-FedAvg call, and donating aliased inputs is undefined.
@jax.jit
def train_step(
    state: TrainState,
    batch: tuple[jax.Array, jax.Array],
    anchor_params: core.FrozenDict[str, Any],
    mu: jax.Array,
    pos_weight: jax.Array = 1.0,
) -> tuple[TrainState, dict[str, jax.Array]]:
    """One SGD step: BCE + (mu/2)||params - anchor||^2, BN stats updated.

    For plain FedAvg pass ``anchor_params=state.params`` and ``mu=0.0`` —
    same compiled program either way. ``pos_weight`` (traced, default 1 =
    reference parity) up-weights crack pixels against the ~7% foreground
    imbalance. Batches may arrive as uint8 transport bytes (1/4 the
    host->device traffic, ``data.pipeline.as_model_batch``) — the on-device
    normalization reproduces the float32 staging values bit for bit (step
    outputs then differ only by XLA's usual program-to-program
    reduction-order noise). When the model config selects a space-to-depth
    ``stem_layout``, images may additionally arrive pre-packed
    (``data.pipeline.space_to_depth_images``) — the model accepts either
    layout; masks are always full-resolution.
    """
    task = state.task
    inputs, targets = task.unpack(batch)

    def loss_fn(params):
        outputs, new_stats = task.apply(params, state.batch_stats, inputs)
        metrics = task.loss_and_metrics(outputs, targets, pos_weight=pos_weight)
        prox = fedprox_penalty(params, anchor_params, mu)
        return metrics["loss"] + prox, (metrics, new_stats)

    (loss, (metrics, new_stats)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        state.params
    )
    updates, new_opt_state = state.tx.update(grads, state.opt_state, state.params)
    new_params = optax.apply_updates(state.params, updates)
    metrics = dict(metrics)
    metrics["loss"] = loss
    new_state = state.replace(
        step=state.step + 1,
        params=new_params,
        batch_stats=new_stats,
        opt_state=new_opt_state,
    )
    return new_state, metrics


@jax.jit
def eval_step(
    state: TrainState,
    batch: tuple[jax.Array, jax.Array],
    pos_weight: jax.Array = 1.0,
) -> dict[str, jax.Array]:
    """Inference-mode metrics (running BN stats). ``pos_weight`` must match
    the training objective: selecting checkpoints by unweighted val loss
    while training a weighted objective would prefer exactly the
    low-recall models the weighting exists to avoid."""
    images, masks = as_model_batch(*batch)
    logits = state.apply_fn(state.variables, images, train=False)
    return fused_segmentation_metrics(logits, masks, pos_weight=pos_weight)


def evaluate(
    state: TrainState, batches: Iterable, pos_weight: float = 1.0
) -> dict[str, float]:
    """Aggregate metrics over a validation set: loss/acc averaged per batch,
    IoU from summed global counts (exact, shard-composable)."""
    pw_arr = jnp.asarray(pos_weight, jnp.float32)
    n = 0
    loss = acc = inter = union = 0.0
    for batch in batches:
        m = eval_step(state, batch, pw_arr)
        loss += float(m["loss"])
        acc += float(m["pixel_acc"])
        inter += float(m["iou_inter"])
        union += float(m["iou_union"])
        n += 1
    if n == 0:
        raise ValueError("empty evaluation set")
    return {
        "loss": loss / n,
        "pixel_acc": acc / n,
        "iou": float(iou_from_counts(jnp.float32(inter), jnp.float32(union))),
        "num_batches": n,
    }


@functools.lru_cache(maxsize=8)
def _calibration_forward(model_config: ModelConfig):
    """Jitted momentum-0 train-mode forward, cached per model config so
    per-epoch recalibration never re-traces the U-Net."""
    model = ResUNet(config=model_config, bn_momentum=0.0)

    @jax.jit
    def moments_of(params, batch_stats, images):
        _, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            normalize_images(images),
            train=True,
            mutable=["batch_stats"],
        )
        return mutated["batch_stats"]

    return moments_of


def recalibrate_batch_stats(
    state: TrainState,
    batches: Iterable,
    model_config: ModelConfig | None = None,
) -> TrainState:
    """Re-estimate BatchNorm running statistics from data (SWA-style BN
    re-estimation): train-mode forwards with momentum 0 yield each batch's
    exact moments; their average replaces the carried running stats. Uses
    images only — labels never enter the calibration.

    Why this exists: Keras-parity BN momentum is 0.99 (the reference relies
    on the default, client_fit_model.py:92-150), so running stats need
    ~500 steps to converge. The reference trains ~3880 steps per round and
    never notices; a short local fit — or a freshly FedAvg-averaged global
    model, whose running stats are a mixture of clients' — evaluates with
    near-initialization statistics and predicts garbage in inference mode.
    One pass over a calibration set fixes the stats without touching params.
    """
    moments_of = _calibration_forward(model_config or ModelConfig())
    # Datasets advance their shuffle epoch on every iteration; calibration is
    # order-independent and must not perturb the training shuffle sequence
    # (a seeded run has to reproduce bit-for-bit with calibration on or off).
    epoch_snapshot = getattr(batches, "_epoch", None)
    try:
        acc = None
        n = 0
        for images, _ in batches:
            stats = moments_of(state.params, state.batch_stats, jnp.asarray(images))
            acc = (
                stats
                if acc is None
                else jax.tree_util.tree_map(jnp.add, acc, stats)
            )
            n += 1
    finally:
        if epoch_snapshot is not None:
            batches._epoch = epoch_snapshot
    if n == 0:
        raise ValueError("empty calibration set")
    mean_stats = jax.tree_util.tree_map(lambda a: a / n, acc)
    return state.replace(batch_stats=mean_stats)


def local_fit(
    state: TrainState,
    train_batches: Iterable,
    epochs: int,
    mu: float = 0.0,
    anchor_params: core.FrozenDict[str, Any] | None = None,
    prefetch: int = 2,
    pos_weight: float = 1.0,
) -> tuple[TrainState, dict[str, float]]:
    """One federated client's local fit for a round.

    The reference runs ``fit(train_gen, epochs=10, ...)`` per round
    (client_fit_model.py:166). ``train_batches`` is re-iterated per epoch
    (fresh shuffle each time); batches prefetch to device ahead of compute.
    Returns the trained state and mean train metrics of the final epoch.
    """
    from fedcrack_tpu.data.pipeline import device_prefetch

    anchor = anchor_params if anchor_params is not None else state.params
    mu_arr = jnp.asarray(mu, jnp.float32)
    pw_arr = jnp.asarray(pos_weight, jnp.float32)
    last: dict[str, float] = {}
    for _ in range(max(1, epochs)):
        n = 0
        acc: dict[str, float] = {}
        for batch in device_prefetch(train_batches, prefetch):
            state, metrics = train_step(state, batch, anchor, mu_arr, pw_arr)
            n += 1
            for k, v in metrics.items():
                acc[k] = acc.get(k, 0.0) + float(v)
        if n == 0:
            raise ValueError("empty training set")
        last = {k: v / n for k, v in acc.items()}
        last["num_steps"] = n
    return state, last


def count_samples(num_batches: int, batch_size: int) -> int:
    """Sample count used to weight this client in FedAvg."""
    return num_batches * batch_size
