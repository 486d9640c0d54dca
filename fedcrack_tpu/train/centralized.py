"""Centralized (non-federated) baseline trainer.

Capability parity with the reference's standalone training script
(reference: test/Segmentation.py): train the same U-Net on the full dataset
for N epochs with a held-out validation split, keep the best-val-loss
weights (the reference's ``ModelCheckpoint(save_best_only=True)`` to
``crack_segmentation.h5``, test/Segmentation.py:177-179), and save the final
weights. Checkpoints are msgpack pytrees, not h5/pickle; the h5 importer in
``fedcrack_tpu.tools`` bridges real Keras checkpoints in.
"""

from __future__ import annotations

import os
from typing import Iterable

import jax

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.fed.serialization import tree_to_bytes
from fedcrack_tpu.ioutils import atomic_write_bytes
from fedcrack_tpu.train.local import (
    TrainState,
    create_train_state,
    evaluate,
    local_fit,
    recalibrate_batch_stats,
)


def train_centralized(
    train_batches: Iterable,
    val_batches: Iterable,
    model_config: ModelConfig | None = None,
    epochs: int = 60,
    learning_rate: float = 1e-3,
    out_dir: str | None = None,
    seed: int = 0,
    log_fn=print,
    recalibrate_bn: bool = True,
    pos_weight: float = 1.0,
    metrics=None,
) -> tuple[TrainState, list[dict]]:
    """Returns the final state and per-epoch history; writes
    ``best.msgpack`` (lowest val loss) and ``final.msgpack`` to ``out_dir``.

    ``recalibrate_bn`` re-estimates BatchNorm running statistics from the
    train set before every validation pass and checkpoint (one extra forward
    sweep per epoch): with Keras-parity BN momentum 0.99 the running stats
    need ~500 steps to converge, so short runs would otherwise select the
    "best" checkpoint with near-initialization statistics. The saved
    checkpoints carry the calibrated stats; the in-training running-stat
    dynamics are untouched.
    """
    state = create_train_state(jax.random.key(seed), model_config, learning_rate)
    history: list[dict] = []
    best_loss = float("inf")
    eval_state = state
    for epoch in range(epochs):
        state, train_metrics = local_fit(
            state, train_batches, epochs=1, pos_weight=pos_weight
        )
        eval_state = (
            recalibrate_batch_stats(state, train_batches, model_config)
            if recalibrate_bn
            else state
        )
        # Same objective as training: weighted val loss drives best-checkpoint
        # selection, otherwise pos_weight>1 runs would checkpoint the
        # low-recall model the weighting exists to avoid.
        val_metrics = evaluate(eval_state, val_batches, pos_weight=pos_weight)
        entry = {
            "epoch": epoch,
            **{f"train_{k}": v for k, v in train_metrics.items()},
            **{f"val_{k}": v for k, v in val_metrics.items()},
        }
        history.append(entry)
        if metrics is not None:
            # Structured per-epoch record (JSONL + TB scalars) — the
            # reference's TensorBoard-per-fit workflow
            # (client_fit_model.py:153-154) for the centralized entry point.
            metrics.log("epoch", **entry)
        log_fn(
            f"epoch {epoch}: train_loss={train_metrics['loss']:.4f} "
            f"val_loss={val_metrics['loss']:.4f} val_iou={val_metrics['iou']:.4f}"
        )
        if out_dir and val_metrics["loss"] < best_loss:
            best_loss = val_metrics["loss"]
            _save(eval_state, os.path.join(out_dir, "best.msgpack"))
    if out_dir:
        _save(eval_state, os.path.join(out_dir, "final.msgpack"))
    return eval_state, history


def _build_datasets(args, model_config: ModelConfig):
    """Train/val datasets from real paired dirs or synthetic fixtures,
    preserving the reference's split semantics (held-out validation tail,
    test/Segmentation.py:84-90)."""
    from fedcrack_tpu.data.pipeline import (
        ArrayDataset,
        dataset_from_source,
        reference_split,
    )
    from fedcrack_tpu.data.synthetic import synth_crack_batch

    if args.synthetic:
        if args.synthetic < 2:
            raise SystemExit("--synthetic needs at least 2 samples (train + val)")
        n_val = max(1, args.synthetic // 5)
        images, masks = synth_crack_batch(
            args.synthetic, model_config.img_size, seed=args.seed
        )
        train = ArrayDataset(
            images[n_val:],
            masks[n_val:],
            batch_size=min(args.batch, args.synthetic - n_val),
            seed=args.seed,
        )
        val = ArrayDataset(
            images[:n_val], masks[:n_val], batch_size=min(args.batch, n_val), seed=args.seed
        )
        return train, val
    # Real dirs: the reference's seeded split, val = held-out tail
    # (test/Segmentation.py:84-90). The shared builder clamps batch sizes so
    # a small validation tail still yields batches, and a split side that
    # comes back empty (e.g. a single-pair directory) is a clear startup
    # error rather than a crash.
    def split_side(i):
        def pick(pairs):
            return reference_split(pairs, args.train_samples, args.split_seed)[i]

        return pick

    try:
        train = dataset_from_source(
            0, args.image_dir, args.mask_dir,
            img_size=model_config.img_size, batch_size=args.batch,
            seed=args.seed, pair_filter=split_side(0),
            transport_dtype=args.transport_dtype,
        )
        val = dataset_from_source(
            0, args.image_dir, args.mask_dir,
            img_size=model_config.img_size, batch_size=args.batch,
            seed=args.seed, pair_filter=split_side(1),
            transport_dtype=args.transport_dtype,
        )
    except ValueError as e:
        raise SystemExit(str(e))
    return train, val


def main(argv=None) -> None:
    """``python -m fedcrack_tpu.train.centralized`` — the reference's
    standalone trainer (test/Segmentation.py) as a real CLI."""
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--image-dir")
    p.add_argument("--mask-dir")
    p.add_argument("--synthetic", type=int, default=0, help="use N generated samples")
    p.add_argument("--epochs", type=int, default=60)  # test/Segmentation.py:185
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--img-size", type=int, default=128)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument(
        "--pos-weight",
        type=float,
        default=1.0,
        help="crack-pixel BCE weight (>1 counters foreground imbalance; "
        "1 = the reference's plain BCE)",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--transport-dtype",
        choices=("uint8", "float32"),
        default="uint8",
        help="host->device staging dtype for file datasets; uint8 ships 1/4 "
        "the bytes and is bit-identical (normalization happens on device)",
    )
    p.add_argument("--train-samples", type=int, default=6213)
    p.add_argument("--split-seed", type=int, default=1337)
    p.add_argument("--out-dir", default="centralized_out")
    p.add_argument(
        "--metrics", dest="metrics_path", help="JSONL file for per-epoch metrics"
    )
    p.add_argument(
        "--tb-dir",
        dest="tb_dir",
        help="TensorBoard event-file directory for per-epoch scalars (the "
        "reference's TB-per-fit workflow, client_fit_model.py:153-154)",
    )
    args = p.parse_args(argv)

    from fedcrack_tpu.jaxcompat import describe_devices, enable_compilation_cache

    enable_compilation_cache()
    print(f"jax devices: {describe_devices()}")

    metrics = None
    if args.metrics_path or args.tb_dir:
        from fedcrack_tpu.obs import MetricsLogger

        metrics = MetricsLogger(
            args.metrics_path or os.devnull, tb_dir=args.tb_dir or None
        )
    model_config = ModelConfig(img_size=args.img_size)
    train, val = _build_datasets(args, model_config)
    _, history = train_centralized(
        train,
        val,
        model_config=model_config,
        epochs=args.epochs,
        learning_rate=args.lr,
        out_dir=args.out_dir,
        seed=args.seed,
        pos_weight=args.pos_weight,
        metrics=metrics,
    )
    best = min(h["val_loss"] for h in history)
    print(f"done: {len(history)} epochs, best val_loss={best:.4f} -> {args.out_dir}")


def _save(state: TrainState, path: str) -> None:
    atomic_write_bytes(path, tree_to_bytes(state.variables))


if __name__ == "__main__":
    main()
