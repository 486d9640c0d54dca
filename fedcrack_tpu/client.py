"""Federated client entry point: ``python -m fedcrack_tpu.client``.

The reference equivalent is ``python fl_client.py`` (fl_client.py:178-188):
open a channel and run one federated session. The local dataset comes from
``--image-dir/--mask-dir`` (paired crack images, reference layout) or
``--synthetic N`` (generated fixtures). After the final round the client runs
prediction + crack quantification on its validation split — the reference
intended this but crashed on a missing method (client_fit_model.py:215,
SURVEY.md §2.2(5)).
"""

from __future__ import annotations

import argparse
import logging
import sys
import zlib

from fedcrack_tpu.configs import FedConfig
from fedcrack_tpu.data.pipeline import dataset_from_source, reference_split
from fedcrack_tpu.train.federated import make_train_fn
from fedcrack_tpu.transport.client import FedClient, default_cname


def main(argv: list[str] | None = None) -> int:
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(message)s")
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", help="JSON FedConfig file")
    p.add_argument("--host")
    p.add_argument("--port", type=int)
    p.add_argument("--name", help="client name (default: random unique)")
    p.add_argument("--image-dir")
    p.add_argument("--mask-dir")
    p.add_argument("--synthetic", type=int, default=0, help="use N generated samples")
    p.add_argument(
        "--transport-dtype",
        choices=("uint8", "float32"),
        default="uint8",
        help="host->device staging dtype for file datasets; uint8 ships 1/4 "
        "the bytes and is bit-identical (normalization happens on device)",
    )
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--num-clients",
        type=int,
        default=None,
        help="total cohort size for data sharding: each client takes a "
        "disjoint shard of the train split (cfg.data.partition: iid or "
        "crack-density skew — the reference gave every client the same "
        "data). Defaults to the config's cohort_size when --client-index "
        "is given.",
    )
    p.add_argument(
        "--client-index", type=int, default=None, help="this client's shard row"
    )
    p.add_argument("--predict-dir", help="write final-round mask predictions here")
    p.add_argument("--metrics", dest="metrics_path", help="JSONL metrics file")
    p.add_argument(
        "--tb-dir",
        dest="tb_dir",
        help="TensorBoard event-file directory for per-round local-fit "
        "scalars (the reference's TB callback, client_fit_model.py:153-154)",
    )
    p.add_argument(
        "--profile-dir",
        dest="profile_dir",
        help="jax.profiler trace dir wrapping each round's local fit",
    )
    p.add_argument(
        "--auth-token",
        dest="auth_token",
        help="shared enrollment token (must match the server's)",
    )
    p.add_argument(
        "--allow-insecure-token",
        dest="allow_insecure_token",
        action="store_const",
        const=True,
        default=None,
        help="accept --auth-token over a plaintext channel (the secret then "
        "travels in cleartext on every message; loopback/testing only)",
    )
    p.add_argument(
        "--tls-ca",
        dest="tls_ca",
        help="root CA (PEM) to verify the server over TLS; plaintext if unset",
    )
    p.add_argument("--tls-cert", dest="tls_cert", help="client certificate for mTLS (PEM)")
    p.add_argument("--tls-key", dest="tls_key", help="client private key for mTLS (PEM)")
    p.add_argument(
        "--max-message-mb",
        type=int,
        dest="max_message_mb",
        help="gRPC send/receive cap in MiB (must cover the server's dense "
        "weight broadcast regardless of the negotiated upload codec)",
    )
    p.add_argument(
        "--dp-clip-norm",
        type=float,
        dest="dp_clip_norm",
        help="update-level local DP (McMahan et al. 2018): clip this "
        "round's (trained - base) delta to this L2 norm before upload "
        "(0 disables)",
    )
    p.add_argument(
        "--dp-noise-multiplier",
        type=float,
        dest="dp_noise_multiplier",
        help="update-level DP noise: one seeded Gaussian N(0, "
        "(sigma*clip)^2) draw added to the clipped delta; the seed is "
        "derived from (dp_seed, name, round) so retried uploads are "
        "bit-identical",
    )
    p.add_argument(
        "--dp-seed",
        type=int,
        dest="dp_seed",
        help="root seed of the per-(client, round) DP noise derivation",
    )
    args = p.parse_args(argv)

    from fedcrack_tpu.jaxcompat import describe_devices, enable_compilation_cache

    enable_compilation_cache()
    logging.info("jax devices: %s", describe_devices())

    # Flags merge into the RAW config dict before FedConfig construction, so
    # __post_init__ validation sees the final merged config (a --tls-ca or
    # --allow-insecure-token flag must be able to rescue a config file that
    # would fail the plaintext-token check on its own).
    if args.config:
        import json

        with open(args.config) as f:
            raw = json.load(f)
    else:
        raw = {}
    overrides = {
        k: v
        for k, v in [
            ("host", args.host),
            ("port", args.port),
            ("metrics_path", args.metrics_path),
            ("tb_dir", args.tb_dir),
            ("profile_dir", args.profile_dir),
            ("auth_token", args.auth_token),
            ("allow_insecure_token", args.allow_insecure_token),
            ("tls_ca", args.tls_ca),
            ("tls_cert", args.tls_cert),
            ("tls_key", args.tls_key),
            ("max_message_mb", args.max_message_mb),
            ("dp_clip_norm", args.dp_clip_norm),
            ("dp_noise_multiplier", args.dp_noise_multiplier),
            ("dp_seed", args.dp_seed),
        ]
        if v is not None
    }
    raw.update(overrides)
    cfg = FedConfig.from_dict(raw)

    batch = cfg.data.batch_size
    if args.num_clients is not None:
        num_clients = args.num_clients
    elif args.client_index is not None:
        num_clients = cfg.cohort_size  # the presets' cohort IS the shard count
    else:
        num_clients = 1
    if num_clients > 1 and args.client_index is None:
        # Defaulting to shard 0 here would pin EVERY client to the same
        # shard and silently leave the rest of the data untrained.
        p.error("--num-clients > 1 requires --client-index")
    client_index = args.client_index if args.client_index is not None else 0
    cname = args.name or default_cname()
    data_seed = args.seed + client_index
    if args.synthetic and args.client_index is None and cfg.cohort_size > 1:
        # Without --client-index every synthetic cohort member would get the
        # same seed and train IDENTICAL data — the reference flaw the
        # sharding work fixes, silently reproduced by the quickstart. Derive
        # the seed from the unique client name instead so each member
        # synthesizes a distinct shard.
        data_seed = args.seed + zlib.crc32(cname.encode())
        logging.warning(
            "synthetic data with no --client-index in a %d-member cohort: "
            "deriving the data seed (%d) from client name %r so cohort "
            "members train distinct shards; pass --client-index for "
            "reproducible sharding",
            cfg.cohort_size,
            data_seed,
            cname,
        )
    if num_clients == 1 and cfg.cohort_size > 1 and not args.synthetic:
        logging.warning(
            "data sharding is OFF (every client would train the same data, "
            "like the reference): pass --client-index (and optionally "
            "--num-clients) so each of the %d cohort members takes a "
            "disjoint shard",
            cfg.cohort_size,
        )

    def local_shard(pairs):
        # Train side of the reference's seeded split
        # (client_fit_model.py:76-82), then this client's disjoint shard:
        # IID or crack-density skew (configs/c4_noniid_fedprox.json). Every client
        # computes the same deterministic assignment and picks its row.
        from fedcrack_tpu.data.sharding import shard_pairs

        train_pairs, _ = reference_split(
            pairs, cfg.data.train_samples, cfg.data.split_seed
        )
        return shard_pairs(
            train_pairs,
            num_clients,
            client_index,
            partition=cfg.data.partition,
            alpha=cfg.data.skew_alpha,
            seed=cfg.data.split_seed,
        )

    try:
        dataset = dataset_from_source(
            # Synthetic shards differ per client through the seed.
            args.synthetic,
            args.image_dir,
            args.mask_dir,
            img_size=cfg.model.img_size,
            batch_size=batch,
            seed=data_seed,
            num_workers=cfg.data.num_workers,
            prefetch=cfg.data.prefetch,
            pair_filter=local_shard,
            transport_dtype=args.transport_dtype,
        )
    except ValueError as e:
        p.error(str(e))

    metrics_logger = None
    if cfg.metrics_path or cfg.tb_dir:
        import os

        from fedcrack_tpu.obs import MetricsLogger

        metrics_logger = MetricsLogger(
            cfg.metrics_path or os.devnull, tb_dir=cfg.tb_dir or None
        )
    train_fn, holder = make_train_fn(
        cfg, dataset, batch, seed=args.seed, metrics_logger=metrics_logger
    )
    if cfg.dp_clip_norm > 0:
        # Update-level local DP (privacy plane, round 23): clip + noise the
        # round delta on the host before it ever reaches the wire — the
        # server and other clients only see the privatized update. The
        # noise key derives from (dp_seed, name, round), so a retried
        # upload of the same round is bit-identical, never double-noised.
        from fedcrack_tpu.fed.serialization import tree_from_bytes, tree_to_bytes
        from fedcrack_tpu.privacy.dpsgd import dp_update_host

        inner_train_fn = train_fn

        def train_fn(blob, rnd, *rest):
            out_blob, n_samples, metrics = inner_train_fn(blob, rnd, *rest)
            base = tree_from_bytes(blob)
            trained = tree_from_bytes(out_blob, template=base)
            private = dp_update_host(
                trained,
                base,
                clip_norm=cfg.dp_clip_norm,
                noise_multiplier=cfg.dp_noise_multiplier,
                dp_seed=cfg.dp_seed,
                cname=cname,
                round_idx=rnd,
            )
            return tree_to_bytes(private), n_samples, metrics

    client = FedClient(cfg, train_fn, cname=cname)
    result = client.run_session()
    if metrics_logger is not None:
        metrics_logger.log(
            "session",
            enrolled=result.enrolled,
            rounds_completed=result.rounds_completed,
        )
        metrics_logger.close()
    if cfg.metrics_path and result.enrolled:
        # Ship the complete per-round metrics JSONL — session summary
        # included, hence after the logger closes — to the coordinator's log
        # sink (reference C2.1/C1.5: its 'L' upload path existed but was
        # never called, fl_client.py:110-118). Best-effort: the server only
        # lingers briefly after FIN.
        try:
            client.upload_file(cfg.metrics_path)
        except Exception:
            logging.warning("metrics upload failed", exc_info=True)
    logging.info(
        "session done: enrolled=%s rounds=%d", result.enrolled, result.rounds_completed
    )
    for entry in result.history:
        logging.info("round metrics: %s", entry)

    if args.predict_dir and result.final_weights is not None:
        from fedcrack_tpu.tools.quantify import predict_and_quantify

        report = predict_and_quantify(
            holder["state"], dataset, out_dir=args.predict_dir
        )
        logging.info("crack quantification: %s", report)
    return 0 if result.enrolled else 1


if __name__ == "__main__":
    sys.exit(main())
