"""A federated round as ONE compiled XLA program.

The reference runs a round as N processes x (3880 Python-driven Keras steps)
followed by a server-side numpy loop over pickled weight lists
(reference: client_fit_model.py:166, fl_server.py:92-105). Here the entire
round is a single ``shard_map`` over ``Mesh(('clients', 'batch'))``:

- each client's local fit is a ``lax.scan`` over its batches (epochs as an
  outer scan) — no Python in the loop, one compilation for all rounds;
- gradients ``lax.pmean`` over the ``batch`` axis (intra-client DP);
- FedAvg is a **masked, sample-weighted ``lax.psum`` over the ``clients``
  axis**: dropped-out clients carry ``active=0`` and the divisor is
  ``psum(active * n_samples)``, so a shrunken cohort needs no recompilation
  (SURVEY.md §7 "masked/variable cohort psum").

BatchNorm moving statistics are carried per client and averaged with the
kernels, matching the reference's implicit behavior (``get_weights()``
includes BN moments — SURVEY.md §7 "hard parts"). BatchNorm is
**sync-BN over the ``batch`` axis** (flax ``axis_name``), so the round is
invariant to how a client's batch is split across its DP shards — the
(clients=C, batch=B) mesh trains exactly like (clients=C, batch=1).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedcrack_tpu.compress.codecs import encoded_bytes_model
from fedcrack_tpu.compress.mesh import (
    int8_roundtrip,
    topk_roundtrip,
    validate_mesh_codec,
)
from fedcrack_tpu.configs import ModelConfig, SdarMoeConfig
from fedcrack_tpu.fed.algorithms import fedprox_penalty
from fedcrack_tpu.tasks import SegmentationTask, task_for
from fedcrack_tpu.train.local import make_optimizer

CLIENTS, BATCH = "clients", "batch"

# The ordered cohort fold moved to fed/aggregation.py (round 21) — the one
# module owning "how updates combine" owns the mesh instance too. Aliased
# under the historical names so every traced program here is the identical
# expression tree (the r13 groups_bitwise_equal contract is unchanged);
# ``axis_name`` defaults to "clients" == CLIENTS.
from fedcrack_tpu.fed.aggregation import (  # noqa: E402
    mesh_finish_cohort_mean as _finish_cohort_mean,
    mesh_ordered_fold as _ordered_cohort_sums,
    mesh_zero_sums as _zero_sums_like,
)


def _host_view(x) -> np.ndarray | None:
    """Host-fetchable float32 view of a cohort mask/weight vector, or None
    when ``x`` is a cross-process sharded jax.Array whose global value this
    process cannot fetch (multi-host jobs — the in-mesh empty-cohort guard
    covers that case)."""
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        return None
    return np.asarray(x, np.float32)


def _epoch_runner(
    task, tx, apply_fn, inner_axis, n_inner, anchor, mu_arr, pw_arr,
    weight_transform=None, dp=None,
):
    """The per-client local-fit core, shared OP FOR OP by the monolithic
    round (``_build_round``) and the epoch-segmented variant
    (``_build_round_segments``): returns ``run_epochs(carry, chunks,
    n_epochs)`` scanning ``sgd_step`` over each step-axis data chunk in
    order (carry threaded across chunks) inside an outer epoch scan.

    ``task`` (``fedcrack_tpu.tasks``) is what the step trains: how a staged
    batch unpacks, the loss with its statistics and how those reduce.
    ``apply_fn`` is the task's train-mode forward, or the builder's own
    sharded form of it (remat-wrapped, halo-exchanging).

    Sharing this closure is what makes "segmented == monolithic, byte for
    byte" hold by construction rather than by parallel maintenance: a
    single-chunk call is exactly the historical monolithic epoch body, and
    splitting one scan into consecutive scans with the carry threaded
    through is the identical step sequence (test-pinned).

    ``weight_transform`` (round 20, the lowp twin): an optional traceable
    map applied to the params INSIDE the loss — the forward computes with
    ``weight_transform(params)`` (e.g. the straight-through int8 fake-quant
    of ``kernels.dequant.fake_quant_params``) while the optimizer, FedProx
    anchor and FedAvg all keep operating on the float32 master weights.
    ``None`` leaves the traced program byte-identical to a pre-r20 build
    (the conditional is Python-level — the codec-twin discipline).

    ``dp`` (round 23, the DP-SGD twin — fedcrack_tpu/privacy/dpsgd.py):
    ``None`` leaves the program untouched (the same Python-level-
    conditional discipline, test-pinned); otherwise a dict ``{"clip",
    "sigma", "seed", "round_seed", "client_index"}`` turns on per-step
    gradient clipping + seeded Gaussian noise right after the grads/
    n_inner divide. The noise key chain is (dp_seed, round_seed, client,
    step) — the round seed is the replicated per-dispatch scalar the int8
    codec already threads (restored on chaos replay via ``codec_state``),
    the step counter rides the scan carry (dp-on only).
    """
    if dp is not None:
        from fedcrack_tpu.privacy.dpsgd import dp_grad_transform, dp_step_key

    def sgd_step(carry, batch):
        if dp is None:
            params, batch_stats, opt_state = carry
        else:
            params, batch_stats, opt_state, dp_step = carry
        # The segmentation task accepts uint8 transport bytes (1/4 the
        # staging traffic); the on-device normalization reproduces float32
        # staging values bit for bit (data.pipeline.as_model_batch).
        with jax.named_scope("unpack"):
            inputs, targets = task.unpack(batch)

        def loss_fn(p):
            if weight_transform is None:
                p_eff = p
            else:
                with jax.named_scope("lowp"):
                    p_eff = weight_transform(p)
            outputs, new_stats = apply_fn(p_eff, batch_stats, inputs)
            with jax.named_scope("loss"):
                m = task.loss_and_metrics(outputs, targets, pos_weight=pw_arr)
                prox = fedprox_penalty(p, anchor, mu_arr)
                return m["loss"] + prox, (m, new_stats)

        (loss, (m, new_stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        # `params` is unvarying over the inner axis, so shard_map's AD
        # already psums the per-shard cotangents; dividing by the shard
        # count turns that sum of local-mean gradients into the gradient
        # of the client's full mean loss (a pmean here would be an
        # identity on the already-summed value and double-count).
        # CAUTION: that AD-inserted psum spans ONLY the inner axis — not
        # the clients axis — solely because the lax.scan carry makes
        # params clients-VARYING after step one (carry-vma unification
        # promotes the whole carry; in the segmented variant the carry
        # arrives already clients-sharded, the same varying state). For
        # fully replicated params the AD psum spans ALL mesh axes
        # (spatial.py's scan-free step divides by the product of both
        # axis sizes for exactly that reason). If this round is ever
        # restructured without the scan, the divisor must change;
        # test_dp_gradient_not_double_counted pins the current behavior.
        with jax.named_scope("grad_scale"):
            if not task.check_vma and n_inner > 1:
                # Without varying-axes tracking no psum was inserted: sum
                # the shards' gradients here (a Python-level branch).
                grads = lax.psum(grads, inner_axis)
            grads = jax.tree_util.tree_map(lambda g: g / n_inner, grads)
        if dp is not None:
            # DP-SGD (Abadi et al. 2016): clip the client's mean gradient
            # to L2 norm C, then add N(0, (sigma*C)^2) noise keyed per
            # (client, round, step, leaf) — replay-identical by seed chain.
            with jax.named_scope("dp"):
                key = dp_step_key(
                    dp["seed"], dp["round_seed"], dp["client_index"], dp_step
                )
                grads = dp_grad_transform(grads, key, dp["clip"], dp["sigma"])
        # BN moments are already pmean-synced inside the forward; this
        # keeps the carried stats bitwise identical across inner shards.
        with jax.named_scope("bn_sync"):
            new_stats = lax.pmean(new_stats, inner_axis)
        with jax.named_scope("optimizer"):
            updates, new_opt_state = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
        with jax.named_scope("step_metrics"):
            metrics = {"loss": lax.pmean(loss, inner_axis)}
            for name, how in task.metric_reductions:
                across = lax.pmean if how == "mean" else lax.psum
                metrics[name] = across(m[name], inner_axis)
        if dp is None:
            return (new_params, new_stats, new_opt_state), metrics
        return (new_params, new_stats, new_opt_state, dp_step + 1), metrics

    def epoch_reductions(step_metrics):
        with jax.named_scope("round_metrics"):
            reduced = {"loss": jnp.mean(step_metrics["loss"])}
            for name, how in task.metric_reductions:
                over_steps = jnp.mean if how == "mean" else jnp.sum
                reduced[name] = over_steps(step_metrics[name], axis=0)
            # The epoch's per-step loss as the scan stacked it, [steps]:
            # the curve whose mean is "loss" above.
            reduced["step_loss"] = step_metrics["loss"].astype(jnp.float32)
            return reduced

    def run_epochs(carry, chunks, n_epochs, idx=None):
        if idx is not None:
            # Resident (gather-assembly) mode: `chunks` is the single
            # ``(pool_images, pool_masks)`` device-resident pool, `idx` the
            # ``[epochs, steps, B]`` int32 gather plan. Each step jnp.takes
            # its batch from the pool — pure data movement, so the gathered
            # batch is byte-identical to the host-assembled slab batch the
            # streamed path stages (pool[idx] on host == take(pool, idx) on
            # device) — then runs the SAME sgd_step closure. The epoch scan
            # consumes one idx row per epoch (epoch-constant rows reproduce
            # the streamed round's reuse-one-slab-per-epoch semantics).
            if len(chunks) != 1:
                raise ValueError("resident mode takes exactly one pool chunk")
            if idx.shape[0] != n_epochs:
                raise ValueError(
                    f"idx carries {idx.shape[0]} epochs, round runs {n_epochs}"
                )
            pool_imgs, pool_msks = chunks[0]

            def gather_epoch(carry, epoch_idx):
                def gather_step(c, step_idx):
                    with jax.named_scope("gather"):
                        batch = (
                            jnp.take(pool_imgs, step_idx, axis=0),
                            jnp.take(pool_msks, step_idx, axis=0),
                        )
                    return sgd_step(c, batch)

                carry, step_metrics = lax.scan(gather_step, carry, epoch_idx)
                return carry, epoch_reductions(step_metrics)

            return lax.scan(gather_epoch, carry, idx)

        def epoch_body(carry, _):
            parts = []
            for imgs, msks in chunks:
                carry, part = lax.scan(sgd_step, carry, (imgs, msks))
                parts.append(part)
            # Single-chunk (monolithic) keeps the historical graph exactly;
            # multi-chunk concatenates the stacked per-step metrics back
            # into one [steps] axis so the epoch reductions below see the
            # same array a monolithic scan would have produced.
            with jax.named_scope("round_metrics"):
                step_metrics = (
                    parts[0]
                    if len(parts) == 1
                    else jax.tree_util.tree_map(
                        lambda *xs: jnp.concatenate(xs), *parts
                    )
                )
            return carry, epoch_reductions(step_metrics)

        return lax.scan(epoch_body, carry, None, length=n_epochs)

    return run_epochs


def _aggregate_and_guard(
    params, batch_stats, fallback_params, fallback_stats, active_i, n_i
):
    """Masked sample-weighted FedAvg over the clients axis, with the in-mesh
    empty-cohort guard: when every client dropped out return the round's
    incoming global model unchanged instead of an all-zero mean. Shared by
    the monolithic round's tail and the segmented variant's finalize program
    (same ops, same order). Round 13: the reduction is the ORDERED client
    fold (``_ordered_cohort_sums``), not a psum, so a time-multiplexed
    cohort accumulating group partials reproduces this tail bitwise."""
    with jax.named_scope("fold"):
        w = active_i * n_i
        update = {"params": params, "batch_stats": batch_stats}
        num, total_w = _ordered_cohort_sums(update, w, _zero_sums_like(update))
        return _finish_cohort_mean(
            num, total_w, {"params": fallback_params, "batch_stats": fallback_stats}
        )


def _require_axes(mesh: Mesh, *axes: str) -> None:
    missing = [a for a in axes if a not in mesh.shape]
    if missing:
        raise ValueError(
            f"mesh has axes {mesh.axis_names}, but this round builder needs "
            f"{axes} (missing {missing})"
        )


def _tree_sub(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b
    )


def _tree_add_cast(base, delta):
    return jax.tree_util.tree_map(
        lambda b, d: (b.astype(jnp.float32) + d.astype(jnp.float32)).astype(b.dtype),
        base,
        delta,
    )


def _build_round(
    mesh: Mesh,
    task,
    learning_rate: float,
    local_epochs: int,
    fedprox_mu: float,
    *,
    inner_axis: str,
    image_spec: P,
    apply_fn=None,
    validate_data=None,
    pos_weight: float = 1.0,
    remat: bool = False,
    data_placement: str = "streamed",
    update_codec: str | None = None,
    topk_fraction: float = 0.01,
    lowp: str | None = None,
    dp_clip_norm: float = 0.0,
    dp_noise_multiplier: float = 0.0,
    dp_seed: int = 0,
):
    """Shared core of the one-program federated round.

    Both public builders are this skeleton with a different intra-client
    sharding: ``task`` (``fedcrack_tpu.tasks``) is what the round trains,
    ``apply_fn(params, batch_stats, inputs) -> (outputs, new_batch_stats)``
    its train-mode forward (``task.apply`` unless the builder brings its own:
    the halo-exchange spatial forward), ``inner_axis`` is the mesh axis the
    client's work is split over (``batch`` or ``space``), and ``image_spec``
    shards the data accordingly.

    ``data_placement="resident"`` (plain rounds only) swaps the data
    contract from staged epoch slabs to a device-resident sample pool plus
    a per-round gather plan: ``round_fn(variables, (pool_images,
    pool_masks), idx, active, n_samples)`` where the pool pair is
    ``[C, N, ...]`` sharded ``P('clients')`` and ``idx`` is
    ``[C, epochs, steps, B]`` int32 with the per-step batch ``B`` split
    over the inner axis. Each step gathers its batch from the pool on
    device and runs the identical sgd_step closure, so the round is
    byte-identical to the streamed round over ``pool[idx]`` (test-pinned).

    ``remat=True`` wraps the forward in ``jax.checkpoint``: the backward
    pass recomputes activations instead of keeping the whole U-Net's
    feature maps live through the scan — the standard HBM/FLOPs trade for
    crops or per-chip batches that don't otherwise fit (~1/2 the
    activation footprint for ~1/3 more forward FLOPs).
    """
    tx = make_optimizer(learning_rate)
    mu = float(fedprox_mu)
    pw = float(pos_weight)
    apply_fn = apply_fn or task.apply
    validate_data = validate_data or task.validate
    if remat:
        # prevent_cse=False is documented-safe (and faster) when the
        # checkpointed function is differentiated inside lax.scan — which is
        # the only place apply_fn is ever differentiated here (sgd_step).
        apply_fn = jax.checkpoint(apply_fn, prevent_cse=False)
    n_client_shards = mesh.shape[CLIENTS]
    n_inner = mesh.shape[inner_axis]
    resident = data_placement == "resident"
    if data_placement not in ("streamed", "resident"):
        raise ValueError(
            f"data_placement must be 'streamed' or 'resident', got {data_placement!r}"
        )
    # On-device update-compression twin (round 12, compress/mesh.py): apply
    # the codec's encode∘decode value map to each client's round delta
    # BEFORE the FedAvg psum, so the mesh trajectory reflects exactly what
    # the gRPC plane's compressed uploads would aggregate to — at zero host
    # cost. "null" leaves the traced program UNTOUCHED (the conditionals
    # below are Python-level, so the null build is byte-identical to a
    # pre-codec build — test-pinned).
    codec = validate_mesh_codec(update_codec)
    if not 0.0 < topk_fraction <= 1.0:
        raise ValueError(f"topk_fraction must be in (0, 1], got {topk_fraction}")
    topk = codec == "topk_delta"
    # Low-precision training twin (round 20, kernels/dequant.py): the local
    # fit's forward computes with straight-through int8 fake-quant weights —
    # the same quantize/dequant math the fused serve plane loads — while the
    # optimizer and FedAvg keep the float32 masters. Same null-build
    # discipline as the codec: None/"null" leaves the traced program
    # byte-identical to a pre-r20 build (Python-level conditional,
    # test-pinned); monolithic-only, like the codec twin.
    if lowp in (None, "null"):
        lowp = "null"
        weight_transform = None
    elif lowp == "fake_quant_int8":
        from fedcrack_tpu.kernels.dequant import fake_quant_params

        weight_transform = fake_quant_params
    else:
        raise ValueError(
            f"lowp must be None, 'null' or 'fake_quant_int8', got {lowp!r}"
        )
    # DP-SGD twin (round 23, privacy/dpsgd.py): per-step clip + seeded
    # Gaussian noise inside sgd_step. Same null-build discipline as the
    # codec and lowp twins — dp off (clip_norm == 0) leaves the traced
    # program byte-identical (test-pinned); monolithic-only.
    if dp_clip_norm < 0.0:
        raise ValueError(f"dp_clip_norm must be >= 0, got {dp_clip_norm}")
    if dp_noise_multiplier < 0.0:
        raise ValueError(
            f"dp_noise_multiplier must be >= 0, got {dp_noise_multiplier}"
        )
    dp_on = dp_clip_norm > 0.0
    if dp_noise_multiplier > 0.0 and not dp_on:
        raise ValueError(
            "dp_noise_multiplier > 0 requires dp_clip_norm > 0 (noise is "
            "calibrated to the clip norm)"
        )
    # The replicated per-dispatch seed scalar feeds int8's stochastic
    # rounding AND the DP noise chain; either consumer pulls it in.
    needs_seed = codec == "int8" or dp_on
    # Normalised at build time: these are static Python config scalars and
    # must stay host casts OUTSIDE the shard_map'd body (TRACE001).
    dp_clip_f = float(dp_clip_norm)
    dp_sigma_f = float(dp_noise_multiplier)
    dp_seed_i = int(dp_seed)

    # `extras` is the side channel: the P('clients')-sharded error-feedback
    # pytree for topk_delta (first), then the replicated per-call seed
    # scalar (int8 stochastic rounding / DP round seed), absent for null.
    def client_fit(variables, data_a, data_b, active, n_samples, *extras):
        # Per-shard blocks: leading clients-axis block is exactly one client.
        # Streamed: data_a/data_b are the [C, steps, B, ...] epoch slabs.
        # Resident: data_a is the (pool_images, pool_masks) pair, data_b the
        # [C, epochs, steps, B] gather plan.
        if resident:
            chunk = (data_a[0][0], data_a[1][0])
            idx = data_b[0]
        else:
            chunk = (data_a[0], data_b[0])
            idx = None
        active_i, n_i = active[0], n_samples[0]
        ei = 0
        ef_extra = None
        if topk:
            ef_extra = extras[ei]
            ei += 1
        seed_in = extras[ei] if needs_seed else None
        params = variables["params"]
        batch_stats = variables["batch_stats"]
        anchor = params  # FedProx anchor = this round's global weights
        with jax.named_scope("round_init"):
            opt_state = tx.init(params)
            mu_arr = jnp.asarray(mu, jnp.float32)
            pw_arr = jnp.asarray(pw, jnp.float32)

        dp = None
        if dp_on:
            dp = {
                "clip": dp_clip_f,
                "sigma": dp_sigma_f,
                "seed": dp_seed_i,
                "round_seed": seed_in,
                "client_index": lax.axis_index(CLIENTS),
            }
        run_epochs = _epoch_runner(
            task, tx, apply_fn, inner_axis, n_inner, anchor, mu_arr, pw_arr,
            weight_transform=weight_transform, dp=dp,
        )
        # The carry becomes client-varying after the first data-dependent
        # update; promote the (replicated) initial carry so scan's carry type
        # is stable under shard_map's varying-axes tracking. The dp-on carry
        # also threads the per-step noise counter (Python-level: absent from
        # the dp-off program).
        carry0 = (params, batch_stats, opt_state)
        if dp_on:
            carry0 = carry0 + (jnp.uint32(0),)
        with jax.named_scope("round_init"):
            carry = jax.tree_util.tree_map(
                lambda x: lax.pcast(x, (CLIENTS,), to="varying"), carry0
            )
        carry, per_epoch = run_epochs(
            carry, [chunk], max(1, local_epochs), idx=idx
        )
        params, batch_stats = carry[0], carry[1]

        ef_out = None
        with jax.named_scope("codec"):
            if codec == "int8":
                update = {"params": params, "batch_stats": batch_stats}
                base = {"params": anchor, "batch_stats": variables["batch_stats"]}
                # Per-client stochastic-rounding stream: the replicated per-call
                # seed folded with this shard's client index.
                key = jax.random.fold_in(
                    jax.random.PRNGKey(seed_in), lax.axis_index(CLIENTS)
                )
                update = _tree_add_cast(
                    base, int8_roundtrip(_tree_sub(update, base), key)
                )
                params, batch_stats = update["params"], update["batch_stats"]
            elif topk:
                update = {"params": params, "batch_stats": batch_stats}
                base = {"params": anchor, "batch_stats": variables["batch_stats"]}
                ef_block = jax.tree_util.tree_map(lambda x: x[0], ef_extra)
                kept, ef_new = topk_roundtrip(
                    _tree_sub(update, base), ef_block, topk_fraction
                )
                update = _tree_add_cast(base, kept)
                params, batch_stats = update["params"], update["batch_stats"]
                # EF advances only for ACTIVE clients: on the wire an inactive
                # client never encodes, so its residual is untouched — without
                # this gate the twin would bank residual mass from a delta the
                # round's active-mask discards and leak it into the client's
                # next active round, diverging from the host-codec semantics.
                is_active = active[0] > 0.0
                ef_new = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(is_active, new, old), ef_new, ef_block
                )
                ef_out = jax.tree_util.tree_map(lambda x: x[None], ef_new)

        new_variables = _aggregate_and_guard(
            params,
            batch_stats,
            anchor,
            variables["batch_stats"],
            active_i,
            n_i,
        )

        with jax.named_scope("round_metrics"):
            step_loss = per_epoch.pop("step_loss")
            last = jax.tree_util.tree_map(lambda a: a[-1], per_epoch)
            metrics = dict(
                task.round_metrics(last),
                active=active_i,
                # Every step's loss of every local epoch, [epochs, steps]:
                # the last row's mean is "loss".
                step_loss=step_loss,
            )
            # [1]-shaped leaves tile back onto the clients axis.
            metrics = jax.tree_util.tree_map(lambda a: a[None], metrics)
        if topk:
            return new_variables, metrics, ef_out
        return new_variables, metrics

    if resident:
        in_specs = (
            P(),
            (P(CLIENTS), P(CLIENTS)),  # pool pair: replicated over inner axis
            _idx_spec(inner_axis),
            P(CLIENTS),
            P(CLIENTS),
        )
    else:
        in_specs = (P(), image_spec, image_spec, P(CLIENTS), P(CLIENTS))
    # Side-channel specs, in the extras order client_fit unpacks: the
    # error-feedback accumulator rides through the program as a
    # P('clients')-sharded pytree (in as this round's residual, out as the
    # next round's — it never leaves device); one replicated uint32 seed
    # per call feeds int8's stochastic rounding and/or the DP noise chain.
    extra_specs: tuple = ()
    if topk:
        extra_specs += (P(CLIENTS),)
    if needs_seed:
        extra_specs += (P(),)
    sharded = jax.shard_map(
        client_fit,
        mesh=mesh,
        in_specs=in_specs + extra_specs,
        out_specs=(P(), P(CLIENTS), P(CLIENTS)) if topk else (P(), P(CLIENTS)),
        check_vma=task.check_vma,
    )
    # A task whose model is a large share of the chip's memory has the
    # incoming global model's buffers back the outgoing one's (the caller's
    # ``variables`` are consumed: run_mesh_federation threads the result).
    jitted = jax.jit(sharded, donate_argnums=(0,) if task.donate_variables else ())

    def _wire_bytes_per_client(variables) -> int:
        """Analytic wire bytes ONE client's upload would cost under this
        codec (compress.codecs.encoded_bytes_model) — the mesh plane never
        materializes host bytes, so the counter is a model, not a measure."""
        sizes = [
            int(leaf.size)
            for leaf in jax.tree_util.tree_leaves(
                {
                    "params": variables["params"],
                    "batch_stats": variables["batch_stats"],
                }
            )
        ]
        return encoded_bytes_model(sizes, codec, topk_fraction=topk_fraction)

    def _init_ef(variables):
        """Round-0 error-feedback state: per-client float32 zeros for every
        update leaf, placed sharded P('clients') — C model-sized copies of
        HBM, the price of faithful per-client DGC on the mesh."""
        zeros = jax.tree_util.tree_map(
            lambda t: np.zeros((n_client_shards,) + tuple(np.shape(t)), np.float32),
            {"params": variables["params"], "batch_stats": variables["batch_stats"]},
        )
        return jax.device_put(zeros, NamedSharding(mesh, P(CLIENTS)))

    ef_state: dict = {"ef": None, "calls": 0}

    def _dispatch(variables, *data_args):
        """Shared jitted-call tail: lazily prices the wire-bytes counter
        from the first call's leaf sizes and threads the codec side
        channel — the device-resident error-feedback state for the topk
        twin, or the call-counter seed for int8's stochastic rounding.
        Both commit as soon as the async dispatch returns — BEFORE a
        non-finite output can surface at the host fetch — so a replaying
        driver must restore ``codec_state()`` alongside its weights
        snapshot (parallel.driver does; the null twin carries no state)."""
        if round_fn.wire_bytes_per_client is None:
            round_fn.wire_bytes_per_client = _wire_bytes_per_client(variables)
        extras = []
        if topk:
            if ef_state["ef"] is None:
                ef_state["ef"] = _init_ef(variables)
            extras.append(ef_state["ef"])
        if needs_seed:
            extras.append(jnp.uint32(ef_state["calls"]))
        out = jitted(variables, *data_args, *extras)
        if needs_seed:
            ef_state["calls"] += 1
        if topk:
            new_vars, metrics, ef_new = out
            ef_state["ef"] = ef_new
            return new_vars, metrics
        return out

    if resident:

        def round_fn(variables, pool, idx, active, n_samples):
            _check_resident_inputs(
                pool, idx, n_client_shards, max(1, local_epochs),
                n_inner, validate_data,
            )
            active, n_samples = _host_cohort_check(active, n_samples)
            return _dispatch(variables, tuple(pool), idx, active, n_samples)

    else:

        def round_fn(variables, images, masks, active, n_samples):
            if images.shape[0] != n_client_shards:
                raise ValueError(
                    f"data carries {images.shape[0]} clients, mesh has "
                    f"{n_client_shards} on the '{CLIENTS}' axis"
                )
            validate_data(images)

            # Same contract as fed.algorithms.fedavg: an empty effective
            # cohort is an error, never a silently-zeroed global model. In a
            # multi-host job the mask arrives as a cross-process sharded
            # jax.Array whose global value THIS process cannot fetch — the
            # check then happens in-mesh instead (all-dropout returns the
            # incoming global model unchanged; see the `keep` guard in
            # client_fit).
            active, n_samples = _host_cohort_check(active, n_samples)
            return _dispatch(variables, images, masks, active, n_samples)

    # Drivers key on this tag to refuse a round/data-contract mismatch
    # before any bytes move (parallel.driver.run_mesh_federation).
    round_fn.data_placement = data_placement
    # What this round trains (fedcrack_tpu.tasks): its step_flops and metric
    # names go with the program.
    round_fn.task = task
    # Compressed-transport observability (round 12): which codec twin this
    # round simulates, the analytic per-client upload bytes under it
    # (priced on first call; parallel.driver folds it into
    # RoundRecord.bytes_per_round), and — for the topk twin — a reset hook
    # dropping the cross-round error-feedback state.
    round_fn.update_codec = codec
    # Which low-precision training twin this round runs ("null" = the exact
    # pre-r20 program).
    round_fn.lowp = lowp
    # Which DP twin this round runs ("null" = the exact pre-r23 program;
    # "dpsgd" = per-step clip + seeded noise in sgd_step). The seed counter
    # DP keys its rounds on is the codec_state "calls" field — replay
    # restores it with the rest of the codec state.
    round_fn.dp = "dpsgd" if dp_on else "null"
    round_fn.wire_bytes_per_client = None
    round_fn.reset_ef = lambda: ef_state.update(ef=None, calls=0)
    # Test hook: the device-resident EF pytree ([C, ...] per leaf), None
    # before the first topk dispatch. Read-only observability.
    round_fn.ef_state = lambda: ef_state["ef"]
    # Retry contract (r12 review fix): a failed round attempt surfaces
    # AFTER the async dispatch already committed this state (JAX defers
    # the non-finite discovery to the host fetch), so the driver's
    # replay path snapshots it alongside its weights snapshot and
    # restores it before the retry — otherwise the topk twin banks
    # residual mass from a round that was never applied (kept mass lost,
    # dropped mass double-counted) and the int8 seed counter drifts.
    # Shallow dict copy is a true snapshot: "ef" holds immutable jax
    # arrays (pointer copy suffices), "calls" an int. Restoring makes
    # the replayed attempt BIT-identical for every codec twin.
    round_fn.codec_state = lambda: dict(ef_state)
    round_fn.set_codec_state = lambda s: (
        ef_state.clear(), ef_state.update(s)
    )
    return round_fn


def _idx_spec(inner_axis: str) -> P:
    """Sharding of the ``[C, epochs, steps, B]`` gather plan: clients on the
    leading axis, the per-step batch split over the inner axis — the same
    per-shard batch the streamed ``P(clients, None, batch)`` slab delivers."""
    return P(CLIENTS, None, None, inner_axis)


def _check_resident_inputs(
    pool, idx, n_client_shards, epochs, n_inner, validate_data
) -> None:
    """Host-side validation of the resident round's data contract."""
    pool_imgs, pool_msks = pool
    if pool_imgs.shape[0] != n_client_shards:
        raise ValueError(
            f"pool carries {pool_imgs.shape[0]} clients, mesh has "
            f"{n_client_shards} on the '{CLIENTS}' axis"
        )
    if pool_imgs.shape[:2] != pool_msks.shape[:2]:
        raise ValueError(
            f"pool images/masks disagree on [C, N]: {pool_imgs.shape[:2]} "
            f"vs {pool_msks.shape[:2]}"
        )
    validate_data(pool_imgs)
    if idx.ndim != 4 or idx.shape[0] != n_client_shards:
        raise ValueError(
            f"idx must be [C={n_client_shards}, epochs, steps, B]; got "
            f"{tuple(idx.shape)}"
        )
    if idx.shape[1] != epochs:
        raise ValueError(
            f"idx carries {idx.shape[1]} epochs, the round runs {epochs}"
        )
    if idx.shape[-1] % n_inner:
        raise ValueError(
            f"per-step batch {idx.shape[-1]} does not divide over the "
            f"{n_inner}-way inner axis"
        )
    # Bounds-check the plan against the pool NOW: jnp.take's in-jit clip
    # mode would silently clamp an out-of-range index to a valid sample —
    # training on wrong data where the streamed fallback's numpy gather
    # raises — and a negative index would clamp to 0 where numpy wraps.
    # Either way the streamed==resident byte-identity contract breaks
    # silently; one host-side reduction over the KB-scale plan closes it.
    if isinstance(idx, jax.Array) and not idx.is_fully_addressable:
        return  # cross-process plan: this process cannot fetch it to check
    n_pool = pool_imgs.shape[1]
    lo, hi = int(np.min(idx)), int(np.max(idx))
    if lo < 0 or hi >= n_pool:
        raise ValueError(
            f"gather plan indexes [{lo}, {hi}] outside the {n_pool}-sample "
            "pool (jnp.take would silently clamp)"
        )


def _host_cohort_check(active, n_samples):
    """Raise on an all-dropped cohort where the mask is host-visible; return
    host float32 views when fetchable (multi-host sharded masks pass through
    untouched — the in-mesh ``keep`` guard covers them)."""
    active_h, n_samples_h = _host_view(active), _host_view(n_samples)
    if active_h is not None and n_samples_h is not None:
        if float(np.sum(active_h * n_samples_h)) <= 0.0:
            raise ValueError(
                "non-positive total FedAvg weight: every client dropped "
                f"out (active={active_h.tolist()}, "
                f"n_samples={n_samples_h.tolist()})"
            )
        return active_h, n_samples_h
    return active, n_samples


def build_federated_round(
    mesh: Mesh,
    model_config: ModelConfig | SdarMoeConfig | None = None,
    learning_rate: float = 1e-3,
    local_epochs: int = 1,
    fedprox_mu: float = 0.0,
    pos_weight: float = 1.0,
    remat: bool = False,
    data_placement: str = "streamed",
    update_codec: str | None = None,
    topk_fraction: float = 0.01,
    lowp: str | None = None,
    dp_clip_norm: float = 0.0,
    dp_noise_multiplier: float = 0.0,
    dp_seed: int = 0,
):
    """Compile-once round function over ``Mesh(('clients', 'batch'))``.

    Returns ``round_fn(variables, images, masks, active, n_samples)``:

    - ``variables``: the global ``{'params', 'batch_stats'}`` pytree
      (replicated over the mesh);
    - ``images``  float32 ``[C, steps, B, H, W, 3]``,
      ``masks``   float32 ``[C, steps, B, H, W, 1]`` — per-client local data,
      ``C == mesh.shape['clients']``; the per-step batch ``B`` is split over
      the ``batch`` axis (must divide evenly);
    - ``active``  float32 ``[C]`` participation mask (1 = reported, 0 =
      dropped out mid-round);
    - ``n_samples`` float32 ``[C]`` per-client sample counts (FedAvg
      weights).

    Returns ``(new_variables, per_client_metrics)`` where metrics leaves are
    ``[C]`` arrays from each client's final local epoch. Adam state is fresh
    each round (the reference rebuilds its model per round,
    client_fit_model.py:155-157; here only the optimizer moments reset).

    Transformed layouts: when ``model_config.stem_layout`` is a
    space-to-depth variant, ``images`` may instead arrive PRE-PACKED as
    ``[C, steps, B, H/2, W/2, 4*ch]`` (``data.pipeline.space_to_depth_images``
    — same bytes, packed on the host instead of on device); the round
    program consumes either staging layout (pick one per federation — the
    two compile to different programs). Masks stay full-resolution always.

    ``data_placement="resident"`` switches to the gather-assembly data
    contract (round 9): ``round_fn(variables, (pool_images, pool_masks),
    idx, active, n_samples)`` over a device-resident
    ``data.pipeline.SamplePool`` placement and a ``[C, epochs, steps, B]``
    int32 gather plan — byte-identical to this streamed round over
    ``pool[idx]`` (test-pinned), at kilobytes of per-round staging instead
    of the full epoch slab.

    ``update_codec`` (round 12): ``None``/``"null"`` leaves the program
    untouched (byte-identical to a pre-codec build, test-pinned);
    ``"int8"``/``"topk_delta"`` apply the on-device encode∘decode twin of
    the wire codec to each client's round delta before the FedAvg psum
    (``compress.mesh``), so ``run_mesh_federation`` A/Bs compressed-
    trajectory quality at zero host cost. The topk twin carries its
    per-client error-feedback accumulator device-resident across calls
    (``round_fn.reset_ef()`` drops it); the returned ``round_fn`` also
    tags ``update_codec`` and prices ``wire_bytes_per_client`` on first
    call for the driver's ``bytes_per_round`` counter. The codec twin is
    monolithic-only — ``build_federated_round_segments`` has no codec arg.

    ``lowp`` (round 20): ``None``/``"null"`` leaves the program untouched
    (byte-identical build, same discipline as the codec); ``"fake_quant_int8"``
    runs every local-fit forward with straight-through int8 fake-quant
    weights (``kernels.dequant.fake_quant_params`` — the quantize/dequant
    math the fused serve plane loads), optimizer/anchor/FedAvg staying on
    the float32 masters. Trajectory pinned within the r12 int8-mesh-twin
    IoU tolerance vs the reference round (tests/test_kernels.py).
    Monolithic-only, like the codec twin.

    ``dp_clip_norm``/``dp_noise_multiplier``/``dp_seed`` (round 23, the
    DP-SGD twin — ``fedcrack_tpu/privacy/dpsgd.py``): ``dp_clip_norm=0``
    leaves the program untouched (byte-identical build, test-pinned, same
    discipline as the codec twin); ``> 0`` clips each client's per-step
    mean gradient to that L2 norm inside ``sgd_step`` and (when
    ``dp_noise_multiplier > 0``) adds ``N(0, (multiplier*clip)^2)`` noise
    keyed per (dp_seed, round, client, step, leaf). The round axis of the
    key chain is the same replicated per-dispatch seed scalar the int8
    codec threads, restored on driver replay via ``codec_state()`` — a
    chaos-retried round reproduces bit-identical noise (test-pinned).
    Monolithic-only, like the codec and lowp twins.
    """
    _require_axes(mesh, CLIENTS, BATCH)
    return _build_round(
        mesh,
        task_for(model_config or ModelConfig(), bn_axis_name=BATCH),
        learning_rate,
        local_epochs,
        fedprox_mu,
        inner_axis=BATCH,
        image_spec=P(CLIENTS, None, BATCH),
        pos_weight=pos_weight,
        remat=remat,
        data_placement=data_placement,
        update_codec=update_codec,
        topk_fraction=topk_fraction,
        lowp=lowp,
        dp_clip_norm=dp_clip_norm,
        dp_noise_multiplier=dp_noise_multiplier,
        dp_seed=dp_seed,
    )


def _as_chunks(x) -> tuple:
    """Normalize a round data argument to a tuple of step-axis chunks: a
    single ``[C, steps, B, ...]`` array is one chunk; a tuple/list of such
    arrays is consumed as consecutive step ranges (their concatenation
    along axis 1 is the monolithic layout)."""
    if isinstance(x, (tuple, list)):
        if not x:
            raise ValueError("empty chunk list for round data")
        return tuple(x)
    return (x,)


@dataclasses.dataclass(frozen=True)
class SegmentedRound:
    """An epoch-segmented federated round: K device-resident-carry segment
    programs instead of one monolithic K*epochs-steps scan.

    The monolithic round (``build_federated_round``) compiles the whole
    ``local_epochs x steps`` trajectory plus FedAvg into ONE XLA program —
    great for dispatch overhead, but it forces round-grain staging (the
    full epoch slab must land before any step runs), caps staging/compute
    overlap at round grain, and at 256 px the 3,880-step program is a
    very large compile. This variant
    splits the trajectory into ``n_segments`` programs of
    ``segment_epochs`` epochs each; the per-client ``(params, batch_stats,
    opt_state)`` carry stays ON DEVICE between segments as a
    ``P('clients')``-sharded pytree and is DONATED to the next segment
    call, so the split costs K-1 extra dispatches and zero extra HBM.

    Byte-exactness contract (test-pinned): for any K dividing
    ``local_epochs`` — and any step-axis chunking of the data — the final
    global weights AND the returned metrics are bit-identical to the
    monolithic round on the same inputs. The segment body is the SAME
    closure the monolithic round traces (``_epoch_runner``), the carry
    crosses program boundaries as pure data movement, and the finalize
    program runs the same masked-psum FedAvg tail.

    Calling the object is round_fn-compatible
    (``(variables, images, masks, active, n_samples) -> (new_variables,
    metrics)``, with ``images``/``masks`` each either one array or a tuple
    of step-axis chunks); ``parallel.driver.run_mesh_federation`` instead
    drives ``init``/``segment``/``finalize`` itself so next-round staging
    can stream at segment grain between dispatches.
    """

    n_segments: int
    segment_epochs: int
    local_epochs: int
    n_client_shards: int
    init_fn: Callable = dataclasses.field(repr=False)
    segment_fn: Callable = dataclasses.field(repr=False)
    finalize_fn: Callable = dataclasses.field(repr=False)
    validate_data: Callable = dataclasses.field(repr=False)
    # "streamed" (staged epoch-slab chunks) or "resident" (device-resident
    # sample pool + per-segment gather plans — see build_federated_round's
    # data_placement doc); drivers key on this to match the data contract.
    data_placement: str = "streamed"
    n_inner: int = 1
    # What the round trains (fedcrack_tpu.tasks): names the round's metrics.
    task: Any = dataclasses.field(default_factory=SegmentationTask, repr=False)

    def check_inputs(self, img_chunks, active, n_samples, idx=None):
        """Host-side validation mirroring the monolithic ``round_fn``;
        returns the (possibly host-viewed) cohort arrays. In resident mode
        ``img_chunks`` is the ``(pool_images, pool_masks)`` pair and ``idx``
        the full-round ``[C, local_epochs, steps, B]`` gather plan."""
        if self.data_placement == "resident":
            _check_resident_inputs(
                img_chunks, idx, self.n_client_shards, self.local_epochs,
                self.n_inner, self.validate_data,
            )
            return _host_cohort_check(active, n_samples)
        for c in img_chunks:
            if c.shape[0] != self.n_client_shards:
                raise ValueError(
                    f"data carries {c.shape[0]} clients, mesh has "
                    f"{self.n_client_shards} on the '{CLIENTS}' axis"
                )
        self.validate_data(img_chunks[0])
        return _host_cohort_check(active, n_samples)

    def init(self, variables):
        """Fresh per-client carry from the round's global variables (Adam
        state zeroed — the reference rebuilds its model per round)."""
        return self.init_fn(variables)

    def segment(self, carry, variables, img_chunks, msk_chunks):
        """Run one segment (``segment_epochs`` epochs over all chunks).
        ``carry`` is DONATED — the caller must thread the returned carry
        and never reuse the argument. Returns ``(carry, raw_last)`` where
        ``raw_last`` is the segment's last-epoch metric counts ([C] each).
        Resident mode: ``img_chunks`` is the pool pair, ``msk_chunks`` the
        segment's ``[C, segment_epochs, steps, B]`` gather-plan slice."""
        if self.data_placement == "resident":
            return self.segment_fn(
                carry, variables, tuple(img_chunks), msk_chunks
            )
        return self.segment_fn(
            carry, variables, _as_chunks(img_chunks), _as_chunks(msk_chunks)
        )

    def finalize(self, carry, variables, active, n_samples, raw_last):
        """Masked FedAvg over the clients axis plus the monolithic round's
        metrics dict from the last segment's counts."""
        # jnp.asarray (not np.asarray): a multi-host cohort mask arrives as
        # a cross-process sharded jax.Array that no single process can
        # fetch to host — the same passthrough contract the monolithic
        # round_fn honors (_host_cohort_check returns it untouched and the
        # in-mesh `keep` guard covers the empty-cohort case).
        active32 = jnp.asarray(active, jnp.float32)
        n32 = jnp.asarray(n_samples, jnp.float32)
        new_variables = self.finalize_fn(carry, variables, active32, n32)
        metrics = dict(
            self.task.round_metrics(raw_last),
            active=active32,
            step_loss=raw_last["step_loss"],
        )
        return new_variables, metrics

    @staticmethod
    def join_raws(raws: Sequence[dict]) -> dict:
        """The ``raw_last`` to hand to :meth:`finalize` from every segment's
        in order: the last segment's counts, with ``step_loss`` the segments'
        ``[C, segment_epochs, steps]`` pieces concatenated back into the
        round's ``[C, local_epochs, steps]`` (the monolithic round's array)."""
        if len(raws) == 1:
            return raws[0]
        return dict(
            raws[-1],
            step_loss=jnp.concatenate([r["step_loss"] for r in raws], axis=1),
        )

    def __call__(self, variables, images, masks, active, n_samples):
        if self.data_placement == "resident":
            # images = (pool_images, pool_masks), masks = the full-round
            # gather plan [C, local_epochs, steps, B]; each segment consumes
            # its own epochs-axis slice.
            pool, idx = tuple(images), masks
            active, n_samples = self.check_inputs(pool, active, n_samples, idx=idx)
            carry = self.init(variables)
            raws = []
            se = self.segment_epochs
            for k in range(self.n_segments):
                carry, raw = self.segment(
                    carry, variables, pool, idx[:, k * se : (k + 1) * se]
                )
                raws.append(raw)
            return self.finalize(
                carry, variables, active, n_samples, self.join_raws(raws)
            )
        img_chunks, msk_chunks = _as_chunks(images), _as_chunks(masks)
        active, n_samples = self.check_inputs(img_chunks, active, n_samples)
        carry = self.init(variables)
        raws = []
        for _ in range(self.n_segments):
            carry, raw = self.segment(carry, variables, img_chunks, msk_chunks)
            raws.append(raw)
        return self.finalize(
            carry, variables, active, n_samples, self.join_raws(raws)
        )


def _build_round_segments(
    mesh: Mesh,
    task,
    learning_rate: float,
    local_epochs: int,
    fedprox_mu: float,
    *,
    inner_axis: str,
    image_spec: P,
    pos_weight: float = 1.0,
    remat: bool = False,
    segments: int = 0,
    data_placement: str = "streamed",
) -> SegmentedRound:
    """Segmented twin of ``_build_round`` (same skeleton, same shared
    ``_epoch_runner``/``_aggregate_and_guard`` closures — see
    :class:`SegmentedRound` for the exactness contract)."""
    tx = make_optimizer(learning_rate)
    mu = float(fedprox_mu)
    pw = float(pos_weight)
    apply_fn, validate_data = task.apply, task.validate
    if remat:
        apply_fn = jax.checkpoint(apply_fn, prevent_cse=False)
    if data_placement not in ("streamed", "resident"):
        raise ValueError(
            f"data_placement must be 'streamed' or 'resident', got {data_placement!r}"
        )
    resident = data_placement == "resident"
    n_client_shards = mesh.shape[CLIENTS]
    n_inner = mesh.shape[inner_axis]
    epochs = max(1, local_epochs)
    n_segments = epochs if not segments else int(segments)
    if n_segments <= 0 or epochs % n_segments:
        raise ValueError(
            f"segments={segments!r} must be a positive divisor of "
            f"local_epochs={epochs} (epoch-grain segmentation)"
        )
    segment_epochs = epochs // n_segments

    def init_shard(variables):
        params = variables["params"]
        with jax.named_scope("round_init"):
            opt_state = tx.init(params)
            # Same promotion as the monolithic round's initial carry: the carry
            # is client-varying from the first data-dependent update on, and
            # here it must leave the program through a P('clients') out_spec.
            carry = jax.tree_util.tree_map(
                lambda x: lax.pcast(x, (CLIENTS,), to="varying"),
                (params, variables["batch_stats"], opt_state),
            )
            return jax.tree_util.tree_map(lambda x: x[None], carry)

    init_fn = jax.jit(
        jax.shard_map(
            init_shard, mesh=mesh, in_specs=(P(),), out_specs=P(CLIENTS),
            check_vma=task.check_vma,
        )
    )

    def segment_shard(carry, variables, img_chunks, msk_chunks):
        # Resident mode: img_chunks is the (pool_images, pool_masks) pair,
        # msk_chunks the segment's [C, segment_epochs, steps, B] gather plan.
        carry = jax.tree_util.tree_map(lambda x: x[0], carry)
        anchor = variables["params"]  # FedProx anchor = round-start globals
        mu_arr = jnp.asarray(mu, jnp.float32)
        pw_arr = jnp.asarray(pw, jnp.float32)
        run_epochs = _epoch_runner(
            task, tx, apply_fn, inner_axis, n_inner, anchor, mu_arr, pw_arr
        )
        if resident:
            chunks = [(img_chunks[0][0], img_chunks[1][0])]
            idx = msk_chunks[0]
        else:
            chunks = [(i[0], m[0]) for i, m in zip(img_chunks, msk_chunks)]
            idx = None
        carry, per_epoch = run_epochs(carry, chunks, segment_epochs, idx=idx)
        with jax.named_scope("round_metrics"):
            step_loss = per_epoch.pop("step_loss")
            last = jax.tree_util.tree_map(lambda a: a[-1], per_epoch)
            # This segment's epochs of the curve, [segment_epochs, steps]:
            # SegmentedRound.join_raws puts the segments back together.
            last["step_loss"] = step_loss
        return (
            jax.tree_util.tree_map(lambda x: x[None], carry),
            jax.tree_util.tree_map(lambda a: a[None], last),
        )

    if resident:
        seg_in_specs = (
            P(CLIENTS),
            P(),
            (P(CLIENTS), P(CLIENTS)),
            _idx_spec(inner_axis),
        )
    else:
        seg_in_specs = (P(CLIENTS), P(), image_spec, image_spec)
    segment_fn = jax.jit(
        jax.shard_map(
            segment_shard,
            mesh=mesh,
            in_specs=seg_in_specs,
            out_specs=(P(CLIENTS), P(CLIENTS)),
            check_vma=task.check_vma,
        ),
        # The previous segment's carry buffers back the next segment's: the
        # split adds zero steady-state HBM over the monolithic scan.
        donate_argnums=(0,),
    )

    def finalize_shard(carry, variables, active, n_samples):
        params, batch_stats, _ = jax.tree_util.tree_map(lambda x: x[0], carry)
        return _aggregate_and_guard(
            params,
            batch_stats,
            variables["params"],
            variables["batch_stats"],
            active[0],
            n_samples[0],
        )

    # No donation here: the finalize outputs (the replicated averaged tree)
    # cannot alias the clients-sharded carry blocks, so donating would only
    # emit "donated buffers were not usable" warnings; the carry dies by
    # refcount right after this call anyway.
    finalize_fn = jax.jit(
        jax.shard_map(
            finalize_shard,
            mesh=mesh,
            in_specs=(P(CLIENTS), P(), P(CLIENTS), P(CLIENTS)),
            out_specs=P(),
            check_vma=task.check_vma,
        )
    )

    return SegmentedRound(
        n_segments=n_segments,
        segment_epochs=segment_epochs,
        local_epochs=epochs,
        n_client_shards=n_client_shards,
        init_fn=init_fn,
        segment_fn=segment_fn,
        finalize_fn=finalize_fn,
        validate_data=validate_data,
        data_placement=data_placement,
        n_inner=n_inner,
        task=task,
    )


def build_federated_round_segments(
    mesh: Mesh,
    model_config: ModelConfig | SdarMoeConfig | None = None,
    learning_rate: float = 1e-3,
    local_epochs: int = 1,
    fedprox_mu: float = 0.0,
    pos_weight: float = 1.0,
    remat: bool = False,
    segments: int = 0,
    data_placement: str = "streamed",
) -> SegmentedRound:
    """Epoch-segmented variant of :func:`build_federated_round`.

    Same data contract and semantics (including ``data_placement`` — in
    resident mode each segment gathers from the shared device-resident
    pool by its own epochs-axis slice of the round's gather plan);
    ``segments`` (default 0 = one segment per local epoch) must divide
    ``local_epochs``. ``segments=1``
    still differs from the monolithic builder operationally — the carry
    crosses one program boundary and FedAvg runs as a separate finalize
    program — but the result is bit-identical (test-pinned), which makes
    K=1 the cheap cross-check of the whole mechanism.

    Why segment: staging can stream at segment grain under the in-flight
    segments (``parallel.driver``), each compiled program is
    ``1/n_segments`` the size (the 256 px reference-scale round compiles
    as 10 x 388-step programs), and carry donation keeps the split
    HBM-neutral.
    """
    _require_axes(mesh, CLIENTS, BATCH)
    return _build_round_segments(
        mesh,
        task_for(model_config or ModelConfig(), bn_axis_name=BATCH),
        learning_rate,
        local_epochs,
        fedprox_mu,
        inner_axis=BATCH,
        image_spec=P(CLIENTS, None, BATCH),
        pos_weight=pos_weight,
        remat=remat,
        segments=segments,
        data_placement=data_placement,
    )


def pad_cohort_axis(arr: np.ndarray, c_pad: int) -> np.ndarray:
    """Zero-pad the leading (cohort) axis of a per-client array to
    ``c_pad`` entries. Padding clients ride with ``active = 0`` /
    ``n_samples = 0``, so their weighted contribution to the ordered fold
    is ``±0.0`` — a bitwise no-op (see ``_ordered_cohort_sums``)."""
    arr = np.asarray(arr)
    c = arr.shape[0]
    if c >= c_pad:
        return arr
    pad = np.zeros((c_pad - c,) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad], axis=0)


@dataclasses.dataclass(frozen=True)
class CohortRound:
    """A time-multiplexed federated round: a cohort of C clients executed
    as ``ceil(C / G)`` SEQUENTIAL groups of ``G = mesh.shape['clients']``
    over the same mesh, with a device-resident partial-aggregate carry.

    The chip count bounds how many clients one mesh program can train at
    once; production cohorts are far larger (ROADMAP "Cohort scale: 8 →
    1,000+"). This round keeps the per-group training programs exactly the
    segmented round's (``_build_round_segments`` — same ``_epoch_runner``
    closure, same carry contract) and splits ONLY the aggregation: each
    group's ``partial`` program folds its clients' weighted updates into a
    replicated ``(num_tree, total_weight)`` carry via the ordered client
    fold, and one ``finish`` program divides + guards at the end.

    Byte-exactness contract (test-pinned for groups in {1, 2, 4}, with
    segments > 0): the final global weights AND the per-client metrics are
    bit-identical to the single-group mesh round over the same C-wide
    cohort whenever C fits the chip count — the ordered fold is ONE
    expression tree regardless of the group split (``_ordered_cohort_sums``
    explains why a psum could never give this), per-client local fits are
    mesh-width-independent, and metrics carry no cross-client reduction.
    Cohorts not divisible by G pad the last group with inactive zero-weight
    clients (bitwise no-ops in the fold, sliced out of the metrics).

    Calling the object is round_fn-compatible over FULL-COHORT arrays
    (``(variables, images [C, ...], masks, active [C], n_samples [C])``,
    or the resident pool/plan contract); ``parallel.driver.
    run_cohort_federation`` instead drives ``zeros``/``run_group``/
    ``finish`` itself so each group's slab (or resident pool slice) can
    stage right before its dispatch and release right after — peak staged
    HBM is ~2 GROUP slices, never the C-wide cohort.

    Update-codec twins are monolithic-only (same precedent as the
    segmented builder); the cohort round has no codec arg.
    """

    group_size: int
    n_segments: int
    segment_epochs: int
    local_epochs: int
    n_inner: int
    seg: SegmentedRound = dataclasses.field(repr=False)
    partial_fn: Callable = dataclasses.field(repr=False)
    zeros_fn: Callable = dataclasses.field(repr=False)
    finish_fn: Callable = dataclasses.field(repr=False)
    data_placement: str = "streamed"

    def n_groups(self, cohort_size: int) -> int:
        if cohort_size <= 0:
            raise ValueError(f"cohort_size must be positive, got {cohort_size}")
        return -(-cohort_size // self.group_size)

    def zeros(self, variables):
        """The round's initial partial-aggregate carry (f32 zeros),
        replicated on the mesh so every group program reads it in-place."""
        return self.zeros_fn(variables)

    def run_group(self, sums, variables, data_a, data_b, active_g, n_g):
        """Train ONE group of G clients (init → ``n_segments`` segment
        programs) and fold its weighted updates into the partial-aggregate
        carry. Streamed: ``data_a``/``data_b`` are the group's ``[G, steps,
        B, ...]`` slab pair; resident: the ``(pool_images, pool_masks)``
        pair and the group's ``[G, local_epochs, steps, B]`` plan. Returns
        ``(sums', raw_last)`` where ``raw_last`` is the group's last-epoch
        metric counts ([G] leaves). An all-inactive group (pure padding)
        is legal and leaves ``sums`` bitwise unchanged."""
        carry = self.seg.init(variables)
        raws = []
        se = self.segment_epochs
        for k in range(self.n_segments):
            if self.data_placement == "resident":
                plan = data_b[:, k * se : (k + 1) * se]
            else:
                plan = data_b
            carry, raw = self.seg.segment(carry, variables, data_a, plan)
            raws.append(raw)
        sums = self.partial_fn(sums, carry, active_g, n_g)
        return sums, self.seg.join_raws(raws)

    def finish(self, sums, variables, raw_lasts, active, cohort_size):
        """Divide the cross-group sums into the new global variables and
        assemble the per-client metrics from the concatenated group counts
        (padding lanes sliced off). Same expression tree as the monolithic
        round's in-program tail — bitwise equal on equal inputs."""
        new_variables = self.finish_fn(sums, variables)
        last = jax.tree_util.tree_map(
            lambda *xs: np.concatenate([np.asarray(x) for x in xs])[:cohort_size],
            *raw_lasts,
        )
        last = {k: jnp.asarray(v) for k, v in last.items()}
        active32 = jnp.asarray(np.asarray(active)[:cohort_size], jnp.float32)
        metrics = dict(
            self.seg.task.round_metrics(last),
            active=active32,
            step_loss=last["step_loss"],
        )
        return new_variables, metrics

    def _padded_cohort(self, active, n_samples):
        active = np.asarray(active, np.float32)
        n_samples = np.asarray(n_samples, np.float32)
        c = active.shape[0]
        c_pad = self.n_groups(c) * self.group_size
        return (
            pad_cohort_axis(active, c_pad),
            pad_cohort_axis(n_samples, c_pad),
            c,
            c_pad,
        )

    def __call__(self, variables, images, masks, active, n_samples):
        if self.data_placement == "resident":
            pool, idx = tuple(images), np.asarray(masks, np.int32)
            c = idx.shape[0]
            _check_resident_inputs(
                pool, idx, c, self.local_epochs, self.n_inner,
                self.seg.validate_data,
            )
            _host_cohort_check(active, n_samples)
            active, n_samples, c, c_pad = self._padded_cohort(active, n_samples)
            pool_i = pad_cohort_axis(pool[0], c_pad)
            pool_m = pad_cohort_axis(pool[1], c_pad)
            idx = pad_cohort_axis(idx, c_pad)
            sums = self.zeros(variables)
            raw_lasts = []
            g = self.group_size
            for lo in range(0, c_pad, g):
                sums, raw = self.run_group(
                    sums,
                    variables,
                    (pool_i[lo : lo + g], pool_m[lo : lo + g]),
                    idx[lo : lo + g],
                    active[lo : lo + g],
                    n_samples[lo : lo + g],
                )
                raw_lasts.append(raw)
            return self.finish(sums, variables, raw_lasts, active, c)
        images = np.asarray(images)
        masks = np.asarray(masks)
        if images.shape[0] != np.asarray(active).shape[0]:
            raise ValueError(
                f"data carries {images.shape[0]} clients, cohort mask "
                f"{np.asarray(active).shape[0]}"
            )
        self.seg.validate_data(images)
        _host_cohort_check(active, n_samples)
        active, n_samples, c, c_pad = self._padded_cohort(active, n_samples)
        images = pad_cohort_axis(images, c_pad)
        masks = pad_cohort_axis(masks, c_pad)
        sums = self.zeros(variables)
        raw_lasts = []
        g = self.group_size
        for lo in range(0, c_pad, g):
            sums, raw = self.run_group(
                sums,
                variables,
                images[lo : lo + g],
                masks[lo : lo + g],
                active[lo : lo + g],
                n_samples[lo : lo + g],
            )
            raw_lasts.append(raw)
        return self.finish(sums, variables, raw_lasts, active, c)


def build_federated_cohort_round(
    mesh: Mesh,
    model_config: ModelConfig | SdarMoeConfig | None = None,
    learning_rate: float = 1e-3,
    local_epochs: int = 1,
    fedprox_mu: float = 0.0,
    pos_weight: float = 1.0,
    remat: bool = False,
    segments: int = 1,
    data_placement: str = "streamed",
) -> CohortRound:
    """Time-multiplexed cohort variant of :func:`build_federated_round`
    (round 13): the returned :class:`CohortRound` executes any cohort size
    as sequential groups of ``mesh.shape['clients']`` with a
    device-resident partial-aggregate carry — byte-identical to a
    hypothetical cohort-wide mesh (see the class docstring for the
    contract and why the aggregation is an ordered fold, not a psum).

    ``segments`` is per GROUP (default 1: one training program per group —
    grouping already bounds program size); values > 1 stream exactly like
    :func:`build_federated_round_segments` and must divide
    ``local_epochs``. ``data_placement="resident"`` takes the pool/plan
    contract with a COHORT-wide pool, sliced per group
    (``parallel.driver.run_cohort_federation`` stages each slice right
    before its group's dispatch).
    """
    _require_axes(mesh, CLIENTS, BATCH)
    seg = _build_round_segments(
        mesh,
        task_for(model_config or ModelConfig(), bn_axis_name=BATCH),
        learning_rate,
        local_epochs,
        fedprox_mu,
        inner_axis=BATCH,
        image_spec=P(CLIENTS, None, BATCH),
        pos_weight=pos_weight,
        remat=remat,
        segments=segments,
        data_placement=data_placement,
    )

    def partial_shard(sums, carry, active, n_samples):
        params, batch_stats, _ = jax.tree_util.tree_map(lambda x: x[0], carry)
        with jax.named_scope("fold"):
            w = active[0] * n_samples[0]
            return _ordered_cohort_sums(
                {"params": params, "batch_stats": batch_stats}, w, sums
            )

    partial_fn = jax.jit(
        jax.shard_map(
            partial_shard,
            mesh=mesh,
            in_specs=(P(), P(CLIENTS), P(CLIENTS), P(CLIENTS)),
            out_specs=P(),
            check_vma=seg.task.check_vma,
        )
    )

    def zeros_fn(variables):
        update = {
            "params": variables["params"],
            "batch_stats": variables["batch_stats"],
        }
        zeros = (
            jax.tree_util.tree_map(
                lambda t: np.zeros(np.shape(t), np.float32), update
            ),
            np.zeros((), np.float32),
        )
        return jax.device_put(zeros, NamedSharding(mesh, P()))

    @jax.jit
    def finish_fn(sums, variables):
        num, total_w = sums
        with jax.named_scope("fold"):
            return _finish_cohort_mean(
                num,
                total_w,
                {
                    "params": variables["params"],
                    "batch_stats": variables["batch_stats"],
                },
            )

    return CohortRound(
        group_size=mesh.shape[CLIENTS],
        n_segments=seg.n_segments,
        segment_epochs=seg.segment_epochs,
        local_epochs=seg.local_epochs,
        n_inner=seg.n_inner,
        seg=seg,
        partial_fn=partial_fn,
        zeros_fn=zeros_fn,
        finish_fn=finish_fn,
        data_placement=data_placement,
    )


def build_spatial_federated_round(
    mesh: Mesh,
    model_config: ModelConfig | None = None,
    learning_rate: float = 1e-3,
    local_epochs: int = 1,
    fedprox_mu: float = 0.0,
    pos_weight: float = 1.0,
    remat: bool = False,
):
    """Federated round over a ``Mesh(('clients', 'space'))``: FedAvg across
    clients whose local fits are each **spatially sharded** over image
    height with halo exchange + sync-BN (``parallel.spatial``). This is the
    composition for crops too large for one chip per client — e.g. 8 chips
    = 4 clients x 2-way spatial — and trains identically to the plain
    (clients, batch=1) round on the same data (cross-checked in tests).

    Same signature/contract as :func:`build_federated_round`, with
    ``images [C, steps, B, H, W, 3]`` sharded ``P('clients', None, None,
    'space')``; H must be a multiple of 16 x n_space.
    """
    from fedcrack_tpu.parallel.spatial import SPACE, _validate_shape, spatial_apply

    model_config = model_config or ModelConfig()
    if model_config.stem_layout != "reference" or model_config.res_layout != "reference":
        # The spatial forward re-implements the reference op-by-op with halo
        # exchange (parallel.spatial's per-op geometry table); the layout
        # transforms repack H/W into channels, which would change every halo
        # width. Layout levers target the per-chip-resident planes.
        raise ValueError(
            "spatial sharding supports the reference layout only; got "
            f"stem_layout={model_config.stem_layout!r}, "
            f"res_layout={model_config.res_layout!r}"
        )
    _require_axes(mesh, CLIENTS, SPACE)
    n_space = mesh.shape[SPACE]

    def apply_fn(params, batch_stats, imgs):
        return spatial_apply(
            {"params": params, "batch_stats": batch_stats},
            imgs,
            config=model_config,
            axis_name=SPACE,
            axis_size=n_space,
            train=True,
            sync_axes=(SPACE,),
        )

    return _build_round(
        mesh,
        SegmentationTask(model_config),
        learning_rate,
        local_epochs,
        fedprox_mu,
        inner_axis=SPACE,
        apply_fn=apply_fn,
        image_spec=P(CLIENTS, None, None, SPACE),
        validate_data=lambda images: _validate_shape(
            images.shape[3], images.shape[4], n_space
        ),
        pos_weight=pos_weight,
        remat=remat,
    )


@jax.jit
def _weighted_mean(stacked: Any, w: jax.Array) -> Any:
    def leaf(x):
        acc = jnp.tensordot(w, x.astype(jnp.float32), axes=1)
        return acc.astype(x.dtype)

    return jax.tree_util.tree_map(leaf, stacked)


def mesh_fedavg(
    stacked: Any,
    weights: Sequence[float] | jax.Array | None = None,
    active: Sequence[float] | jax.Array | None = None,
) -> Any:
    """Masked weighted mean over the leading (client) axis of a stacked
    pytree — the host-callable form of the in-mesh aggregation, used as the
    golden cross-check against :func:`fedcrack_tpu.fed.algorithms.fedavg`
    (SURVEY.md §4: "mesh FedAvg == gRPC FedAvg == numpy mean")."""
    leaves = jax.tree_util.tree_leaves(stacked)
    if not leaves:
        raise ValueError("empty pytree")
    k = leaves[0].shape[0]
    w = (
        jnp.ones((k,), jnp.float32)
        if weights is None
        else jnp.asarray(weights, jnp.float32)
    )
    if active is not None:
        w = w * jnp.asarray(active, jnp.float32)
    total = float(jnp.sum(w))
    if total <= 0.0:
        raise ValueError("non-positive total FedAvg weight (empty effective cohort)")
    return _weighted_mean(stacked, w / total)


def stack_client_data(
    client_batches: Sequence[tuple[np.ndarray, np.ndarray]],
    steps: int,
    batch_size: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Pack per-client (images, masks) sample arrays into the round_fn layout
    ``[C, steps, B, H, W, ch]``, truncating/cycling each client's samples to
    exactly ``steps * batch_size`` (static shapes — SURVEY.md §7)."""
    need = steps * batch_size
    imgs_out, masks_out = [], []
    for images, masks in client_batches:
        n = images.shape[0]
        if n == 0:
            raise ValueError("client with zero samples")
        idx = np.resize(np.arange(n), need)  # cycle if short, truncate if long
        imgs_out.append(images[idx].reshape(steps, batch_size, *images.shape[1:]))
        masks_out.append(masks[idx].reshape(steps, batch_size, *masks.shape[1:]))
    return np.stack(imgs_out), np.stack(masks_out)
