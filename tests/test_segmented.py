"""Epoch-segmented round execution (round 7).

The non-negotiable gate: the segmented round — K device-resident-carry
segment programs threaded by a host loop — must be BYTE-identical to the
monolithic one-program round on the same inputs (same carry, same op
order), for any K dividing local_epochs and any step-axis chunking of the
staged data. Everything else (streamed staging, donation, the 2-epoch-slab
HBM bound, checkpoint resume) is pinned on top of that.
"""

import jax
import numpy as np
import pytest

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.data.pipeline import split_epoch_slab
from fedcrack_tpu.data.synthetic import synth_crack_batch
from fedcrack_tpu.parallel import (
    SegmentedRound,
    build_federated_round,
    build_federated_round_segments,
    make_mesh,
    run_mesh_federation,
    stack_client_data,
)
from fedcrack_tpu.train.local import create_train_state
from treecmp import assert_trees_equal as _assert_trees_bytes_equal, assert_trees_match

TINY = ModelConfig(
    img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
)
STEPS, BATCH, N_CLIENTS = 2, 4, 2
EPOCHS = 10  # the reference's local fit depth — K in {1, 2, 10} divides it


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(N_CLIENTS, 1)


@pytest.fixture(scope="module")
def data():
    per_client = [
        synth_crack_batch(STEPS * BATCH, img_size=TINY.img_size, seed=i)
        for i in range(N_CLIENTS)
    ]
    images, masks = stack_client_data(per_client, STEPS, BATCH)
    active = np.ones(N_CLIENTS, np.float32)
    n_samples = np.full(N_CLIENTS, float(STEPS * BATCH), np.float32)
    return images, masks, active, n_samples


@pytest.fixture(scope="module")
def variables():
    return create_train_state(jax.random.key(0), TINY).variables


@pytest.fixture(scope="module")
def monolithic_result(mesh, data, variables):
    round_fn = build_federated_round(
        mesh, TINY, learning_rate=1e-3, local_epochs=EPOCHS
    )
    new_vars, metrics = round_fn(variables, *data)
    return (
        jax.tree_util.tree_map(np.asarray, new_vars),
        jax.tree_util.tree_map(np.asarray, metrics),
    )


# The driver's chunk-streamed runs against the monolithic path, 3 rounds x 2
# epochs x STEPS steps at lr 1e-3 (gaps read on the CPU backend in
# test_driver_segmented_streaming_matches_monolithic's docstring).
DRIVER_LR, DRIVER_ROUNDS, DRIVER_EPOCHS = 1e-3, 3, 2
STREAMED_TOLERANCE = dict(
    atol=2e-6,
    shadowed_bias_atol=DRIVER_LR * DRIVER_ROUNDS * DRIVER_EPOCHS * STEPS,
    running_mean_atol=5e-4,
)


# K=10 (the flagship one-segment-per-epoch configuration) stays tier-1;
# K=1 (isolates the program-boundary carry round-trip) and K=2 are
# slow-marked — each K is a fresh set of XLA compiles, and on this 2-core
# host with 8 spin-waiting virtual devices the tier-1 wall-clock budget is
# the binding constraint (ROADMAP tier-1 command's 870 s timeout).
@pytest.mark.parametrize(
    "segments",
    [
        pytest.param(1, marks=pytest.mark.slow),
        pytest.param(2, marks=pytest.mark.slow),
        10,
    ],
)
def test_segmented_round_byte_identical(
    mesh, data, variables, monolithic_result, segments
):
    """Post-Adam global weights AND metrics from the segmented round match
    the monolithic round byte for byte, on the 8-device CPU mesh, for K in
    {1, 2, 10}. K=1 isolates the program-boundary carry round-trip; K=10
    is the flagship one-segment-per-epoch configuration."""
    seg = build_federated_round_segments(
        mesh, TINY, learning_rate=1e-3, local_epochs=EPOCHS, segments=segments
    )
    assert isinstance(seg, SegmentedRound)
    assert seg.n_segments == segments
    assert seg.segment_epochs == EPOCHS // segments
    new_vars, metrics = seg(variables, *data)
    want_vars, want_metrics = monolithic_result
    _assert_trees_bytes_equal(new_vars, want_vars)
    _assert_trees_bytes_equal(metrics, want_metrics)


@pytest.mark.slow
def test_segmented_round_chunked_data_byte_identical(
    mesh, data, variables, monolithic_result
):
    """Step-axis chunked staging (what the streaming driver feeds the
    round) changes nothing: consecutive scans with the carry threaded are
    the same step sequence as one scan over the concatenation. Slow-marked
    (the 2-chunk signature is a fresh compile); the chunked path is still
    pinned tier-1 END TO END by the streaming driver test below, whose
    run_mesh_federation stages 2 chunks per round."""
    images, masks, active, n_samples = data
    ic, mc = split_epoch_slab(images, masks, 2)
    assert len(ic) == 2 and sum(c.shape[1] for c in ic) == STEPS
    np.testing.assert_array_equal(np.concatenate(ic, axis=1), images)
    seg = build_federated_round_segments(
        mesh, TINY, learning_rate=1e-3, local_epochs=EPOCHS, segments=2
    )
    new_vars, metrics = seg(variables, ic, mc, active, n_samples)
    _assert_trees_bytes_equal(new_vars, monolithic_result[0])
    _assert_trees_bytes_equal(metrics, monolithic_result[1])


def test_segments_must_divide_epochs(mesh):
    with pytest.raises(ValueError, match="divis"):
        build_federated_round_segments(mesh, TINY, local_epochs=10, segments=3)


# Tier-1 budget re-balance (round 14): donation is the MECHANISM; its
# user-visible bound — peak staged HBM ≤ 2 slabs — stays tier-1 via the
# max_live_staged_bytes pins in the streaming test below.
@pytest.mark.slow
def test_segment_carry_is_donated(mesh, data, variables, seg_round):
    """The carry buffers of segment k back segment k+1's: the split costs
    zero steady-state HBM over the monolithic scan. jax marks donated
    inputs deleted; this CPU backend (and TPU) honor the donation."""
    images, masks, active, n_samples = data
    seg = seg_round
    carry = seg.init(variables)
    old_leaves = jax.tree_util.tree_leaves(carry)
    carry2, _ = seg.segment(carry, variables, images, masks)
    jax.block_until_ready(jax.tree_util.tree_leaves(carry2)[0])
    deleted = [leaf.is_deleted() for leaf in old_leaves]
    assert all(deleted), (
        f"{deleted.count(False)}/{len(deleted)} carry buffers survived "
        "donation — the segmented path would hold two carries live"
    )


def _fresh_data_fn(seed0=100):
    def data_fn(r):
        per_client = [
            synth_crack_batch(
                STEPS * BATCH, img_size=TINY.img_size, seed=seed0 + 10 * r + i
            )
            for i in range(N_CLIENTS)
        ]
        images, masks = stack_client_data(per_client, STEPS, BATCH)
        active = np.ones(N_CLIENTS, np.float32)
        n_samples = np.full(N_CLIENTS, float(STEPS * BATCH), np.float32)
        return images, masks, active, n_samples

    return data_fn


@pytest.fixture(scope="module")
def seg_round(mesh):
    return build_federated_round_segments(
        mesh, TINY, learning_rate=DRIVER_LR, local_epochs=DRIVER_EPOCHS, segments=2
    )


def test_driver_segmented_streaming_matches_monolithic(mesh, variables, seg_round):
    """run_mesh_federation over a SegmentedRound — chunk-grain streamed
    staging, donated carries, explicit buffer release — returns the same
    weights as the monolithic driver path, records the per-segment host
    timeline, and never holds more than 2 epoch slabs of staged data
    (the previous round's chunks are released at the round barrier while
    the next round's stream in — the double buffer, never a third slab).

    Round-grain staging of the same SegmentedRound is byte-identical to the
    monolithic path and is held exactly. The streamed path feeds each
    segment program a CHUNK of the slab, a differently shaped program whose
    reductions XLA orders differently, so its weights are held at ten times
    the gap read on the CPU backend or less (STREAMED_TOLERANCE, by
    treecmp's classes of leaf): BN-shadowed conv biases 4.2e-3 against
    lr * steps = 1.2e-2, running means 1.4e-4 against 5e-4, every other
    leaf 3.1e-7 against 2e-6. Bitwise equality across program boundaries
    is a property of a compiler version (ROADMAP D4); a lost chunk, a stale
    carry or a swapped epoch moves kernels by lr = 1e-3 a step."""
    mono = build_federated_round(
        mesh, TINY, learning_rate=DRIVER_LR, local_epochs=DRIVER_EPOCHS
    )
    v_mono, _ = run_mesh_federation(mono, variables, _fresh_data_fn(), DRIVER_ROUNDS, mesh)
    v_round_grain, _ = run_mesh_federation(
        seg_round, variables, _fresh_data_fn(), DRIVER_ROUNDS, mesh, segment_overlap=False
    )
    _assert_trees_bytes_equal(v_round_grain, v_mono)
    v_stream, rec_stream = run_mesh_federation(
        seg_round, variables, _fresh_data_fn(), DRIVER_ROUNDS, mesh
    )
    assert_trees_match(v_stream, v_mono, **STREAMED_TOLERANCE)
    # The per-segment host timeline is recorded, and overlapped rounds
    # carry the next round's chunk transfers inside it.
    for rec in rec_stream:
        # A segmented round keeps this timeline and leaves the monolithic
        # round's host-side fields empty.
        assert rec.host_s == rec.stage == rec.device_memory == rec.proc == {}
        assert len(rec.segments) >= 2
        assert all("dispatch_s" in e for e in rec.segments if e["segment"] != "drain")
    staged_in_timeline = sum(
        e.get("staged_bytes", 0) for e in rec_stream[0].segments
    )
    assert staged_in_timeline == rec_stream[1].staged_bytes > 0
    # 2-epoch-slab peak, and the bound is TIGHT on overlapped rounds (two
    # slabs really were live — not trivially satisfied by serial staging).
    slab = rec_stream[0].staged_bytes
    assert slab > 0
    for rec in rec_stream:
        assert 0 < rec.max_live_staged_bytes <= 2 * slab
    assert rec_stream[0].max_live_staged_bytes == 2 * slab


def test_driver_round_overlap_bit_identical(mesh, variables, seg_round):
    """Round-overlap (round 14): pipelining round N+1's first segment
    under round N's aggregation tail is pure host scheduling — weights
    AND metrics byte-identical to the unpipelined schedule, including
    across a data_fn(r)->None buffer-reuse round, with the pipelined
    segment visible in the consuming round's timeline."""

    def reuse_data_fn():
        fresh = _fresh_data_fn()

        def data_fn(r):
            return None if r == 2 else fresh(r)

        return data_fn

    v_plain, rec_plain = run_mesh_federation(
        seg_round, variables, reuse_data_fn(), 3, mesh
    )
    v_pipe, rec_pipe = run_mesh_federation(
        seg_round, variables, reuse_data_fn(), 3, mesh, round_overlap=True
    )
    _assert_trees_bytes_equal(v_pipe, v_plain)
    for rp, rq in zip(rec_plain, rec_pipe):
        _assert_trees_bytes_equal(rq.metrics, rp.metrics)
    # Rounds 1 and 2 consumed a pre-dispatched segment 0.
    assert [e["segment"] for e in rec_pipe[1].segments if e.get("pipelined")] == [0]
    assert [e["segment"] for e in rec_pipe[2].segments if e.get("pipelined")] == [0]
    assert not any(e.get("pipelined") for e in rec_pipe[0].segments)


def test_round_overlap_contract_errors(mesh, variables, seg_round):
    mono = build_federated_round(mesh, TINY, learning_rate=1e-3, local_epochs=2)
    with pytest.raises(ValueError, match="SegmentedRound"):
        run_mesh_federation(
            mono, variables, _fresh_data_fn(), 2, mesh, round_overlap=True
        )
    with pytest.raises(ValueError, match="overlap_staging"):
        run_mesh_federation(
            seg_round, variables, _fresh_data_fn(), 2, mesh,
            round_overlap=True, overlap_staging=False,
        )
    with pytest.raises(ValueError, match="max_round_retries"):
        run_mesh_federation(
            seg_round, variables, _fresh_data_fn(), 2, mesh,
            round_overlap=True, max_round_retries=1,
        )


@pytest.mark.slow
def test_driver_segmented_sequential_and_round_grain_modes(
    mesh, variables, seg_round
):
    """The two non-default staging modes also reproduce the monolithic
    weights: round-grain (segment_overlap=False) byte for byte, sequential
    (overlap_staging False, still chunk-streamed) at the streamed path's
    tolerance. Slow-marked belt-and-suspenders: the round-level
    byte-identity (K in {1,2,10}, chunked data) and the default streaming
    mode are pinned tier-1 above."""
    mono = build_federated_round(
        mesh, TINY, learning_rate=DRIVER_LR, local_epochs=DRIVER_EPOCHS
    )
    v_mono, _ = run_mesh_federation(mono, variables, _fresh_data_fn(), DRIVER_ROUNDS, mesh)
    v_seq, rec_seq = run_mesh_federation(
        seg_round, variables, _fresh_data_fn(), DRIVER_ROUNDS, mesh, overlap_staging=False
    )
    v_coarse, _ = run_mesh_federation(
        seg_round, variables, _fresh_data_fn(), DRIVER_ROUNDS, mesh, segment_overlap=False
    )
    assert_trees_match(v_seq, v_mono, **STREAMED_TOLERANCE)
    _assert_trees_bytes_equal(v_coarse, v_mono)
    # Sequential mode charges every round its own staging (boundary fix).
    assert all(r.staging_s > 0.0 for r in rec_seq)


def test_driver_checkpoint_kill_and_resume(tmp_path, mesh, variables, seg_round):
    """VERDICT r5 #7: a federation killed after round r resumes at round
    r+1 with an IDENTICAL trajectory — weights byte-equal to the
    uninterrupted run — via the FedCheckpointer threaded through
    run_mesh_federation (deterministic data_fn, absolute round indices)."""
    orbax = pytest.importorskip("orbax.checkpoint")  # noqa: F841
    from fedcrack_tpu.ckpt.manager import FedCheckpointer

    v_straight, rec_straight = run_mesh_federation(
        seg_round, variables, _fresh_data_fn(), 3, mesh
    )

    # "Kill" after round 2 of 3: run only rounds 0-1 with a checkpointer...
    with FedCheckpointer(tmp_path / "ck") as ck:
        run_mesh_federation(
            seg_round, variables, _fresh_data_fn(), 2, mesh, checkpointer=ck
        )
    # ...then a fresh "process" restores and continues rounds 2..3.
    with FedCheckpointer(tmp_path / "ck") as ck:
        ckpt = ck.restore()
        assert ckpt is not None and ckpt.current_round == 2
        assert len(ckpt.history) == 2
        v_resumed, rec_resumed = run_mesh_federation(
            seg_round,
            ckpt.variables,
            _fresh_data_fn(),
            3,
            mesh,
            checkpointer=ck,
            start_round=ckpt.current_round,
            history=ckpt.history,
        )
        final = ck.restore()
    _assert_trees_bytes_equal(v_resumed, v_straight)
    assert [r.round_idx for r in rec_resumed] == [2]
    for k in rec_straight[2].metrics:
        np.testing.assert_array_equal(
            rec_resumed[0].metrics[k], rec_straight[2].metrics[k]
        )
    # The resumed session's checkpoint carries the FULL 3-round history.
    assert final.current_round == 3
    assert [h["round"] for h in final.history] == [1, 2, 3]


def test_split_epoch_slab_contract():
    images = np.arange(2 * 7 * 3 * 2, dtype=np.uint8).reshape(2, 7, 3, 2)
    masks = np.arange(2 * 7 * 3 * 1, dtype=np.uint8).reshape(2, 7, 3, 1)
    ic, mc = split_epoch_slab(images, masks, 3)
    assert [c.shape[1] for c in ic] == [3, 2, 2]
    np.testing.assert_array_equal(np.concatenate(ic, axis=1), images)
    np.testing.assert_array_equal(np.concatenate(mc, axis=1), masks)
    # n_chunks beyond steps clamps (no empty chunks); views, not copies.
    ic2, _ = split_epoch_slab(images, masks, 99)
    assert len(ic2) == 7
    assert ic[0].base is not None  # view of the slab, not a copy
    with pytest.raises(ValueError, match="n_chunks"):
        split_epoch_slab(images, masks, 0)
    with pytest.raises(ValueError, match="disagree"):
        split_epoch_slab(images, masks[:, :3], 2)


def test_fedconfig_segment_knobs():
    from fedcrack_tpu.configs import FedConfig

    cfg = FedConfig(segments=5, local_epochs=10)
    assert cfg.segments == 5 and cfg.segment_overlap is True
    rt = FedConfig.from_json(cfg.to_json())
    assert rt.segments == 5 and rt.segment_overlap is True
    with pytest.raises(ValueError, match="divide"):
        FedConfig(segments=3, local_epochs=10)
    with pytest.raises(ValueError, match=">= 0"):
        FedConfig(segments=-1)


def test_c7_preset_parses():
    import json
    import os

    from fedcrack_tpu.configs import FedConfig

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "configs",
        "c7_segmented_pipeline.json",
    )
    with open(path) as f:
        cfg = FedConfig.from_dict(json.load(f))
    assert cfg.segments == cfg.local_epochs == 10
    assert cfg.segment_overlap is True
