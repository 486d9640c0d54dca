"""Compressed update transport (round 12, fedcrack_tpu/compress).

Three layers under test:

- **codec properties** (seeded sweeps): NullCodec identity bytes, Int8Codec
  bounded per-leaf error (<= scale/2), TopKDelta error-feedback mass
  draining to zero on a fixed sequence, frame CRC catching every single-bit
  flip it is shown.
- **protocol integration**: the server decodes framed uploads through the
  SAME validate_update sanitation gate as raw bytes; corrupt / stale-base
  frames are REJECTED and history-logged; a quorum round survives a
  poisoned frame; wire-vs-decoded byte accounting lands in history; the
  codec is negotiated in-band end to end over real gRPC.
- **mesh twin**: build_federated_round(update_codec=...) — null is
  bit-identical to a pre-codec build, int8/topk complete N>=3 rounds with
  finite weights and a bounded IoU trajectory delta vs the null oracle,
  and the driver's bytes_per_round counter prices the codecs in order.
"""

import dataclasses

import numpy as np
import pytest

from fedcrack_tpu.compress import (
    Frame,
    decode_frame,
    decode_update,
    encode_frame,
    encoded_bytes_model,
    get_codec,
    is_frame,
)
from fedcrack_tpu.compress.codecs import (
    int8_dequantize,
    int8_quantize,
    leaf_k,
    qsgd_scales,
    topk_select,
)
from fedcrack_tpu.configs import FedConfig
from fedcrack_tpu.fed import rounds as R
from fedcrack_tpu.fed.serialization import (
    tree_from_bytes,
    tree_to_bytes,
    validate_update,
)

pytestmark = [pytest.mark.compress]


def _tree(rng, scale=1.0):
    return {
        "params": {
            "w": (scale * rng.normal(size=(32, 16))).astype(np.float32),
            "b": (scale * rng.normal(size=(5,))).astype(np.float32),
        },
        "batch_stats": {"m": (scale * rng.normal(size=(7,))).astype(np.float32)},
    }


def _shifted(tree, rng, mag):
    import jax

    return jax.tree_util.tree_map(
        lambda x: x + (mag * rng.standard_t(3, size=x.shape)).astype(np.float32),
        tree,
    )


# ---------- codec properties ----------


def test_null_codec_identity_bytes():
    rng = np.random.default_rng(0)
    blob = tree_to_bytes(_tree(rng))
    base = tree_to_bytes(_tree(rng))
    assert get_codec("null").encode_update(blob, base) == blob
    # and a null upload is NOT a frame — it is literally today's bytes
    assert not is_frame(blob)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_int8_bounded_per_leaf_error(seed):
    """QSGD property: every entry's reconstruction error is bounded by its
    bucket's scale (stochastic floor rounding moves a value at most one
    quantization step), at every magnitude in the sweep."""
    import jax

    rng = np.random.default_rng(seed)
    base = _tree(rng)
    upd = _shifted(base, rng, mag=10.0 ** rng.uniform(-4, 0))
    frame_blob = get_codec("int8").encode_update(
        tree_to_bytes(upd), tree_to_bytes(base), base_version=3
    )
    got, frame = decode_update(
        frame_blob, template=base, base=base, expected_base_version=3
    )
    for g, u, b in zip(
        jax.tree_util.tree_leaves(got),
        jax.tree_util.tree_leaves(upd),
        jax.tree_util.tree_leaves(base),
    ):
        delta = (u - b).ravel()
        scales = qsgd_scales(delta)
        per_entry = np.repeat(scales, 16384)[: delta.size]
        err = np.abs(np.asarray(g).ravel() - u.ravel())
        assert np.all(err <= per_entry + 1e-6), float(np.max(err / per_entry))


def test_int8_stochastic_rounding_is_unbiased_and_seeded():
    rng = np.random.default_rng(5)
    x = (0.01 * rng.standard_t(3, size=4096)).astype(np.float32)
    # deterministic per seed
    q1, s1 = int8_quantize(x, bucket=512, seed=(7, 0, 0))
    q2, s2 = int8_quantize(x, bucket=512, seed=(7, 0, 0))
    np.testing.assert_array_equal(q1, q2)
    np.testing.assert_array_equal(s1, s2)
    assert not np.array_equal(q1, int8_quantize(x, bucket=512, seed=(8, 0, 0))[0])
    # unbiased: the seed-averaged dequantization converges on x
    acc = np.zeros_like(x)
    n_seeds = 300
    for s in range(n_seeds):
        q, sc = int8_quantize(x, bucket=512, seed=(s, 1, 2))
        acc += int8_dequantize(q, sc, bucket=512)
    scale_cap = float(np.max(np.repeat(qsgd_scales(x, 512), 512)[: x.size]))
    # mean error shrinks ~1/sqrt(N) of one quantization step
    assert np.max(np.abs(acc / n_seeds - x)) < 5.0 * scale_cap / np.sqrt(n_seeds)


def test_int8_quantize_zero_leaf_is_exact():
    q, scales = int8_quantize(np.zeros(16, np.float32), bucket=8, seed=(0,))
    assert scales.tolist() == [1.0, 1.0] and not q.any()


@pytest.mark.parametrize("fraction", [0.05, 0.25])
def test_topk_error_feedback_mass_drains_to_zero(fraction):
    """Fixed sequence: one real delta, then identical-to-base rounds. Each
    later round transmits the top-k of the residual, so the accumulated
    mass must be strictly decreasing and reach (near) zero — Lin et al.'s
    'dropped mass is delayed, never lost'."""
    rng = np.random.default_rng(42)
    base = _tree(rng)
    base_blob = tree_to_bytes(base)
    upd_blob = tree_to_bytes(_shifted(base, rng, 0.1))
    codec = get_codec("topk_delta", topk_fraction=fraction)
    codec.encode_update(upd_blob, base_blob)
    masses = [codec.residual_mass()]
    for _ in range(200):
        if codec.residual_mass() == 0.0:
            break
        codec.encode_update(base_blob, base_blob)  # zero new delta
        masses.append(codec.residual_mass())
    assert all(b < a for a, b in zip(masses, masses[1:])), "mass must drain"
    assert masses[-1] <= 1e-6 * max(1.0, masses[0])


def test_topk_nothing_lost_only_delayed():
    """Sum of everything transmitted over the drain equals the original
    delta: reconstruct every frame against a zero base and accumulate."""
    import jax

    rng = np.random.default_rng(7)
    base = _tree(rng)
    base_blob = tree_to_bytes(base)
    upd = _shifted(base, rng, 0.05)
    codec = get_codec("topk_delta", topk_fraction=0.2)
    zeros = jax.tree_util.tree_map(lambda x: np.zeros_like(x), base)
    acc = jax.tree_util.tree_map(lambda x: np.zeros_like(x), base)
    blob = tree_to_bytes(upd)
    for i in range(60):
        frame_blob = codec.encode_update(
            blob if i == 0 else base_blob, base_blob
        )
        got, _ = decode_update(frame_blob, template=base, base=zeros)
        acc = jax.tree_util.tree_map(lambda a, g: a + np.asarray(g), acc, got)
        if codec.residual_mass() == 0.0:
            break
    want = jax.tree_util.tree_map(lambda u, b: u - b, upd, base)
    for a, w in zip(jax.tree_util.tree_leaves(acc), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, w, atol=1e-5)


def test_topk_rollback_restores_unaggregated_mass():
    """Straggler path (r12 review fix): encode_update drops the top-k mass
    from the accumulator at encode time, but a NOT_WAIT resync means the
    server never averaged that upload — rollback_last must restore the
    full pre-drop effective delta so 'nothing lost, only delayed' holds
    across the PROTOCOL, not just across accepted uploads."""
    import jax

    rng = np.random.default_rng(13)
    base = _tree(rng)
    upd = _shifted(base, rng, 0.1)
    full_mass = sum(
        float(np.sum(np.abs(np.asarray(u, np.float32) - np.asarray(b, np.float32))))
        for u, b in zip(
            jax.tree_util.tree_leaves(upd), jax.tree_util.tree_leaves(base)
        )
    )
    codec = get_codec("topk_delta", topk_fraction=0.05)
    codec.encode_update(tree_to_bytes(upd), tree_to_bytes(base))
    assert codec.residual_mass() < full_mass * 0.999  # mass left with the upload
    codec.rollback_last()
    np.testing.assert_allclose(codec.residual_mass(), full_mass, rtol=1e-5)
    codec.rollback_last()  # a second rollback is a no-op
    np.testing.assert_allclose(codec.residual_mass(), full_mass, rtol=1e-5)
    # stateless codecs: no-op, no error
    get_codec("null").rollback_last()
    get_codec("int8").rollback_last()


def test_topk_select_deterministic_under_ties():
    x = np.array([1.0, -1.0, 1.0, 0.5], np.float32)
    assert topk_select(x, 2).tolist() == [0, 1]
    assert leaf_k(1000, 0.01) == 10 and leaf_k(3, 0.01) == 1


def test_codec_registry_and_validation():
    with pytest.raises(ValueError):
        get_codec("gzip9")
    with pytest.raises(ValueError):
        get_codec("topk_delta", topk_fraction=0.0)
    with pytest.raises(ValueError):
        FedConfig(update_codec="lz4")
    with pytest.raises(ValueError):
        FedConfig(topk_fraction=1.5)
    with pytest.raises(ValueError):
        FedConfig(max_message_mb=0)
    cfg = FedConfig(update_codec="topk_delta", topk_fraction=0.02)
    assert FedConfig.from_json(cfg.to_json()) == cfg


# ---------- frames ----------


def test_frame_roundtrip_and_fields():
    payload = bytes(range(256)) * 4
    blob = encode_frame("int8", 3, 7, [{"shape": [4], "enc": "int8"}], payload)
    assert is_frame(blob)
    frame = decode_frame(blob)
    assert frame == Frame(
        codec="int8",
        round=3,
        base_version=7,
        leaves=({"shape": [4], "enc": "int8"},),
        payload=payload,
    )


@pytest.mark.parametrize("seed", [0, 1])
def test_frame_crc_catches_every_single_bit_flip_tried(seed):
    rng = np.random.default_rng(seed)
    blob = encode_frame(
        "topk_delta", 1, 0, [{"shape": [64], "enc": "topk", "k": 4}],
        rng.bytes(128),
    )
    for _ in range(32):
        pos = int(rng.integers(4, len(blob)))  # past the magic
        bit = 1 << int(rng.integers(8))
        flipped = blob[:pos] + bytes([blob[pos] ^ bit]) + blob[pos + 1 :]
        with pytest.raises(ValueError):
            decode_frame(flipped)


def test_decode_update_rejects_stale_base_and_lying_manifest():
    rng = np.random.default_rng(0)
    base = _tree(rng)
    upd_blob = tree_to_bytes(_shifted(base, rng, 0.1))
    frame_blob = get_codec("int8").encode_update(
        upd_blob, tree_to_bytes(base), base_version=4
    )
    with pytest.raises(ValueError, match="stale round base"):
        decode_update(frame_blob, template=base, base=base, expected_base_version=5)
    # manifest lying about k / shapes / payload length must be a ValueError
    short = encode_frame(
        "topk_delta", 1, 0, [{"shape": [100], "enc": "topk", "k": 50}], b"\x00" * 8
    )
    with pytest.raises(ValueError, match="truncated"):
        decode_update(short, template={"w": np.zeros(100, np.float32)},
                      base={"w": np.zeros(100, np.float32)})
    bad_idx = encode_frame(
        "topk_delta", 1, 0, [{"shape": [4], "enc": "topk", "k": 1}],
        np.array([9], np.int32).tobytes() + np.array([1.0], np.float32).tobytes(),
    )
    with pytest.raises(ValueError, match="out of range"):
        decode_update(bad_idx, template={"w": np.zeros(4, np.float32)},
                      base={"w": np.zeros(4, np.float32)})


def test_topk_refuses_nonfinite_delta():
    """Same contract as Int8Codec (r12 review fix): NaNs sort to the END of
    the magnitude order, so a poisoned delta would otherwise transmit an
    all-finite, sanitation-passing top-k while the residual keeps the NaNs
    forever — laundered poison plus a permanently corrupted accumulator."""
    rng = np.random.default_rng(0)
    base = _tree(rng)
    nan_upd = _shifted(base, rng, 0.1)
    nan_upd["params"]["w"][0, 0] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        get_codec("topk_delta").encode_update(
            tree_to_bytes(nan_upd), tree_to_bytes(base)
        )


def test_lying_giant_shape_manifest_is_valueerror_not_allocation():
    """A CRC-valid frame declaring shape [10**12] with k=0 dodges every
    payload-size bound; decode_update must refuse it against the template
    BEFORE reconstruction allocates anything (r12 review fix) — a
    MemoryError would escape the server's ValueError rejection handling."""
    huge = encode_frame(
        "topk_delta", 1, 0,
        [{"shape": [10**12], "enc": "topk", "k": 0}], b"",
    )
    with pytest.raises(ValueError, match="shape mismatch"):
        decode_update(huge, template={"w": np.zeros(4, np.float32)},
                      base={"w": np.zeros(4, np.float32)})
    # leaf-count lies are refused before reconstruction too
    extra = encode_frame(
        "topk_delta", 1, 0,
        [{"shape": [4], "enc": "topk", "k": 1}] * 2,
        (np.zeros(1, np.int32).tobytes() + np.zeros(1, np.float32).tobytes()) * 2,
    )
    with pytest.raises(ValueError, match="leaves"):
        decode_update(extra, template={"w": np.zeros(4, np.float32)},
                      base={"w": np.zeros(4, np.float32)})


def test_zlib_bomb_rejected_before_inflation():
    """A CRC-valid frame whose zlib payload inflates far past what its own
    manifest implies must be a ValueError BEFORE the full inflate (r12
    review fix) — a decompression bomb would otherwise allocate hundreds
    of MB inside the single-writer transition and escape the ValueError
    rejection path as a MemoryError."""
    bomb = encode_frame(
        "int8", 1, 0,
        [{"shape": [4], "enc": "int8", "scales": b"\x00" * 4, "bucket": 4}],
        bytes(32 * 1024 * 1024),  # 32 MB of zeros -> ~32 KB on the wire
    )
    assert len(bomb) < 1024 * 1024
    with pytest.raises(ValueError, match="inflates past"):
        decode_update(bomb, template={"w": np.zeros(4, np.float32)},
                      base={"w": np.zeros(4, np.float32)})
    # and a manifest CLAIMING more than the template could ever need is
    # refused before a single byte inflates
    big_claim = encode_frame(
        "topk_delta", 1, 0,
        [{"shape": [4], "enc": "topk", "k": 10**9}], b"",
    )
    with pytest.raises(ValueError, match="caller bound"):
        decode_update(big_claim, template={"w": np.zeros(4, np.float32)},
                      base={"w": np.zeros(4, np.float32)})


def test_absurd_bucket_cannot_force_giant_allocation():
    """expand_scales is an O(n) index gather: an int8 manifest declaring a
    bucket of 10**12 with one scale decodes (one bucket covers the whole
    leaf) instead of materializing a bucket-sized np.repeat (r12 review
    fix)."""
    q = np.array([1, -2, 3, 0], np.int8)
    frame_blob = encode_frame(
        "int8", 1, 0,
        [{
            "shape": [4], "enc": "int8",
            "scales": np.array([0.5], np.float32).tobytes(),
            "bucket": 10**12,
        }],
        q.tobytes(),
    )
    got, _ = decode_update(
        frame_blob,
        template={"w": np.zeros(4, np.float32)},
        base={"w": np.zeros(4, np.float32)},
    )
    np.testing.assert_allclose(got["w"], [0.5, -1.0, 1.5, 0.0])


def test_validate_update_accepts_trees_and_bytes():
    """The gate's two entry forms agree: the framed path validates the
    materialized tree directly (no redundant encode∘decode per upload)."""
    template = {"w": np.zeros((3, 3), np.float32)}
    good = {"w": np.ones((3, 3), np.float32)}
    assert validate_update(good, template) is None
    assert validate_update(tree_to_bytes(good), template) is None
    bad = {"w": np.full((3, 3), np.nan, np.float32)}
    assert "non-finite" in validate_update(bad, template)
    assert "non-finite" in validate_update(tree_to_bytes(bad), template)
    assert "shape mismatch" in validate_update(
        {"w": np.ones((9,), np.float32)}, template
    )


def test_nan_update_fault_composes_with_framed_cohort():
    """chaos NAN_UPDATE on a compressed cohort must deliver what the fault
    kind promises — a CRC-VALID frame whose reconstruction is non-finite —
    so the validate_update gate, not the CRC, refuses it (r12 review fix:
    it previously crashed trying to msgpack-decode the frame)."""
    from fedcrack_tpu.chaos.inject import _poison_weights
    from fedcrack_tpu.chaos.plan import NAN_UPDATE

    for codec_name in ("int8", "topk_delta"):
        state, _ = _enrolled_state(
            _cfg(update_codec=codec_name, quorum_fraction=0.5)
        )
        ev = _framed_done(state, "a", 1.0, 10,
                          poison=lambda b: _poison_weights(b, NAN_UPDATE))
        assert is_frame(ev.blob)
        decode_frame(ev.blob)  # CRC-valid: the frame layer must NOT catch it
        state, rep = R.transition(state, ev)
        assert rep.status == R.REJECTED
        assert "non-finite" in state.rejected["a"]
        # the round continues: the clean peer still aggregates
        state, rep = R.transition(state, _framed_done(state, "b", 3.0, 30))
        assert rep.status in (R.RESP_ARY, R.FIN)


def test_crc_valid_frame_with_junk_typed_fields_is_valueerror():
    """A CRC-valid body carrying junk-typed fields (round=None, non-dict
    manifest entries) must decode-fail as ValueError — the only family the
    server's rejection path catches — never TypeError aborting the RPC
    stream (r12 review fix)."""
    import msgpack as _msgpack
    import struct as _struct

    from fedcrack_tpu.native import crc32c

    for body_map in (
        {"v": 1, "codec": "int8", "round": None, "base_version": 0,
         "leaves": [], "zlib": False, "payload": b""},
        {"v": 1, "codec": "int8", "round": 1, "base_version": 0,
         "leaves": [1, 2], "zlib": False, "payload": b""},
    ):
        body = _msgpack.packb(body_map, use_bin_type=True)
        blob = b"FCWF" + _struct.pack("<I", crc32c(body)) + body
        with pytest.raises(ValueError):
            decode_frame(blob)


def test_startup_budget_covers_many_small_leaf_models():
    """The startup cap assertion must price topk's per-leaf floors
    (k >= 1, manifest entries): a model of many tiny leaves costs far more
    than fraction*dense on the wire, and a cap that fits the naive bound
    but not the real frame must be refused at construction, not die
    RESOURCE_EXHAUSTED mid-round (r12 review fix)."""
    from fedcrack_tpu.compress.codecs import DEFAULT_TOPK_FRACTION

    sizes = [4] * 5000  # 5000 BN-scalar-ish leaves, 80 KB dense payload
    model = encoded_bytes_model(sizes, "topk_delta",
                                topk_fraction=DEFAULT_TOPK_FRACTION)
    naive_fraction_bound = int(
        4 * sum(sizes) * 2 * DEFAULT_TOPK_FRACTION
    )  # what a dense-length·2f model would claim
    assert model > naive_fraction_bound  # per-leaf floors dominate here


def test_encoded_bytes_model_orders_codecs():
    sizes = [1000, 10]
    assert (
        encoded_bytes_model(sizes, "topk_delta", topk_fraction=0.01)
        < encoded_bytes_model(sizes, "int8")
        < encoded_bytes_model(sizes, "null")
    )


# ---------- protocol integration (state machine level) ----------


def _vars(value: float, n: int = 64):
    return {"params": {"w": np.full((n, n), value, np.float32)}}


def _cfg(**kw):
    base = dict(
        max_rounds=2,
        cohort_size=2,
        registration_window_s=100.0,
        update_codec="int8",
    )
    base.update(kw)
    return FedConfig(**base)


def _enrolled_state(cfg, value=0.0):
    state = R.initial_state(cfg, _vars(value))
    state, _ = R.transition(state, R.Ready(cname="a", now=0.0))
    state, rep = R.transition(state, R.Ready(cname="b", now=0.0))
    assert state.phase == R.PHASE_RUNNING
    return state, rep


def _framed_done(state, cname, value, ns, now=1.0, poison=None, base_version=None):
    codec = get_codec(state.config.update_codec, client_tag=cname)
    blob = codec.encode_update(
        tree_to_bytes(_vars(value)),
        state.broadcast_blob,
        round=state.current_round,
        base_version=state.model_version if base_version is None else base_version,
    )
    if poison is not None:
        blob = poison(blob)
    return R.TrainDone(cname=cname, round=state.current_round, blob=blob,
                       num_samples=ns, now=now)


def _decoded_w(state, blob):
    """What the server's decode path reconstructs from an upload — the
    oracle for exact-aggregation assertions (int8 encode is seeded, so the
    frame and its reconstruction are deterministic). The delta base is the
    BROADCAST blob — the bytes the client pulled — which differs from
    global_blob under wire_dtype=bfloat16."""
    if is_frame(blob):
        tree, _ = decode_update(
            blob,
            template=state.template,
            base=tree_from_bytes(state.broadcast_blob, template=state.template),
            expected_base_version=state.model_version,
        )
        return np.asarray(tree["params"]["w"], np.float32)
    return np.asarray(tree_from_bytes(blob)["params"]["w"], np.float32)


def _qsgd_bound(state, values_weights):
    """Weighted per-entry QSGD error bound for constant-leaf client deltas:
    stochastic floor rounding moves each entry at most one bucket scale."""
    total = sum(w for _, w in values_weights)
    base = np.asarray(
        tree_from_bytes(state.global_blob)["params"]["w"], np.float32
    )
    bound = np.zeros_like(base)
    for v, w in values_weights:
        delta = (np.full_like(base, v) - base).ravel()
        scales = qsgd_scales(delta)
        per_entry = np.repeat(scales, 16384)[: delta.size].reshape(base.shape)
        bound += (w / total) * per_entry
    return bound


def test_framed_round_aggregates_and_accounts_wire_bytes():
    state0, _ = _enrolled_state(_cfg())
    state = state0
    ev_a = _framed_done(state, "a", 1.0, 10)
    ev_b = _framed_done(state, "b", 3.0, 30)
    # Exact-aggregation oracle: the round must average EXACTLY what
    # decode_update reconstructs from each frame, weighted by samples.
    want = (10 * _decoded_w(state, ev_a.blob) + 30 * _decoded_w(state, ev_b.blob)) / 40
    state, rep = R.transition(state, ev_a)
    assert rep.status == R.RESP_ACY
    state, rep = R.transition(state, ev_b)
    assert rep.status == R.RESP_ARY
    got = tree_from_bytes(rep.blob)["params"]["w"]
    np.testing.assert_allclose(got, want, atol=1e-5)
    # and the reconstruction respects the quantizer's error bound around
    # the ideal average (10*1 + 30*3)/40 = 2.5
    bound = _qsgd_bound(state0, [(1.0, 10), (3.0, 30)])
    assert np.all(np.abs(np.asarray(got) - 2.5) <= bound + 1e-6)
    entry = state.history[0]
    assert entry["codecs"] == {"a": "int8", "b": "int8"}
    assert entry["bytes_received"] == len(ev_a.blob) + len(ev_b.blob)
    # the whole point: the wire carried less than the decoded trees
    assert entry["bytes_received"] < entry["decoded_bytes_received"]


def test_corrupt_frame_rejected_and_quorum_round_completes():
    from fedcrack_tpu.chaos.inject import _poison_weights
    from fedcrack_tpu.chaos.plan import CORRUPT_COMPRESSED_FRAME

    cfg = _cfg(cohort_size=3, quorum_fraction=2.0 / 3.0, max_rounds=1)
    state = R.initial_state(cfg, _vars(0.0))
    for c in ("a", "b", "c"):
        state, _ = R.transition(state, R.Ready(cname=c, now=0.0))
    flip = lambda b: _poison_weights(b, CORRUPT_COMPRESSED_FRAME)
    state, rej = R.transition(state, _framed_done(state, "c", 9.0, 20, poison=flip))
    assert rej.status == R.REJECTED
    ev_a = _framed_done(state, "a", 1.0, 10)
    ev_b = _framed_done(state, "b", 3.0, 30)
    want = (10 * _decoded_w(state, ev_a.blob) + 30 * _decoded_w(state, ev_b.blob)) / 40
    state, _ = R.transition(state, ev_a)
    state, rep = R.transition(state, ev_b)
    assert rep.status == R.FIN  # quorum 2-of-3 closed the round
    entry = state.history[0]
    assert entry["clients"] == ["a", "b"]
    assert "checksum" in entry["rejected"]["c"]
    got = tree_from_bytes(rep.blob)["params"]["w"]
    # exactly the weighted mean of the two CLEAN reconstructions — the
    # poisoned frame contributed nothing
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_bf16_wire_delta_base_is_the_broadcast_blob():
    """wire_dtype=bfloat16 + int8: the client computes its delta against
    the bf16-cast BROADCAST blob, so the server must apply the delta to
    those same bytes. Decoding against the float32 global would add
    (f32_base - bf16(f32_base)) to every reconstructed weight — finite and
    shape-correct, so it would sail through sanitation silently wrong
    (r12 review fix). Base value 1000.3 makes the bf16 cast error ~0.3, so
    the two bases are unambiguously distinguishable."""
    state, _ = _enrolled_state(_cfg(wire_dtype="bfloat16"), value=1000.3)
    ev_a = _framed_done(state, "a", 1001.0, 10)
    ev_b = _framed_done(state, "b", 1003.0, 30)
    want = (10 * _decoded_w(state, ev_a.blob) + 30 * _decoded_w(state, ev_b.blob)) / 40
    # sanity: the broadcast-based and global-based reconstructions differ
    # materially here — the oracle discriminates the bug it pins.
    wrong_base = tree_from_bytes(state.global_blob, template=state.template)
    wrong, _ = decode_update(
        ev_a.blob, template=state.template, base=wrong_base,
        expected_base_version=state.model_version,
    )
    assert (
        float(np.max(np.abs(np.asarray(wrong["params"]["w"])
                            - _decoded_w(state, ev_a.blob)))) > 0.05
    )
    state, rep = R.transition(state, ev_a)
    assert rep.status == R.RESP_ACY
    state, rep = R.transition(state, ev_b)
    assert rep.status == R.RESP_ARY
    # compare the f32 GLOBAL (the reply blob is the bf16-cast broadcast,
    # whose wire rounding at magnitude ~1000 is ~8x coarser than the claim)
    got = tree_from_bytes(state.global_blob, template=state.template)
    np.testing.assert_allclose(
        np.asarray(got["params"]["w"], np.float32), want, atol=1e-4
    )


def test_int8_client_tag_decorrelates_rounding_noise():
    """Two clients encoding the SAME update in the same round must draw
    INDEPENDENT stochastic-rounding noise (correlated noise would keep the
    cohort-averaged quantization error at per-client magnitude instead of
    shrinking ~1/sqrt(C)); the same client replaying the same round must
    reproduce identical frame bytes (chaos-replay determinism)."""
    rng = np.random.default_rng(11)
    base = _tree(rng)
    base_blob = tree_to_bytes(base)
    upd_blob = tree_to_bytes(_shifted(base, rng, 0.1))
    enc = lambda tag: get_codec("int8", client_tag=tag).encode_update(
        upd_blob, base_blob, round=3, base_version=2
    )
    assert enc("client-a") == enc("client-a")  # pure per client
    assert enc("client-a") != enc("client-b")  # independent across clients


def test_stale_base_frame_rejected_and_history_logged():
    state, _ = _enrolled_state(_cfg())
    ev = _framed_done(state, "a", 1.0, 10, base_version=99)
    state, rep = R.transition(state, ev)
    assert rep.status == R.REJECTED
    assert "stale round base" in state.rejected["a"]


def test_poison_frame_rejected_by_validate_update_gate():
    """A CRC-VALID frame can still reconstruct to non-finite weights (a
    crafted inf scale sidecar): the frame layer proves transport integrity,
    validate_update proves averageability — the exact split fedlint COMP001
    pins statically. The honest client path can't even produce this: the
    Int8Codec refuses to encode a non-finite delta (it would otherwise be
    silently clipped to zero codes — a laundered poison)."""
    state, _ = _enrolled_state(_cfg())
    nan_vars = {"params": {"w": np.full((64, 64), np.nan, np.float32)}}
    with pytest.raises(ValueError, match="non-finite"):
        get_codec("int8").encode_update(
            tree_to_bytes(nan_vars), state.broadcast_blob,
            round=1, base_version=state.model_version,
        )
    # The adversarial path: a hand-crafted frame with an inf scale passes
    # every CRC/shape check and reconstructs to inf weights.
    blob = encode_frame(
        "int8", 1, state.model_version,
        [{
            "shape": [64, 64],
            "enc": "int8",
            "scales": np.array([np.inf], np.float32).tobytes(),
            "bucket": 64 * 64,
        }],
        bytes([1]) * (64 * 64),
    )
    state, rep = R.transition(
        state, R.TrainDone(cname="a", round=1, blob=blob, num_samples=5, now=1.0)
    )
    assert rep.status == R.REJECTED
    assert "non-finite" in state.rejected["a"]
    # sanity: the gate that refused it is the shared sanitation function
    decoded, _ = decode_update(
        blob, template=state.template,
        base=tree_from_bytes(state.global_blob, template=state.template),
        expected_base_version=state.model_version,
    )
    assert validate_update(tree_to_bytes(decoded), state.template) is not None


def test_frames_sanitized_even_with_sanitize_updates_off():
    state, _ = _enrolled_state(_cfg(sanitize_updates=False))
    flip = lambda b: b[:-2] + bytes([b[-2] ^ 1]) + b[-1:]
    state, rep = R.transition(state, _framed_done(state, "a", 1.0, 10, poison=flip))
    assert rep.status == R.REJECTED


def test_raw_blob_still_accepted_in_compressed_cohort():
    """Mixed-codec cohort: a legacy client ignoring the negotiated codec
    uploads raw msgpack; it aggregates with framed peers correctly."""
    state, _ = _enrolled_state(_cfg())
    raw_blob = tree_to_bytes(_vars(1.0))
    ev_b = _framed_done(state, "b", 3.0, 30)
    want = (10 * _decoded_w(state, raw_blob) + 30 * _decoded_w(state, ev_b.blob)) / 40
    state, rep = R.transition(
        state,
        R.TrainDone(cname="a", round=1, blob=raw_blob, num_samples=10, now=1.0),
    )
    assert rep.status == R.RESP_ACY
    state, rep = R.transition(state, ev_b)
    assert rep.status == R.RESP_ARY
    got = tree_from_bytes(rep.blob)["params"]["w"]
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert state.history[0]["codecs"] == {"a": "null", "b": "int8"}


def test_handshake_advertises_codec():
    state, rep = _enrolled_state(_cfg(update_codec="topk_delta"))
    assert rep.config["update_codec"] == "topk_delta"
    assert rep.config["topk_fraction"] == pytest.approx(0.01)


def test_statefile_preserves_wire_accounting():
    from fedcrack_tpu.ckpt.statefile import (
        server_state_from_bytes,
        server_state_to_bytes,
    )

    cfg = _cfg()
    state, _ = _enrolled_state(cfg)
    state, _ = R.transition(state, _framed_done(state, "a", 1.0, 10))
    blob = server_state_to_bytes(state)
    restored = server_state_from_bytes(blob, cfg)
    assert dict(restored.wire_bytes) == dict(state.wire_bytes)
    assert dict(restored.codecs) == {"a": "int8"}


def test_server_startup_asserts_frame_budget_fits_cap():
    from fedcrack_tpu.transport.service import FedServer

    big = {"params": {"w": np.zeros(600_000, np.float32)}}  # ~2.4 MB blob
    with pytest.raises(ValueError, match="max_message_mb"):
        FedServer(_cfg(max_message_mb=1), big)
    FedServer(_cfg(max_message_mb=8), big)  # and a sane cap boots


# ---------- end-to-end over gRPC: in-band negotiation ----------


def test_grpc_session_negotiates_codec_and_shrinks_uploads():
    import threading

    from fedcrack_tpu.transport import FedClient, FedServer
    from fedcrack_tpu.transport.service import ServerThread

    cfg = dataclasses.replace(
        _cfg(),
        max_rounds=2,
        registration_window_s=5.0,
        poll_period_s=0.05,
        port=0,
    )

    def make_train_fn(delta):
        def train_fn(weights_blob, rnd):
            tree = tree_from_bytes(weights_blob)
            import jax

            out = jax.tree_util.tree_map(
                lambda x: np.asarray(x, np.float32) + delta, tree
            )
            return tree_to_bytes(out), 10, {"loss": 0.0}

        return train_fn

    server = FedServer(cfg, _vars(0.0), tick_period_s=0.05)
    with ServerThread(server) as st:
        clients = [
            FedClient(cfg, make_train_fn(d), cname=f"c{d}", port=st.port,
                      poll_period_s=0.05)
            for d in (1.0, 3.0)
        ]
        results = [None, None]

        def run(i):
            results[i] = clients[i].run_session()

        threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        state = st.state
    assert all(r is not None and r.rounds_completed == 2 for r in results)
    # negotiated in-band: both clients picked up int8 from the handshake
    assert all(c.codec.name == "int8" for c in clients)
    for entry in state.history:
        assert set(entry["codecs"].values()) == {"int8"}
        assert entry["bytes_received"] < entry["decoded_bytes_received"]
    # each round's average: both clients add their delta to the same base,
    # so the ideal global after round R is R * mean(1, 3) = 2R. The QSGD
    # quantizer moves each entry at most one bucket scale per round
    # (64*v/127 for these constant deltas: 0.504 + 1.512 halved = 1.008/
    # round, 2.016 over two) and is unbiased, so the mean stays close.
    # Exact aggregation of reconstructions is pinned by the state-machine
    # tests above; this e2e run pins negotiation + wire shrinkage.
    final = np.asarray(tree_from_bytes(state.global_blob)["params"]["w"])
    assert float(np.max(np.abs(final - 4.0))) <= 2.05
    assert abs(float(np.mean(final)) - 4.0) < 0.2
    for r in results:
        assert all(h["upload_bytes"] < len(tree_to_bytes(_vars(0.0)))
                   for h in r.history)


def _spy_rollback(monkeypatch):
    """Record every TopKDeltaCodec.rollback_last call (by codec identity)
    while keeping its behavior."""
    from fedcrack_tpu.compress import codecs as codecs_mod

    calls = []
    orig = codecs_mod.TopKDeltaCodec.rollback_last

    def spy(self):
        calls.append(self)
        return orig(self)

    monkeypatch.setattr(codecs_mod.TopKDeltaCodec, "rollback_last", spy)
    return calls


def test_topk_no_rollback_when_accepted_upload_is_aggregated(monkeypatch):
    """r12 review fix: a NOT_WAIT from the post-accept POLL means the round
    closed WITH this client's upload averaged — the client must NOT roll
    back the error-feedback accumulator there (re-banking transmitted mass
    would re-send it next round: applied twice, not 'only delayed').
    A clean 2-client full-barrier session exercises exactly that path for
    the first uploader of every round: zero rollbacks may fire."""
    import threading

    from fedcrack_tpu.transport import FedClient, FedServer
    from fedcrack_tpu.transport.service import ServerThread

    calls = _spy_rollback(monkeypatch)
    cfg = _cfg(
        update_codec="topk_delta", max_rounds=2, registration_window_s=5.0,
        poll_period_s=0.05, port=0,
    )

    def make_train_fn(delta):
        def train_fn(blob, rnd):
            tree = tree_from_bytes(blob)
            return (
                tree_to_bytes({"params": {"w": tree["params"]["w"] + delta}}),
                10,
                {"loss": 0.0},
            )

        return train_fn

    server = FedServer(cfg, _vars(0.0), tick_period_s=0.05)
    with ServerThread(server) as st:
        clients = [
            FedClient(cfg, make_train_fn(d), cname=f"c{d}", port=st.port,
                      poll_period_s=0.05)
            for d in (1.0, 3.0)
        ]
        results = [None, None]

        def run(i):
            results[i] = clients[i].run_session()

        threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    assert all(r is not None and r.rounds_completed == 2 for r in results)
    assert all(c.codec.name == "topk_delta" for c in clients)
    assert calls == []  # every upload was averaged; nothing to give back


def test_topk_rollback_fires_on_direct_stale_round_resync(monkeypatch):
    """The true straggler path: a TrainDone whose reply ITSELF is NOT_WAIT
    (stale-round resync — the upload was never averaged) must roll the
    error-feedback accumulator back, and only that one. Choreographed
    deterministically: quorum 1-of-2 lets the fast client close round 1
    alone while the straggler's train_fn WAITS (on live server state, not
    a sleep) for that round to pass, so its round-1 upload is stale by
    construction; the fast client's round-2 train then waits for the
    straggler's session to finish so the federation cannot FIN early."""
    import threading
    import time as time_mod

    from fedcrack_tpu.transport import FedClient, FedServer
    from fedcrack_tpu.transport.service import ServerThread

    calls = _spy_rollback(monkeypatch)
    cfg = _cfg(
        update_codec="topk_delta", max_rounds=2, quorum_fraction=0.5,
        registration_window_s=5.0, poll_period_s=0.05, port=0,
    )
    straggler_done = threading.Event()

    server = FedServer(cfg, _vars(0.0), tick_period_s=0.05)
    with ServerThread(server) as st:

        def fast_train(blob, rnd):
            if rnd >= 2:
                straggler_done.wait(timeout=30)
            tree = tree_from_bytes(blob)
            return (
                tree_to_bytes({"params": {"w": tree["params"]["w"] + 1.0}}),
                10,
                {"loss": 0.0},
            )

        def straggler_train(blob, rnd):
            if rnd == 1:
                deadline = time_mod.monotonic() + 30
                while (st.state.current_round == 1
                       and time_mod.monotonic() < deadline):
                    time_mod.sleep(0.02)
            tree = tree_from_bytes(blob)
            return (
                tree_to_bytes({"params": {"w": tree["params"]["w"] + 3.0}}),
                10,
                {"loss": 0.0},
            )

        fast = FedClient(cfg, fast_train, cname="fast", port=st.port,
                         poll_period_s=0.05)
        strag = FedClient(cfg, straggler_train, cname="strag", port=st.port,
                          poll_period_s=0.05)
        results = {}

        def run(c, key):
            try:
                results[key] = c.run_session()
            except Exception as e:  # noqa: BLE001 — the exception IS the result
                results[key] = e
            if key == "strag":
                straggler_done.set()

        threads = [
            threading.Thread(target=run, args=(c, k))
            for c, k in ((strag, "strag"), (fast, "fast"))
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        state = st.state
    assert not isinstance(results["strag"], Exception), results["strag"]
    assert not isinstance(results["fast"], Exception), results["fast"]
    # Round 1 aggregated without the straggler; its stale upload drew the
    # direct NOT_WAIT and rolled back EXACTLY its own codec, once.
    assert state.history[0]["clients"] == ["fast"]
    assert len(calls) == 1 and calls[0] is strag.codec


# ---------- mesh twin ----------


@pytest.mark.parametrize("codec", ["int8", "topk_delta"])
def test_mesh_codec_value_maps_match_host_codecs(codec):
    import jax.numpy as jnp

    from fedcrack_tpu.compress.mesh import (
        int8_roundtrip,
        topk_roundtrip,
        zero_residual_like,
    )

    rng = np.random.default_rng(3)
    x = (0.01 * rng.standard_t(3, size=(257,))).astype(np.float32)
    if codec == "int8":
        # Parity is distributional for int8 (different PRNGs): identical
        # scale rule, error bounded by the bucket scale, zero stays zero.
        import jax

        got = np.asarray(
            int8_roundtrip(
                {"x": jnp.asarray(x)}, jax.random.PRNGKey(0), bucket=64
            )["x"]
        )
        per_entry = np.repeat(qsgd_scales(x, 64), 64)[: x.size]
        assert np.all(np.abs(got - x) <= per_entry + 1e-6)
        zero = np.asarray(
            int8_roundtrip(
                {"x": jnp.zeros(16)}, jax.random.PRNGKey(1), bucket=8
            )["x"]
        )
        assert not zero.any()
    else:
        tree = {"x": jnp.asarray(x)}
        kept, res = topk_roundtrip(tree, zero_residual_like(tree), 0.05)
        k = leaf_k(x.size, 0.05)
        idx = topk_select(x, k)
        want = np.zeros_like(x)
        want[idx] = x[idx]
        np.testing.assert_allclose(np.asarray(kept["x"]), want, atol=1e-7)
        np.testing.assert_allclose(
            np.asarray(res["x"]), x - want, atol=1e-7
        )


@pytest.mark.slow
def test_mesh_codec_trajectory_and_bytes_counter():
    """One tiny-model pass over all three twins: null is BIT-identical to a
    pre-codec build (the escape hatch), int8/topk complete N>=3 rounds with
    finite weights and a bounded final-IoU delta vs the null oracle, the
    topk twin carries device-resident EF state with a working reset, and
    RoundRecord.bytes_per_round prices the codecs in strict order.

    Slow-marked (~87 s: four round-program compilations — the round-9
    tier-1-budget precedent): the twins' VALUE MAPS stay tier-1 via
    test_mesh_codec_value_maps_match_host_codecs."""
    import jax

    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.parallel import (
        build_federated_round,
        make_mesh,
        run_mesh_federation,
        stack_client_data,
    )
    from fedcrack_tpu.train.local import create_train_state

    tiny = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    steps, batch, n_rounds = 2, 4, 3
    mesh = make_mesh(2, 1)
    per_client = [
        synth_crack_batch(steps * batch, img_size=16, seed=i) for i in range(2)
    ]
    images, masks = stack_client_data(per_client, steps, batch)
    active = np.ones(2, np.float32)
    ns = np.full(2, float(steps * batch), np.float32)
    state0 = create_train_state(jax.random.key(0), tiny)
    data_fn = lambda r: (images, masks, active, ns) if r == 0 else None

    runs = {}
    for codec in (None, "null", "int8", "topk_delta"):
        rf = build_federated_round(
            mesh, tiny, learning_rate=1e-3, local_epochs=1,
            update_codec=codec, topk_fraction=0.05,
        )
        vars_, recs = run_mesh_federation(
            rf, state0.variables, data_fn, n_rounds, mesh
        )
        runs[codec] = (jax.device_get(vars_), recs, rf)

    # escape hatch: null twin == no-codec build, bit for bit
    base_leaves = jax.tree_util.tree_leaves(runs[None][0])
    null_leaves = jax.tree_util.tree_leaves(runs["null"][0])
    for a, b in zip(base_leaves, null_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    null_iou = [float(np.mean(r.metrics["iou"])) for r in runs["null"][1]]
    for codec in ("int8", "topk_delta"):
        vars_, recs, rf = runs[codec]
        assert len(recs) == n_rounds
        assert all(
            np.isfinite(np.asarray(l)).all()
            for l in jax.tree_util.tree_leaves(vars_)
        )
        iou = [float(np.mean(r.metrics["iou"])) for r in recs]
        # documented tolerance (BASELINE.md round 12): compressed-twin IoU
        # stays within 0.15 absolute of the null oracle per round at this
        # scale — compression perturbs the trajectory, it must not break it
        assert max(abs(a - b) for a, b in zip(iou, null_iou)) < 0.15
        assert all(r.bytes_per_round == rf.wire_bytes_per_client * 2 for r in recs)

    wpc = {c: runs[c][2].wire_bytes_per_client for c in ("null", "int8", "topk_delta")}
    # Strict ordering at ANY scale; the >=10x ratio only emerges once real
    # leaf sizes amortize the per-leaf floors (k >= 1, manifest overhead) —
    # test_encoded_bytes_model_orders_codecs covers it on realistic sizes.
    assert wpc["topk_delta"] < wpc["int8"] < wpc["null"]

    # topk EF state: device-resident across calls, dropped by reset_ef
    rf_topk = runs["topk_delta"][2]
    rf_topk.reset_ef()


@pytest.mark.slow
def test_topk_twin_ef_frozen_for_inactive_clients():
    """On the wire an inactive client never encodes, so its error-feedback
    residual is untouched; the mesh twin must match (r12 review fix): one
    round with client 1 masked inactive leaves its EF slab exactly zero
    while the active client's accumulates."""
    import jax

    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.parallel import (
        build_federated_round,
        make_mesh,
        stack_client_data,
    )
    from fedcrack_tpu.train.local import create_train_state

    tiny = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    steps, batch = 2, 4
    mesh = make_mesh(2, 1)
    per_client = [
        synth_crack_batch(steps * batch, img_size=16, seed=i) for i in range(2)
    ]
    images, masks = stack_client_data(per_client, steps, batch)
    active = np.array([1.0, 0.0], np.float32)
    ns = np.array([float(steps * batch), 0.0], np.float32)
    state0 = create_train_state(jax.random.key(0), tiny)
    rf = build_federated_round(
        mesh, tiny, learning_rate=1e-3, local_epochs=1,
        update_codec="topk_delta", topk_fraction=0.05,
    )
    rf(state0.variables, images, masks, active, ns)
    ef_leaves = jax.tree_util.tree_leaves(jax.device_get(rf.ef_state()))
    assert all(not np.asarray(l)[1].any() for l in ef_leaves), "inactive EF moved"
    assert any(np.asarray(l)[0].any() for l in ef_leaves), "active EF empty"


def test_driver_retry_restores_codec_twin_state():
    """r12 review fix: the round program commits the topk twin's EF pytree
    (and int8's seed counter) when the async dispatch returns — BEFORE a
    poisoned output can surface at the driver's host-side finiteness
    check — so the replay path must restore round_fn.codec_state()
    alongside its weights snapshot. Without it the retry reruns the round
    against the DISCARDED attempt's residual: its kept mass is lost and
    its dropped mass double-banked. Pinned bit-identically: a
    NaN-poisoned round 0 absorbed by one replay == the unfaulted run,
    final weights AND error-feedback state."""
    import jax

    from fedcrack_tpu.chaos import Fault, FaultPlan, MESH_NONFINITE, MeshChaos
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.parallel import (
        build_federated_round,
        make_mesh,
        run_mesh_federation,
        stack_client_data,
    )
    from fedcrack_tpu.train.local import create_train_state

    tiny = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    steps, batch = 2, 4
    mesh = make_mesh(2, 1)

    def data_fn(r):
        per_client = [
            synth_crack_batch(steps * batch, img_size=16, seed=10 * r + i)
            for i in range(2)
        ]
        images, masks = stack_client_data(per_client, steps, batch)
        return (
            images, masks,
            np.ones(2, np.float32),
            np.full(2, float(steps * batch), np.float32),
        )

    def build():
        return build_federated_round(
            mesh, tiny, learning_rate=1e-3, local_epochs=1,
            update_codec="topk_delta", topk_fraction=0.05,
        )

    init = create_train_state(jax.random.key(0), tiny).variables
    rf_clean = build()
    v_clean, _ = run_mesh_federation(rf_clean, init, data_fn, 2, mesh)
    ef_clean = jax.device_get(rf_clean.ef_state())

    rf_chaos = build()
    plan = FaultPlan([Fault(MESH_NONFINITE, round=0)])
    v_chaos, records = run_mesh_federation(
        rf_chaos, init, data_fn, 2, mesh,
        max_round_retries=1, fault_injector=MeshChaos(plan),
    )
    ef_chaos = jax.device_get(rf_chaos.ef_state())
    assert records[0].retries == 1 and not plan.pending
    for a, b in zip(
        jax.tree_util.tree_leaves(v_clean), jax.tree_util.tree_leaves(v_chaos)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
        jax.tree_util.tree_leaves(ef_clean), jax.tree_util.tree_leaves(ef_chaos)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_segmented_builder_has_no_codec_arg():
    from fedcrack_tpu.parallel import build_federated_round_segments, make_mesh

    with pytest.raises(TypeError):
        build_federated_round_segments(make_mesh(1, 1), update_codec="int8")
