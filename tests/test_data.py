"""Data pipeline: tensor contract, split determinism, pairing, sharding."""

import numpy as np
import pytest

from fedcrack_tpu.data import (
    CrackDataset,
    list_pairs,
    load_example,
    partition_iid,
    partition_skew,
    reference_split,
    synth_crack_batch,
    write_synthetic_dataset,
)
from fedcrack_tpu.data.sharding import crack_density


@pytest.fixture(scope="module")
def fixture_dirs(tmp_path_factory):
    root = tmp_path_factory.mktemp("crackds")
    return write_synthetic_dataset(str(root), n=24, img_size=64, seed=7)


def test_synth_contract():
    images, masks = synth_crack_batch(4, img_size=64, seed=0)
    assert images.shape == (4, 64, 64, 3) and images.dtype == np.float32
    assert masks.shape == (4, 64, 64, 1) and masks.dtype == np.float32
    assert images.min() >= 0.0 and images.max() <= 1.0
    assert set(np.unique(masks)) <= {0.0, 1.0}


def test_synth_deterministic():
    a = synth_crack_batch(2, 32, seed=3)
    b = synth_crack_batch(2, 32, seed=3)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_list_pairs_matches_by_stem(fixture_dirs):
    image_dir, mask_dir = fixture_dirs
    pairs = list_pairs(image_dir, mask_dir)
    assert len(pairs) == 24
    for img_path, mask_path in pairs:
        import os

        assert os.path.splitext(os.path.basename(img_path))[0] == os.path.splitext(
            os.path.basename(mask_path)
        )[0]


def test_disk_masks_lossless_roundtrip(fixture_dirs):
    """On-disk fixture masks must binarize back to the generated masks exactly
    (JPEG artifacts would leak spurious crack pixels through '>0')."""
    image_dir, mask_dir = fixture_dirs
    _, masks = synth_crack_batch(24, img_size=64, seed=7)
    pairs = list_pairs(image_dir, mask_dir)
    for i, (_, mask_path) in enumerate(pairs):
        _, loaded = load_example(pairs[i][0], mask_path, img_size=64)
        assert np.array_equal(loaded[:, :, 0], masks[i, :, :, 0]), f"mask {i} corrupted"


def test_early_consumer_exit_does_not_strand_producer(fixture_dirs):
    import threading

    pairs = list_pairs(*fixture_dirs)
    before = threading.active_count()
    for _ in range(3):
        ds = CrackDataset(pairs, img_size=64, batch_size=2, prefetch=1, num_workers=2)
        it = iter(ds)
        next(it)
        it.close()  # early exit mid-epoch
    assert threading.active_count() <= before + 1, "producer threads leaked"


def test_load_example_binarizes_and_scales(fixture_dirs):
    image_dir, mask_dir = fixture_dirs
    pairs = list_pairs(image_dir, mask_dir)
    image, mask = load_example(*pairs[0], img_size=64)
    assert image.shape == (64, 64, 3) and 0.0 <= image.min() and image.max() <= 1.0
    assert mask.shape == (64, 64, 1)
    assert set(np.unique(mask)) <= {0.0, 1.0}


def test_reference_split_deterministic_and_disjoint(fixture_dirs):
    pairs = list_pairs(*fixture_dirs)
    tr1, va1 = reference_split(pairs, train_samples=16, seed=1337)
    tr2, va2 = reference_split(pairs, train_samples=16, seed=1337)
    assert tr1 == tr2 and va1 == va2
    assert len(tr1) == 16 and len(va1) == 8
    assert not (set(tr1) & set(va1))


def test_dataset_static_batches_and_prefetch(fixture_dirs):
    pairs = list_pairs(*fixture_dirs)
    ds = CrackDataset(pairs, img_size=64, batch_size=5, seed=0, num_workers=2)
    batches = list(ds)
    assert len(batches) == 4  # 24 // 5, last partial dropped (static shapes)
    for images, masks in batches:
        assert images.shape == (5, 64, 64, 3)
        assert masks.shape == (5, 64, 64, 1)


def test_dataset_reshuffles_between_epochs(fixture_dirs):
    pairs = list_pairs(*fixture_dirs)
    ds = CrackDataset(pairs, img_size=64, batch_size=24, seed=0, num_workers=0)
    (e1, _), (e2, _) = next(iter(ds)), next(iter(ds))
    assert not np.array_equal(e1, e2)


def test_partition_iid_disjoint_cover():
    shards = partition_iid(103, 8, seed=1)
    all_idx = np.concatenate(shards)
    assert len(all_idx) == 103
    assert len(np.unique(all_idx)) == 103
    sizes = [len(s) for s in shards]
    assert max(sizes) - min(sizes) <= 1


def test_partition_skew_disjoint_cover_and_skewed():
    rng = np.random.default_rng(0)
    scores = rng.uniform(size=200)
    shards = partition_skew(scores, 4, alpha=0.05, seed=0)
    all_idx = np.concatenate(shards)
    assert len(all_idx) == 200 and len(np.unique(all_idx)) == 200
    # with tiny alpha each client's mean score should be well separated
    means = sorted(float(np.mean(scores[s])) for s in shards)
    assert means[-1] - means[0] > 0.3


def test_crack_density():
    _, masks = synth_crack_batch(6, 32, seed=0, crack_prob=1.0)
    d = crack_density(masks)
    assert d.shape == (6,)
    assert (d > 0).all()


def test_dataset_from_source_synthetic_clamps_batch():
    from fedcrack_tpu.data import dataset_from_source

    ds = dataset_from_source(
        4, None, None, img_size=32, batch_size=16, drop_last=False
    )
    batches = list(ds)
    assert sum(b[0].shape[0] for b in batches) == 4  # every sample seen


def test_dataset_from_source_dirs_and_filter(tmp_path):
    from fedcrack_tpu.data import dataset_from_source, write_synthetic_dataset

    write_synthetic_dataset(str(tmp_path), 6, img_size=32)
    ds = dataset_from_source(
        0,
        str(tmp_path / "images"),
        str(tmp_path / "masks"),
        img_size=32,
        batch_size=4,
        pair_filter=lambda pairs: pairs[:3],
    )
    assert len(ds.pairs) == 3 and ds.batch_size == 3  # clamped

    with pytest.raises(ValueError, match="no image/mask pairs"):
        dataset_from_source(
            0,
            str(tmp_path / "images"),
            str(tmp_path / "masks"),
            img_size=32,
            batch_size=4,
            pair_filter=lambda pairs: [],
        )

    with pytest.raises(ValueError, match="image-dir"):
        dataset_from_source(0, None, None, img_size=32, batch_size=4)


def test_shard_pairs_disjoint_cover_iid_and_skew(tmp_path):
    from fedcrack_tpu.data import list_pairs, write_synthetic_dataset
    from fedcrack_tpu.data.sharding import shard_pairs

    write_synthetic_dataset(str(tmp_path), 12, img_size=32)
    pairs = list_pairs(str(tmp_path / "images"), str(tmp_path / "masks"))

    for kind in ("iid", "skew"):
        shards = [shard_pairs(pairs, 3, i, partition=kind, seed=7) for i in range(3)]
        flat = [p for s in shards for p in s]
        assert sorted(flat) == sorted(pairs), kind  # disjoint + cover
        # deterministic: every process computes the same assignment
        again = shard_pairs(pairs, 3, 1, partition=kind, seed=7)
        assert again == shards[1], kind

    assert shard_pairs(pairs, 1, 0) == list(pairs)
    with pytest.raises(ValueError, match="out of range"):
        shard_pairs(pairs, 3, 3)
    with pytest.raises(ValueError, match="unknown partition"):
        shard_pairs(pairs, 3, 0, partition="sorted")


def test_partition_skew_no_empty_shards():
    from fedcrack_tpu.data.sharding import partition_skew

    # Small dataset vs many clients: Dirichlet draws can zero out a client's
    # floor counts — the rebalance must leave every shard non-empty.
    for seed in range(6):
        shards = partition_skew(np.linspace(0, 1, 24), 8, alpha=0.1, seed=seed)
        assert all(len(s) > 0 for s in shards), seed
        flat = np.concatenate(shards)
        assert sorted(flat.tolist()) == list(range(24)), seed


def test_uint8_transport_bit_identical(fixture_dirs):
    """uint8 staging must be EXACTLY the float32 pipeline: the decode path
    resizes in uint8 before normalizing either way, so on-device /255 of the
    shipped bytes reproduces the float batch bit for bit at 1/4 the
    host->device traffic."""
    from fedcrack_tpu.data import as_model_batch

    pytest.importorskip("cv2")  # without cv2 the dataset degrades to float32
    image_dir, mask_dir = fixture_dirs
    pairs = list_pairs(image_dir, mask_dir)
    f32 = CrackDataset(pairs, img_size=64, batch_size=4, shuffle=False,
                       num_workers=0)
    u8 = CrackDataset(pairs, img_size=64, batch_size=4, shuffle=False,
                      num_workers=0, transport_dtype="uint8")
    for (fi, fm), (ui, um) in zip(f32, u8):
        assert ui.dtype == np.uint8 and um.dtype == np.uint8
        assert ui.nbytes == fi.nbytes // 4
        ni, nm = as_model_batch(ui, um)
        np.testing.assert_array_equal(np.asarray(ni), fi)
        np.testing.assert_array_equal(np.asarray(nm), fm)


def test_uint8_transport_without_cv2(fixture_dirs, monkeypatch):
    """The 1/4-staging-bytes property must hold with OpenCV absent: the PIL
    path decodes uint8 transport via the native uint8-domain resize instead
    of silently degrading to float32."""
    from fedcrack_tpu.data import as_model_batch, pipeline

    monkeypatch.setattr(pipeline, "_CV2", None)
    monkeypatch.setattr(pipeline, "_CV2_PROBED", True)
    image_dir, mask_dir = fixture_dirs
    pairs = list_pairs(image_dir, mask_dir)
    f32 = CrackDataset(pairs, img_size=64, batch_size=4, shuffle=False,
                       num_workers=0)
    u8 = CrackDataset(pairs, img_size=64, batch_size=4, shuffle=False,
                      num_workers=0, transport_dtype="uint8")
    assert u8.transport_dtype == "uint8"  # no silent downgrade
    for (fi, fm), (ui, um) in zip(f32, u8):
        assert ui.dtype == np.uint8 and um.dtype == np.uint8
        assert ui.nbytes == fi.nbytes // 4
        ni, nm = as_model_batch(ui, um)
        # the float path interpolates in float; uint8 transport quantizes to
        # the nearest uint8 step — within half a step after /255
        np.testing.assert_allclose(np.asarray(ni), fi, atol=0.5 / 255.0 + 1e-6)
        # mask labels are bit-identical across transport dtypes
        np.testing.assert_array_equal(np.asarray(nm), fm)


def test_train_and_eval_steps_accept_uint8_batches():
    """A uint8 transport batch must train/evaluate the same as its float32
    equivalent — normalization happens inside the jitted step. The staged
    VALUES are bit-identical (previous test); the uint8 step is a different
    XLA program, so outputs carry the usual program-to-program
    reduction-order noise (same tolerance class as the repo's mesh-vs-host
    golden tests), nothing more."""
    import jax
    import jax.numpy as jnp

    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.train.local import create_train_state, eval_step, train_step

    tiny = ModelConfig(
        img_size=16, stem_features=4, encoder_features=(8,), decoder_features=(8, 4)
    )
    rng = np.random.default_rng(3)
    img_u8 = rng.integers(0, 256, (4, 16, 16, 3), np.uint8)
    msk_u8 = (rng.random((4, 16, 16, 1)) > 0.8).astype(np.uint8)
    img_f32 = img_u8.astype(np.float32) * np.float32(1.0 / 255.0)
    msk_f32 = msk_u8.astype(np.float32)

    state = create_train_state(jax.random.key(0), tiny)
    mu = jnp.float32(0.0)
    s_f, m_f = train_step(state, (img_f32, msk_f32), state.params, mu)
    s_u, m_u = train_step(state, (img_u8, msk_u8), state.params, mu)
    assert float(m_f["loss"]) == pytest.approx(float(m_u["loss"]), rel=1e-5)
    # One Adam step at lr=1e-3: any leaf can move at most ~lr, and for
    # zero-gradient leaves (BN-shadowed biases) reassociation noise flips
    # the step sign — so the bound is ~2*lr, not exactness.
    for a, b in zip(
        jax.tree_util.tree_leaves(s_f.params), jax.tree_util.tree_leaves(s_u.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2.5e-3)

    e_f = eval_step(state, (img_f32, msk_f32))
    e_u = eval_step(state, (img_u8, msk_u8))
    assert float(e_f["loss"]) == pytest.approx(float(e_u["loss"]), rel=1e-5)
    assert float(e_f["iou_inter"]) == pytest.approx(float(e_u["iou_inter"]), abs=1.0)


def test_to_uint8_transport_matches_decode_contract():
    """The shared synthetic-data uint8 encoder (the refscale tool) must
    be the exact inverse of the on-device normalization: u8 = rint(f32*255),
    masks {0,1} preserved — so uint8 staging of synthetic data keeps the
    bit-exact round-trip the file-decode path guarantees."""
    from fedcrack_tpu.data.pipeline import normalize_images, to_uint8_transport

    rng = np.random.default_rng(0)
    images = rng.uniform(0.0, 1.0, size=(4, 8, 8, 3)).astype(np.float32)
    masks = (rng.uniform(size=(4, 8, 8, 1)) > 0.5).astype(np.float32)
    u8i, u8m = to_uint8_transport(images, masks)
    assert u8i.dtype == np.uint8 and u8m.dtype == np.uint8
    np.testing.assert_array_equal(u8i, np.rint(images * 255.0).astype(np.uint8))
    np.testing.assert_array_equal(u8m.astype(np.float32), masks)
    # Round-trip through the on-device normalization: bit-exact u8 * (1/255)
    # (NOT u8/255.0 — the multiply-by-reciprocal differs from true division
    # by 1 ulp for ~half the byte values, and the multiply is the contract).
    back = np.asarray(normalize_images(u8i))
    np.testing.assert_array_equal(back, u8i.astype(np.float32) * np.float32(1.0 / 255.0))
