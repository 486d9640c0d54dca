"""Crack quantifier: closed-form shapes and the predict flow."""

import numpy as np
import pytest

from fedcrack_tpu.tools import quantify_mask
from fedcrack_tpu.tools.quantify import annotate


def test_single_square_crack():
    mask = np.zeros((64, 64), np.uint8)
    mask[20:40, 20:40] = 255  # 20x20 square
    s = quantify_mask(mask)
    assert s.contour_count == 1
    # cv2 contour area of a filled 20x20 block is (19)^2 (contour runs on
    # pixel centers); perimeter ~ 4*19
    assert abs(s.total_area_px - 361) < 2
    assert abs(s.total_perimeter_px - 76) < 2
    c = s.contours[0]
    assert c.approx_points_10pct == 4  # a square simplifies to 4 vertices
    assert abs(s.crack_fraction - 400 / 4096) < 1e-6


def test_empty_mask():
    s = quantify_mask(np.zeros((32, 32), np.uint8))
    assert s.contour_count == 0 and s.total_area_px == 0


def test_float01_mask_accepted():
    mask = np.zeros((32, 32), np.float32)
    mask[8:16, 8:24] = 1.0
    s = quantify_mask(mask)
    assert s.contour_count == 1


def test_two_separate_cracks():
    mask = np.zeros((64, 64), np.uint8)
    mask[5:15, 5:15] = 255
    mask[40:60, 40:50] = 255
    s = quantify_mask(mask)
    assert s.contour_count == 2


def test_annotate_returns_uint8_rgb():
    img = np.random.default_rng(0).uniform(size=(32, 32, 3)).astype(np.float32)
    mask = np.zeros((32, 32), np.uint8)
    mask[10:20, 10:20] = 255
    out = annotate(img, mask)
    assert out.dtype == np.uint8 and out.shape == (32, 32, 3)
    assert (out != (np.clip(img, 0, 1) * 255).astype(np.uint8)).any()


# Tier-1 budget re-balance (round 14): a full predict+quantify tool smoke
# (~15 s of model compiles); quantify's contour math stays tier-1 in this
# module's unit tests and the predict program in test_serve/test_model.
@pytest.mark.slow
def test_predict_and_quantify_writes_outputs(tmp_path):
    import jax

    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.data.pipeline import ArrayDataset
    from fedcrack_tpu.data.synthetic import synth_crack_batch
    from fedcrack_tpu.tools.quantify import predict_and_quantify
    from fedcrack_tpu.train import create_train_state

    state = create_train_state(jax.random.key(0), ModelConfig(img_size=32))
    images, masks = synth_crack_batch(4, 32, seed=0)
    ds = ArrayDataset(images, masks, batch_size=2, shuffle=False)
    reports = predict_and_quantify(state, ds, out_dir=str(tmp_path), max_images=3)
    assert len(reports) == 3
    assert (tmp_path / "pred_000.png").exists()
    assert (tmp_path / "overlay_002.png").exists()
    assert all("area_px" in r for r in reports)


def _write_mask_pngs(out_dir, specs):
    """specs: {name: (size, fill_box or None)} -> PNG masks on disk."""
    import os

    import cv2

    os.makedirs(out_dir, exist_ok=True)
    for name, (size, box) in specs.items():
        mask = np.zeros((size, size), np.uint8)
        if box is not None:
            y0, y1, x0, x1 = box
            mask[y0:y1, x0:x1] = 255
        cv2.imwrite(str(out_dir / name), mask)


def test_quantify_mask_dir_batch_stats(tmp_path):
    """Round-10 batch mode: a directory of predicted masks (what the serving
    plane emits via load_gen --out-dir) quantified WITHOUT a model, with
    per-image records in stable sorted order plus aggregate totals."""
    from fedcrack_tpu.tools.quantify import quantify_mask_dir

    _write_mask_pngs(
        tmp_path,
        {
            "mask_00002.png": (64, (20, 40, 20, 40)),  # one 20x20 crack
            "mask_00000.png": (64, None),              # empty
            "mask_00001.png": (64, (5, 15, 5, 15)),    # one 10x10 crack
        },
    )
    (tmp_path / "notes.txt").write_text("not a mask")  # ignored (not an image)
    report = quantify_mask_dir(str(tmp_path))
    names = [r["image"] for r in report["images"]]
    assert names == ["mask_00000.png", "mask_00001.png", "mask_00002.png"]
    assert report["images"][0]["contours"] == 0
    assert report["images"][1]["contours"] == 1
    assert report["totals"]["images"] == 3
    assert report["totals"]["contours"] == 2
    assert report["totals"]["area_px"] == pytest.approx(
        sum(r["area_px"] for r in report["images"])
    )
    assert report["totals"]["mean_crack_fraction"] == pytest.approx(
        np.mean([r["crack_fraction"] for r in report["images"]])
    )
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(ValueError, match="no mask images"):
        quantify_mask_dir(str(empty))
    with pytest.raises(ValueError, match="not a directory"):
        quantify_mask_dir(str(tmp_path / "does_not_exist"))


def test_quantify_cli_pred_dir_out_json(tmp_path, capsys):
    """The CLI contract the serving pipeline uses: --pred-dir needs no
    --weights, prints one JSON line per image + a totals line, and --out-json
    writes the machine-readable report."""
    import json

    from fedcrack_tpu.tools.quantify import main as quantify_main

    pred = tmp_path / "pred"
    _write_mask_pngs(pred, {"a.png": (32, (8, 16, 8, 24)), "b.png": (32, None)})
    out_json = tmp_path / "stats.json"
    quantify_main(
        ["--pred-dir", str(pred), "--out-json", str(out_json)]
    )
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 3  # 2 per-image lines + totals
    per_image = [json.loads(line) for line in lines[:2]]
    assert [r["image"] for r in per_image] == ["a.png", "b.png"]
    totals = json.loads(lines[-1])["totals"]
    assert totals["images"] == 2 and totals["contours"] == 1
    with open(out_json) as f:
        on_disk = json.load(f)
    assert on_disk["totals"] == totals
    assert [r["image"] for r in on_disk["images"]] == ["a.png", "b.png"]


def test_quantify_cli_weights_still_required_without_pred_dir(capsys):
    from fedcrack_tpu.tools.quantify import main as quantify_main

    with pytest.raises(SystemExit):
        quantify_main(["--synthetic", "2"])
    assert "--weights is required" in capsys.readouterr().err


@pytest.mark.slow
def test_refscale_federation_tool_smoke(tmp_path):
    """The reference-complete federation driver (tools/refscale_federation)
    at toy scale: artifact schema, N-client serial fits with non-degenerate
    FedAvg, per-round eval records, and the staging overlap wiring all
    exercised — the real run is this at 2 clients x 5 rounds x 10 epochs
    x 388 steps."""
    import json

    from fedcrack_tpu.tools.refscale_federation import main

    out = tmp_path / "refscale.json"
    rc = main(
        [
            "--clients", "2", "--rounds", "2", "--epochs", "1",
            "--samples", "32", "--batch", "4",
            "--img", "32", "--eval-samples", "8", "--dtype", "float32",
            "--out", str(out),
        ]
    )
    assert rc == 0
    art = json.loads(out.read_text())
    assert art["workload"]["rounds"] == 2
    assert art["workload"]["clients"] == 2
    assert len(art["rounds"]) == 2
    for r in art["rounds"]:
        assert len(r["fits"]) == 2
        for f in r["fits"]:
            assert f["staged_bytes"] > 0
        assert "iou" in r["eval"] and "loss" in r["eval"]
        # Non-degenerate aggregation: both clients moved, and they moved to
        # DIFFERENT weights (distinct shards diverge under local SGD).
        assert len(r["update_l2"]) == 2 and all(u > 0 for u in r["update_l2"])
        assert len(r["client_divergence_l2"]) == 1
        assert r["client_divergence_l2"][0] > 0
    # The very last fit of the schedule has nothing left to stage ahead.
    assert art["rounds"][-1]["fits"][-1]["overlapped_next_fit_staging"] is False
    assert art["rounds"][0]["fits"][0]["overlapped_next_fit_staging"] is True
    assert len(art["summary"]["eval_iou_trajectory"]) == 2
    # Round 9: the held-out eval slab is device-resident — the one-time
    # transfer is charged to the first round's eval_stage_s, 0.0 after.
    assert art["rounds"][0]["eval_stage_s"] > 0.0
    assert all(r["eval_stage_s"] == 0.0 for r in art["rounds"][1:])
    assert art["summary"]["eval_staged_bytes"] > 0
    assert art["workload"]["data_placement"] == "streamed"


@pytest.mark.slow
def test_ab_pallas_bce_harness_smoke(tmp_path):
    """The BCE-kernel A/B harness (tools/ab_pallas_bce) at toy scale:
    artifact schema + slope-fit wiring, single impl — the Pallas INTERPRETER
    cannot run inside the shard_map round program on CPU (jax
    hlo_interpreter vma limitation), and the compiled kernel needs a real
    TPU, so the two-impl comparison needs a chip run (chip_smoke.py compiles
    the kernel against its twin). Kernel-vs-XLA numerics parity is
    test_pallas_bce's job. Slow-marked (round-12 tier-1 budget re-balance,
    the r4/r9 precedent): ~80-95 s of tools-level compiles whose numeric
    semantics stay tier-1 via test_pallas_bce."""
    import json

    from fedcrack_tpu.tools.ab_pallas_bce import main

    out = tmp_path / "ab.json"
    rc = main(
        [
            "--sizes", "32", "--steps", "2", "--batch", "2", "--reps", "1",
            "--fit-factor", "2", "--impls", "jnp",
            "--dtype", "float32", "--out", str(out),
        ]
    )
    assert rc == 0
    art = json.loads(out.read_text())
    point = art["points"]["float32_32"]
    # ADVICE r5 #3: per-impl dicts live under "impls"; derived scalars are
    # sibling keys — impl iteration needs no non-dict special case.
    pts = point["impls"]
    assert all(isinstance(v, dict) for v in pts.values())
    assert pts["jnp"]["round_s_short"] > 0
    assert pts["jnp"]["round_s_long"] > 0
    # per_step_ms may be None if CPU timing noise defeats the 2-point fit at
    # this toy scale; the schema must carry the key either way.
    assert "per_step_ms" in pts["jnp"]
    # env must be restored (other tests rely on auto-dispatch)
    import os

    assert os.environ.get("FEDCRACK_BCE_IMPL") is None


@pytest.mark.slow
def test_profile_step_tool_smoke(tmp_path):
    """tools/profile_step at toy scale: the driven rounds, the traced slice
    through the public profiler and the host spans read back from it. The
    CPU backend records no device plane, so the per-scope table is null
    here and the artifact says why (``obs/devtrace.py`` has its own tests).
    Slow-marked (round-12 tier-1 budget re-balance, the r4/r9 precedent): a
    tools-level smoke, no protocol semantics ride on it."""
    import json

    from fedcrack_tpu.tools.profile_step import main

    out = tmp_path / "prof.json"
    rc = main(
        [
            "--img", "32", "--steps", "2", "--batch", "2", "--slice-s", "0.2",
            "--dtype", "float32", "--out", str(out), "--trace-dir", str(tmp_path / "trace"),
        ]
    )
    assert rc == 0
    art = json.loads(out.read_text())
    assert [r["round"] for r in art["rounds"]] == [0, 1, 2, 3]
    for r in art["rounds"]:
        assert set(r["host_s"]) == {"dispatch", "feed", "stage", "barrier", "handoff"}
        assert set(r["proc"]) == {"cpu_s", "nivcsw", "majflt"} and r["device_memory"] == {}
    assert all(set(r["stage"]) == {"put_s", "land_s", "bytes"} for r in art["rounds"][:-1])
    assert art["slice"]["xplane"], "profiler produced no xplane capture"
    assert art["step_loss"]["shape"] == [1, 1, 2] and art["step_loss"]["finite"]
    assert art["step_loss"]["last_epoch_mean_minus_loss"] < 1e-6
    # Which of the driver's spans begin AND end inside the slice is a matter
    # of timing (tests/test_driver.py pins the full set under one trace).
    assert art["host_spans"] and all(name.startswith("driver.") for name in art["host_spans"])
    if art["by_scope"] is None:
        assert "XLA Ops" in art["by_scope_missing"]
    else:
        table = art["by_scope"]
        assert abs(sum(r["seconds"] for r in table["rows"]) - table["busy_s"]) < 1e-9


@pytest.mark.slow
def test_refscale_federation_resident_placement_matches_streamed():
    """--data-placement resident (session-resident client pools + per-fit
    index uploads) reproduces the streamed run's eval trajectory exactly —
    both placements consume one rng permutation per fit — while shipping
    only kilobytes per fit after the one-time pool staging."""
    import argparse

    from fedcrack_tpu.tools.refscale_federation import run_refscale_federation

    def mk(placement):
        return argparse.Namespace(
            clients=2, rounds=2, epochs=2, samples=16, batch=4, img=32,
            dtype="float32", eval_samples=8, pos_weight=2.0, lr=1e-3, seed=0,
            segments=0, server_optimizer="fedavg", server_lr=1.0,
            server_momentum=0.9, ckpt_dir="", resume=False,
            data_placement=placement,
        )

    streamed = run_refscale_federation(mk("streamed"))
    resident = run_refscale_federation(mk("resident"))
    assert resident["workload"]["data_placement"] == "resident"
    assert [r["eval"] for r in resident["rounds"]] == [
        r["eval"] for r in streamed["rounds"]
    ]
    slab = streamed["rounds"][0]["fits"][0]["staged_bytes"]
    assert resident["summary"]["pool_bytes_total"] > 0
    for r in resident["rounds"]:
        for f in r["fits"]:
            assert 0 < f["staged_bytes"] * 20 <= slab  # indices only
    assert streamed["summary"]["pool_bytes_total"] is None


# Slow-marked (round 9): three full tool runs with fresh 32 px compiles cost
# ~155 s — the single largest tier-1 line item — and the kill->resume
# semantics stay pinned tier-1 at the driver level
# (test_segmented.py::test_driver_checkpoint_kill_and_resume) plus the
# statefile tests in test_ckpt.py; this tool-level twin is belt-and-
# suspenders coverage the slow suite keeps (same budget policy as
# test_segmented's K in {1,2}).
@pytest.mark.slow
def test_refscale_federation_kill_and_resume(tmp_path):
    """Round 7 (VERDICT r5 #7): the tool checkpointed after every round
    resumes a killed session at round r+1 with an identical trajectory —
    per-round evals equal to the uninterrupted run — including the FedOpt
    server-optimizer moments and the per-client shuffle rng state."""
    import argparse

    from fedcrack_tpu.tools.refscale_federation import run_refscale_federation

    def mk(rounds, **kw):
        base = dict(
            clients=2, rounds=rounds, epochs=2, samples=16, batch=4, img=32,
            dtype="float32", eval_samples=8, pos_weight=2.0, lr=1e-3, seed=0,
            segments=0, server_optimizer="fedavgm", server_lr=1.0,
            server_momentum=0.9, ckpt_dir="", resume=False,
        )
        base.update(kw)
        return argparse.Namespace(**base)

    straight = run_refscale_federation(mk(3))
    # "Kill" after round 2 of 3: a 2-round run leaves the checkpoint a
    # 3-round run would have left at that boundary...
    run_refscale_federation(mk(2, ckpt_dir=str(tmp_path / "ck")))
    # ...and the resumed process finishes round 3 on the same trajectory.
    resumed = run_refscale_federation(
        mk(3, ckpt_dir=str(tmp_path / "ck"), resume=True)
    )
    assert resumed["resumed_from"] == 2
    assert straight["resumed_from"] == 0
    assert [r["eval"] for r in resumed["rounds"]] == [
        r["eval"] for r in straight["rounds"]
    ]
    assert resumed["workload"]["server_optimizer"] == "fedavgm"
    assert "segments" in resumed["workload"]
