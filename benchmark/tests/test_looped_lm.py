"""The looped language model's cell on the CPU at a tiny size, the look for a
chip skipped: a sound program comes out ``correct``, the timed path broken
underneath does not; the configuration's file against the catalog's keys and
itself; the parameter and operation counts by hand and at the cell's size; the
new metric readers on made-up inputs; the step marker on a made-up trace in
which a loop's instruction outweighs every once-a-step one; where a traced
window restarts after a slow collection; the accepted causal driver is left
as it was."""

import json
import re
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from lib import federated_causal_lm_rounds as accepted, federated_looped_lm_rounds as fl, flops_ouro
from lib.federated_rounds import _load_module, load_reference

from conftest import BENCH_DIR, read_json

CELL = "ouro_round_l8192_b1_1chip"
CONFIG = "ouro2p6b_pp12_bf16"
FAULTS = ("one_loop", "last_exit_only", "no_entropy", "no_post_norm", "norm_not_carried", "stale_slab")


@pytest.fixture(scope="module")
def catalog_row():
    """The catalog's ``config`` of Ouro-2.6B (model-configs guide), key for
    key, as this file was written from it."""
    return {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
        "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False,
        "vocab_size": 49152,
    }


@pytest.fixture
def tiny_spec():
    spec = run.load_spec(CELL)
    config = spec["config"]
    config.update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16, intermediate_size=96,
        vocab_size=64, compute_dtype="float32", batch_size=2, train_samples=8,
    )
    config["training"] = dict(config["training"], seq_len=128)
    config["optimizer"] = dict(config["optimizer"], learning_rate=1e-3)
    return spec


def _stale_slab(round_fn):
    """A round that trains on its first round's data ever after."""
    first = {}

    def broken(variables, ids, weight, active, n_samples):
        if not first:  # copies: the driver releases a round's slab
            first["data"] = (jnp.copy(ids), jnp.copy(weight))
        return round_fn(variables, *first["data"], active, n_samples)
    return broken


def _unchanged(round_fn):
    def broken(variables, ids, weight, active, n_samples):
        kept = jax.tree_util.tree_map(jnp.copy, variables)  # the round consumes its input
        _, metrics = round_fn(variables, ids, weight, active, n_samples)
        return kept, metrics
    return broken


@pytest.mark.parametrize("fault,expected", [(None, True), (_stale_slab, False), (_unchanged, False)],
                         ids=lambda v: getattr(v, "__name__", str(v)))
def test_correct_follows_the_timed_path(tiny_spec, monkeypatch, fault, expected):
    if fault is not None:
        real = fl._driver.build_federated_round

        def builder(*args, **kwargs):
            broken = fault(real(*args, **kwargs))
            broken.data_placement = "streamed"
            return broken

        monkeypatch.setattr(fl._driver, "build_federated_round", builder)
    result = fl.run(tiny_spec, 2**31 + 77, 0.3, False, time.perf_counter(), require_chip=False)
    assert result["correct"] is expected, result["compared"]
    assert list(result)[-1] == "compared"
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"round_s", "setup_s"}
    numbers = result["info"]["numbers"]
    if fault is None:
        assert numbers["direction_r0"] < 1e-3 and numbers["step_loss_r1"] < 1e-4 and numbers["exit_r0"] < 1e-3
        assert result["info"]["held_pairs_a_layer"] == 0.0


def test_the_study_names_every_fault_the_reference_plants():
    study = _load_module(f"{BENCH_DIR}/study/looped_lm_study.py", "bench_study_looped_test")
    planted = set(re.findall(r'``"(\w+)"``', load_reference({"reference": "ouro_looped_lm"}).__doc__.split("``fault`` plants")[1]))
    assert planted | {"stale_slab"} == set(study.FAULTS) == set(FAULTS)
    assert set(study.VARIANTS) == {"control_fp8", "witness_bf16"} | {f"fault_{name}" for name in FAULTS}
    assert study._study.fc is fl and study._study.study_seed is study.study_seed


def test_the_accepted_causal_driver_is_left_as_it_was():
    """This kind binds names in an instance of the accepted driver that it
    loaded for itself: the accepted cell's own module still reads its own."""
    assert fl._driver is not accepted and fl._driver.Cell is fl.Cell
    assert accepted.Cell is not fl.Cell and "mtp_loss" in accepted.PROGRAM_METRICS
    assert accepted.flops_joyai is not flops_ouro and accepted.MODULE_SCOPES == ("mtp",)
    assert fl.MODULE_SCOPES == ("loop0", "loop1", "loop2", "loop3") and "exit_mass" in fl.PROGRAM_METRICS


def test_the_configuration_agrees_with_the_catalog_and_itself(catalog_row):
    config = read_json("benchmark", "configs", CONFIG + ".json")
    entry = next(c for c in read_json("BENCHMARK.json")["configs"] if c["name"] == CONFIG)
    differing = {k for k, v in catalog_row.items() if config.get(k, "missing") != v}
    assert differing == {"num_hidden_layers", "layer_types"}
    assert set(entry["reduced"]) == differing | {"local_epochs", "mesh_clients"} == set(config["published"])
    assert config["published"]["num_hidden_layers"] == 48 and config["layer_types"] == ["full_attention"] * 4
    # No width is cut, no pass and no row of the vocabulary: depth alone.
    assert not any(k.endswith(("_dim", "_rank", "_size")) for k in entry["reduced"])
    assert config["total_ut_steps"] == 4 and config["vocab_size"] == 49152 and config["num_hidden_layers"] >= 4
    assert config["share"]["pipeline_stages"] * config["num_hidden_layers"] == catalog_row["num_hidden_layers"]
    for key in ("deployment", "assumed", "sources"):
        assert config[key]
    model = fl.reference_config(config)
    program = fl.program_config(config)
    assert program.seq_len == model["seq_len"] == 8192 and program.total_ut_steps == 4
    assert program.exit_entropy_beta == model["exit_entropy_beta"] == 0.05 and program.vocab_size == 49152
    # 406.88 M parameters, by the reference's shapes and by the arithmetic.
    n = sum(int(np.prod(shape)) for _, shape, _ in load_reference(config)._shapes(model))
    assert n == flops_ouro.parameters(model) == 406_884_353
    assert 4 * 51_388_416 + 2 * 100_663_296 + 4_097 == n
    # 20 B a parameter: float32 weights, gradient, Adam's two moments and the incoming model.
    assert abs(20 * n / 1e9 - 8.14) < 0.01


def test_operation_counts_by_hand_and_at_the_cell_sizes():
    small = dict(
        hidden_size=8, num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2, head_dim=4,
        intermediate_size=6, vocab_size=16, total_ut_steps=3, seq_len=4,
    )
    assert flops_ouro.applications(small) == 6 and flops_ouro.causal_pairs(small) == 10
    products = 2 * 4 * (8 * (2 * 8 + 2 * 8) + 3 * 8 * 6)                           # 3,200
    scores = 2 * 10 * 2 * 2 * 4                                                    # 320
    exit_ = 2 * 4 * 8 * (16 + 1)                                                   # 1,088
    assert flops_ouro.forward_flops(small, 1) == 6 * (products + scores) + 3 * exit_
    assert flops_ouro.attention_step(small, 1) == (3 * 6 * scores, 3 * 6 * 2 * 4 * 4 * (2 * 2 + 2 * 2))
    assert flops_ouro.exit_step(small, 1) == (3 * 3 * exit_, 3 * 3 * (2 * 8 * 16 + 4 * 4 * 8))

    config = run.load_spec(CELL)["config"]
    model = fl.reference_config(config)
    parts = flops_ouro.forward_parts(model, 1)
    # TFLOP forward a step of one sequence, by part.
    assert abs(parts["products"] / 1e12 - 0.842) < 0.001 and abs(parts["scores"] / 1e12 - 0.275) < 0.001
    assert abs(16 * parts["products"] / 1e12 - 13.469) < 0.001 and abs(16 * parts["scores"] / 1e12 - 4.399) < 0.001
    assert abs(4 * parts["exit"] / 1e12 - 6.597) < 0.001
    assert abs(flops_ouro.forward_flops(model, 1) / 1e12 - 24.46) < 0.01
    assert abs(flops_ouro.train_step_flops(model, 1) / 1e12 - 73.39) < 0.01
    # The looped stack is 73% of the operations, the exits 27%.
    assert abs(4 * parts["exit"] / flops_ouro.forward_flops(model, 1) - 0.27) < 0.005
    # The program's own arithmetic counts the same.
    from fedcrack_tpu.tasks import task_for

    assert abs(task_for(fl.program_config(config)).step_flops(1) / flops_ouro.train_step_flops(model, 1) - 1) < 1e-12
    # The exits are bound by their operations on a v5e, the scores too.
    peaks = read_json("benchmark", "peaks.json")["TPU v5 lite"]
    for ops, moved in (flops_ouro.exit_step(model, 1), flops_ouro.attention_step(model, 1)):
        assert ops / peaks["bf16_flops_per_s"] > moved / peaks["hbm_bytes_per_s"]


def test_new_metric_readers():
    def reader(name):
        return _load_module(f"{BENCH_DIR}/metrics/{name}.py", "m_" + name).read

    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    run_ctx = {
        "scope_seconds": {"loop_attn": 2.0, "loop_mlp": 1.0, "loop_exit": 4.0, "loop_attn_proj": 0.5},
        "peaks": peaks, "kernel_work": {"loop_attn": (100.0, 1.0), "loop_exit": (10.0, 20.0)},
    }
    assert reader("loop_attn_ms")(run_ctx) == 2000.0 and reader("loop_mlp_ms")(run_ctx) == 1000.0
    assert reader("loop_exit_ms")(run_ctx) == 4000.0
    assert reader("loop_attn_roofline")(run_ctx) == pytest.approx(50.0)  # compute bound: 1 s of 2
    assert reader("loop_exit_roofline")(run_ctx) == pytest.approx(50.0)  # memory bound: 2 s of 4
    # A program without the scopes: silent, never an error.
    old = {"records": [types.SimpleNamespace(metrics={"loss": np.zeros(1)})], "trace": {}, "peaks": peaks}
    for name in ("loop_attn_ms", "loop_attn_roofline", "loop_mlp_ms", "loop_exit_ms", "loop_exit_roofline"):
        assert reader(name)(old) is None


def _profile(instructions, gap_after=None):
    """A made-up device plane: ``instructions`` is ``[(name, events a step,
    ms an event)]`` run in that order step after step for ``steps`` steps,
    every event at its own start; ``gap_after`` adds a round's boundary."""
    events, t = [], 0
    steps, gap_after = 6, gap_after or {}
    for step in range(steps):
        for name, times, ms in instructions:
            for _ in range(times):
                events.append(types.SimpleNamespace(name=f"%{name} = f32[8] fusion(...)", start_ns=t, duration_ns=int(ms * 1e6)))
                t += int(ms * 1e6)
        t += gap_after.get(step, 0)
    return types.SimpleNamespace(planes=[types.SimpleNamespace(name="/device:TPU:0", lines=[types.SimpleNamespace(name="XLA Ops", events=events)])])


def test_steps_of_a_slice_are_marked_by_an_instruction_that_runs_once_a_step():
    """The heads' chunks (``token_losses``, a ``lax.map``) run 32 times a step
    and outweigh every once-a-step instruction in the slice: the accepted
    readers would mark a step by a chunk. This kind marks it by the heaviest
    of the instructions with the fewest events, four or more."""
    instructions = [("dkv.1", 1, 6.0), ("opt.1", 1, 1.0), ("head.1", 32, 1.2), ("twice.1", 2, 0.5)]
    # 100 ms of boundary after the fourth step.
    profile = _profile(instructions, {3: 100_000_000})
    hlo = "\n".join(
        f'  %{name} = f32[8]{{0}} fusion(%p), kind=kLoop, metadata={{op_name="jit(client_fit)/while/body/loop0/{scope}/mul"}}'
        for name, scope in [("dkv.1", "loop_attn"), ("opt.1", "optimizer"), ("head.1", "loop_exit"), ("twice.1", "loop_mlp")]
    )
    scopes = fl._driver._load_module(f"{BENCH_DIR}/trace/scopes.py", "bench_trace_scopes_looped_test")
    ours = scopes.seconds_a_step(profile, hlo, ("loop_attn", "optimizer", "loop_exit", "loop_mlp"), 1)
    assert ours["loop_attn"] == pytest.approx(0.006) and ours["optimizer"] == pytest.approx(0.001)
    assert ours["loop_exit"] == pytest.approx(32 * 0.0012) and ours["loop_mlp"] == pytest.approx(0.001)
    # The step's period and the boundary, from the marker's starts.
    reduce = fl._driver._load_module(f"{BENCH_DIR}/trace/reduce.py", "bench_trace_reduce_looped_test")
    period_ms = 6.0 + 1.0 + 32 * 1.2 + 2 * 0.5
    out = reduce.reduce_profile(profile, 1)
    assert out["step_period_s"] == pytest.approx(period_ms / 1e3)
    assert out["per_device"][0]["boundary_s"] == pytest.approx(0.100, rel=1e-6)
    # What the accepted readers make of the same slice: a chunk marks the step.
    plain = _load_module(f"{BENCH_DIR}/trace/reduce.py", "bench_trace_reduce_plain_test")
    assert not hasattr(plain, "accepted_steps")
    assert plain.reduce_profile(profile, 1)["step_period_s"] == pytest.approx(0.0012)


def test_the_marker_leaves_the_recorded_trace_as_the_accepted_reader_reads_it():
    """On the recorded U-Net slice (one instruction a step recurs most) both
    markers find the same step."""
    from jax.profiler import ProfileData

    recorded = ProfileData.from_file(f"{BENCH_DIR}/trace/recorded/tiny_round_1chip.xplane.pb")
    ours = fl._driver._load_module(f"{BENCH_DIR}/trace/reduce.py", "bench_trace_reduce_looped_rec").reduce_profile(recorded, 1)
    plain = _load_module(f"{BENCH_DIR}/trace/reduce.py", "bench_trace_reduce_plain_rec").reduce_profile(recorded, 1)
    assert ours["step_period_s"] == pytest.approx(plain["step_period_s"], rel=0.05)


class _FakeTracer:
    """A trace that starts once armed, passes the boundary at the end of the
    round after, and reports ``collect_s`` for its collection."""

    def __init__(self, collect_s):
        self.collect_s, self.round_s, self.armed = collect_s, None, False

    def arm(self, expected_round_s):
        self.armed = True

    def round_ended(self, round_s):
        if self.armed:
            self.round_s = round_s
        return self.armed

    def collect(self):
        return self.collect_s


@pytest.mark.parametrize("collect_s", [0.0, 30.0], ids=["quick_collection", "slow_collection"])
def test_a_traced_window_restarts_after_every_round_the_collection_held_up(tiny_spec, collect_s):
    """The window's first round is the first whose data was fed after the
    collection; one round later still where the collection outlasted the
    traced round."""
    from lib.compile_log import CompileLog

    cell = fl.Cell(tiny_spec, 2**31 + 5, jax.devices()[:1])
    tracer = _FakeTracer(collect_s)
    driven = cell.drive(0.2, tracer, time.perf_counter(), CompileLog())
    # Rounds 0 and 1 are checked, round 2 arms the trace, round 3 is traced
    # and the collection runs under round 4, in ``data_fn(5)``.
    assert driven["collect_s"] == collect_s and tracer.round_s < 30.0
    assert driven["records"][0].round_idx == (6 if collect_s > tracer.round_s else 5)
    assert driven["elapsed_s"] >= 0.2 and len(driven["program_rounds"]) == 2


def test_the_limits_name_what_the_comparison_gives():
    limits = read_json("benchmark", "limits", CELL + ".json")
    assert limits["window_compiles"] == 0 and limits["failed_rounds"] == 0
    for k in (0, 1):
        assert {f"direction_r{k}", f"total_change_r{k}", f"step_loss_r{k}", f"loss_r{k}",
                f"exit_r{k}", f"loop_nll_r{k}"} <= set(limits)
        # No reading of a fault parts from the program's by more than the
        # control's one flipped token: reported, held to no limit.
        assert f"next_acc_r{k}" not in limits
        # Between the program's largest reading and the least fault's.
        assert 0.0000079 < limits[f"loss_r{k}"] < 0.000084
    assert json.dumps(limits)


def test_a_side_with_fewer_exits_reads_a_full_gap():
    assert fl._worst_exit_gap([[1.0]], [[0.5, 0.25, 0.125, 0.125]]) == pytest.approx(1.0)
    assert fl._worst_exit_gap([[0.5, 0.25, 0.125, 0.125]], [[0.5, 0.25, 0.125, 0.125]]) == 0.0
