"""obs/devtrace.py: every instruction of the compiled round resolves to a
named scope, and a trace's device events sum over scopes to busy time.

The scopes themselves (``jax.named_scope`` in ``parallel/fedavg_mesh.py``
and ``models/resunet.py``) are metadata; that they change no value is what
the byte-identity pins of the round builders keep proving.
"""

import re
import types

import jax
import numpy as np
import pytest

from fedcrack_tpu.configs import ModelConfig
from fedcrack_tpu.obs import devtrace
from fedcrack_tpu.parallel import build_federated_round, make_mesh

TINY = ModelConfig(
    img_size=16, stem_features=4, encoder_features=(8, 8), decoder_features=(8, 8, 4)
)
STEPS, BATCH, CLIENTS = 3, 2, 2
# Opcodes that materialise a constant: the lowering of the scan body's
# closed-over constants makes them, no traced operation does, so they carry
# the body's bare path.
CONSTANTS = ("constant", "iota", "broadcast")


@pytest.fixture(scope="module")
def round_hlo():
    from fedcrack_tpu.models.resunet import init_variables

    mesh = make_mesh(CLIENTS, 1)
    round_fn = build_federated_round(mesh, TINY, learning_rate=1e-3, local_epochs=2)
    rng = np.random.default_rng(0)
    images = rng.integers(0, 255, (CLIENTS, STEPS, BATCH, 16, 16, 3), dtype=np.uint8)
    masks = (rng.random((CLIENTS, STEPS, BATCH, 16, 16, 1)) > 0.8).astype(np.uint8)
    variables = init_variables(jax.random.key(0), TINY)
    _, metrics = round_fn(
        variables, images, masks, np.ones(CLIENTS, np.float32),
        np.full(CLIENTS, float(STEPS * BATCH), np.float32),
    )
    jax.block_until_ready(metrics)
    # The executable stays loaded while round_fn lives: read it now.
    return devtrace.loaded_hlo_text(), {k: np.asarray(v) for k, v in metrics.items()}


def _with_op_name(text):
    for body in devtrace._computations(text).values():
        for name, _, opcode, rest in body:
            m = devtrace._OP_NAME.search(rest)
            if m:
                yield name, opcode, m.group(1)


def test_scan_body_resolves_to_named_scopes(round_hlo):
    text, _ = round_hlo
    scopes = devtrace.scope_map(text)
    body = [
        (name, op_name) for name, opcode, op_name in _with_op_name(text)
        if "/while/body/closed_call/while/body/closed_call" in op_name and opcode not in CONSTANTS
    ]
    assert len(body) > 500
    named = [name for name, _ in body if scopes[name][0] is not None]
    assert len(named) >= 0.95 * len(body)
    found = {scopes[name][0] for name in named}
    assert {"unpack", "loss", "bn_sync", "optimizer", "step_metrics"} <= found
    assert {scopes[name] for name in named} >= {("loss", "fwd"), ("loss", "bwd"), ("optimizer", "other")}


def test_every_model_instruction_resolves_to_a_block(round_hlo):
    text, _ = round_hlo
    scopes = devtrace.scope_map(text)
    model = [(name, op_name) for name, _, op_name in _with_op_name(text) if devtrace.MODEL in op_name]
    assert len(model) > 500
    blocks = set()
    for name, op_name in model:
        scope, phase = scopes[name]
        assert scope is not None and devtrace.BLOCK.match(scope), op_name
        assert phase in ("fwd", "bwd"), op_name
        blocks.add((scope, phase))
    expected = {"stem", "enc0", "enc1", "dec0", "dec1", "dec2", "head"}
    assert blocks == {(b, p) for b in expected for p in ("fwd", "bwd")}


def test_the_fold_has_a_name(round_hlo):
    """``round_init`` is in the source too, but fresh Adam state is zeros:
    the compiler folds it into constants and no instruction is left."""
    text, _ = round_hlo
    found = {scope for scope, _ in devtrace.scope_map(text).values()}
    assert {"fold", "round_metrics"} <= found
    gathers = [name for name, opcode, _ in _with_op_name(text) if opcode == "all-gather"]
    assert gathers and all(devtrace.scope_map(text)[g][0] == "fold" for g in gathers)


def test_step_loss_rides_out_of_the_round_program(round_hlo):
    _, metrics = round_hlo
    curve = metrics["step_loss"]
    assert curve.shape == (CLIENTS, 2, STEPS) and curve.dtype == np.float32
    np.testing.assert_allclose(curve[:, -1].mean(axis=-1), metrics["loss"], atol=1e-6)


def test_every_scope_in_the_sources_is_known_to_the_reader():
    """A scope added to the program and not to ``devtrace`` would read as
    unscoped time."""
    import fedcrack_tpu.models.resunet as resunet
    import fedcrack_tpu.parallel.fedavg_mesh as fedavg_mesh

    literal = re.compile(r'jax\.named_scope\(f?"([^"]+)"\)')
    for module in (fedavg_mesh, resunet):
        names = literal.findall(open(module.__file__).read())
        assert names, module.__name__
        for name in names:
            name = name.replace("{i}", "0")
            assert name in devtrace.STEP_SCOPES + devtrace.ROUND_SCOPES or devtrace.BLOCK.match(name), name


@pytest.mark.parametrize(
    "op_name, want",
    [
        ("jit(client_fit)/shard_map/while/body/closed_call/while/body/closed_call/optimizer/add", ("optimizer", "other")),
        ("jit(client_fit)/while/body/closed_call/jvp(ResUNet)/enc0/enc0_bn1/mul", ("enc0", "fwd")),
        ("jit(client_fit)/while/body/transpose(jvp(ResUNet))/dec2/dec2_convT1/conv_general_dilated[window=(1, 1) a/b]", ("dec2", "bwd")),
        ("jit(client_fit)/while/body/closed_call/transpose(jvp(loss))/mul", ("loss", "bwd")),
        ("jit(client_fit)/shard_map/fold/while/body/closed_call/add", ("fold", "other")),
        ("jit(client_fit)/shard_map/while/body/closed_call/while/body/closed_call", (None, "other")),
        # A block's name outside the model is not a block.
        ("jit(other)/head/add", (None, "other")),
        # The compiler's copy of an argument carries the argument's name.
        ("data_a", ("arguments", "other")),
    ],
)
def test_resolve(op_name, want):
    assert devtrace._resolve(op_name) == want


HLO = """HloModule jit_client_fit

%fused_slice (param_0.1: u8[8,4,16], param_1.1: s32[]) -> f32[4,16] {
  %param_0.1 = u8[8,4,16]{2,1,0} parameter(0)
  %param_1.1 = s32[] parameter(1)
  %ds = u8[1,4,16]{2,1,0} dynamic-slice(%param_0.1, %param_1.1), dynamic_slice_sizes={1,4,16}
  ROOT %cv = f32[4,16]{1,0} convert(%ds)
}

%body (p: (f32[4,16], u8[8,4,16], s32[])) -> (f32[4,16], u8[8,4,16], s32[]) {
  %p = (f32[4,16]{1,0}, u8[8,4,16]{2,1,0}, s32[]) parameter(0)
  %w = f32[4,16]{1,0:T(8,128)S(1)} get-tuple-element(%p), index=0
  %slab = u8[8,4,16]{2,1,0} get-tuple-element(%p), index=1
  %i = s32[] get-tuple-element(%p), index=2
  %copy.1 = f32[4,16]{1,0} copy(%w)
  %fusion.1 = f32[4,16]{1,0} fusion(%slab, %i), kind=kLoop, calls=%fused_slice, metadata={op_name="jit(client_fit)/while/body/closed_call/unpack/convert_element_type"}
  %fusion.2 = f32[4,16]{1,0} fusion(%copy.1, %fusion.1), kind=kLoop, calls=%fused_add, metadata={op_name="jit(client_fit)/while/body/closed_call/transpose(jvp(ResUNet))/enc0/enc0_sep1/mul"}
  %add.3 = f32[4,16]{1,0} add(%fusion.2, %fusion.2), metadata={op_name="jit(client_fit)/while/body/closed_call/optimizer/add"}
  %mystery = f32[4,16]{1,0} negate(%add.3), metadata={op_name="jit(client_fit)/while/body/closed_call"}
  ROOT %t = (f32[4,16]{1,0}, u8[8,4,16]{2,1,0}, s32[]) tuple(%mystery, %slab, %i)
}

ENTRY %main (a: f32[4,16]) -> f32[4,16] {
  %a = f32[4,16]{1,0} parameter(0)
  %while.1 = (f32[4,16]{1,0}, u8[8,4,16]{2,1,0}, s32[]) while(%a), condition=%cond, body=%body
  ROOT %fold.1 = f32[4,16]{1,0} multiply(%a, %a), metadata={op_name="jit(client_fit)/shard_map/fold/mul"}
}
"""


def test_scope_map_and_bytes_on_a_hand_made_module():
    scopes = devtrace.scope_map(HLO)
    assert scopes["fusion.1"] == ("unpack", "other")
    assert scopes["fusion.2"] == ("enc0", "bwd")
    assert scopes["mystery"] == (None, "other")
    # The compiler's copy has no metadata: it is its consumer's.
    assert scopes["copy.1"] == ("enc0", "bwd")
    assert "while.1" not in scopes and "a" not in scopes
    nbytes = devtrace.instruction_bytes(HLO)
    f32 = 4 * 16 * 4
    # copy.1 reads an array the compiler keeps on the chip (S(1)): only its
    # result goes through HBM.
    assert nbytes["add.3"] == 3 * f32 and nbytes["copy.1"] == f32
    # The fusion only slices its slab operand: one step's 64 bytes, not 512.
    assert nbytes["fusion.1"] == f32 + 4 * 16 + 4
    assert "while.1" not in nbytes and nbytes["slab"] == 0.0


def _profile(events):
    """A stand-in for ``jax.profiler.ProfileData``: one device plane."""
    event = lambda name, start, dur: types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)
    ops = types.SimpleNamespace(name="XLA Ops", events=[event(*e) for e in events])
    steps = types.SimpleNamespace(name="Steps", events=[event("0", 0, 10**6)])
    host = types.SimpleNamespace(name="python", events=[event("driver.round", 0, 5000), event("driver.feed", 10, 300), event("other", 0, 9)])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0", lines=[steps, ops]),
        types.SimpleNamespace(name="/host:CPU", lines=[host]),
    ])


def test_by_scope_sums_to_busy_time_and_skips_enclosing_events():
    events = []
    for step in range(4):
        t = 1000 * step
        events += [
            ("%fusion.1 = f32[4,16]{1,0} fusion(...)", t, 100),
            ("%copy.1 = f32[4,16]{1,0} copy(...)", t + 100, 50),
            ("%fusion.2 = f32[4,16]{1,0} fusion(...)", t + 150, 300),
            ("%add.3", t + 450, 150),
            ("%mystery", t + 600, 40),
        ]
    events += [("%while.1 = (...) while(...)", 0, 4000), ("%fold.1", 4000, 200), ("%unknown.9", 4200, 60)]
    table = devtrace.by_scope(_profile(events), HLO)
    assert table["steps"] == 4
    busy = (4 * (100 + 50 + 300 + 150 + 40) + 200 + 60) * 1e-9
    assert table["busy_s"] == pytest.approx(busy) == pytest.approx(table["busy_union_s"])
    assert sum(r["seconds"] for r in table["rows"]) == pytest.approx(busy)
    assert sum(r["share_of_busy"] for r in table["rows"]) == pytest.approx(1.0)
    rows = {(r["scope"], r["phase"]): r for r in table["rows"]}
    assert rows[("enc0", "bwd")]["seconds_per_step"] == pytest.approx(350e-9)
    assert rows[("optimizer", "other")]["seconds_per_step"] == pytest.approx(150e-9)
    assert rows[("fold", "other")]["seconds"] == pytest.approx(200e-9)
    assert rows[("fold", "other")]["per"] == "round" and rows[("fold", "other")]["seconds_per_step"] is None
    assert [name for name, _ in table["unscoped_ops"]] == ["mystery", "unknown.9"]
    assert table["unscoped_share"] == pytest.approx((4 * 40 + 60) * 1e-9 / busy)
    # 3 x 256 bytes in 150 ns.
    assert rows[("optimizer", "other")]["gbytes_per_s"] == pytest.approx(768 / 150)
    assert table["top_ops"][0]["op"] == "fusion.2"
    # What a call costs is read off its own events, however a slice cuts the steps.
    assert table["top_ops"][0]["events"] == 4 and table["top_ops"][0]["seconds"] == pytest.approx(4 * 300e-9)
    assert devtrace.host_spans(_profile(events)) == {
        "driver.round": {"count": 1, "seconds": pytest.approx(5e-6)},
        "driver.feed": {"count": 1, "seconds": pytest.approx(3e-7)},
    }


def test_by_scope_counts_steps_by_the_marking_operation_not_the_most_frequent():
    """A step with a loop inside it: fusion.1 runs three times a step, so the
    most frequent instruction says 12 steps where there are 4. The operation
    that recurs and takes the most time (fusion.2, once a step) marks them,
    benchmark/trace/reduce.py's own rule; a collective never does, however
    long it runs; where nothing recurs there is no step to divide by."""
    events = []
    for step in range(4):
        t = 1000 * step
        events += [("%fusion.1", t + 60 * i, 50) for i in range(3)]
        events += [("%fusion.2", t + 200, 300), ("%add.3", t + 500, 150), ("%all-reduce.7", t + 650, 340)]
    table = devtrace.by_scope(_profile(events), HLO)
    assert table["steps"] == 4
    rows = {(r["scope"], r["phase"]): r for r in table["rows"]}
    assert rows[("unpack", "other")]["seconds_per_step"] == pytest.approx(3 * 50e-9)
    assert rows[("enc0", "bwd")]["seconds_per_step"] == pytest.approx(300e-9)
    assert table["top_ops"][0]["op"] == "all-reduce.7"
    assert table["top_ops"][1]["seconds_per_step"] == pytest.approx(300e-9)
    cut = devtrace.by_scope(_profile([e for e in events[:18] if e[0] != "%fusion.1"]), HLO)  # three steps, no loop
    assert cut["steps"] == 0
    assert all(r["seconds_per_step"] is None for r in cut["rows"])
    assert all(r["seconds_per_step"] is None for r in cut["top_ops"])


def test_profile_step_prints_a_table_without_a_step_count():
    """A slice in which nothing recurs has no ms-a-step column to print; the
    tool's table says so in place of dividing by nothing."""
    from fedcrack_tpu.tools.profile_step import format_table

    events = [(name, 1000 * step + at, dur) for step in range(3) for name, at, dur in (("%fusion.2", 0, 300), ("%add.3", 300, 150))]
    profile = _profile(events)
    text = format_table({"by_scope": devtrace.by_scope(profile, HLO), "idle_gaps": [], "host_spans": devtrace.host_spans(profile)})
    assert "traced slice: 0 steps" in text
    enc0 = next(line for line in text.splitlines() if line.startswith("enc0"))
    assert enc0.split()[:3] == ["enc0", "bwd", "-"]


def test_idle_gaps_are_named_after_the_innermost_covering_host_span():
    events = [("%fusion.1", 0, 1000), ("%fusion.2", 1050, 1000), ("%add.3", 200_000, 1000)]
    profile = _profile(events)
    host = profile.planes[1].lines[0]
    event = lambda name, start, dur: types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)
    host.events = [event("driver.handoff", 1000, 100_000), event("driver.dispatch", 101_000, 120_000), event("PjitFunction(client_fit)", 102_000, 110_000)]
    (gap,) = devtrace.idle_gaps(profile, min_s=1e-4)  # the 50 ns gap is too short
    assert gap["seconds"] == pytest.approx(197_950e-9)
    assert gap["host"] == "driver.handoff"  # covers 0.5 of it, the dispatch 0.49
    assert gap["driver_share"] == pytest.approx(1.0)
    host.events = host.events[1:]
    assert devtrace.idle_gaps(profile)[0]["host"] == "PjitFunction(client_fit)"  # inside driver.dispatch
    host.events = []
    assert devtrace.idle_gaps(profile)[0]["host"] is None
    assert devtrace.idle_gaps(profile)[0]["driver_share"] == 0.0



# ---- the second family: the block pattern comes from the task ---------------


@pytest.fixture(scope="module")
def text_round_hlo():
    from fedcrack_tpu.data.textdiff import stage_pair

    from test_sdar_moe import small_config

    config = small_config()
    mesh = make_mesh(1, 1)
    round_fn = build_federated_round(mesh, config, learning_rate=1e-3)
    rng = np.random.default_rng(0)
    sequences = rng.integers(0, config.mask_token, (1, 4, config.seq_len), dtype=np.int32)
    ids, weight = stage_pair(sequences, 2, 2, config.block_length, rng)
    _, metrics = round_fn(
        round_fn.task.init(jax.random.key(0)), ids, weight, np.ones(1, np.float32), np.full(1, 4.0, np.float32)
    )
    jax.block_until_ready(metrics)
    return devtrace.loaded_hlo_text(round_fn.task.program_name), round_fn.task


def test_text_round_resolves_to_the_tasks_blocks(text_round_hlo):
    text, task = text_round_hlo
    scopes = devtrace.scope_map(text, task)
    found = {scope for scope, _ in scopes.values()}
    blocks = {"embed", "attn_proj", "blockdiff_attn", "router", "moe_dispatch", "moe_experts", "moe_combine", "lm_head"}
    assert blocks <= found, blocks - found
    assert {"unpack", "loss", "optimizer", "fold"} <= found
    for block in ("attn_proj", "blockdiff_attn", "moe_experts"):
        assert {(block, "fwd"), (block, "bwd")} <= set(scopes.values()), block
    body = [
        name for name, opcode, op_name in _with_op_name(text)
        if "/while/body/closed_call/while/body/closed_call" in op_name and opcode not in CONSTANTS
    ]
    assert len(body) > 200
    assert sum(scopes[name][0] is not None for name in body) >= 0.9 * len(body)
    # Read with the crack U-Net's blocks, none of the model's names is found.
    assert not blocks & {scope for scope, _ in devtrace.scope_map(text).values()}


@pytest.mark.parametrize("family", ["sdar_moe", "mla_moe", "gdn_moe"])
def test_every_scope_of_a_text_family_is_known_to_its_task(family):
    """The family's own module and the layers both share (``moe_layers``)."""
    import importlib

    from fedcrack_tpu import configs, tasks
    from fedcrack_tpu.models import moe_layers

    # The causal task reads its blocks off the model its configuration names.
    task = {
        "sdar_moe": tasks.TextDiffusionTask(), "mla_moe": tasks.CausalLMTask(configs.MlaMoeConfig()),
        "gdn_moe": tasks.CausalLMTask(configs.GdnMoeConfig()),
    }[family]
    block = re.compile(task.block_scope)
    own = importlib.import_module(f"fedcrack_tpu.models.{family}")
    names = [
        name for module in (own, moe_layers)
        for name in re.findall(r'jax\.named_scope\(f?"([^"]+)"\)', open(module.__file__).read())
    ]
    assert len(names) >= 12 and {"router", "moe_dispatch", "moe_experts", "moe_combine"} <= set(names)
    for name in names:
        # ``layer<i>`` and the multi-token-prediction module enclose blocks.
        assert block.match(name) or re.match(r"^(layer\{i\}|mtp)$", name), name


@pytest.mark.parametrize(
    "op_name, want",
    [
        ("jit(client_fit)/while/body/closed_call/jvp(layer2)/checkpoint/moe_experts/ragged_dot", ("moe_experts", "fwd")),
        ("jit(client_fit)/while/body/closed_call/transpose(jvp(layer0))/checkpoint/blockdiff_attn/pallas_call", ("blockdiff_attn", "bwd")),
        ("jit(client_fit)/while/body/closed_call/jvp(lm_head)/while/body/checkpoint/dot_general", ("lm_head", "fwd")),
        ("jit(client_fit)/while/body/closed_call/jvp(layer1)/checkpoint/mul", (None, "fwd")),
    ],
)
def test_resolve_with_the_text_tasks_blocks(op_name, want):
    from fedcrack_tpu.tasks import TextDiffusionTask

    block, model = devtrace._blocks_of(TextDiffusionTask())
    assert devtrace._resolve(op_name, block, model) == want
