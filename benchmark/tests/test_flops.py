import json
import os

import pytest

from conftest import BENCH_DIR, read_json
from lib import flops


@pytest.mark.parametrize("name,batch", [("resunet256_bf16", 32), ("resunet512_bf16", 16)])
def test_the_copy_counts_what_the_programs_own_arithmetic_counts(name, batch):
    from fedcrack_tpu.configs import ModelConfig
    from fedcrack_tpu.obs.flops import train_step_flops

    config = read_json("benchmark", "configs", name + ".json")
    assert config["batch_size"] == batch
    ours = flops.train_step_flops(config["model"], batch)
    assert ours == train_step_flops(ModelConfig(img_size=config["model"]["img_size"]), batch)


def test_peaks_name_their_source():
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    row = peaks["TPU v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes"] == 16e9 and row["source"]
