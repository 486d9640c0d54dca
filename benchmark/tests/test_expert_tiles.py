"""``expert_tiles_a_round``, the reader of the held-expert layer's
``expert_tiles`` counter, on hand-made records: the counter summed over the
clients, mean over the window's rounds; silent on a program without it."""

import types

import numpy as np
import pytest

from test_host_metrics import reader


def rounds(*tiles):
    return {"records": [
        types.SimpleNamespace(metrics={"loss": np.zeros(len(t)), "expert_tiles": np.asarray(t, np.float32)})
        for t in tiles
    ]}


def test_reads_the_mean_over_the_windows_rounds_of_the_clients_sum():
    # Two rounds of two clients: 1,600 + 1,620 and 1,604 + 1,596 tiles.
    assert reader("expert_tiles_a_round")(rounds([1600, 1620], [1604, 1596])) == pytest.approx(3210.0)


def test_silent_without_records_or_without_the_counter():
    read = reader("expert_tiles_a_round")
    assert read({"records": []}) is None
    parent = types.SimpleNamespace(metrics={"loss": np.zeros(1), "budget_overflows": np.zeros(1)})
    assert read({"records": [parent]}) is None
    # A window in which any round lacks it reads nothing rather than a part.
    assert read({"records": [*rounds([1600])["records"], parent]}) is None
