"""``expert_moved_rows_a_round``, the reader of the held-expert layer's
``moved_rows`` counter, on hand-made records: the counter summed over the
clients, mean over the window's rounds; silent on a program without it."""

import types

import numpy as np
import pytest

from test_host_metrics import reader


def rounds(*moved):
    return {"records": [
        types.SimpleNamespace(metrics={"loss": np.zeros(len(m)), "moved_rows": np.asarray(m, np.float32)})
        for m in moved
    ]}


def test_reads_the_mean_over_the_windows_rounds_of_the_clients_sum():
    # Two rounds of two clients, 64 calls each: kept rows on the kernels.
    assert reader("expert_moved_rows_a_round")(rounds([560_640, 561_000], [560_000, 559_360])) == pytest.approx(
        1_120_500.0
    )


def test_the_xla_forms_reading_is_its_row_arrays_length_times_the_calls():
    # 64 calls of the budget's 24,576 rows a round, one client.
    assert reader("expert_moved_rows_a_round")(rounds([24_576 * 64])) == pytest.approx(1_572_864.0)


def test_silent_without_records_or_without_the_counter():
    read = reader("expert_moved_rows_a_round")
    assert read({"records": []}) is None
    parent = types.SimpleNamespace(metrics={"loss": np.zeros(1), "expert_tiles": np.zeros(1)})
    assert read({"records": [parent]}) is None
    # A window in which any round lacks it reads nothing rather than a part.
    assert read({"records": [*rounds([8760])["records"], parent]}) is None
