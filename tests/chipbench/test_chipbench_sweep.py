"""``sweep.knee``: the knee of an open-loop cell named by its capacity,
on the rows the four-chip chat sweep printed (20-s windows)."""
import pytest

import chipbench_helpers  # noqa: F401  (the harness on the path)
import sweep

# rate_per_s, output_tok_s, grows: qwen3-8b-tp4.chat at 32 slots, one v5e-4
TP4_CHAT = [(2.2, 288.8, False), (2.42, 301.7, False), (2.662, 310.35, False),
            (2.9282, 316.5, False), (3.22102, 316.75, False),
            (3.543122, 315.6, True), (3.8974342, 314.05, True)]
MEAN_ANSWER = 141.6484375  # the chat mix's answers


def _rows(table):
    return [{"rate_per_s": r, "output_tok_s": o, "grows": g}
            for r, o, g in table]


def test_the_knee_is_the_highest_rate_the_capacity_covers():
    """Output saturates near 316.75 tokens/s, 2.236 req/s of the mix's
    answers: 2.2 is the knee, though the queue test passed up to 3.22."""
    k = sweep.knee(_rows(TP4_CHAT), MEAN_ANSWER)
    assert k["capacity_req_s"] == pytest.approx(316.75 / MEAN_ANSWER)
    assert k["knee"] == 2.2
    assert round(0.8 * k["knee"], 2) == 1.76


@pytest.mark.parametrize("table, expected", [
    ([(1.0, 100.0, False), (1.1, 110.0, False), (1.21, 118.0, True),
      (1.331, 119.0, True)], 1.1),
    ([(2.0, 100.0, False), (2.2, 101.0, True)], None),
    ([(1.0, 150.0, False), (1.1, 151.0, True), (1.21, 300.0, False)], 1.0),
], ids=["queue_grows_first", "start_past_capacity", "stops_at_growth"])
def test_the_knee_stops_at_a_growing_queue_or_past_capacity(table,
                                                            expected):
    assert sweep.knee(_rows(table), 100.0)["knee"] == expected
