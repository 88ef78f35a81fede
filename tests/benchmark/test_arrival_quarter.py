"""The fault "one four-chip cell more than a quarter of the cells allows", at
whatever count today's spec and the rehearsed arrival make.

``test_arrival.py`` holds that fault at one count: ``a-second-four-chip-cell-
at-seven-cells`` turns the arrival into a second four-chip cell and expects
"2 four-chip cells of 7", which is six cells and the arrival. With the seventh
cell (PR 62) the arrival makes eight, of which two may take four chips
(``rules.top_level``: a quarter, rounded down), so that case no longer breaks
the rule it means to; ``tests/conftest.py`` marks it an expected failure until
a ``benchmark`` PR rewrites it (a ``model_config`` PR edits no file the
benchmark has). The rule it stood for is held here."""

import pytest

from tests.benchmark import rules
from tests.benchmark.test_arrival import arrival, keep     # noqa: F401


def four_chips(spec, more):
    """Give four chips to as many one-chip cells as the quarter leaves room
    for, and ``more`` besides."""
    cells = spec["workloads"]
    room = max(1, len(cells) // 4) - sum(w["chips"] == 4 for w in cells)
    assert room >= 0
    for w in [w for w in cells if w["chips"] == 1][:room + more]:
        w["chips"] = 4
    return room


def test_a_four_chip_cell_past_the_quarter_is_refused(arrival):
    spec, root = arrival
    rules.top_level(spec, root)                       # sound before
    four_chips(spec, 1)
    keep(spec, root)
    with pytest.raises(AssertionError, match=r"\d+ four-chip cells of \d+"):
        rules.top_level(spec, root)


def test_four_chip_cells_up_to_the_quarter_pass(arrival):
    spec, root = arrival
    four_chips(spec, 0)
    keep(spec, root)
    rules.top_level(spec, root)
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four == max(1, len(spec["workloads"]) // 4)
