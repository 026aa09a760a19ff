"""Window arithmetic: a rate over the whole window."""

import pytest

from window import rate


@pytest.mark.parametrize("units,t0,t1,want", [
    (4096 * 200, 10.0, 40.0, 4096 * 200 / 30.0),
    (4096, 0.0, 0.25, 16384.0),
])
def test_rate_counts_all_work_over_all_time(units, t0, t1, want):
    assert rate(units, t0, t1) == pytest.approx(want)


@pytest.mark.parametrize("t0,t1", [(5.0, 5.0), (5.0, 4.0)])
def test_rate_refuses_an_empty_window(t0, t1):
    with pytest.raises(ValueError):
        rate(1, t0, t1)
