"""The fence-aligned window and the latency arithmetic."""
import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the path)
from starbench import stats


@pytest.mark.parametrize("fences,start,seconds,want", [
    ([0.0, 5.0, 10.0, 15.0], 0, 10.0, 2),      # a fence exactly at 10 s
    ([0.0, 4.9, 9.9, 14.8], 0, 10.0, 3),       # never cut inside an epoch
    ([1.0, 2.0, 3.0, 4.0], 1, 2.0, 3),         # measured from fence `start`
    ([0.0, 1.0, 2.0], 0, 5.0, None),           # not closed yet
])
def test_window_end_is_the_first_fence_past_the_length(fences, start,
                                                        seconds, want):
    assert stats.window_end(fences, start, seconds) == want


def test_percentiles_interpolate_between_samples():
    lat = np.array([1.0, 2.0, 3.0, 4.0])       # seconds
    assert stats.percentile_ms(lat, 50) == pytest.approx(2500.0)
    assert stats.percentile_ms(lat, 99) == pytest.approx(3970.0)
    assert np.isnan(stats.percentile_ms([], 50))


def test_rate_spans_fence_to_fence():
    assert stats.rate(1000, 10.0, 15.0) == pytest.approx(200.0)


def test_a_traffic_file_asking_for_arrivals_the_generator_lacks_is_refused():
    from starbench.traffic import Ledger, make_client
    with pytest.raises(ValueError, match="unknown traffic keys"):
        make_client({"loop": "open", "rate_txn_s": 10.0, "process": "bursty"},
                    None, Ledger(), 1)
