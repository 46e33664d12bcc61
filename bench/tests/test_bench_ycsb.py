"""The benchmark's own YCSB generator keeps the source's shapes."""
import numpy as np

import bench_tiny  # noqa: F401  (puts bench/ and src/ on the path)
from starbench.reference import READ, SET
from starbench.ycsb import YCSBSource

PARAMS = {"n_partitions": 16, "records_per_partition": 200_000,
          "row_words": 25, "ops_per_txn": 10, "write_ops": 1,
          "cross_ratio": 0.10}


def test_requests_have_the_sources_widths_and_mix():
    src = YCSBSource(PARAMS, seed=2**31 + 5)
    req = src.generate(20_000)
    n = 20_000
    assert req["deltas"].shape == (n, 10, 25)
    assert src.row_bytes.tolist() == [100] * 10
    assert ((req["kinds"] == SET).sum(axis=1) == 1).all()
    assert ((req["kinds"] == READ) | (req["kinds"] == SET)).all()
    assert (req["rows"] >= 0).all() and (req["rows"] < 200_000).all()
    cross = req["home"] < 0
    assert abs(cross.mean() - 0.10) < 0.01
    # a single-partition transaction stays on its home partition; a cross
    # one keeps its first op there
    assert (req["parts"][~cross] == req["home"][~cross, None]).all()
    assert len(np.unique(req["parts"][cross][:, 0])) == 16
    # reads carry no payload, a write carries a whole row
    assert not req["deltas"][req["kinds"] == READ].any()
    assert req["deltas"][req["kinds"] == SET].any(axis=1).all()


def test_the_same_seed_gives_the_same_requests():
    a = YCSBSource(PARAMS, seed=7).generate(100)
    b = YCSBSource(PARAMS, seed=7).generate(100)
    assert all(np.array_equal(a[k], b[k]) for k in a)
