"""Build what a configuration file describes: its data from the seed, the
engine holding it, and the source of its transactions.

A configuration file names its ``kind``; each kind here maps the file's
parameters onto a transaction source: the benchmark's own YCSB generator,
or the program's TPC-C workload module, whose host mirror is program
state fed back each epoch.  The initial records are drawn on the host
from the seed in one bulk call and handed, as one array, both to the
engine and to the reference.
"""
from __future__ import annotations

import numpy as np


def seeds(seed: int, n: int = 4) -> list[int]:
    """``n`` independent 32-bit seeds drawn from the run's seed."""
    return [int(s) for s in np.random.SeedSequence(seed).generate_state(n)]


class World:
    """init_val: (P, R, C) int32 records; index_specs: the ordered indexes,
    or None; source: the transaction source; feedback: the service's
    per-epoch hook, or None."""

    def __init__(self, init_val, index_specs, source, feedback):
        self.init_val = init_val
        self.index_specs = index_specs
        self.source = source
        self.feedback = feedback


def build_tpcc(params: dict, data_seed: int, source_seed: int) -> World:
    from repro.db import tpcc
    from repro.service import TPCCSource
    cfg = tpcc.TPCCConfig(**params)
    state = tpcc.TPCCState(cfg)
    init = tpcc.init_values(cfg, np.random.default_rng(data_seed),
                            state=state)
    return World(init, tpcc.index_specs(cfg),
                 TPCCSource(cfg, state=state, seed=source_seed),
                 lambda b, m: tpcc.apply_consume_feedback(state, b, m))


def build_ycsb(params: dict, data_seed: int, source_seed: int) -> World:
    from starbench.ycsb import YCSBSource
    source = YCSBSource(params, source_seed)
    init = source.init_values(np.random.default_rng(data_seed))
    return World(init, None, source, None)


BUILDERS = {"tpcc": build_tpcc, "ycsb": build_ycsb}


def build(config: dict, seed: int) -> World:
    data_seed, source_seed = seeds(seed, 2)
    return BUILDERS[config["kind"]](config["params"], data_seed, source_seed)
