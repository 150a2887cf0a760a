"""The open-loop generator on ``fleet.steady``: the plan checks of
``test_plan.py`` for this cell, and the dense fleet's own bounds."""

import pytest

from bench.lib import cells, plan
from bench.tests import test_plan as base


@pytest.mark.parametrize("check", [
    base.test_same_work_every_seed, base.test_deterministic_and_inside_window,
    base.test_no_study_leaves_its_bucket], ids=lambda f: f.__name__[5:])
def test_fleet_plan(check):
    check("fleet.steady")


@pytest.mark.parametrize("seed", [1, 2**31 + 13, 2**32 + 7, 3141592653])
def test_fleet_stays_dense_inside_its_buckets_and_cache_shares(seed):
    """``fleet.steady`` at its rate over the benchmark's 51 s: each study,
    with every trial the warm-up and the window can add, stays in its train
    bucket and on the dense path, and the studies of each datastore shard
    fit that shard's share of the terminal-trial cache."""
    from repro.pythia.sparse_posterior import SPARSE_THRESHOLD
    from repro.service import datastore, operations

    cell = cells.load_cell("fleet.steady")
    p = plan.build(cell.config, cell.traffic, seed, 51.0)
    shards = int(cell.config["server"]["database_shards"])
    held = [0] * shards
    for s, (size, bucket, asks) in enumerate(zip(
            p.study_sizes, p.study_buckets, p.asks_per_study())):
        most = size + asks + 2 * p.warm_count
        assert most <= bucket and most <= SPARSE_THRESHOLD
        held[operations.shard_of(f"owners/bench/studies/s{s:02d}",
                                 shards)] += most
    assert max(held) <= datastore.TERMINAL_CACHE_TRIALS // shards
    assert sum(1 for n in held if n) == shards   # every shard holds studies
