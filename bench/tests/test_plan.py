"""The open-loop generator: same work for every seed, in another order."""

import numpy as np
import pytest

from bench.lib import cells, plan

CELLS = ["sparse.steady"]
SEEDS = [0, 7, 2**31 + 5, 2**33 + 1]


def _plan(cell_name, seed, seconds=30.0):
    cell = cells.load_cell(cell_name)
    return plan.build(cell.config, cell.traffic, seed, seconds)


@pytest.mark.parametrize("cell_name", CELLS)
def test_same_work_every_seed(cell_name):
    plans = [_plan(cell_name, s) for s in SEEDS]
    base = plans[0]
    for p in plans[1:]:
        assert p.study_sizes == base.study_sizes
        assert p.asks_per_study() == base.asks_per_study()
        assert sorted(e.count for e in p.events) == sorted(
            e.count for e in base.events)
        assert sorted(p.objectives) == sorted(base.objectives)
        gaps = np.sort(np.diff([e.due_s for e in p.events]))
        base_gaps = np.sort(np.diff([e.due_s for e in base.events]))
        assert len(gaps) == len(base_gaps)
    assert [e.study for e in plans[1].events] != [e.study for e in base.events]


@pytest.mark.parametrize("cell_name", CELLS)
def test_deterministic_and_inside_window(cell_name):
    a, b = _plan(cell_name, 2**31 + 9), _plan(cell_name, 2**31 + 9)
    assert a.events == b.events and a.objectives == b.objectives
    due = [e.due_s for e in a.events]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 30.0
    cell = cells.load_cell(cell_name)
    assert len(a.events) == round(cell.traffic["rate_per_s"] * 30.0)


@pytest.mark.parametrize("cell_name", CELLS)
def test_no_study_leaves_its_bucket(cell_name):
    p = _plan(cell_name, 11)
    for size, bucket, asks in zip(p.study_sizes, p.study_buckets,
                                  p.asks_per_study()):
        assert bucket // 2 < size
        assert size + asks + 2 * p.warm_count <= bucket


def test_workers_take_events_in_turn():
    p = _plan("sparse.steady", 3)
    for s in range(len(p.study_sizes)):
        workers = [e.worker for e in p.events if e.study == s]
        assert workers == [i % p.workers for i in range(len(workers))]


def test_zipf_quota_and_hot_studies_have_room():
    cell = cells.load_cell("sparse.steady")
    config = dict(cell.config, workers_per_study=4, studies={"bands": [
        {"count": 16, "size_min": 260, "size_max": 400, "bucket": 512},
        {"count": 16, "size_min": 600, "size_max": 900, "bucket": 1024}]})
    traffic = dict(cell.traffic, rate_per_s=5.2,
                   popularity={"kind": "zipf", "s": 1.1})
    p = plan.build(config, traffic, 3, 30.0)
    per = np.bincount([e.study for e in p.events],
                      minlength=len(p.study_sizes))
    assert per[0] == per.max()                     # rank 1 is study 0
    assert p.study_buckets[0] == 1024              # in the larger bucket
    assert per[0] / per.sum() == pytest.approx(0.2837, abs=0.01)


def test_rate_too_high_is_refused():
    cell = cells.load_cell("sparse.steady")
    traffic = dict(cell.traffic, rate_per_s=200.0)
    with pytest.raises(plan.PlanError):
        plan.build(cell.config, traffic, 1, 30.0)


def test_quotas_sum_and_follow_weights():
    q = plan.quotas([0.4, 0.2, 0.2, 0.2], 101)
    assert sum(q) == 101 and q[0] == max(q)
