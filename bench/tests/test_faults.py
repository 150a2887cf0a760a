"""A run with the timed path broken underneath comes out not correct.

Each test drives the whole harness on the CPU (the look for a chip is the
one step skipped) at the size of ``tiny.py``, plants one fault after the
warm-up, and checks that ``correct`` is false for the number that should
catch it. A run with no fault comes out correct.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_faults.py -q
"""

import numpy as np
import pytest

from bench import run
from bench.tests.tiny import tiny_cell

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def _run(fault=None, kind="dense", seed=2**31 + 77):
    return run.run_cell(tiny_cell(kind), seed, 3.0, False, DEVICE,
                        grace_s=30.0, fault=fault)


def _failing(res):
    return {k for k, v in res["checks"].items() if v["value"] > v["limit"]}


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_sound_run_is_correct(kind):
    res = _run(kind=kind)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0


def test_completion_acknowledged_but_not_stored(monkeypatch):
    """A step that returns its state unchanged: CompleteTrial acknowledges
    and the stored trial stays ACTIVE."""
    from repro.core.study import Measurement
    from repro.service import vizier_service

    def lost(self, study_name, trial_id, params):
        trial = self._ds.get_trial(study_name, trial_id)
        trial.complete(Measurement.from_proto(params.get("final_measurement")))
        return trial

    res = _run(lambda server: monkeypatch.setattr(
        vizier_service.VizierService, "_complete_trial_locked", lost))
    assert not res["correct"]
    assert "lost_completions" in _failing(res)


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_half_the_design_left_out(monkeypatch, kind):
    """The factorization conditions on the first half of the trials only."""
    from repro.pythia import posterior, sparse_posterior

    def plant(server):
        if kind == "dense":
            orig = posterior._factor

            def half(raw, xp, yp, mask):
                n = int(np.asarray(mask).sum())
                keep = (np.arange(mask.shape[0]) < n // 2).astype(np.float32)
                return orig(raw, xp, yp * keep, mask * keep)
            monkeypatch.setattr(posterior, "_factor", half)
        else:
            orig = sparse_posterior._sfactor

            def half(raw, z, xp, yp, mask):
                n = int(np.asarray(mask).sum())
                keep = (np.arange(mask.shape[0]) < n // 2).astype(np.float32)
                return orig(raw, z, xp, yp * keep, mask * keep)
            monkeypatch.setattr(sparse_posterior, "_sfactor", half)

    res = _run(plant, kind=kind)
    assert not res["correct"]
    assert "ucb_gap" in _failing(res)


def _beneath_capture(monkeypatch, owner, name, make):
    """Plants ``make(original)`` under the harness's wrapper of ``name``."""
    from bench.lib import capture

    for key, (o, n, orig) in list(capture._INSTALLED.items()):
        if o is owner and n == name:
            monkeypatch.setitem(capture._INSTALLED, key, (o, n, make(orig)))
            return
    raise KeyError(name)


@pytest.mark.parametrize("kind", ["dense", "sparse"])
def test_fit_step_returns_its_state_unchanged(monkeypatch, kind):
    """Each Adam step hands back the hyperparameters and moments it was
    given: the fit serves where it started. ``fit_gap`` reads 1; the
    configuration does not compare it (PERF.md), so the tiny cell is given
    a limit here."""
    from repro.pythia import gp_bandit

    def stuck(orig):
        def step(raw, m, v, *a):
            return (raw, m, v) + tuple(orig(raw, m, v, *a)[3:])
        return step

    cell = tiny_cell(kind)
    cell.config["limits"] = dict(cell.config["limits"], fit_gap=0.1)
    sound = run.run_cell(cell, 2**31 + 77, 3.0, False, DEVICE, grace_s=30.0)
    assert sound["correct"], sound["checks"]
    res = run.run_cell(cell, 2**31 + 77, 3.0, False, DEVICE, grace_s=30.0,
                       fault=lambda server: _beneath_capture(
                           monkeypatch, gp_bandit, "_fit_step", stuck))
    assert not res["correct"]
    assert "fit_gap" in _failing(res)
    assert res["checks"]["fit_gap"]["value"] == pytest.approx(1.0)


def test_scores_altered_where_produced(monkeypatch):
    """The pool's UCB comes out of the device perturbed."""
    from repro.pythia import posterior

    orig = posterior._pool_scores

    def noisy(mean, var, beta):
        s = orig(mean, var, beta)
        return s + 0.5 * np.sin(np.arange(s.shape[0], dtype=np.float32))

    res = _run(lambda server: monkeypatch.setattr(posterior, "_pool_scores",
                                                  noisy))
    assert not res["correct"]
    assert "ucb_gap" in _failing(res)


def test_suggestion_altered_on_its_way_out(monkeypatch):
    """The service hands out a batch whose second trial repeats the first."""
    from repro.service import vizier_service

    orig = vizier_service.VizierService._create_trials_locked

    def repeat(self, study_name, client_id, suggestions):
        suggestions = list(suggestions)
        if len(suggestions) > 1:
            suggestions[1] = suggestions[0]
        return orig(self, study_name, client_id, suggestions)

    res = _run(lambda server: monkeypatch.setattr(
        vizier_service.VizierService, "_create_trials_locked", repeat))
    assert not res["correct"]
    assert "bad_suggestions" in _failing(res)
