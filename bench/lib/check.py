"""Decides ``correct``: each number compared, beside its limit.

Policy numbers (the timed path against ``reference.py``, on a sample of the
window's policy calls drawn from the seed, always with the call that asked
for the most trials at the largest design among them):

* ``nll_gap``: the fit's loss, as the program computed it, against the
  reference's, relative (|a - b| / max(1, |b|)), at the fit's first Adam
  step and at the hyperparameters the op served, where the fit evaluated
  its loss there;
* ``fit_gap``: the fit's hyperparameters after its first K = min(3,
  steps) Adam steps against the reference's K steps from the same start,
  moments and schedule, by the worst leaf: the norm of the two changes'
  difference over the larger of the reference's change of that leaf and
  of the median leaf. Leaves whose reference gradient at the start is
  under a thousandth of the median leaf's leave the comparison (log_ell,
  whose gradient is NaN in both and zeroed as the step states). A fit that
  leaves the hyperparameters where it started reads 1, one that steps the
  wrong way about 2. It is compared only where the configuration gives it
  a limit, and printed in every run;
* ``ucb_gap``: the widest gap between a UCB the op scored and the
  reference's, over every candidate not yet picked and every batch member,
  in units of the study's standardized labels;
* ``pick_gap``: the widest gap by which the reference's UCB at a member the
  program picked lies below the reference's best candidate for that
  member.

Service numbers (exact, limit 0): ``failed_ops`` (ops that returned an
error or never returned), ``bad_suggestions`` (an op with the wrong count,
a parameter out of its domain or two equal suggestions), ``dup_trials`` (a
trial id handed to two ops) and ``lost_completions`` (an acknowledged
``CompleteTrial`` that does not read back, SUCCEEDED with the value sent,
from the SQLite shard files after the server stopped).
"""

from __future__ import annotations

import glob
import os
import sqlite3
from typing import Dict, List, Optional

import numpy as np

from bench.lib import halton, reference
from bench.lib.objectives import check_suggestions

POLICY_NUMBERS = ("nll_gap", "fit_gap", "ucb_gap", "pick_gap")
SERVICE_NUMBERS = ("failed_ops", "bad_suggestions", "dup_trials",
                   "lost_completions")


def sample_calls(calls, seed: int, k: int) -> list:
    usable = [c for c in calls
              if c.engine is not None and c.posterior is not None
              and c.fit is not None and len(c.scores) == c.engine["count"]]
    if len(usable) <= k:
        return usable
    longest = max(range(len(usable)), key=lambda i: (
        usable[i].engine["count"], len(usable[i].posterior["x"])))
    rest = [i for i in range(len(usable)) if i != longest]
    rng = np.random.default_rng(seed)
    picked = [longest] + list(rng.choice(rest, size=k - 1, replace=False))
    return [usable[i] for i in sorted(picked)]


def fetch(calls) -> None:
    """Device values of the sampled calls to the host (after the window)."""
    import jax

    for c in calls:
        c.fit = jax.device_get(c.fit)
        c.fit_trace = jax.device_get(c.fit_trace)
        c.fit_close = jax.device_get(c.fit_close)
        c.posterior = dict(c.posterior, raw=jax.device_get(c.posterior["raw"]))


def _same(a: dict, b: dict) -> bool:
    return all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in b)


def _loss(stats) -> float:
    return float(np.ravel(np.asarray(stats))[0])


def served_loss(c) -> Optional[float]:
    """The program's loss at the hyperparameters the op served, where the
    fit evaluated it (a step's input, or the closing evaluation)."""
    served = c.posterior["raw"]
    points = [(st["raw"], st["stats"]) for st in c.fit_trace]
    if c.fit_close is not None:
        points.append(c.fit_close)
    for raw, stats in points:
        if _same(raw, served):
            return _loss(stats)
    return None


def fit_steps_taken(c) -> int:
    """K: the first steps the fit took and kept (three at most; none where
    one of them met a non-finite loss, which the fit discards)."""
    k = min(3, len(c.fit_trace))
    if not all(np.isfinite(_loss(st["stats"])) for st in c.fit_trace[:k]):
        return 0
    return k


def program_after(c, k: int) -> dict:
    """The program's hyperparameters after its first k steps: the input of
    step k + 1, or the output of step k where the fit stopped there."""
    if len(c.fit_trace) > k:
        return c.fit_trace[k]["raw"]
    return c.fit_trace[k - 1]["new_raw"]


def change_gap(start: dict, got: dict, ref: dict, ref_grad: dict) -> float:
    """Worst leaf's |change - reference change| over the larger of the
    reference's change of that leaf and of the median leaf."""
    g = {k: float(np.linalg.norm(ref_grad[k])) for k in ref_grad}
    g_med = float(np.median(list(g.values())))
    leaves = [k for k in g if g[k] > 0.0 and g[k] >= 1e-3 * g_med]
    if not leaves:
        return 0.0
    s64 = {k: np.asarray(start[k], np.float64) for k in leaves}
    d_ref = {k: float(np.linalg.norm(ref[k] - s64[k])) for k in leaves}
    d_med = float(np.median(list(d_ref.values())))
    worst = 0.0
    for k in leaves:
        diff = float(np.linalg.norm(np.asarray(got[k], np.float64) - ref[k]))
        scale = max(d_ref[k], d_med)
        if scale > 0.0:
            worst = max(worst, diff / scale)
        elif diff > 0.0:
            worst = float("inf")
    return worst


def _acq(c, precision: str, config: dict):
    e, p = c.engine, c.posterior
    z = None
    if p["kind"] == "sparse":
        z = halton.inducing_sites(int(config["policy"]["n_inducing"]),
                                  p["x"].shape[1],
                                  int(config["policy"]["seed"]))
    return reference.acquisition(p["kind"], p["raw"], p["x"], p["y"],
                                 e["fantasy_x"], e["y_pend"], e["pool"],
                                 e["picks"], precision, z=z)


def _open(n: int, picked: List[int]) -> np.ndarray:
    mask = np.ones(n, bool)
    mask[picked] = False
    return mask


def policy_numbers(calls, config: dict,
                   control: Optional[str] = None) -> Dict[str, float]:
    """The policy numbers of the program (``control=None``), or of the
    reference computed at the lower precision ``control`` in its place."""
    out = {k: 0.0 for k in POLICY_NUMBERS}
    for c in calls:
        f = c.fit
        points = [(f["raw"], _loss(f["stats"]))]
        served = served_loss(c)
        if served is not None:
            points.append((c.posterior["raw"], served))
        for raw, loss in points:
            ref_nll = reference.fit_nll(raw, f["x"], f["y"], f["mask"],
                                        "highest")
            got_nll = (loss if control is None else
                       reference.fit_nll(raw, f["x"], f["y"], f["mask"],
                                         control))
            out["nll_gap"] = max(out["nll_gap"], abs(got_nll - ref_nll)
                                 / max(1.0, abs(ref_nll)))
        k = fit_steps_taken(c)
        if k:
            schedule = [(st["bc1"], st["bc2"], st["lr_t"])
                        for st in c.fit_trace[:k]]
            path, grad0 = reference.adam_path(f, schedule, "highest")
            got = (program_after(c, k) if control is None else
                   reference.adam_path(f, schedule, control)[0][-1])
            out["fit_gap"] = max(out["fit_gap"],
                                 change_gap(f["raw"], got, path[-1], grad0))
        ref = _acq(c, "highest", config)
        got = ([np.asarray(s, np.float64) for s in c.scores]
               if control is None else _acq(c, control, config))
        picks = c.engine["picks"]
        for b, (r, s) in enumerate(zip(ref, got)):
            live = _open(len(r), picks[:b])
            out["ucb_gap"] = max(out["ucb_gap"],
                                 float(np.max(np.abs(s - r)[live])))
            chosen = (picks[b] if control is None else
                      int(np.flatnonzero(live)[np.argmax(s[live])]))
            out["pick_gap"] = max(out["pick_gap"],
                                  float(np.max(r[live]) - r[chosen]))
    return out


def read_back(db_dir: str) -> Dict[tuple, tuple]:
    """(study name, trial id) -> (state, objective value) from the shard
    files, read with sqlite3 alone."""
    import msgpack

    out = {}
    for path in sorted(glob.glob(os.path.join(db_dir, "*.sqlite3"))):
        conn = sqlite3.connect(f"file:{path}?mode=ro", uri=True)
        try:
            rows = conn.execute(
                "SELECT study_name, trial_id, state, proto FROM trials"
            ).fetchall()
        finally:
            conn.close()
        for study, tid, state, blob in rows:
            proto = msgpack.unpackb(blob, raw=False)
            value = None
            for m in (proto.get("final_measurement") or {}).get("metrics", []):
                if m.get("metric_id") == "obj":
                    value = m.get("value")
            out[(study, int(tid))] = (state, value)
    return out


def service_numbers(records, unfinished: int, study_names: List[str],
                    configs: list, stored: Dict[tuple, tuple]
                    ) -> Dict[str, float]:
    out = {k: 0 for k in SERVICE_NUMBERS}
    out["failed_ops"] = unfinished + sum(1 for r in records if not r.ok)
    owner: Dict[tuple, int] = {}
    for i, r in enumerate(records):
        if not r.ok:
            continue
        if r.kind == "suggest":
            if check_suggestions(r.trials, configs[r.study], r.count):
                out["bad_suggestions"] += 1
            for t in r.trials:
                key = (r.study, t.id)
                if owner.setdefault(key, i) != i:
                    out["dup_trials"] += 1
        else:
            state, value = stored.get((study_names[r.study], r.trial_id),
                                      (None, None))
            if state != "SUCCEEDED" or value != r.value:
                out["lost_completions"] += 1
    return out
