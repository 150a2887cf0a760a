"""The open-loop plan of one run: the fleet as seeded, and its event schedule.

One general generator reads a configuration file and a traffic file:

* studies come in size bands (``studies.bands`` of the configuration); each
  band's sizes are evenly spaced between its bounds, and each band names
  the train bucket its studies must stay in;
* popularity is Zipf(s) or uniform over the studies (``popularity`` of the
  traffic). Ranks go to bands in turn, the band with the largest bucket
  first, and within a band the hotter rank gets the smaller size, so the
  hottest studies have the most room to grow;
* an event is one worker's report-and-ask: it completes the trials the
  worker holds and asks for ``count`` new ones. Events arrive open loop at
  ``rate_per_s`` with exponential gaps.

Every seed gets the same work in another order: the gaps are the
exponential distribution's quantiles, the events per study and each
study's counts are fixed quotas of the weights, and the seed only shuffles
them (and the objectives, the trials seeded and their values). Sizes are capped so that
no study leaves its bucket in the window: completed + pending + the trials
a coalesced op asks for never exceed the trials ever asked of the study,
and that total is bounded by the bucket.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


class PlanError(ValueError):
    """The traffic cannot run on this configuration without leaving a bucket."""


@dataclasses.dataclass(frozen=True)
class Event:
    index: int
    due_s: float      # seconds after the window opens
    study: int
    worker: int
    count: int


@dataclasses.dataclass
class Plan:
    study_sizes: List[int]      # completed trials seeded per study
    study_buckets: List[int]
    objectives: List[str]
    workers: int
    warm_count: int             # trials each warm-up ask takes
    events: List[Event]

    def asks_per_study(self) -> List[int]:
        asks = [0] * len(self.study_sizes)
        for e in self.events:
            asks[e.study] += e.count
        return asks


def quotas(weights, total: int) -> List[int]:
    """Integer counts proportional to ``weights`` summing to ``total``
    (largest remainder), the same for every seed."""
    w = np.asarray(weights, np.float64)
    exact = total * w / w.sum()
    base = np.floor(exact).astype(int)
    rest = total - int(base.sum())
    order = np.argsort(-(exact - base), kind="stable")
    base[order[:rest]] += 1
    return [int(b) for b in base]


def popularity(spec: dict, n: int) -> np.ndarray:
    if spec["kind"] == "zipf":
        w = np.arange(1, n + 1, dtype=np.float64) ** -float(spec["s"])
    elif spec["kind"] == "uniform":
        w = np.ones(n)
    else:
        raise ValueError(f"unknown popularity {spec['kind']!r}")
    return w / w.sum()


def arrival_times(rate: float, seconds: float, rng) -> np.ndarray:
    """Open-loop due times in [0, seconds): n = rate * seconds events whose
    gaps are the exponential quantiles at (i + 1/2)/n, shuffled."""
    n = int(round(rate * seconds))
    if n < 1:
        raise PlanError(f"rate {rate}/s gives no event in {seconds} s")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = rng.permutation(gaps)
    cum = np.cumsum(gaps)
    return seconds * (cum - cum[0]) / cum[-1]


def build(config: dict, traffic: dict, seed: int, seconds: float) -> Plan:
    rng = np.random.default_rng(seed)
    bands = config["studies"]["bands"]
    n_studies = sum(b["count"] for b in bands)
    workers = int(config["workers_per_study"])
    counts_spec = traffic["count"]
    # a coalesced op sums its asks, so batch-member appends run even where
    # every ask is for one trial: the warm-up asks for two at least
    warm_count = max(2, max(counts_spec["values"]))

    # ranks -> studies: study i has popularity rank i + 1
    order = sorted(range(len(bands)), key=lambda j: -bands[j]["bucket"])
    members: List[List[int]] = [[] for _ in bands]
    rank, turn = 0, 0
    while rank < n_studies:
        j = order[turn % len(order)]
        turn += 1
        if len(members[j]) < bands[j]["count"]:
            members[j].append(rank)
            rank += 1
    sizes = [0] * n_studies
    buckets = [0] * n_studies
    for j, band in enumerate(bands):
        band_sizes = np.linspace(band["size_min"], band["size_max"],
                                 band["count"]).round().astype(int)
        for k, study in enumerate(members[j]):
            sizes[study] = int(band_sizes[k])
            buckets[study] = int(band["bucket"])

    due = arrival_times(float(traffic["rate_per_s"]), seconds, rng)
    n = len(due)
    study_of = np.repeat(np.arange(n_studies),
                         quotas(popularity(traffic["popularity"], n_studies), n))
    study_of = rng.permutation(study_of)
    # each study's counts are its own fixed quota, so every seed asks the
    # same number of trials of every study
    counts: List[List[int]] = []
    for s in range(n_studies):
        k = int(np.sum(study_of == s))
        counts.append(list(rng.permutation(np.repeat(
            np.asarray(counts_spec["values"]),
            quotas(counts_spec["weights"], k)))))
    seen = [0] * n_studies
    events = []
    for i in range(n):
        s = int(study_of[i])
        events.append(Event(i, float(due[i]), s, seen[s] % workers,
                            int(counts[s][seen[s]])))
        seen[s] += 1

    plan = Plan(sizes, buckets, [], workers, warm_count, events)
    # two warm-up asks per study at most (see run.py's warm-up)
    for s, asks in enumerate(plan.asks_per_study()):
        room = buckets[s] - asks - 2 * warm_count
        if room < sizes[s]:
            if room <= buckets[s] // 2:
                raise PlanError(
                    f"study {s} would leave train bucket {buckets[s]}: "
                    f"{asks} trials asked in the window")
            sizes[s] = room
    names = list(config["objectives"])
    plan.objectives = [names[i] for i in
                       rng.permutation(np.arange(n_studies) % len(names))]
    return plan
