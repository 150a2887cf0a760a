"""Search spaces, BBOB objectives and suggestion checks, by name.

The search space is built from the configuration file's ``search_space``
entry: ``box`` is a continuous box of ``dim`` floats on [-5, 5].

Objectives are the noiseless BBOB functions f1, f8, f10 and f15 (Hansen et
al., "Real-Parameter Black-Box Optimization Benchmarking 2009: Noiseless
Functions Definitions", COCO, arXiv:1603.08785), defined on x in [-5, 5]^d.
A trial's parameters reach x through the unit cube: each parameter to
[0, 1] between its bounds, then x = -5 + 10 u. The optimum x_opt, the
rotations and f_opt = 0 come from a seed, never from the code under test.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List

import numpy as np

LO, HI = -5.0, 5.0


def build_space(spec: dict):
    """A ``StudyConfig`` with the named search space and one MINIMIZE metric."""
    from repro.core import StudyConfig

    cfg = StudyConfig()
    root = cfg.search_space.select_root()
    kind = spec["kind"]
    if kind == "box":
        for i in range(int(spec["dim"])):
            root.add_float_param(f"x{i:02d}", LO, HI)
    else:
        raise ValueError(f"unknown search space kind {kind!r}")
    cfg.metrics.add("obj", goal="MINIMIZE")
    cfg.algorithm = spec.get("algorithm", "DEFAULT")
    return cfg


def unit_vector(params, space_params) -> np.ndarray:
    """Each (float) parameter to [0, 1] between its bounds."""
    u = []
    for cfg in space_params:
        lo, hi = cfg.bounds
        u.append((params[cfg.name].as_float - lo) / (hi - lo))
    return np.asarray(u, np.float64)


# --- BBOB transformations (the 2009 definitions) ---------------------------


def t_osz(x: np.ndarray) -> np.ndarray:
    xh = np.where(x != 0.0, np.log(np.abs(x) + (x == 0.0)), 0.0)
    c1 = np.where(x > 0, 10.0, 5.5)
    c2 = np.where(x > 0, 7.9, 3.1)
    return np.sign(x) * np.exp(xh + 0.049 * (np.sin(c1 * xh) + np.sin(c2 * xh)))


def t_asy(x: np.ndarray, beta: float) -> np.ndarray:
    d = x.shape[0]
    i = np.arange(d) / max(d - 1, 1)
    pos = np.maximum(x, 0.0)
    return np.where(x > 0, pos ** (1.0 + beta * i * np.sqrt(pos)), x)


def lam(alpha: float, d: int) -> np.ndarray:
    return alpha ** (0.5 * np.arange(d) / max(d - 1, 1))


def _rotation(rng: np.random.Generator, d: int) -> np.ndarray:
    q, r = np.linalg.qr(rng.standard_normal((d, d)))
    return q * np.sign(np.diag(r))


class BBOB:
    """One seeded instance of a BBOB function on [-5, 5]^d."""

    def __init__(self, name: str, d: int, seed: int):
        rng = np.random.default_rng(seed)
        self.name, self.d = name, d
        self.x_opt = rng.uniform(-4.0, 4.0, d)
        self.R = _rotation(rng, d)
        self.Q = _rotation(rng, d)
        self._f: Callable[[np.ndarray], float] = getattr(self, "_" + name)

    def __call__(self, x: np.ndarray) -> float:
        return float(self._f(np.asarray(x, np.float64)))

    def _f1(self, x):
        z = x - self.x_opt
        return np.dot(z, z)

    def _f8(self, x):
        z = max(1.0, math.sqrt(self.d) / 8.0) * (x - self.x_opt) + 1.0
        return np.sum(100.0 * (z[:-1] ** 2 - z[1:]) ** 2 + (z[:-1] - 1.0) ** 2)

    def _f10(self, x):
        z = t_osz(self.R @ (x - self.x_opt))
        return np.dot(10.0 ** (6.0 * np.arange(self.d) / (self.d - 1)), z * z)

    def _f15(self, x):
        z = t_asy(t_osz(self.R @ (x - self.x_opt)), 0.2)
        z = self.R @ (lam(10.0, self.d) * (self.Q @ z))
        return 10.0 * (self.d - np.sum(np.cos(2.0 * np.pi * z))) + np.dot(z, z)


class StudyObjective:
    """A study's objective: the trial's unit vector to x, then its BBOB."""

    def __init__(self, name: str, config, seed: int):
        self._params = list(config.search_space.parameters)
        self.fn = BBOB(name, len(self._params), seed)

    def __call__(self, params) -> Dict[str, float]:
        u = unit_vector(params, self._params)
        return {"obj": self.fn(LO + (HI - LO) * u)}


def check_suggestions(trials, config, count: int) -> List[str]:
    """Faults of one op's suggestions: count, domain, distinctness."""
    faults = []
    if len(trials) != count:
        faults.append(f"{len(trials)} suggestions, wanted {count}")
    keys = set()
    for t in trials:
        for cfg in config.search_space.parameters:
            if (cfg.name not in t.parameters
                    or not cfg.contains(t.parameters[cfg.name])):
                faults.append(f"trial {t.id} out of domain at {cfg.name}")
        keys.add(tuple(sorted((k, v.value) for k, v in t.parameters.items())))
    if len(keys) != len(trials):
        faults.append(f"{len(trials) - len(keys)} duplicate suggestions")
    return faults
