"""Scrambled-Halton points: the inducing sites the SGPR reference uses.

A copy of the program's generator (``repro/pythia/halton.py``), kept here
so that the reference imports nothing of the program: the radical inverses
of 0..n-1 in the first ``dim`` primes, each digit position permuted by a
seeded ``RandomState``. The sites are a function of the configuration's
policy seed alone, so the reference places them where the configuration
says, not where the program put them.
"""

from __future__ import annotations

import numpy as np


def _primes(count: int) -> "list[int]":
    primes = [2]
    c = 3
    while len(primes) < count:
        if all(c % p for p in primes if p * p <= c):
            primes.append(c)
        c += 2
    return primes


def scrambled_halton(n: int, dim: int,
                     rng: np.random.RandomState) -> np.ndarray:
    out = np.empty((n, dim), np.float64)
    idx = np.arange(n, dtype=np.int64)
    for d, b in enumerate(_primes(dim)):
        n_digits = 1
        while b ** n_digits < max(n, 2):
            n_digits += 1
        n_digits += 2
        rem = idx.copy()
        value = np.zeros(n, np.float64)
        scale = 1.0 / b
        for _pos in range(n_digits):
            digit = rem % b
            rem //= b
            value += rng.permutation(b)[digit] * scale
            scale /= b
        out[:, d] = value
    return out


def inducing_sites(m: int, dim: int, seed: int) -> np.ndarray:
    return scrambled_halton(m, dim, np.random.RandomState(seed)).astype(
        np.float32)
