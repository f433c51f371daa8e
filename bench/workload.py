"""Inputs of a cell: a deployment's bodies and the per-call updates of a
traffic mix.

A deployment file (`bench/configs/<name>.json`) fixes the geometry (its
distribution and its own `geometry_seed`), the charge law, the partition
and the precision of the data; a traffic file (`bench/traffic/<mix>.json`)
fixes what each call changes and how its answers are checked.  This module
is the one generator that reads both, so a new cell is data only.

The positions are the configuration's, the same in every run: they fix the
plan and with it every padded shape the program compiles, so no seed can
move a table across a power-of-two envelope.  `--seed` draws everything
else: the first charges, each call's charges and the checked targets.
Streams are independent `SeedSequence` children of `[seed, purpose, ...]`,
so any seed up to and past 2**31 gives the same sizes and a different
instance.
"""
from __future__ import annotations

import numpy as np

BODIES, CHARGES, TARGETS, GEOMETRY = 0, 1, 2, 3     # stream purposes


def rng(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *key])


def positions(dist: dict, n: int, g: np.random.Generator) -> np.ndarray:
    """(n, 3) float64 positions of an `ellipsoid_surface`: uniform
    directions scaled by `axes` (a sphere for equal axes; the paper's
    boundary distributions)."""
    if dist["kind"] != "ellipsoid_surface":
        raise ValueError(f"unknown distribution kind {dist['kind']!r}")
    v = g.normal(size=(n, 3))
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    return v * np.asarray(dist["axes"], np.float64)


def charges(law: dict, n: int, g: np.random.Generator) -> np.ndarray:
    """(n,) float64 charges, `uniform` in [low, high)."""
    if law["law"] != "uniform":
        raise ValueError(f"unknown charge law {law['law']!r}")
    return g.uniform(law["low"], law["high"], n)


def stated(a: np.ndarray, deploy: dict) -> np.ndarray:
    """`a` as values of the deployment's stated `precision`, held in
    float64: the data the program computes on and the reference sums are
    then the same numbers."""
    return a.astype(np.dtype(deploy["precision"])).astype(np.float64)


def bodies(deploy: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """The deployment's positions (from its `geometry_seed`) and the first
    charges for `seed`."""
    n = int(deploy["bodies"])
    x = positions(deploy["distribution"], n,
                  rng(deploy["geometry_seed"], GEOMETRY))
    q = charges(deploy["charges"], n, rng(seed, BODIES))
    return stated(x, deploy), stated(q, deploy)


class Traffic:
    """A closed loop of calls, each `FMMSession.step` then
    `FMMSession.evaluate()`.

    `update` in the mix: `charges` (fresh charges from the deployment's law
    every call, drawn from the call's own stream).  Call 0 is the warm-up;
    the window's calls are 1, 2, ...  `check_targets` bodies, drawn from
    the seed, are compared with the reference in every call."""

    UPDATES = ("charges",)

    def __init__(self, mix: dict, deploy: dict, seed: int, x: np.ndarray):
        if mix["update"] not in self.UPDATES:
            raise ValueError(f"unknown update {mix['update']!r}; expected "
                             f"one of {self.UPDATES}")
        self.mix, self.deploy, self.seed, self.x = mix, deploy, seed, x
        n = len(x)
        self.targets = np.sort(rng(seed, TARGETS).choice(
            n, size=min(int(mix["check_targets"]), n), replace=False))

    def charges(self, k: int) -> np.ndarray:
        """Call `k`'s charges."""
        return stated(charges(self.deploy["charges"], len(self.x),
                              rng(self.seed, CHARGES, k)), self.deploy)

    def apply(self, session, k: int) -> None:
        """Call `k`'s update of the session."""
        session.step(self.x, self.charges(k))
