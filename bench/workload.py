"""The deployment and its inputs, made from ``--seed`` by the benchmark.

Everything random here derives from ``np.random.SeedSequence([seed, ...])``,
so any whole-number seed (larger than 32 bits too) gives the same fleet,
the same telemetry and the same job keys on every machine.
"""
from __future__ import annotations

import numpy as np

SITES_SALT, TELEMETRY_SALT, JOBS_SALT, SAMPLE_SALT = 0, 1, 2, 3


def rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed) % 2**64,
                                                         *salt]))


def fetch_costs(cfg: dict, seed: int) -> np.ndarray:
    """Per-site full-service fetch cost M [B], drawn from the config's mix."""
    return rng(seed, SITES_SALT).choice(
        np.asarray(cfg["M_choices"], np.float64), size=cfg["sites"])


def job_key(seed: int, job: int) -> np.ndarray:
    """uint32[2] base PRNG key of pricing job ``job``."""
    return np.random.SeedSequence([int(seed) % 2**64, JOBS_SALT,
                                   int(job)]).generate_state(2, np.uint32)


class Telemetry:
    """Live telemetry for every site, slot-major, made in blocks of
    ``BLOCK`` slots: arrivals x [N, B] int32 and spot rents c [N, B]
    float32.  Block i draws from its own stream of the seed and the chain
    and ARMA states carry across blocks, so a longer series always begins
    with the shorter one.

    Arrivals are Gilbert-Elliott modulated Poisson counts, the chain started
    from its stationary law; rents follow the config's ARMA(p, q) around
    their mean, clipped to the spot rails."""

    BLOCK = 256

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        self.arr, self.rent = cfg["arrivals"], cfg["rents"]
        B = cfg["sites"]
        f32 = np.float32
        g = rng(seed, TELEMETRY_SALT)
        p_hl, p_lh = f32(self.arr["p_hl"]), f32(self.arr["p_lh"])
        self.s = g.random(B, dtype=f32) < p_lh / (p_lh + p_hl)
        mean = self.rent["mean"]
        self.sigma = f32(self.rent["rel_sigma"] * mean)
        self.e = [g.standard_normal(B, dtype=f32) * self.sigma
                  for _ in self.rent["ma"]]          # e_{t-1}, ..., e_{t-q}
        self.dev = [np.zeros(B, f32) for _ in self.rent["ar"]]
        self.lo = f32(max(self.rent["min_frac"] * mean, 1e-3))
        self.hi = f32(self.rent["max_frac"] * mean)
        self.blocks = 0
        self.x = np.empty((0, B), np.int32)
        self.c = np.empty((0, B), f32)

    def extend(self, n_slots: int):
        """Grow the series to at least ``n_slots`` slots; returns (x, c)."""
        parts = [(self.x, self.c)]
        have = self.x.shape[0]
        while have < n_slots:
            parts.append(self._block())
            have += self.BLOCK
        if len(parts) > 1:
            self.x = np.concatenate([p[0] for p in parts])
            self.c = np.concatenate([p[1] for p in parts])
        return self.x, self.c

    def _block(self):
        f32, n, B = np.float32, self.BLOCK, self.cfg["sites"]
        g = rng(self.seed, TELEMETRY_SALT, self.blocks)
        self.blocks += 1
        u = g.random((n, B), dtype=f32)
        p_hl, p_lh = f32(self.arr["p_hl"]), f32(self.arr["p_lh"])
        high = np.empty((n, B), bool)
        s = self.s
        for t in range(n):
            s = np.where(s, u[t] >= p_hl, u[t] < p_lh)
            high[t] = s
        self.s = s
        x = np.empty((n, B), np.int32)
        n_high = int(high.sum())
        x[high] = g.poisson(self.arr["rate_h"], n_high)
        x[~high] = g.poisson(self.arr["rate_l"], n * B - n_high)
        ar = [f32(a) for a in self.rent["ar"]]
        ma = [f32(m) for m in self.rent["ma"]]
        z = g.standard_normal((n, B), dtype=f32) * self.sigma
        c = np.empty((n, B), f32)
        mean = f32(self.rent["mean"])
        for t in range(n):
            d = z[t].copy()
            for i, a in enumerate(ar):
                d += a * self.dev[i]
            for j, m in enumerate(ma):
                d += m * self.e[j]
            self.dev = [d] + self.dev[:-1]
            self.e = [z[t]] + self.e[:-1]
            c[t] = np.clip(mean + d, self.lo, self.hi)
        return x, c


def sample_rows(seed: int, n_rows: int, n_blocks: int, k: int) -> np.ndarray:
    """``k`` distinct rows of ``n_rows``, drawn from the seed and spread
    evenly over ``n_blocks`` equal contiguous blocks (one block per chip's
    shard of a fleet mesh), sorted."""
    g = rng(seed, SAMPLE_SALT)
    per = n_rows // n_blocks
    take = [k // n_blocks + (i < k % n_blocks) for i in range(n_blocks)]
    rows = [i * per + g.choice(per, size=m, replace=False)
            for i, m in enumerate(take)]
    return np.sort(np.concatenate(rows))
