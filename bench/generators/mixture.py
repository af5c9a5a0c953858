"""Seeded Gaussian-mixture stream, drawn block by block on demand.

One generator serves every configuration whose data is a mixture of
Gaussian clusters, optionally with uniform background noise:

  * the paper's Sec. 5 synthetic set (``weights: "uniform"``, one std, no
    noise): ``centers ~ U[-r, r]^(c, d)``, labels uniform over the
    clusters, ``x = center + N(0, std^2 I)``, standardised;
  * the Table 1 stand-ins (``weights: "zipf"``, a std per cluster drawn
    from a range, a share of uniform noise rows per block).

The cluster model (centers, weights, stds) is part of the deployment and
comes from the configuration's ``model_seed``.  The points come from the
run's seed: block ``b`` is drawn from ``default_rng([seed, b])``, so the
stream never runs out or wraps, two processes given one seed draw the
same points, and no seed leans on Python's salted ``hash()``.  A
configuration whose deployment starts from one fixed data set names its
length in blocks, ``initial_blocks``: those blocks are drawn from
``default_rng([model_seed, b])`` instead, the same for every run, and the
stream after them from the run's seed.

Every block is standardised with one fixed map, the mixture's exact
population mean and standard deviation per coordinate, so a point's value
does not depend on which block or batch carries it.
"""

from __future__ import annotations

import numpy as np


class Generator:
    def __init__(self, params: dict, seed: int):
        self.d = int(params["d"])
        self.block_size = int(params["block"])
        self.seed = int(seed) % 2**64
        c = int(params["clusters"])
        self.model_seed = int(params["model_seed"])
        self.initial_blocks = int(params.get("initial_blocks", 0))
        rng = np.random.default_rng(self.model_seed)
        r = float(params["center_range"])
        self.centers = rng.uniform(-r, r, size=(c, self.d))
        if params["weights"] == "uniform":
            w = np.full(c, 1.0 / c)
        elif params["weights"] == "zipf":
            w = 1.0 / np.arange(1, c + 1)
            w /= w.sum()
        else:
            raise ValueError(f"unknown weights {params['weights']!r}")
        self.weights = w
        lo, hi = (float(s) for s in params["std"])
        self.stds = (np.full(c, lo) if lo == hi
                     else rng.uniform(lo, hi, size=c))
        self.noise_frac = float(params["noise_frac"])
        self.noise_range = float(params.get("noise_range", 0.0))
        self.mean, self.std = self._population_moments()

    def _population_moments(self):
        """Per-coordinate mean and std of one block's distribution."""
        q = (round(self.noise_frac * self.block_size) / self.block_size)
        w = self.weights[:, None]
        mean_c = (w * self.centers).sum(0)
        sq_c = (w * (self.centers ** 2 + self.stds[:, None] ** 2)).sum(0)
        # uniform noise on [-a, a]: mean 0, second moment a^2 / 3
        mean = (1 - q) * mean_c
        sq = (1 - q) * sq_c + q * self.noise_range ** 2 / 3.0
        return mean, np.sqrt(sq - mean ** 2)

    def _draw(self, rng, n: int) -> np.ndarray:
        """``n`` standardised points of the mixture, drawn from ``rng``."""
        labels = rng.choice(len(self.weights), size=n, p=self.weights)
        X = (self.centers[labels]
             + rng.normal(0.0, 1.0, size=(n, self.d))
             * self.stds[labels][:, None])
        noise_rows = int(round(self.noise_frac * n))
        if noise_rows:
            rows = rng.choice(n, size=noise_rows, replace=False)
            X[rows] = rng.uniform(-self.noise_range, self.noise_range,
                                  size=(noise_rows, self.d))
        return (X - self.mean) / self.std

    def block(self, b: int) -> np.ndarray:
        """Block ``b`` of the stream: ``(block_size, d)`` float64."""
        seed = self.model_seed if b < self.initial_blocks else self.seed
        return self._draw(np.random.default_rng([seed, int(b)]),
                          self.block_size)
