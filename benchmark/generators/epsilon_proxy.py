"""Rows of the Epsilon shape (Pascal Large Scale Learning Challenge, as the
reference's GPU benchmark trains it: dense columns by the thousand, every row
scaled to unit length, binary label).

Standard-normal float32 columns, each row divided by its length, and a label
that is 1 where `x . w` plus seeded noise is positive.  `w` is a dense vector
over all the columns made from a constant: the seed changes the rows, never
the rule, and no handful of columns carries the label, so a tree's splits
spread over many columns as they do on the real table.  Rows are drawn in
slabs straight into the one array returned: the host never holds a second
copy of a table that is 8.8 GB at 1,100,000 x 2000.

Before a row is drawn the generator asks the program whether its kernels take
a table this wide (`lightgbm_tpu.ops.pallas_histogram.supported`).  A program
that says no would spend minutes finding bins and then train on the XLA
one-hot grower, which `run.check_path` refuses after the fact; asked first,
the run ends in seconds with the shape named.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

RULE_SEED = 20080908        # the challenge's year and month: a constant
SLAB_ROWS = 32768


def ask_program(features: int, bins: int) -> None:
    from lightgbm_tpu.ops.pallas_histogram import supported
    if not supported(int(features), int(bins), np.dtype(np.uint8)):
        raise SystemExit(
            f"epsilon_proxy: the program's histogram kernels do not take "
            f"{features} columns x {bins} bins "
            f"(lightgbm_tpu.ops.pallas_histogram.supported); no row drawn")


def make(rng: np.random.Generator, rows: int, features: int, bins: int = 64,
         noise: float = 0.25) -> Tuple[np.ndarray, np.ndarray]:
    ask_program(features, bins)
    w = np.random.default_rng(RULE_SEED).standard_normal(
        features).astype(np.float32)
    X = np.empty((rows, features), dtype=np.float32)
    y = np.empty(rows, dtype=np.float64)
    for a in range(0, rows, SLAB_ROWS):
        slab = X[a:a + SLAB_ROWS]
        rng.standard_normal(slab.shape, dtype=np.float32, out=slab)
        slab /= np.sqrt(np.einsum("ij,ij->i", slab, slab))[:, None]
        eps = rng.standard_normal(len(slab), dtype=np.float32)
        # x . w is about standard normal for unit rows: noise is its share
        y[a:a + SLAB_ROWS] = slab @ w + eps * np.float32(noise) > 0
    return X, y
