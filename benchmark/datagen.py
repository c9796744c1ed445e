"""Rows and labels from a seed, by the generator the configuration names.

`higgs_proxy` is `chip_smoke.make_data` (itself bench.py's HIGGS proxy):
standard-normal float32 features and a label from a fixed non-linear rule
with noise, drawn in bulk.  The same seed gives the same rows.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def higgs_proxy(rng: np.random.Generator, rows: int, features: int,
                noise: float = 0.5) -> Tuple[np.ndarray, np.ndarray]:
    if features < 5:
        raise ValueError("higgs_proxy's label rule reads five features")
    X = rng.standard_normal((rows, features), dtype=np.float32)
    logit = (2.0 * X[:, 0] + X[:, 1] - X[:, 2] * X[:, 3]
             + 0.5 * np.sin(3 * X[:, 4]))
    eps = rng.standard_normal(rows, dtype=np.float32)
    return X, (logit + eps * np.float32(noise) > 0).astype(np.float64)


GENERATORS = {"higgs_proxy": higgs_proxy}


def make(data: Dict, rows: int, rng: np.random.Generator):
    """`data` is the configuration's `data` group: the generator's name and
    its parameters.  `rows` is passed apart: a rehearsal cuts it."""
    kind = data["generator"]
    if kind not in GENERATORS:
        raise SystemExit(f"unknown generator {kind!r}")
    kwargs = {k: v for k, v in data.items()
              if k not in ("generator", "rows")}
    return GENERATORS[kind](rng, int(rows), **kwargs)
