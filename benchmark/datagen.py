"""Rows and labels from a seed, by the generator the configuration names.

`higgs_proxy` is `chip_smoke.make_data` (itself bench.py's HIGGS proxy):
standard-normal float32 features and a label from a fixed non-linear rule
with noise, drawn in bulk.  The same seed gives the same rows.  Any other
name is a file, `generators/<name>.py`, whose `make(rng, rows, **data)`
returns float32 rows and the labels: a later PR's data shape is a new file.
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Tuple

import numpy as np

from manifest import load_module

HERE = os.path.dirname(os.path.abspath(__file__))


def higgs_proxy(rng: np.random.Generator, rows: int, features: int,
                noise: float = 0.5) -> Tuple[np.ndarray, np.ndarray]:
    if features < 5:
        raise ValueError("higgs_proxy's label rule reads five features")
    X = rng.standard_normal((rows, features), dtype=np.float32)
    logit = (2.0 * X[:, 0] + X[:, 1] - X[:, 2] * X[:, 3]
             + 0.5 * np.sin(3 * X[:, 4]))
    eps = rng.standard_normal(rows, dtype=np.float32)
    return X, (logit + eps * np.float32(noise) > 0).astype(np.float64)


def generator(name: str, here: str = HERE) -> Callable:
    """`higgs_proxy`, or `make` of `generators/<name>.py`, found as
    `manifest.reader` finds a metric."""
    if name == "higgs_proxy":
        return higgs_proxy
    path = os.path.join(here, "generators", f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"unknown generator {name!r}: no file {path}")
    return load_module(path, f"generator_{name}").make


def make(data: Dict, rows: int, rng: np.random.Generator, here: str = HERE):
    """`data` is the configuration's `data` group: the generator's name and
    its parameters.  `rows` is passed apart: a rehearsal cuts it."""
    kwargs = {k: v for k, v in data.items()
              if k not in ("generator", "rows")}
    return generator(data["generator"], here)(rng, int(rows), **kwargs)
