"""Rows and labels from a seed, by the generator the configuration names.

`higgs_proxy` is `chip_smoke.make_data` (itself bench.py's HIGGS proxy):
standard-normal float32 features and a label from a fixed non-linear rule
with noise, drawn in bulk.  The same seed gives the same rows.  Any other
name is a file, `generators/<name>.py`, whose `make(rng, rows, **data)`
returns float32 rows and the labels: a later PR's data shape is a new file.
A generator may return a third item, `extras`, a dict of what the rows come
with: its `"group"` holds the query sizes in row order, which sum to the
rows asked for (a rehearsal's rows end with a whole query).
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
    eps = rng.standard_normal(rows, dtype=np.float32)
    return X, higgs_label(X, eps, noise).astype(np.float64)


def higgs_label(X: np.ndarray, eps: np.ndarray, noise: float) -> np.ndarray:
    """`higgs_proxy`'s label rule: a fixed non-linear logit of the first five
    features plus `noise` x the standard-normal `eps`, above 0."""
    logit = (2.0 * X[:, 0] + X[:, 1] - X[:, 2] * X[:, 3]
             + 0.5 * np.sin(3 * X[:, 4]))
    return logit + eps * np.float32(noise) > 0


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
    """(X, y, extras).  `data` is the configuration's `data` group: the
    generator's name and its parameters.  `rows` is passed apart: a
    rehearsal cuts it.  `extras` is {} where the generator returns none."""
    kwargs = {k: v for k, v in data.items()
              if k not in ("generator", "rows")}
    X, y, *rest = generator(data["generator"], here)(rng, int(rows), **kwargs)
    extras = dict(rest[0]) if rest else {}
    if "group" in extras:
        group = np.asarray(extras["group"])
        if (group.ndim != 1 or group.dtype != np.int64 or np.any(group < 1)
                or int(group.sum()) != len(y)):
            raise ValueError(f"{data['generator']}: extras['group'] must be "
                             f"int64 query sizes >= 1 summing to the "
                             f"{len(y)} rows")
    return X, y, extras


def query_of_rows(group: np.ndarray) -> np.ndarray:
    """The query each row belongs to, numbered in row order."""
    return np.repeat(np.arange(len(group)), group)


def keep_rows(extras: Dict, keep: np.ndarray) -> Dict:
    """`extras` for the rows `keep` (a boolean mask or indices in row order)
    of the rows it came with: each query keeps the rows it had among them,
    and a query left with none is gone."""
    if "group" not in extras:
        return dict(extras)
    kept = query_of_rows(extras["group"])[keep]
    sizes = np.bincount(kept, minlength=len(extras["group"]))
    return dict(extras, group=sizes[sizes > 0].astype(np.int64))
