"""Rows of the MS-LTR shape (MSLR-WEB30K, as the reference's GPU benchmark
trains it under lambdarank): queries of documents, dense feature columns and
graded relevance labels 0-4.

The real file cannot be fetched here, so everything below is assumed and
listed under `assumed` in a configuration that uses this generator:

- `features` standard-normal float32 columns (136 in the source);
- query sizes heavy-tailed (log-normal), rounded and clipped to 1-1,251 with
  a mean of about 120, as Fold 1 of MSLR-WEB30K has (18,919 queries in
  2,270,296 rows, the largest 1,251); the sizes sum to the rows asked for,
  the last query holding what is left;
- a label from a fixed rule on five columns, plus a per-query offset (some
  queries hold more relevant documents than others, some none) and noise,
  cut at fixed thresholds into grades 0-4 held by about 51/33/13/2/1 % of
  the rows.

The sizes and the offsets come from `rng` first; the rows then come in
pieces of PIECE_ROWS, each from a generator of its own spawned from `rng`
and filled by a pool of threads straight into the one array returned, as
`higgs_proxy_pieces` does: the same seed gives the same rows on any host.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Tuple

import numpy as np

PIECE_ROWS = 1 << 20
THREADS = 16
MAX_QUERY = 1251
# log-normal sizes: exp(N(SIZE_MU, SIZE_SIGMA)) rounded and clipped to
# 1-MAX_QUERY has a mean of 120.0 (checked over 10^6 draws)
SIZE_MU, SIZE_SIGMA = 4.47, 0.8
QUERY_SD, NOISE_SD = 0.6, 0.8
# the rule's variance (1 + 0.25 + 0.25 + 0.25 E[sin^2 2x] = 1.6248) plus
# the offset's and the noise's: the score below is about standard normal
SCORE_SD = float(np.sqrt(1.6248 + QUERY_SD ** 2 + NOISE_SD ** 2))
# standard-normal quantiles at 51, 84, 97 and 99 %: grades 0-4
GRADE_CUTS = np.array([0.0251, 0.9945, 1.8808, 2.3263], np.float32)


def query_sizes(rng: np.random.Generator, rows: int) -> np.ndarray:
    """int64 sizes in row order summing to `rows`."""
    sizes = np.zeros(0, np.int64)
    while sizes.sum() < rows:
        more = np.rint(np.exp(rng.normal(SIZE_MU, SIZE_SIGMA,
                                         rows // 100 + 16)))
        sizes = np.concatenate([sizes, np.clip(more, 1, MAX_QUERY)
                                .astype(np.int64)])
    ends = np.cumsum(sizes)
    n = int(np.searchsorted(ends, rows))       # the query that reaches rows
    sizes = sizes[:n + 1]
    sizes[n] = rows - (ends[n - 1] if n else 0)
    return sizes


def grade(X: np.ndarray, offset: np.ndarray, eps: np.ndarray) -> np.ndarray:
    """Grades 0-4 from the rule on columns 0-4, the rows' query offsets and
    standard-normal noise `eps`."""
    rule = (X[:, 0] + 0.5 * X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
            + 0.5 * np.sin(2 * X[:, 4]))
    score = (rule + offset + eps * np.float32(NOISE_SD)) / np.float32(
        SCORE_SD)
    return np.searchsorted(GRADE_CUTS, score, side="right").astype(
        np.float64)


def make(rng: np.random.Generator, rows: int,
         features: int) -> Tuple[np.ndarray, np.ndarray, Dict]:
    if features < 5:
        raise ValueError("mslr_proxy's label rule reads five features")
    group = query_sizes(rng, rows)
    offset = rng.normal(0.0, QUERY_SD, len(group)).astype(np.float32)
    row_offset = np.repeat(offset, group)
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float64)
    pieces = -(-rows // PIECE_ROWS)
    kids = rng.spawn(pieces)

    def piece(i: int) -> None:
        a, b = i * PIECE_ROWS, min(rows, (i + 1) * PIECE_ROWS)
        kids[i].standard_normal(out=X[a:b], dtype=np.float32)
        eps = kids[i].standard_normal(b - a, dtype=np.float32)
        y[a:b] = grade(X[a:b], row_offset[a:b], eps)

    with ThreadPoolExecutor(min(THREADS, os.cpu_count() or 1)) as pool:
        list(pool.map(piece, range(pieces)))
    return X, y, {"group": group}
