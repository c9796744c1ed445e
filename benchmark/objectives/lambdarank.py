"""The plain lambdarank reference: LambdaMART gradients of NDCG by query.

Written from the equations of LambdaMART (Burges, "From RankNet to
LambdaRank to LambdaMART: An Overview", MSR-TR-2010-82) with LightGBM's
normalisation, not from the program's objective.  For each query its rows
are ordered by score, highest first, ties kept in row order; row i sits at
position r_i, counted from 0.  With the gain G(l) = label_gain[l] (2^l - 1
by default), the discount D(r) = 1 / log2(2 + r) and inv_q = 1 / (the
largest DCG@max_position the query's labels allow), or 0 where that is 0,
every pair with l_i > l_j adds

    delta = s_i - s_j
    w     = (G(l_i) - G(l_j)) |D(r_i) - D(r_j)| inv_q
            / (0.01 + |delta|)      with norm, unless the query's scores
                                    are all equal
    rho   = 1 / (1 + exp(sigma delta))
    g_i  += -sigma w rho,  g_j -= -sigma w rho
    h_i  += sigma^2 w rho (1 - rho),  h_j the same

and with norm the query's g and h are then multiplied by log2(1 + S) / S,
where S = sum of 2 sigma w rho over its pairs, if S > 0.  The init score is
0.  `loss` is the |delta NDCG|-weighted pairwise logistic loss,
sum of w log(1 + exp(-sigma delta)) with w taken without the division by
0.01 + |delta|, as a mean over the queries: it is smooth in the scores, so a
gap in leaf values shows in it, where NDCG is flat between two swaps.

Departures from LightGBM, noted as the equations are:
- Ties: LightGBM orders a query with `std::sort`, which leaves the order of
  equal scores unspecified; here it is row order (stable).  At the first
  step every score is 0, so the first tree depends on this rule alone.
- The sigmoid: LightGBM reads it from a table of precomputed values; here it
  is evaluated exactly, as the program does.
- All pairs count: there is no truncation level.

Queries are computed whole, in chunks of queries of one padded length P
(a power of two, at least MIN_PAD), [C, P, P] pair entries at a time,
C x P x P <= PAIR_BUDGET (`chunks`): on the device in float32 under
`jax.default_matmul_precision("highest")` (`gradients`, `loss`), or with
numpy in float64 on the host (`_per_query`, which the tests hold against
a loop over pairs).  Where a length has more queries than a chunk
holds, its chunks have the same shape whatever the seed, so a program
compiles once for it.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import numpy as np

# every length a program of its own to compile (~3 s each on a v5e): below
# 64 rows a query's pairs are few, and padding them to 64 costs ~1% more
# pairs on MS-LTR's sizes where 8, 16 and 32 would each take a compile
MIN_PAD = 64
PAIR_BUDGET = 1 << 25       # pair entries of one chunk: 128 MB in float32
LABEL_GAIN_SIZE = 31        # LightGBM's default table: 2^l - 1, l < 31


def settings(params: Dict) -> Dict:
    """sigma, max_position, norm and the gain table, LightGBM's defaults
    where `params` does not set them."""
    gain = params.get("label_gain") or []
    if isinstance(gain, str):
        gain = [float(v) for v in gain.split(",") if v.strip()]
    if not len(gain):
        gain = 2.0 ** np.arange(LABEL_GAIN_SIZE) - 1.0
    norm = params.get("lambdamart_norm", True)
    if isinstance(norm, str):
        norm = norm.strip().lower() in ("true", "1")
    return {"sigma": float(params.get("sigmoid", 1.0)),
            "max_position": int(params.get("max_position", 20)),
            "norm": bool(norm),
            "gains": np.asarray(gain, np.float64)}


def labels_of(y: np.ndarray, gains: np.ndarray) -> np.ndarray:
    lab = np.asarray(y)
    if (np.any(lab < 0) or np.any(lab >= len(gains))
            or np.any(lab != np.floor(lab))):
        raise ValueError(f"lambdarank labels are whole numbers in "
                         f"[0, {len(gains)}): the gain table's length")
    return lab.astype(np.int32)


def inverse_max_dcg(lab: np.ndarray, group: np.ndarray, max_position: int,
                    gains: np.ndarray) -> np.ndarray:
    """[queries] float64: 1 / the largest DCG@max_position of each query's
    labels, 0 where that DCG is 0.  The best order puts the highest label
    first, so the rows of label l fill the positions after those of every
    higher label: a run of positions whose discounts are a difference of
    cumulative sums."""
    Q, K = len(group), len(gains)
    q = np.repeat(np.arange(Q), group)
    count = np.bincount(q * K + lab, minlength=Q * K).reshape(Q, K)
    after = np.cumsum(count[:, ::-1], axis=1)[:, ::-1]     # rows of label >= l
    first = after - count                                   # rows of label > l
    k = max(int(max_position), 0)
    cum_d = np.concatenate([[0.0],
                            np.cumsum(1.0 / np.log2(2.0 + np.arange(k)))])
    dcg = ((cum_d[np.minimum(after, k)] - cum_d[np.minimum(first, k)])
           * gains[None, :]).sum(axis=1)
    return np.where(dcg > 0, 1.0 / np.where(dcg > 0, dcg, 1.0), 0.0)


def pairwise(xp, s, lab, mask, inv, gains, sigma: float, norm: bool):
    """One chunk of whole queries, [C, P] scores, labels and the mask of real
    rows, [C] inv_q: (g [C, P], h [C, P], loss [C]) by the equations of the
    module's docstring, in the precision of `s`."""
    order = xp.argsort(xp.where(mask, -s, xp.inf), axis=1, stable=True)
    rank = xp.argsort(order, axis=1)                # r_i: position of row i
    disc = 1.0 / xp.log2(2.0 + rank.astype(s.dtype))
    gain = xp.asarray(gains, s.dtype)[lab]
    pair = (mask[:, :, None] & mask[:, None, :]
            & (lab[:, :, None] > lab[:, None, :]))
    delta = s[:, :, None] - s[:, None, :]
    w = ((gain[:, :, None] - gain[:, None, :])
         * xp.abs(disc[:, :, None] - disc[:, None, :]) * inv[:, None, None])
    w = xp.where(pair, w, 0.0)
    z = sigma * delta
    # log(1 + exp(-z)) without overflow
    loss = xp.sum(w * (xp.maximum(-z, 0.0) + xp.log1p(xp.exp(-xp.abs(z)))),
                  axis=(1, 2))
    if norm:
        top = xp.max(xp.where(mask, s, -xp.inf), axis=1)
        bottom = xp.min(xp.where(mask, s, xp.inf), axis=1)
        spread = (top != bottom)[:, None, None]
        w = xp.where(spread, w / (0.01 + xp.abs(delta)), w)
    rho = 1.0 / (1.0 + xp.exp(z))
    lam = -sigma * w * rho
    hes = sigma * sigma * w * rho * (1.0 - rho)
    g = xp.sum(lam, axis=2) - xp.sum(lam, axis=1)
    h = xp.sum(hes, axis=2) + xp.sum(hes, axis=1)
    if norm:
        total = -2.0 * xp.sum(lam, axis=(1, 2))
        factor = xp.where(total > 0, xp.log2(1.0 + total)
                          / xp.where(total > 0, total, 1.0), 1.0)
        g, h = g * factor[:, None], h * factor[:, None]
    return g, h, loss


def chunks(group: np.ndarray):
    """The queries in chunks of one padded length: (P, C, [nq, P] row index
    with -1 past a query's end, [nq] query numbers), nq a multiple of C,
    the padding queries numbered -1.  C is what PAIR_BUDGET allows, or the
    power of two at or above the queries of that length where fewer."""
    starts = np.concatenate([[0], np.cumsum(group)[:-1]])
    pads = np.maximum(MIN_PAD, 1 << np.ceil(np.log2(group)).astype(np.int64))
    for P in np.unique(pads):
        P = int(P)
        qs = np.flatnonzero(pads == P)
        C = min(max(1, PAIR_BUDGET // (P * P)),
                1 << int(np.ceil(np.log2(len(qs)))))
        qs = np.concatenate([qs, np.full(-len(qs) % C, -1, np.int64)])
        at = np.arange(P)[None, :]
        real = (qs >= 0)[:, None] & (at < group[qs][:, None])
        idx = np.where(real, starts[qs][:, None] + at, -1)
        yield P, C, idx, qs


@functools.lru_cache(maxsize=None)
def _on_device(sigma: float, norm: bool, want: str):
    """The jitted pass over one chunk on the device.  It reads the chunk's
    scores from the whole score vector by the chunk's rows (padding points
    at a row past the last), and for `want` "grad" writes its g and h into
    the whole vectors (donated), for "loss" returns each query's loss."""
    import jax
    import jax.numpy as jnp

    def run(s_all, g_all, h_all, rows, lab, mask, inv, gains):
        with jax.default_matmul_precision("highest"):
            g, h, loss = pairwise(jnp, s_all[rows], lab, mask, inv, gains,
                                  sigma, norm)
        if want == "loss":
            return loss
        return (g_all.at[rows].set(jnp.where(mask, g, 0.0)),
                h_all.at[rows].set(jnp.where(mask, h, 0.0)))
    return jax.jit(run, donate_argnums=(1, 2) if want == "grad" else ())


class _Layout:
    """The queries of one table on the device, chunk by chunk: each chunk's
    rows (padding: row n, past the last), labels, mask of real rows and
    inv_q, uploaded once and read by every pass after."""

    def __init__(self, lab: np.ndarray, group: np.ndarray, st: Dict):
        import jax.numpy as jnp
        inv = inverse_max_dcg(lab, group, st["max_position"], st["gains"])
        self.n = len(lab)
        self.gains = jnp.asarray(st["gains"], jnp.float32)
        self.chunks = []
        for P, C, idx, qs in chunks(group):
            mask = idx >= 0
            rows = np.where(mask, idx, self.n).astype(np.int32)
            labs = np.where(mask, lab[np.maximum(idx, 0)], 0).astype(np.int32)
            invs = np.where(qs >= 0, inv[np.maximum(qs, 0)], 0.0)
            for a in range(0, len(qs), C):
                part = slice(a, a + C)
                self.chunks.append(tuple(jnp.asarray(v) for v in (
                    rows[part], labs[part], mask[part],
                    invs[part].astype(np.float32))))


# the layout of the last table asked for, with what it was built from: the
# follower asks about one table many times (a gradient and two losses a
# step), and building the layout costs more than a pass
_LAST: Dict = {}


def _layout(y, extras, st) -> _Layout:
    group = extras["group"]
    key = (st["max_position"], tuple(st["gains"]))
    if not (_LAST.get("y") is y and _LAST.get("group") is group
            and _LAST.get("key") == key):
        _LAST.clear()
        lab = labels_of(y, st["gains"])
        _LAST.update(y=y, group=group, key=key,
                     layout=_Layout(lab, np.asarray(group, np.int64), st))
    return _LAST["layout"]


def _device_pass(y, score, params, extras, want: str):
    import jax.numpy as jnp
    st = settings(params)
    lay = _layout(y, extras, st)
    fn = _on_device(st["sigma"], st["norm"], want)
    s_all = jnp.asarray(np.append(np.asarray(score, np.float32),
                                  np.float32(0)))
    if want == "loss":
        parts = [fn(s_all, None, None, *c, lay.gains) for c in lay.chunks]
        return sum(float(np.asarray(p, np.float64).sum()) for p in parts)
    g_all = jnp.zeros(lay.n + 1, jnp.float32)
    h_all = jnp.zeros(lay.n + 1, jnp.float32)
    for c in lay.chunks:
        g_all, h_all = fn(s_all, g_all, h_all, *c, lay.gains)
    return np.asarray(g_all)[:lay.n], np.asarray(h_all)[:lay.n]


def _per_query(y, score, params, extras):
    """(g, h) per row and the loss of each query in float64 with numpy on
    the host, chunk by chunk: what the device computes in float32, for the
    tests to hold against a loop over pairs and against the device."""
    st = settings(params)
    group = np.asarray(extras["group"], np.int64)
    lab = labels_of(y, st["gains"])
    inv = inverse_max_dcg(lab, group, st["max_position"], st["gains"])
    score = np.asarray(score, np.float64)
    g, h = np.zeros(len(lab)), np.zeros(len(lab))
    loss = np.zeros(len(group))
    for P, C, idx, qs in chunks(group):
        safe = np.maximum(idx, 0)
        mask = idx >= 0
        for a in range(0, len(qs), C):
            part = slice(a, a + C)
            with np.errstate(over="ignore"):
                cg, ch, cl = pairwise(
                    np, score[safe[part]], lab[safe[part]], mask[part],
                    np.where(qs[part] >= 0, inv[qs[part]], 0.0),
                    st["gains"], st["sigma"], st["norm"])
            m = mask[part]
            g[idx[part][m]] = cg[m]
            h[idx[part][m]] = ch[m]
            real = qs[part] >= 0
            loss[qs[part][real]] = cl[real]
    return g, h, loss


def init_score(y, params, extras) -> float:
    return 0.0


def gradients(y, score, params, extras) -> Tuple[np.ndarray, np.ndarray]:
    """float32 g and h of every row at `score`, on the device."""
    return _device_pass(y, score, params, extras, "grad")


def loss(y, score, params, extras) -> float:
    """The weighted pairwise logistic loss at `score`, a mean over the
    queries: each query's in float32 on the device, added in float64."""
    return _device_pass(y, score, params, extras, "loss") / len(
        extras["group"])
