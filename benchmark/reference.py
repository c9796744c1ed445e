"""The plain reference: gradient boosting in jax.numpy, under the objective
the configuration names (`params["objective"]`).

It imports nothing of the program and takes nothing the program made except
the answer under test: the model as the program serialised it
(`Booster.model_to_string()`), parsed here by `parse_model`.  From the raw
float32 rows, the labels and the configuration's parameters it follows the
first steps of training on its own score vector:

    gradients of the objective at its own scores
    -> every row routed through the program's tree by the serialised
       real-valued thresholds (x <= threshold goes left)
    -> per-leaf sums of gradient, hessian and rows, and for every leaf a
       histogram over every candidate threshold (every threshold the model
       uses on that feature: each is a bin boundary of the program's)
    -> leaf values -lr * G / H, split gains G_l^2/H_l + G_r^2/H_r - G_p^2/H_p,
       the best gain any candidate would have given each node under the
       configuration's min_data_in_leaf and min_sum_hessian_in_leaf
    -> its own scores moved by its own leaf values, and the loss there.

Routing and the sums are matrix products over one-hot rows, in blocks of
rows, so that 26M rows x 255 leaves takes seconds on the device and not the
minutes a gather per tree level takes.  float32 sums go through the MXU as
three exact bfloat16 pieces (cut by bit mask), accumulated in float32 and
compensated across blocks.  `precision="bfloat16"` is the control: scores,
gradients and the summed products kept in bfloat16, the step a later PR
would be tempted by.

Nothing is set for a width.  The block is sized from the table's shape
(`block_rows`), routing reads only the columns a tree splits on, the rows
sit on the device once, raw float32, in the layout the passes read
(`_upload_blocks`), and the host's part is one BLAS product and a pass over
a few splits at a time: the cost follows the rows and the candidates, not
the columns a tree does not use.

Binary logloss is this file's own: its gradients are taken inside the
jitted pass over the blocks, from the labels and the scores there.  Any
other objective is a file, `objectives/<name>.py`, found by the name as a
generator is, which gives `init_score(y, params, extras)`,
`gradients(y, score, params, extras) -> (g, h)` (float32 a row) and
`loss(y, score, params, extras)`; the follower takes the scores to the
host, uploads g and h in its blocks' layout, and runs the same sums,
candidate gains and update on them.  `extras` is what the rows came with
(`datagen.make`): a query's rows, for one.  An objective with neither is
refused before anything is trained.

Rows that outgrow one chip are sharded (`Follower(devices=...)`): each
device holds a contiguous run of whole blocks and runs the same passes on
them, and the host adds what the shards return: the per-block sums in
float64 in the blocks' order, as it does on one device, and the shards'
candidate histograms in float64.  Nothing crosses between the devices.
With one device every pass and every number is the unsharded one.
"""

from __future__ import annotations

import functools
import math
import os
import re
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
K_EPS = 1e-15       # LightGBM's kEpsilon, added to every hessian sum

MAX_BLOCK = 16384           # rows a block, where the width allows it
MIN_BLOCK = 256             # `dot_t` sums leaves over 256 rows at a time
PARTIAL_ROWS = 4096         # rows one float32 partial sum of a histogram takes
TEMP_BYTES = 2 << 30        # what one block's temporaries may take
SLAB_BYTES = 256 << 20      # rows uploaded in one transfer
SPLITS_AT_ONCE = 16         # splits whose candidate gains are held at once


def block_rows(n_feat: int, n_cand: int, n_leaf: int) -> int:
    """Rows a block, from the shape alone: the largest power of two, at most
    MAX_BLOCK and at least MIN_BLOCK, whose temporaries stay under TEMP_BYTES
    (2 GiB, an eighth of a v5e chip; a constant and not the device's own
    figure, because the block sets the order of the float32 sums, and the
    readings should not move with the device).

    A row of a block is counted at 8 bytes for every (feature, candidate)
    pair and 64 bytes a leaf.  The pairs: the compiler keeps the row's value
    spread over its candidates in float32 and fuses the compare and the
    bfloat16 0/1 into the product, 4 bytes a pair (compiled for a described
    v5e at 2048 and 4096 rows, PR 29); row-major blocks took 7.1 on the
    chip (PR 28), so 8 leaves a margin of two.  The leaves: the one-hot, the
    [R, 3L] float32 weights and their [R, 9L] bfloat16 pieces.

        28 x 64 x 256:    30,720 B a row; 16384 rows take 0.50 GB  -> 16384
        2000 x 64 x 256:  1,040,384 B a row; 2048 rows take 2.13 GB -> 2048

    What does not shrink with the block: the histogram, [3L, F * B] float32
    (393 MB at 2000 x 64 x 256), held as a Kahan pair and copied out, and
    the product's [9L, F * B] output before its three pieces are added
    (1.18 GB there) times the partial sums a block is cut into, which is
    why a partial sum takes min(block, PARTIAL_ROWS) rows (one at 2048, four
    at 16384 as before PR 29) and not a fixed quarter of the block.  With
    the rows themselves (8.8 GB at 1,100,000 x 2000) the compiler counts
    12.24 GB there, of the chip's 16.9; `PERF.md` section 2 has the peak
    measured."""
    per_row = 8 * n_feat * n_cand + 64 * n_leaf
    rows = MAX_BLOCK
    while rows > MIN_BLOCK and rows * per_row > TEMP_BYTES:
        rows //= 2
    return rows


def objective(params: Dict, here: str = HERE):
    """None for binary logloss, this file's own branch; for any other name
    the module `objectives/<name>.py`.  A name with neither is refused."""
    name = params.get("objective")
    if name == "binary":
        return None
    path = os.path.join(here, "objectives", f"{name}.py")
    if not (isinstance(name, str) and re.fullmatch(r"[A-Za-z0-9_]+", name)
            and os.path.exists(path)):
        raise SystemExit(f"the reference knows no objective {name!r}: it "
                         f"follows binary logloss, or an objective with a "
                         f"file {path}")
    from manifest import load_module
    return load_module(path, f"objective_{name}")


# ---------------------------------------------------------------- the model

def _ints(n: int = 0):
    return field(default_factory=lambda: np.zeros(n, np.int64))


def _floats(n: int = 0):
    return field(default_factory=lambda: np.zeros(n))


@dataclass
class RefTree:
    """One tree as serialised: LightGBM's flat arrays.  Internal node i is
    the i-th split made; a child < 0 is leaf ~child."""
    num_leaves: int
    shrinkage: float
    split_feature: np.ndarray = _ints()
    threshold: np.ndarray = _floats()
    decision_type: np.ndarray = _ints()
    left_child: np.ndarray = _ints()
    right_child: np.ndarray = _ints()
    split_gain: np.ndarray = _floats()
    internal_weight: np.ndarray = _floats()
    internal_count: np.ndarray = _ints()
    leaf_value: np.ndarray = _floats(1)
    leaf_weight: np.ndarray = _floats(1)
    leaf_count: np.ndarray = _ints(1)


_FLOAT_KEYS = ("threshold", "split_gain", "internal_weight", "leaf_value",
               "leaf_weight")
_INT_KEYS = ("split_feature", "decision_type", "left_child", "right_child",
             "internal_count", "leaf_count")


def parse_model(text: str) -> List[RefTree]:
    """The trees of a LightGBM text model, in order."""
    body = text.split("end of trees")[0]
    trees = []
    for block in body.split("\nTree=")[1:]:
        kv = {}
        for line in block.splitlines()[1:]:
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k.strip()] = v.strip()
        t = RefTree(num_leaves=int(kv["num_leaves"]),
                    shrinkage=float(kv.get("shrinkage", 1.0)))
        if int(kv.get("num_cat", 0)) != 0:
            raise ValueError("the reference routes numerical splits only")
        for k in _FLOAT_KEYS:
            if k in kv:
                setattr(t, k, np.array(kv[k].split(), dtype=np.float64))
        for k in _INT_KEYS:
            if k in kv:
                setattr(t, k, np.array(kv[k].split(), dtype=np.int64))
        trees.append(t)
    return trees


def f32_floor(thr: np.ndarray) -> np.ndarray:
    """The largest float32 not above each float64 threshold, so that for a
    float32 x, `x <= f32_floor(t)` is exactly `float64(x) <= t`."""
    t32 = thr.astype(np.float32)
    over = t32.astype(np.float64) > thr
    return np.where(over, np.nextafter(t32, np.float32(-np.inf)), t32)


def tree_tables(t: RefTree, n_split: int, n_leaf: int):
    """Dense tables for routing by matrix product, padded to fixed sizes.

    path[s, l] is +1 where split s is an ancestor of leaf l and l lies to
    its right, -1 to its left, 0 elsewhere; depth[l] counts l's ancestors.
    A row sits in leaf l exactly when its +-1 decisions times path[:, l]
    add up to depth[l].  anc[s, l] = 1 where leaf l lies under split s."""
    S = t.num_leaves - 1
    feat = np.zeros(n_split, np.int32)
    thr = np.full(n_split, np.inf, np.float32)
    path = np.zeros((n_split, n_leaf), np.float32)
    depth = np.full(n_leaf, 1e6, np.float32)      # padding: never matched
    if S == 0:
        depth[0] = 0.0
        return feat, thr, path, depth
    if np.any(t.decision_type & 1):
        raise ValueError("categorical split in a numerical-only reference")
    feat[:S] = t.split_feature
    thr[:S] = f32_floor(t.threshold)

    stack = [(0, [])]
    while stack:
        node, trail = stack.pop()
        if node < 0:
            depth[~node] = len(trail)
            for s, sign in trail:
                path[s, ~node] = sign
            continue
        stack.append((int(t.left_child[node]), trail + [(node, -1.0)]))
        stack.append((int(t.right_child[node]), trail + [(node, 1.0)]))
    return feat, thr, path, depth


def candidate_thresholds(trees: List[RefTree], n_feat: int,
                         width: int) -> np.ndarray:
    """[n_feat, width] float32: for each feature the sorted thresholds the
    model uses on it anywhere (each one a bin boundary), padded with +inf
    (a candidate that sends every row left, which no constraint admits)."""
    per = [set() for _ in range(n_feat)]
    for t in trees:
        if t.num_leaves > 1:
            for f, v in zip(t.split_feature, f32_floor(t.threshold)):
                per[int(f)].add(float(v))
    out = np.full((n_feat, width), np.inf, np.float32)
    for f, vals in enumerate(per):
        vals = sorted(vals)
        if len(vals) > width:
            raise ValueError(f"feature {f} uses {len(vals)} thresholds, more "
                             f"than the configuration's {width} bins allow")
        out[f, :len(vals)] = vals
    return out


# ------------------------------------------------------- the device passes

def _split3(a):
    """float32 -> three bfloat16 pieces that add up to it.  The pieces are
    cut with a bit mask and not with a round trip through bfloat16: XLA
    (allow_excess_precision) removes such a round trip, and all three pieces
    would silently be the first."""
    import jax
    import jax.numpy as jnp

    def top16(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)

    hi = top16(a)
    r = a - hi              # exact: at most 16 significant bits are left
    mid = top16(r)
    lo = r - mid            # exact: at most 8 bits, a bfloat16 holds them
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            lo.astype(jnp.bfloat16))


def route(X, feat, thr, path, depth):
    """[R, L] one-hot of the leaf each row of a block falls in, by the tables
    of `tree_tables`.  It reads only the columns the tree splits on, one
    gather of [R, S], so a block costs the same at 28 columns and at 2000."""
    import jax.numpy as jnp
    xs = jnp.take(X, feat, axis=1)
    # +-1 and 0 are exact in whatever precision the product is made
    d = jnp.where(xs > thr[None, :], 1.0, -1.0).astype(jnp.float32)
    match = jnp.dot(d, path, preferred_element_type=jnp.float32)
    return match == depth[None, :]


def _build_passes(sigmoid: float, low: bool, with_hist: bool):
    """The jitted passes over all blocks: sums under one tree with binary
    logloss's gradients taken inside, the same sums under given gradients,
    the score update with binary logloss, and the sum of a tree's values."""
    import jax
    import jax.numpy as jnp
    f32, bf16 = jnp.float32, jnp.bfloat16

    def gradients(ysign, score):
        if low:
            y, s = ysign.astype(bf16), score.astype(bf16)
            sg = jnp.asarray(sigmoid, bf16)
            resp = -y * sg / (jnp.asarray(1, bf16) + jnp.exp(y * sg * s))
            a = jnp.abs(resp)
            return resp, a * (sg - a)
        resp = -ysign * sigmoid / (1.0 + jnp.exp(ysign * sigmoid * score))
        a = jnp.abs(resp)
        return resp, a * (sigmoid - a)

    def pieces(a):
        """[R, K] float32 -> [R, 3K] bfloat16 (K in the control): the three
        pieces side by side, so that one product handles them and no
        rewrite can add them up before it."""
        if low:
            return a.astype(bf16)
        return jnp.concatenate(_split3(a), axis=1)

    def dot_t(a, b, sub):
        """a^T b, [K, J], accumulated in float32 over `sub` rows at a time
        and then over those partial sums: a chain of float32 additions stays
        short.  b holds 0/1 and is exact in bfloat16."""
        R, K = a.shape
        p = pieces(a).reshape(R // sub, sub, -1)
        out = jnp.einsum("nrk,nrj->nkj", p, b.reshape(R // sub, sub, -1),
                         preferred_element_type=f32).sum(axis=0)
        return out if low else out[:K] + out[K:2 * K] + out[2 * K:]

    def kahan(total, comp, x):
        y = x - comp
        t = total + y
        return t, (t - total) - y

    def binary(ysign, score):
        """g, h and the rows that count (0: padding) of a block."""
        g, h = gradients(ysign, score)
        return g, h, ysign

    def given(rows, g, h):
        return g, h, rows

    def sums_pass(weights, Xb, cols, feat, thr, path, depth, cand):
        """`cols`: the per-block vectors `weights` takes."""
        L = path.shape[1]

        def body(carry, blk):
            Xt, *vecs = blk
            X = Xt.T
            g, h, rows = weights(*vecs)
            onehot = route(X, feat, thr, path, depth)
            valid = (rows != 0)
            w = jnp.stack([g.astype(f32), h.astype(f32),
                           jnp.ones_like(rows)], axis=1) * valid[:, None]
            if low:
                w = w.astype(bf16).astype(f32)
            oh = onehot.astype(f32)
            a = (oh[:, :, None] * w[:, None, :]).reshape(X.shape[0], L * 3)
            leaf_sums = dot_t(a, jnp.ones((X.shape[0], 1), bf16), 256)[:, 0]
            leaf_id = jnp.argmax(onehot, axis=1).astype(jnp.int32)
            routed = jnp.sum(onehot, axis=1)
            bad = jnp.sum(jnp.where(valid, routed != 1, False))
            if with_hist:
                ind = (X[:, :, None] <= cand[None, :, :]).reshape(
                    X.shape[0], -1).astype(bf16)
                hist = dot_t(a, ind, min(X.shape[0], PARTIAL_ROWS))
                tot, comp = kahan(carry[0], carry[1], hist)
                carry = (tot, comp)
            return carry, (leaf_sums, leaf_id, bad)

        if with_hist:
            z = jnp.zeros((L * 3, cand.shape[0] * cand.shape[1]), f32)
            carry0 = (z, z)
        else:
            carry0 = ()
        carry, (leaf_sums, leaf_id, bad) = jax.lax.scan(
            body, carry0, (Xb, *cols))
        hist = carry[0] if with_hist else None
        return leaf_sums, leaf_id, bad, hist

    @jax.jit
    def update_pass(yb, sb, leaf_id, values):
        L = values.shape[0]
        pick = (leaf_id[..., None] == jnp.arange(L)[None, None, :])
        add = jnp.sum(jnp.where(pick, values[None, None, :], 0.0), axis=-1)
        if low:
            s = (sb.astype(bf16) + add.astype(bf16)).astype(f32)
        else:
            s = sb + add
        # log(1 + exp(-y s)), rows of padding (y = 0) left out
        z = -yb * s
        loss = jnp.where(yb != 0,
                         jnp.maximum(z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z))),
                         0.0)
        return s, jnp.sum(loss, axis=1)

    @jax.jit
    def apply_pass(Xb, sb, feat, thr, path, depth, values):
        """score + values[leaf of the row], block by block."""
        def body(_, blk):
            Xt, score = blk
            onehot = route(Xt.T, feat, thr, path, depth)
            add = jnp.sum(jnp.where(onehot, values[None, :], 0.0), axis=1)
            return (), score + add
        return jax.lax.scan(body, (), (Xb, sb))[1]

    return (jax.jit(functools.partial(sums_pass, binary)),
            jax.jit(functools.partial(sums_pass, given)),
            update_pass, apply_pass)


# ------------------------------------------------------------ the follower

@dataclass
class StepReading:
    """What the reference found under one of the program's trees."""
    leaf_g: np.ndarray          # [L] float64 sums over the leaf's rows
    leaf_h: np.ndarray
    leaf_c: np.ndarray          # [L] int64
    leaf_value: np.ndarray      # [L] -lr*G/H (no init score)
    node_g: np.ndarray          # [S] sums over each split's rows
    node_h: np.ndarray
    node_c: np.ndarray
    gain: np.ndarray            # [S] gain of the split the program made
    best_gain: np.ndarray | None    # [S] best over all candidates
    other_gain: np.ndarray | None   # [S] best over the other features'
    loss: float                 # the objective's loss after this step (a
                                # mean over rows, or as its file says)
    unrouted: int               # rows not in exactly one leaf
    tested_loss: float | None = None    # the same under the tested values


def _upload_blocks(X: np.ndarray, R: int, device=None, nb: int = 0):
    """[n, F] float32 rows on the host -> [nb, F, R] on `device` (the default
    one where None): blocks of R rows, each with its rows along the last
    axis, zero rows after the last (and whole zero blocks up to `nb`).

    The passes read a block as `block.T`, [R, F].  Rows-last is the layout
    the compiler wants under the compare and the products: compiled for a
    described v5e at 1,100,000 x 2000, row-major [nb, R, F] blocks get a
    copy of the whole table hoisted out of the loop (19 GB, refused), these
    none (12.24 GB).  The rows go up flat, a slab of whole blocks at a time
    (no tiling for the host to lay out), are turned on the device and
    written into a buffer that each call donates and gets back: the host
    pads the last block only, and the device holds the table and one slab."""
    import jax
    import jax.numpy as jnp
    n, F = X.shape
    nb = max(nb, -(-n // R))
    full = n // R

    def put(buf, flat, at):
        part = jnp.swapaxes(flat.reshape(-1, R, F), 1, 2)
        return jax.lax.dynamic_update_slice(buf, part, (at, 0, 0))

    put = jax.jit(put, donate_argnums=0)
    buf = jnp.zeros((nb, F, R), jnp.float32, device=device)
    slab = max(1, SLAB_BYTES // (R * F * 4))
    for b in range(0, full, slab):
        e = min(b + slab, full)
        # waited for: slabs sent ahead would wait on the device, as many
        # bytes again as the table (peak 16.2 GB of 16.9 at 1,100,000 x 2000)
        buf = jax.block_until_ready(put(buf, np.ascontiguousarray(
            X[b * R:e * R], np.float32).reshape(-1), b))
    if full * R < n:
        last = np.zeros((R, F), np.float32)
        last[:n - full * R] = X[full * R:]
        buf = put(buf, last.reshape(-1), full)
    return buf


@dataclass
class _Shard:
    """One device's run of whole blocks: its rows, labels and two score
    vectors, and the candidates."""
    device: object          # None: the default device, uncommitted
    Xb: object
    yb: object
    sb: object
    sp: object
    cand: object

    def put(self, a):
        import jax
        import jax.numpy as jnp
        return (jnp.asarray(a) if self.device is None
                else jax.device_put(a, self.device))


class Follower:
    """Holds the rows on the device in blocks and follows training step by
    step under the trees it is given.  The block is `block_rows` of the shape:
    nothing is set for a width.  With `devices` the blocks are sharded over
    them, an equal number of blocks each, the last shard padded.  `extras`:
    what the rows came with, for an objective's file."""

    def __init__(self, X: np.ndarray, y: np.ndarray, params: Dict,
                 cand: np.ndarray, n_leaf: int, *,
                 precision: str = "float32", with_hist: bool = True,
                 devices=None, extras: Dict | None = None):
        import jax.numpy as jnp
        self.objective = objective(params)
        self.y, self.params, self.extras = y, params, extras or {}
        self.n, self.F = X.shape
        self.lr = float(params["learning_rate"])
        self.sigmoid = float(params.get("sigmoid", 1.0))
        self.min_data = float(params.get("min_data_in_leaf", 20))
        self.min_hess = float(params.get("min_sum_hessian_in_leaf", 1e-3))
        # padded to a multiple of 8: 255 leaves and 254 splits both take 256
        self.n_leaf = -(-int(n_leaf) // 8) * 8
        self.n_split = self.n_leaf
        self.low = precision == "bfloat16"
        if precision not in ("float32", "bfloat16"):
            raise ValueError(f"unknown precision {precision!r}")
        self.with_hist = with_hist
        R = self.block_rows = block_rows(self.F, cand.shape[1], self.n_leaf)
        devices = list(devices) if devices and len(devices) > 1 else [None]
        D = len(devices)
        blocks = -(-self.n // R)
        per = self.per = -(-blocks // D)        # blocks a shard: all hold
        nb = self.nb = per * D                  # as many, the last padded
        pad = nb * R - self.n
        if self.objective is None:
            pos = y > 0
            ysign = np.where(pos, 1.0, -1.0).astype(np.float32)
            yb = np.concatenate([ysign, np.zeros(pad, np.float32)]).reshape(
                nb, R)
            # BoostFromScore: log-odds of the positive rate over sigmoid
            pavg = min(max(float(pos.sum()) / self.n, 1e-10), 1.0 - 1e-10)
            self.init_score = math.log(pavg / (1.0 - pavg)) / self.sigmoid
        else:
            # 1 a row, 0 for padding: the rows that count
            yb = self._blocks(np.ones(self.n, np.float32))
            self.init_score = float(self.objective.init_score(
                y, params, self.extras))

        def shard(d):
            at = slice(d * per * R, (d + 1) * per * R)
            sh = _Shard(devices[d], None, None, None, None, None)
            sh.Xb = _upload_blocks(X[at], R, devices[d], per)
            sh.yb = sh.put(yb[d * per:(d + 1) * per])
            sh.sb = jnp.full((per, R), np.float32(self.init_score),
                             jnp.float32, device=devices[d])
            # a second score vector, moved by the leaf values under test
            sh.sp = sh.sb
            sh.cand = sh.put(cand)
            return sh

        with ThreadPoolExecutor(D) as pool:     # the uploads side by side
            self.shards = list(pool.map(shard, range(D)))
        self.cand_np = np.asarray(cand, np.float64)
        self._sums, self._sums_given, self._update, self._apply = \
            _build_passes(self.sigmoid, self.low, with_hist)

    def _blocks(self, v: np.ndarray) -> np.ndarray:
        """[n] per row -> [nb, R] float32 in the blocks' layout, zero past
        the last row."""
        out = np.zeros(self.nb * self.block_rows, np.float32)
        out[:self.n] = v
        return out.reshape(self.nb, self.block_rows)

    def scores(self, name: str = "sb") -> np.ndarray:
        """The reference's own scores (`sb`) or those moved by the values
        under test (`sp`), [n] float32 on the host."""
        return np.concatenate([np.asarray(getattr(sh, name)).reshape(-1)
                               for sh in self.shards])[:self.n]

    def _sums_of_objective(self, feat, thr, path, depth):
        """The objective file's g and h at the reference's scores, uploaded
        in the blocks' layout, and the sums pass on them."""
        g, h = self.objective.gradients(self.y, self.scores(), self.params,
                                        self.extras)
        gb, hb = self._blocks(g), self._blocks(h)
        at = [slice(d * self.per, (d + 1) * self.per)
              for d in range(len(self.shards))]
        return [self._sums_given(sh.Xb, (sh.yb, sh.put(gb[a]), sh.put(hb[a])),
                                 sh.put(feat), sh.put(thr), sh.put(path),
                                 sh.put(depth), sh.cand)
                for sh, a in zip(self.shards, at)]

    def step(self, tree: RefTree, tested_values=None) -> StepReading:
        """Follow one step under `tree`.  `tested_values` are the leaf values
        under test, init score taken out; they move the second score vector,
        whose loss is `tested_loss`."""
        if tree.num_leaves > self.n_leaf:
            raise ValueError(f"tree has {tree.num_leaves} leaves, the "
                             f"configuration allows {self.n_leaf}")
        feat, thr, path, depth = tree_tables(tree, self.n_split, self.n_leaf)
        # every shard's pass is dispatched before any result is read
        if self.objective is None:
            outs = [self._sums(sh.Xb, (sh.yb, sh.sb), sh.put(feat),
                               sh.put(thr), sh.put(path), sh.put(depth),
                               sh.cand)
                    for sh in self.shards]
        else:
            outs = self._sums_of_objective(feat, thr, path, depth)
        L, S = tree.num_leaves, tree.num_leaves - 1
        sums = self._gather([o[0] for o in outs]).sum(axis=0).reshape(
            self.n_leaf, 3)[:L]
        leaf_g, leaf_h = sums[:, 0], sums[:, 1]
        leaf_c = np.rint(sums[:, 2]).astype(np.int64)
        value = -self.lr * leaf_g / (leaf_h + K_EPS)
        anc = (path[:S, :L] != 0).astype(np.float64)            # [S, L]
        right = (path[:S, :L] > 0).astype(np.float64)
        node = anc @ sums
        rsum = right @ sums
        lsum = node - rsum

        def score(g, h):
            return g * g / (h + K_EPS)

        gain = (score(lsum[:, 0], lsum[:, 1]) + score(rsum[:, 0], rsum[:, 1])
                - score(node[:, 0], node[:, 1]))
        best = other = None
        if self.with_hist and S > 0:
            hist = np.asarray(outs[0][3], np.float64)
            for o in outs[1:]:
                hist = hist + np.asarray(o[3], np.float64)
            best, other = self._candidate_gains(
                hist.reshape(self.n_leaf, -1)[:L], anc, node,
                np.asarray(tree.split_feature))
        vals = np.zeros(self.n_leaf, np.float32)
        vals[:L] = value
        losses = []
        for sh, o in zip(self.shards, outs):
            sh.sb, loss = self._update(sh.yb, sh.sb, o[1], sh.put(vals))
            losses.append(loss)
        loss = self._loss(losses, "sb")
        tested_loss = None
        if tested_values is not None:
            tv = np.zeros(self.n_leaf, np.float32)
            tv[:L] = tested_values
            tls = []
            for sh, o in zip(self.shards, outs):
                sh.sp, tl = self._update(sh.yb, sh.sp, o[1], sh.put(tv))
                tls.append(tl)
            tested_loss = self._loss(tls, "sp")
        return StepReading(
            tested_loss=tested_loss,
            leaf_g=leaf_g, leaf_h=leaf_h, leaf_c=leaf_c, leaf_value=value,
            node_g=node[:, 0], node_h=node[:, 1],
            node_c=np.rint(node[:, 2]).astype(np.int64), gain=gain,
            best_gain=best, other_gain=other, loss=loss,
            unrouted=int(sum(np.asarray(o[2]).sum() for o in outs)))

    def _loss(self, logloss_parts, name: str) -> float:
        """The loss at the scores `name`: binary logloss's mean over the
        rows from the update pass's per-block sums, or the objective's."""
        if self.objective is None:
            return float(self._gather(logloss_parts).sum() / self.n)
        return float(self.objective.loss(self.y, self.scores(name),
                                         self.params, self.extras))

    @staticmethod
    def _gather(parts) -> np.ndarray:
        """The shards' per-block arrays as one float64 array, in the blocks'
        order."""
        return np.concatenate([np.asarray(p, np.float64) for p in parts])

    def _candidate_gains(self, hist, anc, node, split_feature):
        """For each split the best gain any candidate threshold of any
        feature would have given its node, and the best with the feature
        the program chose left out (the planted fault that sets
        best_split_shortfall's upper reading).

        `hist` is [L, 3 * F * B] float64: each leaf's sums of gradient,
        hessian and rows at or under each candidate.  A node's are its
        leaves' added up: ONE product `anc @ hist`, [S, L] x [L, 3 * F * B],
        which BLAS makes in seconds where einsum's loop took a minute at
        2000 x 64.  Its result ([S, 3, F, B]: three [S, F, B] float64
        arrays, 260 MB each at 254 x 2000 x 64) and `hist` (as large) are
        what is alive at width; the gains are taken SPLITS_AT_ONCE splits at
        a time, and their dozen temporaries are 16 MB each."""
        S = anc.shape[0]
        nh = (anc @ hist).reshape(S, 3, self.F, -1)             # left sums
        finite = np.isfinite(self.cand_np)[None]
        parent = node[:, 0] * node[:, 0] / (node[:, 1] + K_EPS)
        best, other = np.empty(S), np.empty(S)
        for s0 in range(0, S, SPLITS_AT_ONCE):
            at = slice(s0, min(s0 + SPLITS_AT_ONCE, S))
            n = at.stop - s0
            lg, lh, lc = nh[at, 0], nh[at, 1], nh[at, 2]
            tot = node[at, :, None, None]
            rg, rh, rc = tot[:, 0] - lg, tot[:, 1] - lh, tot[:, 2] - lc
            ok = ((lc >= self.min_data) & (rc >= self.min_data)
                  & (lh >= self.min_hess) & (rh >= self.min_hess) & finite)
            cg = (lg * lg / (lh + K_EPS) + rg * rg / (rh + K_EPS)
                  - parent[at, None, None])
            cg = np.where(ok, cg, -np.inf)
            best[at] = cg.reshape(n, -1).max(axis=1)
            cg[np.arange(n), split_feature[at]] = -np.inf
            other[at] = cg.reshape(n, -1).max(axis=1)
        return best, other

    def sum_forest(self, trees) -> np.ndarray:
        """The raw score of every row under `trees` as serialised (the first
        tree carries the init score in its leaves): float32 additions of
        float32 leaf values, in the trees' order, as training makes them."""
        import jax.numpy as jnp
        scores = [jnp.zeros(sh.yb.shape, jnp.float32, device=sh.device)
                  for sh in self.shards]
        for t in trees:
            feat, thr, path, depth = tree_tables(t, self.n_split, self.n_leaf)
            vals = np.zeros(self.n_leaf, np.float32)
            vals[:t.num_leaves] = t.leaf_value
            scores = [self._apply(sh.Xb, score, sh.put(feat), sh.put(thr),
                                  sh.put(path), sh.put(depth), sh.put(vals))
                      for sh, score in zip(self.shards, scores)]
        return np.concatenate([np.asarray(score).reshape(-1)
                               for score in scores])[:self.n]

    def close(self) -> None:
        for sh in self.shards:
            sh.sp = None
            for name in ("Xb", "yb", "sb", "cand"):
                arr = getattr(sh, name, None)
                if arr is not None:
                    arr.delete()
                    setattr(sh, name, None)

