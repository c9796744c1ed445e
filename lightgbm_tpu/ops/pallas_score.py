"""Pallas score-update kernel: ``score + table[leaf_id]`` as a one-hot
MXU contraction.

The boosting score update is a [L]-table gather by a full-N index
vector; XLA lowers that gather at ~1.6 GB/s on v5e (81 ms/iter at 10.5M
rows — round-4 ``score_table_gather`` micro), while the one-hot
formulation streams the row blocks at full block bandwidth like the
histogram kernels.  It is EXACT in f32: each row's dot product has
exactly one nonzero term (1.0f * table[leaf]), so no rounding
accumulates — required for train-score/predict parity.

Covers the score side of the reference's ScoreUpdater
(src/boosting/score_updater.hpp:84-99), whose AddScore(tree, ...) loops
rows on the host threadpool.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from .pallas_histogram import _interpret_default, gate_self_check

BLOCK = 32768
CHUNK = 512

_SELF_CHECK: bool | None = None


def scorer_available() -> bool:
    """Whether the one-hot scorer should replace the table gather.

    ``LIGHTGBM_TPU_SCORE_KERNEL=0/1`` forces it; the default ("auto")
    runs a one-shot self-check on the live backend: the kernel must
    lower AND reproduce ``score + table[leaf_id]`` bit-for-bit.  The
    interpret-mode parity tests run in full f32 and cannot see MXU
    rounding or Mosaic lowering failures, so the check has to happen
    here, non-interpret, on the real device.
    """
    global _SELF_CHECK
    env = os.environ.get("LIGHTGBM_TPU_SCORE_KERNEL", "auto").lower()
    if env in ("0", "off", "false"):
        return False
    if env in ("1", "on", "true"):
        return True
    if _SELF_CHECK is None:
        _SELF_CHECK = gate_self_check("score-kernel",
                                      _score_kernel_self_check)
    return _SELF_CHECK


def _score_kernel_self_check() -> bool:
    """``score_gather_add`` must reproduce ``score + table[leaf_id]``
    bit-for-bit on the live backend."""
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal(255), jnp.float32)
    lid = jnp.asarray(rng.integers(0, 255, 4096), jnp.int32)
    score = jnp.asarray(rng.standard_normal(4096), jnp.float32)
    return bool(jnp.array_equal(score_gather_add(score, lid, table),
                                score + table[lid]))


def _kernel(lv_ref, lid_ref, score_ref, out_ref, *, table_pad):
    def one_chunk(c, carry):
        sl = pl.ds(c * CHUNK, CHUNK)
        lid = lid_ref[0, sl]
        iota = lax.broadcasted_iota(jnp.int32, (table_pad, CHUNK), 0)
        onehot = (iota == lid[None, :]).astype(jnp.float32)
        # Precision.HIGHEST: the MXU otherwise rounds f32 operands to
        # bf16, corrupting the leaf-value table and breaking the
        # train-score/predict exactness contract above.  The 3-pass
        # bf16 decomposition is exact here (one nonzero 1.0f term per
        # row), and the matmul is not the kernel bound.
        v = lax.dot_general(lv_ref[...], onehot, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=lax.Precision.HIGHEST)
        out_ref[0, sl] = score_ref[0, sl] + v[0]
        return carry

    lax.fori_loop(0, BLOCK // CHUNK, one_chunk, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def score_gather_add(score_row: jax.Array, leaf_id: jax.Array,
                     table: jax.Array,
                     interpret: bool | None = None) -> jax.Array:
    """``score_row + table[leaf_id]`` — [N] f32, [N] i32, [L] f32.

    Scale factors (shrinkage, DART normalization) belong pre-applied to
    ``table``; indices >= len(table) contribute zero (all-zero one-hot
    column), and callers never produce them.
    """
    if interpret is None:
        interpret = _interpret_default()
    n = score_row.shape[0]
    L = table.shape[0]
    table_pad = -(-L // 128) * 128
    pad = (-n) % BLOCK
    sp = jnp.pad(score_row.astype(jnp.float32), (0, pad)).reshape(1, -1)
    lp = jnp.pad(leaf_id, (0, pad)).reshape(1, -1)
    tv = jnp.pad(table.astype(jnp.float32),
                 (0, table_pad - L)).reshape(1, -1)
    out = pl.pallas_call(
        functools.partial(_kernel, table_pad=table_pad),
        grid=(sp.shape[1] // BLOCK,),
        in_specs=[pl.BlockSpec((1, table_pad), lambda i: (0, 0)),
                  pl.BlockSpec((1, BLOCK), lambda i: (0, i)),
                  pl.BlockSpec((1, BLOCK), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct(sp.shape, jnp.float32),
        interpret=interpret,
        name="score_gather_add",               # as the trace shows it
    )(tv, lp, sp)
    return out[0, :n]
