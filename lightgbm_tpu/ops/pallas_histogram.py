"""Pallas TPU histogram kernels — the performance core.

Replaces the reference's OpenCL local-atomic kernels
(src/treelearner/ocl/histogram{16,64,256}.cl) and its 4-way unrolled CPU
loop (src/io/dense_bin.hpp:69-193) with a TPU-native formulation:

  * bins live feature-major ``[F, N]`` so each feature's stream is
    contiguous on the lane axis;
  * a COMBINED (feature, bin) one-hot ``[F*B, chunk]`` is built with int32
    VPU compares and never leaves VMEM;
  * ONE bf16 matmul per chunk contracts it against the ``[8, chunk]``
    weight channels on the MXU with f32 accumulation — all features in a
    single large-output matmul (round-2's per-feature ``[8, rb] x [rb, B]``
    loop left >90% of the MXU idle; the combined form measures ~2.9 ns/row
    for 28 features x 64 bins on v5e).  Gradients/hessians are carried as
    bf16 hi+lo channel pairs (``pack_channels``), giving ~16 mantissa
    bits — the same single-precision stance as the reference GPU learner's
    default ``gpu_use_dp=false`` (src/treelearner/gpu_tree_learner.cpp:677),
    with the count channel exact in f32 accumulation.

Two kernels share the inner body:

  * ``histogram_all``: every row block contributes (the root / full-data
    case);
  * ``histogram_segment``: a scalar-prefetched ``(start_block, n_blocks,
    target_leaf)`` descriptor restricts DMA *and* compute to the blocks of
    one leaf's confinement interval — the TPU equivalent of the reference's
    ordered bins (src/io/ordered_sparse_bin.hpp) whose histogram cost is
    proportional to the leaf, not the dataset.  Out-of-range grid steps
    re-map to the last in-range block, so the pipeline issues no new DMA
    for them, and ``pl.when`` skips their compute.

The 8 weight channels are ``[g_hi, g_lo, h_hi, h_lo, member, 0, 0, 0]``;
``unpack_hist`` folds a kernel output ``[F, B, 8]`` back to the
``[F, B, 3]`` (sum_grad, sum_hess, count) layout the split scan consumes.

Two env-gated variant fronts ride the same kernels (docs/KERNELS.md has
the full catalogue and measured verdicts):

  * ``LIGHTGBM_TPU_PACKED_ACC``: a packed int16 accumulator stream
    (``quantize_pack_channels``) — grad/hess stochastically rounded to
    int16 and packed into ONE i32 lane, halving both the weight-stream
    HBM DMA and the accumulator channel width (the arxiv 1806.11248 /
    1706.08359 lever).  Kernels detect the i32 dtype (it is part of the
    jit avals, so no new static args) and widen to ``PACKED_CHANNELS``
    bf16 lanes in VMEM; ``unpack_hist_packed`` rescales at unpack.  The
    count channel stays exact.
  * ``LIGHTGBM_TPU_ONEHOT_BUILD``: alternative one-hot constructions
    (``gather``: row-gather from an eye tile; ``twolevel``: two half-
    width compares multiplied) — bit-identical to the iota build by
    construction (same matmul, same accumulation order).

Both are auto-gated by one-shot self-checks on the live backend
(``gate_self_check``: a mismatch falls back to the f32 / iota path with a
warning, a lowering failure on TPU raises), and neither flips to default
without a v5e number.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import os as _os

NUM_CHANNELS = 8
# channel width of the packed-accumulator stream once widened in VMEM:
# [g_q, h_q, member, 0] — half the 8-channel hi/lo path
PACKED_CHANNELS = 4
DEFAULT_BLOCK_ROWS = 16384
# inner sub-chunk of a row block: the one-hot [fblk*B, CHUNK] lives in
# VMEM only for the duration of one matmul.  Env-tunable (read at
# import) for on-chip inner-loop sweeps: the build is ~5x off its VPU
# bound and these two shape the materialized tile.
CHUNK = int(_os.environ.get("LIGHTGBM_TPU_ONEHOT_CHUNK", "512"))
# feature sub-block: keep fblk*B*CHUNK*2B (one-hot) around 2MB
_FBLK_BIN_BUDGET = int(_os.environ.get("LIGHTGBM_TPU_FBLK_BINS", "2048"))
# VMEM working-set budget for auto block sizing (bytes, of ~16MB/core)
_VMEM_BUDGET = 10 * 1024 * 1024
# Mosaic's default scoped-VMEM limit per kernel on v5e (the unfused
# kernels run under it; the fused ones size their own, fused_vmem_limit),
# what supported() leaves free of it for the kernel's temporaries, and the
# lane width the last dimension of every VMEM array is padded to
_SCOPED_VMEM_LIMIT = 16 * 1024 * 1024
_SCOPED_VMEM_SLACK = 1024 * 1024
_LANES = 128


def _fblk(num_bins: int) -> int:
    return max(1, _FBLK_BIN_BUDGET // num_bins)


def _pick_chunk(rb: int) -> int:
    """Largest lane-aligned chunk <= CHUNK dividing the row block; falls
    back to the whole block for odd user-chosen tpu_row_chunk values."""
    for c in (CHUNK, 256, 128):
        if rb % c == 0:
            return c
    return rb


def _block_rows_for(cols: int, num_bins: int, num_rows: int = 0) -> int:
    """Largest power-of-two row block whose VMEM working set fits the
    budget when a pass holds ``cols`` (a multiple of 4) columns."""
    acc = cols * num_bins * NUM_CHANNELS * 4
    # one-hot chunk (bf16) + its integer compare intermediate
    onehot = _fblk(num_bins) * num_bins * CHUNK * (2 + 4)
    rb = 4 * DEFAULT_BLOCK_ROWS
    if num_rows > 0:
        cap = 1 << max(0, (num_rows - 1).bit_length())
        rb = min(rb, max(CHUNK, cap))
    while rb > CHUNK:
        # double-buffered input blocks (bins u8, w8 bf16, leaf_id i32)
        streams = 2 * rb * (cols + 2 * NUM_CHANNELS + 4)
        if acc + streams + onehot <= _VMEM_BUDGET:
            return rb
        rb //= 2
    return rb


def _scoped_vmem_need(cols: int, num_bins: int) -> int:
    """The scoped-VMEM stack of a pass over ``cols`` columns as Mosaic
    lays it out (tests/test_tpu_compile.py holds this to the compiler).
    The [cols*B, och] f32 accumulator is tiled (8, 128), so its och <= 128
    lanes pad to 128 (16x the logical bytes at och = 8); the
    double-buffered bins / weights / leaf-id blocks share the stack, and
    the one-hot temporaries took up to 0.7 MB more in the compiles
    measured (_SCOPED_VMEM_SLACK)."""
    acc = cols * num_bins * _LANES * 4
    streams = 2 * _block_rows_for(cols, num_bins) * (
        cols + 2 * NUM_CHANNELS + 4)
    return acc + streams + _SCOPED_VMEM_SLACK


# Feature tiles.  A table too wide for one accumulator (2000 columns x 64
# bins is 65.5 MB of lane-padded f32) is walked in tiles of columns by the
# routed segment kernels: each tile's accumulator gets a quarter of the
# scoped-VMEM limit, which leaves room for the lookahead kernel's (block
# sum, hi, lo) triple and its double-buffered output under
# _FUSED_VMEM_CAP.  A tile is a whole number of u8 (32, 128) VMEM tiles
# of physical bin rows.  Tiles are admitted at the one accumulator height
# a tiled pass has run at on the chip, 64 bins (128 columns a tile:
# benchmark cell epsilon63-train); at any other a table past one
# accumulator is refused as before (256 bins would walk 32-column tiles:
# compiled for a described v5e, never run, PERF.md section 7).
_TILE_ACC_BYTES = _SCOPED_VMEM_LIMIT // 4
_TILE_ROW_ALIGN = 32
_TILE_BINS = 64


def feature_tile(num_features: int, num_bins: int) -> int:
    """Columns one pass of the routed segment kernels accumulates at a
    time: all of them (rounded up to a multiple of 4 — the segment grower
    pads features to pack them into sort words) where the whole
    accumulator fits the compiler's default limit as ``supported`` always
    reckoned it or the bins are not ``_TILE_BINS`` (such a table is not
    ``supported``), else the tile width."""
    F4 = -(-num_features // 4) * 4
    if (num_bins != _TILE_BINS
            or _scoped_vmem_need(F4, num_bins) <= _SCOPED_VMEM_LIMIT):
        return F4
    cols = _TILE_ACC_BYTES // (num_bins * _LANES * 4)
    return max(_TILE_ROW_ALIGN, cols // _TILE_ROW_ALIGN * _TILE_ROW_ALIGN)


def feature_tiles(num_features: int, num_bins: int) -> int:
    """Tiles a pass walks at this shape; 1 where every kernel takes the
    table whole."""
    return -(-num_features // feature_tile(num_features, num_bins))


def supported(num_features: int, num_bins: int, dtype) -> bool:
    """Whether the serial segment grower's kernels handle this shape
    (else callers fall back to the XLA one-hot path in ops/histogram.py):
    whole, or tile by tile (``feature_tiles`` > 1: the routed segment
    kernels alone walk tiles, so GBDT keeps every other learner and
    grower at such a shape off the Pallas backend)."""
    if dtype not in (jnp.uint8, jnp.int8):
        return False
    if num_bins > 256:
        return False
    return (_scoped_vmem_need(feature_tile(num_features, num_bins), num_bins)
            <= _SCOPED_VMEM_LIMIT)


def pick_block_rows(num_features: int, num_bins: int,
                    num_rows: int = 0) -> int:
    """Largest power-of-two row block whose VMEM working set fits budget,
    reckoned for one feature tile.

    ``num_rows`` (when known) caps the block at the next power of two >=
    the dataset, so small datasets are not padded to a huge block.
    """
    return _block_rows_for(feature_tile(num_features, num_bins), num_bins,
                           num_rows)


def pack_channels(grad: jax.Array, hess: jax.Array,
                  member: jax.Array) -> jax.Array:
    """[N] f32 grad/hess/member -> [8, N] bf16 weight channels.

    ``lax.reduce_precision`` performs the hi/lo split; a plain
    f32->bf16->f32 round-trip is elided under XLA's
    ``--xla_allow_excess_precision`` and would zero the lo channel.
    """
    gm = grad * member
    hm = hess * member
    g_hi = lax.reduce_precision(gm, 8, 7)
    h_hi = lax.reduce_precision(hm, 8, 7)
    g_lo = (gm - g_hi).astype(jnp.bfloat16)
    h_lo = (hm - h_hi).astype(jnp.bfloat16)
    z = jnp.zeros(gm.shape, jnp.bfloat16)
    return jnp.stack([g_hi.astype(jnp.bfloat16), g_lo,
                      h_hi.astype(jnp.bfloat16), h_lo,
                      member.astype(jnp.bfloat16), z, z, z])


def unpack_hist(out: jax.Array) -> jax.Array:
    """[F, B, 8] channel sums -> [F, B, 3] (sum_grad, sum_hess, count)."""
    g = out[..., 0] + out[..., 1]
    h = out[..., 2] + out[..., 3]
    c = out[..., 4]
    return jnp.stack([g, h, c], axis=-1)


def packed_acc_bits() -> int:
    """Quantization width for the packed accumulator
    (``LIGHTGBM_TPU_PACKED_BITS``, default 8, clamped to [2, 15]).

    8 bits is the exactness sweet spot: quantized ints up to +-127 are
    EXACT in the bf16 lanes the MXU contracts (8 mantissa bits), so the
    only error is the stochastic rounding itself.  Widths above 8 trade
    that in-matmul exactness for resolution (bf16 rounds ints > 256) —
    the self-check bound still holds but the verdict belongs on-chip."""
    try:
        bits = int(_os.environ.get("LIGHTGBM_TPU_PACKED_BITS", "8"))
    except ValueError:
        bits = 8
    return max(2, min(bits, 15))


def quantize_pack_channels(grad: jax.Array, hess: jax.Array,
                           member: jax.Array, key=None, bits: int = 8):
    """[N] f32 grad/hess/member -> ``([2, N] i32, [2] f32 scales, clips)``
    packed weight stream for the packed-accumulator kernels.

    Row 0 packs the stochastically-rounded int16 pair — grad*member in
    the high halfword, hess*member in the low — so the weight stream is
    8 bytes/row instead of 16; row 1 carries the member bits (f32
    bitcast) so the count channel stays exact.  ``scales`` rescales the
    summed quantized lanes back to real units at unpack: quantization is
    per CALL, so the rescale is per tree (segment/frontier growers, one
    quantize per grow) or per leaf (plain grower).  Stochastic rounding
    keeps every per-bin sum unbiased; ``clips`` counts saturated lanes
    (|q| == qmax, the rows quantized at the coarsest step) for the
    ``hist/quant_clips`` telemetry counter.
    """
    gm = grad * member
    hm = hess * member
    qmax = float(2 ** (bits - 1) - 1)
    gscale = jnp.maximum(jnp.max(jnp.abs(gm)), 1e-30) / qmax
    hscale = jnp.maximum(jnp.max(jnp.abs(hm)), 1e-30) / qmax
    if key is None:
        # deterministic data-derived key: the rounding only needs per-row
        # uniforms decorrelated from the values, and deriving the fold
        # from the gradient bits gives fresh draws every tree without
        # threading a PRNG key through the growers
        seed = jnp.sum(lax.bitcast_convert_type(
            gm[:8].astype(jnp.float32), jnp.int32).astype(jnp.uint32))
        key = jax.random.fold_in(jax.random.PRNGKey(0x517CC1B7), seed)
    kg, kh = jax.random.split(key)

    def _q(x, scale, k):
        t = x / scale
        fl = jnp.floor(t)
        up = jax.random.uniform(k, t.shape) < (t - fl)
        return jnp.clip(fl + up.astype(jnp.float32),
                        -qmax, qmax).astype(jnp.int32)

    gq = _q(gm, gscale, kg)
    hq = _q(hm, hscale, kh)
    clips = (jnp.sum((jnp.abs(gq) >= qmax).astype(jnp.int32))
             + jnp.sum((jnp.abs(hq) >= qmax).astype(jnp.int32)))
    w2 = jnp.stack([
        (gq << 16) | (hq & 0xFFFF),
        lax.bitcast_convert_type(member.astype(jnp.float32), jnp.int32)])
    return w2, jnp.stack([gscale, hscale]), clips


def unpack_hist_packed(out: jax.Array, scales: jax.Array) -> jax.Array:
    """[..., B, PACKED_CHANNELS] packed-accumulator sums -> [..., B, 3]
    real-unit (sum_grad, sum_hess, count); ``scales`` is
    quantize_pack_channels's [2] rescale pair."""
    g = out[..., 0] * scales[0]
    h = out[..., 1] * scales[1]
    return jnp.stack([g, h, out[..., 2]], axis=-1)


def _packed_wrows(wb: jax.Array) -> jax.Array:
    """[2, chunk] i32 packed stream block -> [PACKED_CHANNELS, chunk]
    bf16 rows [g_q, h_q, member, 0] for the shared matmul.

    Arithmetic shifts sign-extend the int16 halves (v5e-safe: plain i32
    VPU ops, no narrow iota/compare); i32 -> f32 -> bf16 are supported
    single-step converts, and the member lane takes the same f32 -> bf16
    rounding as pack_channels so counts match the 8-channel path
    bitwise."""
    wq = wb[0:1]
    gq = (wq >> 16).astype(jnp.float32).astype(jnp.bfloat16)
    hq = ((wq << 16) >> 16).astype(jnp.float32).astype(jnp.bfloat16)
    m = lax.bitcast_convert_type(wb[1:2], jnp.float32).astype(jnp.bfloat16)
    return jnp.concatenate([gq, hq, m, jnp.zeros_like(m)], axis=0)


def _accumulate_block(binsT_ref, wfn, acc_ref, num_bins, packed4=False,
                      onehot_build="iota", unroll=1):
    """Shared inner body: one [F, rb] bin block into the [F*B, 8]
    accumulator, one combined-one-hot matmul per (chunk, fblock).

    ``wfn(c)`` returns the [8, chunk] weight channels of chunk ``c``.
    Chunks are walked with an in-kernel ``fori_loop`` so the Mosaic program
    size is independent of the row-block size (a fully unrolled 64-chunk
    body made kernel compilation a large share of the jit time).
    ``unroll`` chunks share one loop body, in order (same accumulation
    order, same bits), so one chunk's weights and one-hot build can
    overlap the previous chunk's matmul.

    ``packed4``: the bin block holds TWO <=16-bin features per byte
    (feature 2i in the low nibble of row i, 2i+1 in the high) — the TPU
    equivalent of the reference's Dense4bitsBin (dense_nbits_bin.hpp:42):
    half the HBM bin-stream DMA for narrow-bin datasets; unpacking is two
    VPU ops per block.

    ``onehot_build`` picks the one-hot construction (the measured ~18 ms
    VPU bound of the 12.4 ms/pass baseline).  All three builds produce
    the SAME [nf*B, chunk] matrix feeding the SAME dot_general, so the
    f32 accumulation order — and therefore the output bits — cannot
    differ:

      * ``iota``  — compare-vs-broadcasted-iota (the baseline);
      * ``gather``— one eye(B) bf16 tile built in VMEM, one row-gather
        of the chunk's bin indices, one sublane transpose (nf*chunk
        gather rows instead of nf*B*chunk compares);
      * ``twolevel`` — split the bin index into high/low halves and
        multiply two half-width compare one-hots (nf*(Bh+Bl)*chunk
        compares instead of nf*B*chunk; power-of-two B only, falls
        back to iota statically otherwise).
    """
    Fp, rb = binsT_ref.shape
    F = Fp * 2 if packed4 else Fp
    B = num_bins
    fblk = max(1, _fblk(B) // (2 if packed4 else 1))
    chunk = _pick_chunk(rb)

    # LIGHTGBM_TPU_ONEHOT_DTYPE picks the compare dtype for the one-hot
    # build — the kernel's measured bound (~18 ms of the ~27 ms full-N
    # pass at i32).  v5e VERDICT (2026-08-01 on-chip): narrow compares
    # are DEAD on this hardware — u8 iota doesn't lower, 16-bit iota is
    # "not supported by hardware", and even with the i32-iota+downcast
    # construction below both i16 and bf16 fail Mosaic compile with
    # "Target does not support this comparison".  i32 is the default
    # and the only mode known to compile on v5e; the narrow paths stay
    # for backends whose VPU does support them.
    import os as _os
    _env = _os.environ.get("LIGHTGBM_TPU_ONEHOT_DTYPE", "")
    if _env == "u8":
        # no u8 iota on Mosaic and no u8 vector compare on v5e — route
        # to i16 (itself v5e-dead but the nearest requested intent)
        # instead of crashing deep in kernel compilation
        from ..utils.log import log_warning
        log_warning("LIGHTGBM_TPU_ONEHOT_DTYPE=u8 does not lower on "
                    "this backend; using i16")
        _env = "i16"
    cmp_dtype = {"bf16": jnp.bfloat16, "i16": jnp.int16}.get(
        _env, jnp.int32)

    build = onehot_build
    if build == "twolevel" and (B & (B - 1) or B < 4):
        build = "iota"   # two-level needs a power-of-two bin count

    def one_chunk(c, carry):
        wc = wfn(c, chunk)                                  # [8, chunk]
        for p0 in range(0, Fp, fblk):
            np_ = min(fblk, Fp - p0)
            b = binsT_ref[p0:p0 + np_, pl.ds(c * chunk, chunk)]
            if packed4:
                # unpack nibbles in integer space (bitwise ops are not
                # defined for the bf16 compare dtype), then cast
                bi = b.astype(jnp.int32)
                b = jnp.stack([bi & 15, bi >> 4], axis=1).reshape(
                    2 * np_, chunk)
            nf = b.shape[0]
            if build == "gather":
                eye = jnp.eye(B, dtype=jnp.bfloat16)
                oh = jnp.take(eye, b.astype(jnp.int32).reshape(-1),
                              axis=0)                  # [nf*chunk, B]
                onehot = oh.reshape(nf, chunk, B).transpose(
                    0, 2, 1).reshape(nf * B, chunk)
            elif build == "twolevel":
                s = (B.bit_length() - 1) // 2
                Bl = 1 << s
                Bh = B // Bl
                bi = b.astype(jnp.int32)
                ih = lax.broadcasted_iota(jnp.int32, (nf, Bh, chunk), 1)
                il = lax.broadcasted_iota(jnp.int32, (nf, Bl, chunk), 1)
                oh_hi = ((bi >> s)[:, None, :] == ih).astype(jnp.bfloat16)
                oh_lo = ((bi & (Bl - 1))[:, None, :] == il).astype(
                    jnp.bfloat16)
                onehot = (oh_hi[:, :, None, :]
                          * oh_lo[:, None, :, :]).reshape(nf * B, chunk)
            else:
                # narrow compare dtypes: v5e has no 16-bit iota ("16-bit
                # iota not supported by hardware") and no direct u8->bf16
                # convert — build both sides from i32/f32 with supported
                # single-step converts
                iota32 = lax.broadcasted_iota(jnp.int32, (nf, B, chunk), 1)
                if cmp_dtype == jnp.bfloat16:
                    b = b.astype(jnp.int32).astype(jnp.float32).astype(
                        jnp.bfloat16)
                    iota = iota32.astype(jnp.float32).astype(jnp.bfloat16)
                elif cmp_dtype == jnp.int16:
                    b = b.astype(jnp.int32).astype(jnp.int16)
                    iota = iota32.astype(jnp.int16)
                else:
                    b = b.astype(cmp_dtype)
                    iota = iota32
                onehot = (b[:, None, :] == iota).astype(
                    jnp.bfloat16).reshape(nf * B, chunk)
            f0 = (2 * p0 if packed4 else p0)
            acc_ref[f0 * B:(f0 + nf) * B] += lax.dot_general(
                onehot, wc, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        return carry

    n_chunks = rb // chunk
    if n_chunks % unroll:
        unroll = 1

    def body(c, carry):
        for u in range(unroll):
            one_chunk(c * unroll + u, carry)
        return carry

    lax.fori_loop(0, n_chunks // unroll, body, 0)


def _kernel_all(binsT_ref, w_ref, out_ref, acc_ref, *, num_bins, packed4,
                onehot_build="iota"):
    # w_ref may carry MULTIPLE 8-channel sets ([8*C, rb]): the matmul
    # output widens to 8*C and each set accumulates independently — used
    # to histogram all C class-trees' roots in one pass (multiclass).
    # An i32 w_ref is the packed-accumulator stream ([2, rb] -> 4 lanes).
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def wfn(c, chunk):
        wc = w_ref[:, pl.ds(c * chunk, chunk)]
        return _packed_wrows(wc) if w_ref.dtype == jnp.int32 else wc

    _accumulate_block(binsT_ref, wfn, acc_ref, num_bins, packed4,
                      onehot_build)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def _kernel_segment(sref, binsT_ref, w_ref, lid_ref, out_ref, acc_ref, *,
                    num_bins, packed4, onehot_build="iota"):
    # sref: prefetched [3] i32 = (start_block, n_blocks, target_leaf)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(i < sref[1])
    def _():
        def wfn(c, chunk):
            wc = w_ref[:, pl.ds(c * chunk, chunk)]
            if w_ref.dtype == jnp.int32:
                wc = _packed_wrows(wc)
            lc = lid_ref[:, pl.ds(c * chunk, chunk)]
            return wc * (lc == sref[2]).astype(jnp.bfloat16)

        _accumulate_block(binsT_ref, wfn, acc_ref, num_bins, packed4,
                          onehot_build)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def gate_self_check(name: str, check) -> bool:
    """Run the one-shot self-check behind a kernel gate.

    On a ``tpu`` backend a check that RAISES is an error of the program —
    a kernel written for this device failed to lower or run on it — and
    surfaces.  Elsewhere (the interpreter) a raise selects the other
    path, with its traceback on stderr.  A check that runs and reports a
    mismatch selects the other path on every backend; the gates memoize,
    so that is said once, through ``log_warning`` and the
    ``hist/self_check_fallbacks`` counter."""
    try:
        ok = bool(check())
    except Exception:
        if jax.default_backend() == "tpu":
            raise
        import sys
        import traceback
        sys.stderr.write(f"{name} self-check raised:\n"
                         + traceback.format_exc()[-2000:] + "\n")
        ok = False
    if not ok:
        from ..utils.log import log_warning
        from ..utils.telemetry import TELEMETRY
        TELEMETRY.counter_add("hist/self_check_fallbacks", 1)
        log_warning(f"{name} self-check failed on the "
                    f"{jax.default_backend()} backend; using the path it "
                    f"was checked against")
    return ok


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_rows", "interpret",
                                    "packed4", "onehot_build"))
def _histogram_all(binsT: jax.Array, w8: jax.Array, num_bins: int,
                   block_rows: int = 0,
                   interpret: bool | None = None,
                   packed4: bool = False,
                   onehot_build: str = "iota") -> jax.Array:
    F, n = binsT.shape
    F_log = 2 * F if packed4 else F
    CH = int(w8.shape[0])
    if w8.dtype == jnp.int32:
        # packed-accumulator stream: single channel set only (the
        # multiclass batched-roots path keeps the f32 channels)
        assert CH == 2, CH
        C, och = 1, PACKED_CHANNELS
    else:
        assert CH % NUM_CHANNELS == 0, CH
        C = CH // NUM_CHANNELS
        och = CH
    if block_rows <= 0:
        block_rows = pick_block_rows(F_log, num_bins)
    if interpret is None:
        interpret = _interpret_default()
    assert n % block_rows == 0, (n, block_rows)
    out = pl.pallas_call(
        functools.partial(_kernel_all, num_bins=num_bins, packed4=packed4,
                          onehot_build=onehot_build),
        out_shape=jax.ShapeDtypeStruct((F_log * num_bins, och),
                                       jnp.float32),
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((F, block_rows), lambda i: (0, i)),
            pl.BlockSpec((CH, block_rows), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((F_log * num_bins, och),
                               lambda i: (0, 0)),
        scratch_shapes=[pltpu.VMEM((F_log * num_bins, och), jnp.float32)],
        interpret=interpret,
    )(binsT, w8)
    if C == 1:
        return out.reshape(F_log, num_bins, och)
    # [F*B, C*8] -> [C, F, B, 8]
    return out.reshape(F_log, num_bins, C, NUM_CHANNELS).transpose(
        2, 0, 1, 3)


def histogram_all(binsT: jax.Array, w8: jax.Array, num_bins: int,
                  block_rows: int = 0,
                  interpret: bool | None = None,
                  packed4: bool = False) -> jax.Array:
    """Full-data histogram: [F, Npad] bins x [8*C, Npad] channels ->
    [C, F, B, 8] (squeezed to [F, B, 8] for the common C == 1).

    ``w8`` may stack C independent 8-channel sets (multiclass batched
    roots: every class-tree's root histogram in ONE pass — C x fewer
    full-data scans, and 8*C output columns fill more of the MXU tile),
    or be the [2, Npad] i32 packed-accumulator stream
    (quantize_pack_channels; output [F, B, PACKED_CHANNELS], rescale via
    unpack_hist_packed).  Npad must be a multiple of ``block_rows``; pad
    rows must carry zero weight channels (the bin values there may be
    anything).  With ``packed4`` the bins hold two <=16-bin features per
    byte and F here means PHYSICAL rows; the output has 2F logical
    features.  The one-hot build (LIGHTGBM_TPU_ONEHOT_BUILD) is resolved
    HERE, outside the jitted dispatch, so an env change can never be
    masked by a stale jit cache entry.
    """
    return _histogram_all(binsT, w8, num_bins, block_rows, interpret,
                          packed4, onehot_build_mode())


def _segment_buckets(max_blocks: int) -> list:
    """Static grid-size ladder for histogram_segment.

    A pallas grid is static, but a leaf's confinement interval is data-
    dependent: one kernel sized for max_blocks pays a skipped-but-not-free
    grid step for every block outside the interval, which dominates late-
    tree splits (intervals of a few blocks under a 300+-step grid burned
    >1s/iter at 10.5M rows).  Instead the caller lax.switches between a
    few size variants and runs the smallest one that covers the interval.

    Every variant is a separate Mosaic compile on the backend, so the
    ladder step trades per-iter skipped-step waste against compile
    warmup; LIGHTGBM_TPU_BUCKET_STEP (default 8) tunes it on-chip.
    """
    import os
    step = max(2, int(os.environ.get("LIGHTGBM_TPU_BUCKET_STEP", "8")))
    buckets = []
    b = max_blocks
    while b > 1:
        buckets.append(b)
        b = max(1, b // step)
    buckets.append(1)
    return sorted(set(buckets))


def bucket_index(bucket_list, n_blocks) -> jax.Array:
    """Index of the smallest ladder bucket covering an ``n_blocks``-long
    interval — THE smallest-covering rule.  Shared by the kernels'
    ``lax.switch`` dispatch, ``segment_grid_size`` accounting, and the
    growers' windowed routing so the three can never drift."""
    nb = jnp.asarray(n_blocks, jnp.int32).reshape(())
    return jnp.minimum(jnp.sum(jnp.asarray(bucket_list, jnp.int32) < nb),
                       len(bucket_list) - 1)


def segment_grid_size(bucket_arr: jax.Array, n_blocks) -> jax.Array:
    """Grid steps the bucketed dispatch runs for an ``n_blocks``-long
    interval — the same smallest-covering-bucket rule histogram_segment
    and histogram_frontier apply (``bucket_arr`` is
    ``jnp.asarray(_segment_buckets(max_blocks))``).  Lives here so the
    growers' seg-stats grid accounting can never drift from the actual
    dispatch."""
    if dyn_grid_enabled():
        # dynamic grids are sized exactly to the interval (min 1 step)
        return jnp.maximum(jnp.asarray(n_blocks, jnp.int32), 1)
    return bucket_arr[bucket_index(bucket_arr, n_blocks)]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_rows", "grid_blocks",
                                    "interpret", "packed4", "onehot_build"))
def _histogram_segment_fixed(binsT: jax.Array, w8: jax.Array,
                             leaf_id: jax.Array, start_block: jax.Array,
                             n_blocks: jax.Array, target_leaf: jax.Array,
                             num_bins: int, block_rows: int,
                             grid_blocks: int,
                             interpret: bool | None = None,
                             packed4: bool = False,
                             onehot_build: str = "iota") -> jax.Array:
    """One static-grid variant; grid_blocks must be >= n_blocks."""
    F, n = binsT.shape
    F_log = 2 * F if packed4 else F
    CHW = int(w8.shape[0])
    och = PACKED_CHANNELS if w8.dtype == jnp.int32 else NUM_CHANNELS
    if interpret is None:
        interpret = _interpret_default()
    max_blocks = n // block_rows
    scalars = jnp.stack([start_block, n_blocks, target_leaf]).astype(
        jnp.int32)

    def im_data(i, s):
        blk = jnp.minimum(s[0] + jnp.minimum(i, jnp.maximum(s[1] - 1, 0)),
                          max_blocks - 1)
        return (0, blk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid_blocks,),
        in_specs=[
            pl.BlockSpec((F, block_rows), im_data),
            pl.BlockSpec((CHW, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
        ],
        out_specs=pl.BlockSpec((F_log * num_bins, och),
                               lambda i, s: (0, 0)),
        scratch_shapes=[pltpu.VMEM((F_log * num_bins, och),
                                   jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_segment, num_bins=num_bins,
                          packed4=packed4, onehot_build=onehot_build),
        out_shape=jax.ShapeDtypeStruct((F_log * num_bins, och),
                                       jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(scalars, binsT, w8, leaf_id.reshape(1, -1))
    return out.reshape(F_log, num_bins, och)


# Mosaic accepts traced grid dims (validated on a v5e 2026-07-31: strict
# 10.5M probe 1.53 s/iter dyn vs 1.81-1.91 ladder; compiled for a
# described v5e by tests/test_tpu_compile.py), so exact grids are the
# default — one kernel compile instead of a bucket ladder, zero skipped
# steps.  LIGHTGBM_TPU_DYN_GRID=0 restores the ladder.
_DYN_GRID_DEFAULT = True


def dyn_grid_enabled() -> bool:
    """LIGHTGBM_TPU_DYN_GRID=1 dispatches segment/frontier histograms on
    a DYNAMIC pallas grid sized exactly to the interval: one Mosaic
    compile instead of a bucket-ladder of variants (less compile warmup)
    and zero skipped grid steps.  =0 forces the bucket ladder."""
    import os
    env = os.environ.get("LIGHTGBM_TPU_DYN_GRID", "")
    if env == "1":
        return True
    if env == "0":
        return False
    return _DYN_GRID_DEFAULT


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_rows", "interpret",
                                    "packed4", "onehot_build"))
def _histogram_segment_dyn(binsT: jax.Array, w8: jax.Array,
                           leaf_id: jax.Array, start_block: jax.Array,
                           n_blocks: jax.Array, target_leaf: jax.Array,
                           num_bins: int, block_rows: int,
                           interpret: bool | None = None,
                           packed4: bool = False,
                           onehot_build: str = "iota") -> jax.Array:
    """Dynamic-grid variant: the grid is the traced interval length, so
    every step is in-range (no remapping, no skipped steps)."""
    F, n = binsT.shape
    F_log = 2 * F if packed4 else F
    CHW = int(w8.shape[0])
    och = PACKED_CHANNELS if w8.dtype == jnp.int32 else NUM_CHANNELS
    if interpret is None:
        interpret = _interpret_default()
    max_blocks = n // block_rows
    # grid 0 would leave the output unwritten; a 1-step grid with
    # n_blocks == 0 masks all compute and writes zeros (sref[1] == 0)
    grid_n = jnp.clip(n_blocks, 1, max_blocks).astype(jnp.int32)
    scalars = jnp.stack([start_block, n_blocks, target_leaf]).astype(
        jnp.int32)

    def im_data(i, s):
        return (0, jnp.minimum(s[0] + i, max_blocks - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid_n,),
        in_specs=[
            pl.BlockSpec((F, block_rows), im_data),
            pl.BlockSpec((CHW, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
        ],
        out_specs=pl.BlockSpec((F_log * num_bins, och),
                               lambda i, s: (0, 0)),
        scratch_shapes=[pltpu.VMEM((F_log * num_bins, och),
                                   jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_segment, num_bins=num_bins,
                          packed4=packed4, onehot_build=onehot_build),
        out_shape=jax.ShapeDtypeStruct((F_log * num_bins, och),
                                       jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(scalars, binsT, w8, leaf_id.reshape(1, -1))
    return out.reshape(F_log, num_bins, och)


def histogram_segment(binsT: jax.Array, w8: jax.Array, leaf_id: jax.Array,
                      start_block: jax.Array, n_blocks: jax.Array,
                      target_leaf: jax.Array, num_bins: int,
                      block_rows: int = 0,
                      interpret: bool | None = None,
                      packed4: bool = False) -> jax.Array:
    """Histogram of one leaf, scanning only its confinement blocks.

    ``leaf_id`` is [Npad] i32 row->leaf; rows outside the leaf (or padding,
    which must carry zero weights) contribute nothing.  DMA, compute AND
    grid length are proportional to ``n_blocks``, not N: the call
    dispatches to the smallest static-grid variant covering the interval
    (``_segment_buckets``).  Returns [F, B, 8] (logical features when
    ``packed4``).
    """
    F, n = binsT.shape
    if block_rows <= 0:
        block_rows = pick_block_rows(2 * F if packed4 else F, num_bins)
    assert n % block_rows == 0, (n, block_rows)
    max_blocks = n // block_rows
    ob = onehot_build_mode()
    if dyn_grid_enabled():
        return _histogram_segment_dyn(binsT, w8, leaf_id,
                                      jnp.asarray(start_block, jnp.int32),
                                      jnp.asarray(n_blocks, jnp.int32),
                                      target_leaf, num_bins, block_rows,
                                      interpret, packed4, ob)
    buckets = _segment_buckets(max_blocks)
    if len(buckets) == 1:
        return _histogram_segment_fixed(binsT, w8, leaf_id, start_block,
                                        n_blocks, target_leaf, num_bins,
                                        block_rows, buckets[0], interpret,
                                        packed4, ob)
    n_blocks = jnp.asarray(n_blocks, jnp.int32)
    idx = bucket_index(buckets, n_blocks)
    branches = [
        (lambda gb: lambda b, w, l, s0, nb, tl: _histogram_segment_fixed(
            b, w, l, s0, nb, tl, num_bins, block_rows, gb, interpret,
            packed4, ob))(gb)
        for gb in buckets
    ]
    return jax.lax.switch(idx, branches, binsT, w8, leaf_id, start_block,
                          n_blocks, target_leaf)


_FRONTIER_K = 16   # leaves per batched kernel call: 8 channels x 16 = 128


def frontier_width(num_features: int, num_bins: int) -> int:
    """Batched-frontier width K for this shape: 8*K output channels fill
    the 128-wide MXU tile at K=16; shrink K when the [F*B, 8K] f32
    accumulator would blow the VMEM budget (wide-bin datasets)."""
    F4 = -(-num_features // 4) * 4
    k = _FRONTIER_K
    while k > 1 and F4 * num_bins * NUM_CHANNELS * k * 4 > 6 * 1024 * 1024:
        k //= 2
    return k


def channel_set_capacity(num_features: int, num_bins: int,
                         block_rows: int = 0) -> int:
    """Max stacked 8-channel sets histogram_all can take for this shape
    before VMEM blows: bounds BOTH the [F*B, 8*C] f32 scratch and the
    double-buffered [8*C, block_rows] bf16 weight stream (pick_block_rows
    sized the block for 8 channels, so a wide stack would otherwise
    overrun on narrow-bin datasets with many classes).  Callers batching
    more sets (multiclass roots with large num_class) must chunk."""
    F4 = -(-num_features // 4) * 4
    if block_rows <= 0:
        block_rows = pick_block_rows(num_features, num_bins)
    per_set = (F4 * num_bins * NUM_CHANNELS * 4          # scratch
               + 2 * block_rows * NUM_CHANNELS * 2)      # streamed w8
    return max(1, (6 * 1024 * 1024) // max(per_set, 1))


def _kernel_frontier(sref, binsT_ref, w_ref, lid_ref, out_ref, acc_ref, *,
                     num_bins, K, packed4, onehot_build="iota"):
    """K-leaf batched histogram: one [F*B, 8K] accumulator, the one-hot
    matmul's output dim carries K leaves' channel sets — the structural
    fix for the 8-wide output that capped MXU utilization at ~6%
    (PERF_NOTES round 3): 8*K = 128 fills the MXU lane tile.

    sref layout: [2 + K + n_grid] i32 =
      (n_blocks, pad, targets[K], block_list[n_grid]) — ``block_list``
    holds the union of the K leaves' confinement blocks, so DMA is
    proportional to the union, not to N and not to K separate interval
    scans (siblings share blocks; after compaction the union is small).
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(i < sref[0])
    def _():
        def wfn(c, chunk):
            wc = w_ref[:, pl.ds(c * chunk, chunk)]          # [8, chunk]
            if w_ref.dtype == jnp.int32:
                wc = _packed_wrows(wc)   # packed stream -> [4, chunk]
            lc = lid_ref[:, pl.ds(c * chunk, chunk)]        # [1, chunk]
            # K is static, so the target loads unroll into K SCALAR reads
            # (Mosaic rejects vector loads from SMEM — sref[2:2+K] lowers
            # on the CPU interpreter but not on the chip) and the [8K,
            # chunk] weight block is a K-way concat of masked channels
            rows = []
            for k in range(K):
                mask = (lc == sref[2 + k]).astype(jnp.bfloat16)
                rows.append(mask * wc)                      # [8, chunk]
            return jnp.concatenate(rows, axis=0)            # [8K, chunk]

        _accumulate_block(binsT_ref, wfn, acc_ref, num_bins, packed4,
                          onehot_build)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_rows", "grid_blocks",
                                    "K", "interpret", "packed4",
                                    "onehot_build"))
def _histogram_frontier_fixed(binsT: jax.Array, w8: jax.Array,
                              leaf_id: jax.Array, block_list: jax.Array,
                              n_blocks: jax.Array, targets: jax.Array,
                              num_bins: int, block_rows: int,
                              grid_blocks: int, K: int,
                              interpret: bool | None = None,
                              packed4: bool = False,
                              onehot_build: str = "iota") -> jax.Array:
    F, n = binsT.shape
    F_log = 2 * F if packed4 else F
    CHW = int(w8.shape[0])
    och = PACKED_CHANNELS if w8.dtype == jnp.int32 else NUM_CHANNELS
    if interpret is None:
        interpret = _interpret_default()
    max_blocks = n // block_rows
    bl = jnp.pad(block_list.astype(jnp.int32),
                 (0, max(0, grid_blocks - block_list.shape[0])))[:grid_blocks]
    scalars = jnp.concatenate([
        jnp.stack([n_blocks.astype(jnp.int32), jnp.int32(0)]),
        targets.astype(jnp.int32), bl])

    def im_data(i, s):
        # out-of-range grid steps re-read the last in-range block (no new
        # DMA); pl.when skips their compute
        idx = jnp.minimum(i, jnp.maximum(s[0] - 1, 0))
        return (0, jnp.minimum(s[2 + K + idx], max_blocks - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid_blocks,),
        in_specs=[
            pl.BlockSpec((F, block_rows), im_data),
            pl.BlockSpec((CHW, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
        ],
        out_specs=pl.BlockSpec((F_log * num_bins, K * och),
                               lambda i, s: (0, 0)),
        scratch_shapes=[pltpu.VMEM((F_log * num_bins, K * och),
                                   jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_frontier, num_bins=num_bins, K=K,
                          packed4=packed4, onehot_build=onehot_build),
        out_shape=jax.ShapeDtypeStruct((F_log * num_bins, K * och),
                                       jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(scalars, binsT, w8, leaf_id.reshape(1, -1))
    # [F*B, K*8] -> [K, F, B, 8]
    return out.reshape(F_log, num_bins, K, och).transpose(
        2, 0, 1, 3)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_rows", "K",
                                    "interpret", "packed4", "onehot_build"))
def _histogram_frontier_dyn(binsT: jax.Array, w8: jax.Array,
                            leaf_id: jax.Array, block_list: jax.Array,
                            n_blocks: jax.Array, targets: jax.Array,
                            num_bins: int, block_rows: int, K: int,
                            interpret: bool | None = None,
                            packed4: bool = False,
                            onehot_build: str = "iota") -> jax.Array:
    """Dynamic-grid frontier variant: grid == union size, one compile."""
    F, n = binsT.shape
    F_log = 2 * F if packed4 else F
    CHW = int(w8.shape[0])
    och = PACKED_CHANNELS if w8.dtype == jnp.int32 else NUM_CHANNELS
    if interpret is None:
        interpret = _interpret_default()
    max_blocks = n // block_rows
    grid_n = jnp.clip(n_blocks, 1, max_blocks).astype(jnp.int32)
    bl = block_list.astype(jnp.int32)[:max_blocks]
    scalars = jnp.concatenate([
        jnp.stack([n_blocks.astype(jnp.int32), jnp.int32(0)]),
        targets.astype(jnp.int32), bl])

    def im_data(i, s):
        idx = jnp.minimum(i, jnp.maximum(s[0] - 1, 0))
        return (0, jnp.minimum(s[2 + K + idx], max_blocks - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid_n,),
        in_specs=[
            pl.BlockSpec((F, block_rows), im_data),
            pl.BlockSpec((CHW, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
        ],
        out_specs=pl.BlockSpec((F_log * num_bins, K * och),
                               lambda i, s: (0, 0)),
        scratch_shapes=[pltpu.VMEM((F_log * num_bins, K * och),
                                   jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_frontier, num_bins=num_bins, K=K,
                          packed4=packed4, onehot_build=onehot_build),
        out_shape=jax.ShapeDtypeStruct((F_log * num_bins, K * och),
                                       jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(scalars, binsT, w8, leaf_id.reshape(1, -1))
    return out.reshape(F_log, num_bins, K, och).transpose(
        2, 0, 1, 3)


def histogram_frontier(binsT: jax.Array, w8: jax.Array, leaf_id: jax.Array,
                       block_list: jax.Array, n_blocks: jax.Array,
                       targets: jax.Array, num_bins: int,
                       block_rows: int = 0,
                       interpret: bool | None = None,
                       packed4: bool = False) -> jax.Array:
    """Histograms of K frontier leaves in ONE kernel pass.

    ``block_list`` [M] i32 lists the row blocks to scan (union of the K
    leaves' confinement intervals; entries past ``n_blocks`` are ignored);
    ``targets`` [K] i32 are the leaf ids (-1 entries produce zero
    histograms — masks never match, since real leaf ids are >= 0).
    Returns [K, F, B, 8] (logical features when ``packed4``).
    """
    F, n = binsT.shape
    K = int(targets.shape[0])
    if block_rows <= 0:
        block_rows = pick_block_rows(2 * F if packed4 else F, num_bins)
    assert n % block_rows == 0, (n, block_rows)
    max_blocks = n // block_rows
    ob = onehot_build_mode()
    if dyn_grid_enabled():
        return _histogram_frontier_dyn(binsT, w8, leaf_id, block_list,
                                       jnp.asarray(n_blocks, jnp.int32),
                                       targets, num_bins, block_rows, K,
                                       interpret, packed4, ob)
    cap = min(int(block_list.shape[0]), max_blocks)
    buckets = _segment_buckets(cap)
    n_blocks = jnp.asarray(n_blocks, jnp.int32)
    if len(buckets) == 1:
        return _histogram_frontier_fixed(
            binsT, w8, leaf_id, block_list, n_blocks, targets, num_bins,
            block_rows, buckets[0], K, interpret, packed4, ob)
    idx = jnp.sum(jnp.asarray(buckets, jnp.int32) < n_blocks)
    branches = [
        (lambda gb: lambda b, w, l, bl, nb, tg: _histogram_frontier_fixed(
            b, w, l, bl, nb, tg, num_bins, block_rows, gb, K, interpret,
            packed4, ob))(gb)
        for gb in buckets
    ]
    return jax.lax.switch(idx, branches, binsT, w8, leaf_id, block_list,
                          n_blocks, targets)


# ---------------------------------------------------------------------------
# Fused route + histogram (PERF_NOTES "Designed, not yet built", landed r5).
#
# The windowed route (grower_seg.route_split_windowed) runs as separate XLA
# slice/where/update passes over the SAME blocks the smaller-child histogram
# kernel DMAs anyway.  These kernels fold the split routing into the
# histogram pass: per block, update the leaf_id VMEM block with the split's
# route, THEN accumulate the target leaf's histogram from the UPDATED ids.
# The split feature's bin row is pre-sliced host-side into its own [1, n]
# (frontier: [K, n]) operand: dynamic sublane indexing of the u8 block is
# not safely supported on Mosaic, and a row-selecting index map over the
# [F, n] array needs an (F-misaligned) [1, rb] block that Mosaic rejects
# (sublane dim must be 8-divisible or whole) — the slice is one row of HBM
# traffic per call, noise next to the pass itself.  leaf_id is
# an aliased input/output: blocks outside the interval are never written and
# keep their values; the route update is idempotent (rows moved to new_leaf
# stop matching leaf), so out-of-range grid-step remapping to the last
# in-range block stays correct even when a revisited block re-reads
# post-write data.  Reference analog: routing rides the partition work the
# histogram already pays for (src/treelearner/data_partition.hpp:111).
# ---------------------------------------------------------------------------

_ROUTE_WORDS = 19  # leaf,new_leaf,row,col,thr,dl,cat,mt,dbin,nbf,off + 8 bitset
_MISSING_ZERO = 1  # core/binning.py:24-26 (kept literal: kernels must not
_MISSING_NAN = 2   # import the host-side binning module)


def pack_route(leaf, new_leaf, f, t, dl, cat, bitset, fmeta,
               packed4: bool) -> jax.Array:
    """[_ROUTE_WORDS] i32 route descriptor for the fused kernels.

    ``f`` is the LOGICAL feature; the descriptor carries the physical
    bin row, the group column (for the packed4 nibble parity) and the
    EFB reconstruction scalars so the kernel can reproduce
    reconstruct_feature_column + routed_left exactly."""
    f = jnp.asarray(f, jnp.int32)
    col = (fmeta.feat_group[f] if fmeta.feat_group is not None else f)
    row = col // 2 if packed4 else col
    off = (fmeta.feat_offset[f] if fmeta.feat_group is not None
           else jnp.int32(0))
    head = jnp.stack([
        jnp.asarray(leaf, jnp.int32), jnp.asarray(new_leaf, jnp.int32),
        row, col, jnp.asarray(t, jnp.int32),
        jnp.asarray(dl, jnp.int32), jnp.asarray(cat, jnp.int32),
        fmeta.missing_type[f], fmeta.default_bin[f], fmeta.num_bin[f],
        off]).astype(jnp.int32)
    return jnp.concatenate([head, lax.bitcast_convert_type(
        jnp.asarray(bitset, jnp.uint32), jnp.int32)])


def null_route() -> jax.Array:
    """Route that matches nothing (leaf == -1): the root-histogram case."""
    return (jnp.zeros(_ROUTE_WORDS, jnp.int32).at[0].set(-1))


def _route_go_left(word, g, packed4: bool):
    """0/1 i32 "goes left" of the bin values ``g`` under the route whose
    word ``j`` is ``word(j)`` (``pack_route``'s layout): an SMEM scalar
    for the split at hand ([1, rb] ``g``), or a [K, chunk] tile of the
    lookahead slots' operand ([K, chunk] ``g``, one slot a sublane).

    All mask logic is i32 0/1 arithmetic and every select predicate is
    a single fresh compare: Mosaic materializes composed bool vectors
    (scalar-bool broadcasts, i1 & / ~ chains) through i8 and then fails
    to compile the i8->i1 trunci ("Unsupported target bitwidth for
    truncation", v5e)."""
    if packed4:
        par = word(3) % 2                               # 0/1 i32
        g = par * (g >> 4) + (1 - par) * (g & 15)
    thr, dl = word(4), word(5)                          # dl: 0/1 i32
    cat, mt = word(6), word(7)                          # cat: 0/1 i32
    dbin, nbf, off = word(8), word(9), word(10)
    in_range = ((g >= off).astype(jnp.int32)
                * (g < off + nbf).astype(jnp.int32))
    fcol = jnp.where(in_range == 1, g - off, dbin)
    miss_z = ((mt == _MISSING_ZERO).astype(jnp.int32)
              * (fcol == dbin).astype(jnp.int32))
    miss_n = ((mt == _MISSING_NAN).astype(jnp.int32)
              * (fcol == nbf - 1).astype(jnp.int32))
    is_missing = jnp.minimum(miss_z + miss_n, 1)
    num_left = (is_missing * dl
                + (1 - is_missing) * (fcol <= thr).astype(jnp.int32))
    idx = jnp.clip(fcol, 0, 255)
    # cat bitset membership: 8 unrolled word selects (no vector SMEM loads)
    bits = jnp.zeros_like(g)
    for k in range(8):
        bits = jnp.where(idx // 32 == k, word(11 + k), bits)
    cat_left = (bits >> (idx % 32)) & 1
    return cat * cat_left + (1 - cat) * num_left


def _route_block_ids(sref, o: int, frow, lid, packed4: bool):
    """[1, rb] updated leaf ids from the route descriptor at scalar
    offset ``o`` (all sref reads are static-offset SMEM scalars);
    ``frow`` is the split feature's [1, rb] bin-row block (a value)."""
    go_left = _route_go_left(lambda j: sref[o + j],
                             frow.astype(jnp.int32), packed4)
    take = (lid == sref[o]).astype(jnp.int32) * (1 - go_left)
    return jnp.where(take == 1, sref[o + 1], lid)


def _kernel_segment_routed(sref, binsT_ref, w_ref, frow_ref, lid_ref,
                           lid_out_ref, out_ref, acc_ref, *,
                           num_bins, packed4, onehot_build="iota",
                           block_axis=0):
    # sref: [3 + _ROUTE_WORDS] = (start_block, n_blocks, target_leaf, route)
    # block_axis 1: grid (feature tiles, blocks), one tile's columns in
    # binsT_ref / acc_ref / out_ref, every tile walking the same blocks
    i = pl.program_id(block_axis)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # 1) route this block — unconditional: skipped steps revisit an
    # in-range block and the update is idempotent (so is a later tile's:
    # it finds the block routed, or routes the ids it prefetched before
    # the write landed, to the same values)
    lid_out_ref[...] = _route_block_ids(sref, 3, frow_ref[...],
                                        lid_ref[...], packed4)

    # 2) accumulate the target's histogram from the UPDATED ids
    @pl.when(i < sref[1])
    def _():
        def wfn(c, chunk):
            wc = w_ref[:, pl.ds(c * chunk, chunk)]
            if w_ref.dtype == jnp.int32:
                wc = _packed_wrows(wc)
            lc = lid_out_ref[:, pl.ds(c * chunk, chunk)]
            return wc * (lc == sref[2]).astype(jnp.bfloat16)

        _accumulate_block(binsT_ref, wfn, acc_ref, num_bins, packed4,
                          onehot_build)

    @pl.when(i == pl.num_programs(block_axis) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def _tile_index_maps(max_blocks: int):
    """Index maps of a (feature tiles, blocks) grid over the interval the
    scalars open with: a per-row stream's block, which every tile reads
    again, and the tile's own block of the bin table."""
    def im_row(t, i, s):
        return (0, jnp.minimum(s[0] + i, max_blocks - 1))

    def im_tile(t, i, s):
        return (t, jnp.minimum(s[0] + i, max_blocks - 1))

    return im_row, im_tile


def _tile_rows(binsT: jax.Array, num_bins: int, packed4: bool,
               feature_tile_cols: int | None) -> int:
    """Physical bin rows a tile of the routed segment kernels holds, or 0
    where the pass takes the table whole (today's program).
    ``feature_tile_cols`` (logical columns) overrides ``feature_tile``'s
    arithmetic: how the tests force several tiles at a small shape."""
    F = binsT.shape[0]
    F_log = 2 * F if packed4 else F
    cols = feature_tile_cols or feature_tile(F_log, num_bins)
    if cols >= F_log:
        return 0
    rows = cols // 2 if packed4 else cols
    assert rows > 0 and F % rows == 0, (
        f"a pass in feature tiles of {rows} bin rows needs the table "
        f"padded to whole tiles, got {F} rows")
    return rows


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_rows", "interpret",
                                    "packed4", "onehot_build", "tile_rows"))
def _histogram_segment_routed(binsT: jax.Array, w8: jax.Array,
                              leaf_id: jax.Array, start_block: jax.Array,
                              n_blocks: jax.Array, target_leaf: jax.Array,
                              route: jax.Array, num_bins: int,
                              block_rows: int = 0,
                              interpret: bool | None = None,
                              packed4: bool = False,
                              onehot_build: str = "iota",
                              tile_rows: int = 0):
    F, n = binsT.shape
    F_log = 2 * F if packed4 else F
    CHW = int(w8.shape[0])
    och = PACKED_CHANNELS if w8.dtype == jnp.int32 else NUM_CHANNELS
    if block_rows <= 0:
        block_rows = pick_block_rows(F_log, num_bins)
    assert n % block_rows == 0, (n, block_rows)
    if interpret is None:
        interpret = _interpret_default()
    if tile_rows:
        return _histogram_segment_routed_tiled(
            binsT, w8, leaf_id, start_block, n_blocks, target_leaf, route,
            num_bins, block_rows, interpret, packed4, onehot_build,
            tile_rows)
    max_blocks = n // block_rows
    grid_n = jnp.clip(n_blocks, 1, max_blocks).astype(jnp.int32)
    scalars = jnp.concatenate([
        jnp.stack([start_block, n_blocks, target_leaf]).astype(jnp.int32),
        route.astype(jnp.int32)])
    # split feature's physical bin row (route[2]), as its own [1, n] operand
    frow = lax.dynamic_slice(binsT, (route[2].astype(jnp.int32), 0), (1, n))

    def im_data(i, s):
        return (0, jnp.minimum(s[0] + i, max_blocks - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid_n,),
        in_specs=[
            pl.BlockSpec((F, block_rows), im_data),
            pl.BlockSpec((CHW, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
        ],
        out_specs=[
            pl.BlockSpec((1, block_rows), im_data),
            pl.BlockSpec((F_log * num_bins, och),
                         lambda i, s: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((F_log * num_bins, och),
                                   jnp.float32)],
    )
    lid_out, hist = pl.pallas_call(
        functools.partial(_kernel_segment_routed, num_bins=num_bins,
                          packed4=packed4, onehot_build=onehot_build),
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32),
                   jax.ShapeDtypeStruct((F_log * num_bins, och),
                                        jnp.float32)],
        grid_spec=grid_spec,
        # alias indices include the scalar operand: input 4 is leaf_id
        input_output_aliases={4: 0},
        # the extra frow/lid streams push the double-buffered working
        # set past Mosaic's 16 MB default scoped-vmem limit at
        # production shapes (measured 17.14 MB, v5e) — auto-sized from
        # the computed need instead of a hand-set override
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=fused_vmem_limit(F, num_bins, 1, block_rows,
                                              packed4)),
        interpret=interpret,
        # the name a profiler trace shows (PERF.md, the ledger's
        # breakdown): pinned, so renaming this function cannot move it
        name="_histogram_segment_routed",
    )(scalars, binsT, w8, frow, leaf_id.reshape(1, -1))
    return lid_out[0], hist.reshape(F_log, num_bins, och)


def _histogram_segment_routed_tiled(binsT, w8, leaf_id, start_block,
                                    n_blocks, target_leaf, route, num_bins,
                                    block_rows, interpret, packed4,
                                    onehot_build, tile_rows):
    """``_histogram_segment_routed`` over a table too wide for one
    accumulator: grid (feature tiles, blocks).  Each tile accumulates its
    ``tile_rows`` bin rows over the whole interval into its own
    accumulator and writes its slab of the histogram at its last block;
    the channel, id and split-row streams are read again by every tile
    (25 bytes a row beside the tile's bin rows) and every tile routes the
    block it holds, which is idempotent.  Per (tile, block) the matmuls
    are the untiled kernel's over those columns, in the same chunk order:
    the sums are the untiled kernel's bit for bit."""
    F, n = binsT.shape
    T = tile_rows
    n_tiles = F // T
    F_log = 2 * F if packed4 else F
    T_log = 2 * T if packed4 else T
    CHW = int(w8.shape[0])
    och = PACKED_CHANNELS if w8.dtype == jnp.int32 else NUM_CHANNELS
    max_blocks = n // block_rows
    grid_n = jnp.clip(n_blocks, 1, max_blocks).astype(jnp.int32)
    scalars = jnp.concatenate([
        jnp.stack([start_block, n_blocks, target_leaf]).astype(jnp.int32),
        route.astype(jnp.int32)])
    frow = lax.dynamic_slice(binsT, (route[2].astype(jnp.int32), 0), (1, n))

    im_row, im_tile = _tile_index_maps(max_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles, grid_n),
        in_specs=[
            pl.BlockSpec((T, block_rows), im_tile),
            pl.BlockSpec((CHW, block_rows), im_row),
            pl.BlockSpec((1, block_rows), im_row),
            pl.BlockSpec((1, block_rows), im_row),
        ],
        out_specs=[
            pl.BlockSpec((1, block_rows), im_row),
            pl.BlockSpec((T_log * num_bins, och), lambda t, i, s: (t, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((T_log * num_bins, och), jnp.float32)],
    )
    with jax.named_scope("tile_walk"):
        lid_out, hist = pl.pallas_call(
            functools.partial(_kernel_segment_routed, num_bins=num_bins,
                              packed4=packed4, onehot_build=onehot_build,
                              block_axis=1),
            out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32),
                       jax.ShapeDtypeStruct((F_log * num_bins, och),
                                            jnp.float32)],
            grid_spec=grid_spec,
            input_output_aliases={4: 0},
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=fused_vmem_limit(F, num_bins, 1, block_rows,
                                                  packed4)),
            interpret=interpret,
            # the routed segment pass, tile by tile: the trace keeps its name
            name="_histogram_segment_routed",
        )(scalars, binsT, w8, frow, leaf_id.reshape(1, -1))
    return lid_out[0], hist.reshape(F_log, num_bins, och)


def histogram_segment_routed(binsT: jax.Array, w8: jax.Array,
                             leaf_id: jax.Array, start_block: jax.Array,
                             n_blocks: jax.Array, target_leaf: jax.Array,
                             route: jax.Array, num_bins: int,
                             block_rows: int = 0,
                             interpret: bool | None = None,
                             packed4: bool = False,
                             feature_tile_cols: int | None = None):
    """Apply one split's route to ``leaf_id`` AND histogram ``target_leaf``
    in a single pass over the confinement interval.

    ``route`` is a [_ROUTE_WORDS] i32 descriptor (pack_route /
    null_route).  Returns ``(leaf_id', [F, B, 8] hist)`` where the ids
    are post-route over the whole array (blocks outside the interval
    keep their values via input/output aliasing); a [2, Npad] i32
    ``w8`` runs the packed-accumulator stream ([F, B, 4] output).
    Dynamic-grid only — callers needing the bucket ladder use the
    unfused pair.  A table wider than one accumulator holds
    (``feature_tile``) is walked tile by tile, its bin rows padded to
    whole tiles by the caller.
    """
    return _histogram_segment_routed(
        binsT, w8, leaf_id, start_block, n_blocks, target_leaf, route,
        num_bins, block_rows, interpret, packed4, onehot_build_mode(),
        _tile_rows(binsT, num_bins, packed4, feature_tile_cols))


# ---------------------------------------------------------------------------
# Lookahead lane sets (PERF.md, PR 27).  The routed segment pass contracts a
# [F*B, chunk] one-hot against [8, chunk] channels, and the MXU pads those 8
# output lanes to 128: the pass costs the same whether it fills 1 lane set
# or 16.  This variant spends the other lane sets on the smaller children
# that PENDING best splits of other leaves inside the scanned interval will
# need (grower_seg.lookahead_split), so that a later split of such a leaf
# only routes.  Each lane set is an independent column block of the same
# matmuls in the same chunk order, so lane set k holds bit for bit what lane
# set 0 of a pass over the same blocks would hold for that membership.
# ---------------------------------------------------------------------------

# chunks a loop body of the lookahead kernel (``_accumulate_block``):
# 3.31 ns a row at 1, 3.02 at 4 (16 lane sets; PERF.md, PR 27)
_LOOKAHEAD_UNROLL = 4
# the K memberships of a chunk are one [K, chunk] i32 tile: 8 rows are one
# vreg high, 16 are two.  The second eight cost 0.24 ns a row scanned and
# found a pending leaf to fill on few passes (PERF.md, PR 27: 0.562 of the
# splits served at 16 lane sets against 0.545 at 8), so a pass fills 8.
_LOOKAHEAD_SETS = 8


def lookahead_width(F_log: int, num_bins: int, block_rows: int,
                    packed4: bool) -> int:
    """Lane sets one routed segment pass fills at this shape: the split at
    hand plus the lookahead slots.  ``frontier_width`` says how many
    8-channel sets the accumulator holds (16 fill the 128 lanes), of
    which ``_LOOKAHEAD_SETS`` are used; 1 where only one fits or the
    wider kernel's working set does not (``fused_route_fits``): the
    callers then keep today's kernel.  All of it is reckoned for one
    feature tile, which is what a pass holds at a time."""
    K = min(frontier_width(feature_tile(F_log, num_bins), num_bins),
            _LOOKAHEAD_SETS)
    F_phys = (F_log + 1) // 2 if packed4 else F_log
    if K > 1 and not fused_route_fits(F_phys, num_bins, 1, block_rows,
                                      packed4, targets_k=K):
        return 1
    return K


def pack_lookahead_slots(leaves, smaller_is_left, f, t, dl, cat, bitset,
                         fmeta, packed4: bool) -> jax.Array:
    """[K-1, _ROUTE_WORDS] i32 lookahead-slot descriptors for
    ``histogram_segment_lookahead``: ``pack_route``'s layout, one row a
    pending leaf (-1: empty slot, matches no row), with the smaller side
    (1 = left) where a route carries the new leaf's id.  All arguments
    are [K-1] vectors ([K-1, 8] ``bitset``)."""
    return jax.vmap(
        lambda *a: pack_route(*a, fmeta, packed4))(
            leaves, jnp.asarray(smaller_is_left, jnp.int32), f, t, dl, cat,
            bitset)


def empty_lookahead_slots(k: int) -> jax.Array:
    """[k, _ROUTE_WORDS] slots that match no row (a pass that fills no
    lane set but its own: the root's, a reference's)."""
    return jnp.tile(null_route()[None], (k, 1))


def _slot_rows_of_block(slots_ref, KP: int, bins_i32):
    """[KP, chunk] i32: each slot's split-feature bin row (word 2 of the
    slot), picked out of ``bins_i32`` ([F_phys, chunk]: the bin block the
    pass already holds in VMEM), never from a second stream out of HBM."""
    rows = slots_ref[KP * 2:KP * 3, :]
    g = jnp.zeros(rows.shape, jnp.int32)
    for r in range(bins_i32.shape[0]):
        g = jnp.where(rows == r, bins_i32[r:r + 1], g)
    return g


def _lookahead_masks(slots_ref, KP: int, g, lc, first, packed4: bool):
    """[KP, chunk] 0/1 i32 memberships of one chunk, one slot a sublane.

    Row 0 is ``first`` ([1, chunk]: the split at hand, from the routed
    ids).  Row k >= 1 is a lookahead slot: the rows of pending leaf X
    (``lc == X``, ids AFTER this pass's route) that X's cached best split
    sends to its smaller child, decided by the arithmetic that routes
    (``_route_go_left``).  ``slots_ref`` ([_ROUTE_WORDS * KP, chunk] i32
    in VMEM, fetched once a call) holds word j of slot k at row KP j + k,
    already spread along the lanes, so the KP evaluations are one
    sublane-dense tile and not KP serial [1, chunk] updates.  ``g``
    ([KP, chunk] i32) holds the slots' split-feature bin rows
    (``_slot_rows_of_block``, or the tiled pass's own operand)."""
    shape = (KP, lc.shape[1])

    def word(j):
        return slots_ref[KP * j:KP * (j + 1), :]

    go_left = _route_go_left(word, g, packed4)
    member = ((lc == word(0)).astype(jnp.int32)
              * (go_left == word(1)).astype(jnp.int32))
    slot = lax.broadcasted_iota(jnp.int32, shape, 0)
    return jnp.where(slot == 0, first, member)


def _kernel_segment_lookahead(sref, binsT_ref, w_ref, frow_ref, lid_ref,
                              slots_ref, *rest, num_bins, K, packed4,
                              onehot_build="iota", block_axis=0):
    # sref: [4 + _ROUTE_WORDS] = (start_block, n_blocks, target_leaf,
    #   route, n_acc): every block of the interval is routed, the first
    #   n_acc of them accumulate (n_blocks, or 0 for a pass that only
    #   routes: the caller already holds the histogram)
    # block_axis 1: grid (feature tiles, blocks) as _kernel_segment_routed
    #   walks it; the slots' split-feature rows, which may lie in another
    #   tile's columns, then come as an operand of their own (srows_ref,
    #   [KP, rb] u8)
    srows_ref = None
    if block_axis:
        srows_ref, *rest = rest
    lid_out_ref, out_ref, acc_ref, hi_ref, lo_ref = rest
    i = pl.program_id(block_axis)
    KP = slots_ref.shape[0] // _ROUTE_WORDS

    @pl.when(i == 0)
    def _():
        hi_ref[:] = jnp.zeros_like(hi_ref)
        lo_ref[:] = jnp.zeros_like(lo_ref)

    # 1) route this block (as _kernel_segment_routed)
    lid_out_ref[...] = _route_block_ids(sref, 3, frow_ref[...],
                                        lid_ref[...], packed4)

    # 2) accumulate the K lane sets from the UPDATED ids
    @pl.when(i < sref[3 + _ROUTE_WORDS])
    def _():
        def wfn(c, chunk):
            wc = w_ref[:, pl.ds(c * chunk, chunk)]          # [8, chunk]
            lc = lid_out_ref[:, pl.ds(c * chunk, chunk)]    # [1, chunk]
            if srows_ref is None:
                g = _slot_rows_of_block(
                    slots_ref, KP,
                    binsT_ref[:, pl.ds(c * chunk, chunk)].astype(jnp.int32))
            else:
                g = srows_ref[:, pl.ds(c * chunk, chunk)].astype(jnp.int32)
            masks = _lookahead_masks(
                slots_ref, KP, g, lc, (lc == sref[2]).astype(jnp.int32),
                packed4)
            # [8K, chunk]: K masked copies of the channels, as
            # _kernel_frontier builds them
            return jnp.concatenate(
                [(masks[k:k + 1] == 1).astype(jnp.bfloat16) * wc
                 for k in range(K)], axis=0)

        # the block's own sum first, then into the running (hi, lo) pair
        # with an error-free addition: a lookahead lane set is summed over
        # the whole interval the pass covers, its rows a few to a block,
        # where the scan it stands in for would have found them packed
        # after a compaction.  Adding every such sliver straight into one
        # f32 total rounds once a row; (hi, lo) rounds once a block's
        # chunks and carries the rest.  A block outside the leaf adds an
        # exact zero: s == hi, err == 0.
        acc_ref[:] = jnp.zeros_like(acc_ref)
        _accumulate_block(binsT_ref, wfn, acc_ref, num_bins, packed4,
                          onehot_build, unroll=_LOOKAHEAD_UNROLL)
        p = acc_ref[:]
        h = hi_ref[:]
        s = h + p
        bb = s - h
        lo_ref[:] += (h - (s - bb)) + (p - bb)
        hi_ref[:] = s

    @pl.when(i == pl.num_programs(block_axis) - 1)
    def _():
        out_ref[:] = hi_ref[:] + lo_ref[:]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_rows", "interpret",
                                    "packed4", "onehot_build", "tile_rows"))
def _histogram_segment_lookahead(binsT: jax.Array, w8: jax.Array,
                                 leaf_id: jax.Array, start_block: jax.Array,
                                 n_blocks: jax.Array, target_leaf: jax.Array,
                                 route: jax.Array, slots: jax.Array,
                                 n_acc: jax.Array, num_bins: int,
                                 block_rows: int = 0,
                                 interpret: bool | None = None,
                                 packed4: bool = False,
                                 onehot_build: str = "iota",
                                 tile_rows: int = 0):
    F, n = binsT.shape
    F_log = 2 * F if packed4 else F
    assert w8.dtype != jnp.int32, "lookahead lane sets ride the f32 stream"
    och = NUM_CHANNELS
    K = 1 + int(slots.shape[0])
    assert K * och <= _LANES, K
    if block_rows <= 0:
        block_rows = pick_block_rows(F_log, num_bins)
    assert n % block_rows == 0, (n, block_rows)
    if interpret is None:
        interpret = _interpret_default()
    max_blocks = n // block_rows
    grid_n = jnp.clip(n_blocks, 1, max_blocks).astype(jnp.int32)
    scalars = jnp.concatenate([
        jnp.stack([start_block, n_blocks, target_leaf]).astype(jnp.int32),
        route.astype(jnp.int32),
        jnp.asarray(n_acc, jnp.int32).reshape(1)])
    frow = lax.dynamic_slice(binsT, (route[2].astype(jnp.int32), 0), (1, n))
    # word j of slot k at row KP j + k, spread along one chunk's lanes
    # (_lookahead_masks); slot 0 (the split at hand, which the scalars
    # describe) and the rows that pad K to whole sublane groups match
    # nothing.  622 KB at K = 16 and 512 lanes, fetched once.
    KP = -(-K // 8) * 8
    chunk = _pick_chunk(block_rows)
    words = jnp.concatenate([
        null_route()[None], slots.astype(jnp.int32),
        empty_lookahead_slots(KP - K)]).T                   # [words, KP]
    slots_op = jnp.broadcast_to(
        words[:, :, None], (_ROUTE_WORDS, KP, chunk)).reshape(
            _ROUTE_WORDS * KP, chunk)

    if tile_rows:
        # grid (feature tiles, blocks), as _histogram_segment_routed_tiled
        # walks it, plus the slots' split-feature rows as a [KP, n] operand
        # (row 0 for slot 0 and the padding, which match no row)
        T = tile_rows
        T_log = 2 * T if packed4 else T
        # (one dynamic slice a slot, as ``frow`` is taken: a gather of
        # rows costs a temporary half the table's size on the chip)
        srows = jnp.concatenate(
            [lax.dynamic_slice(binsT, (r, 0), (1, n))
             for r in [0] + list(slots[:, 2].astype(jnp.int32))
             + [0] * (KP - K)])

        im_row, im_tile = _tile_index_maps(max_blocks)
        tile_shape = (T_log * num_bins, K * och)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(F // T, grid_n),
            in_specs=[
                pl.BlockSpec((T, block_rows), im_tile),
                pl.BlockSpec((NUM_CHANNELS, block_rows), im_row),
                pl.BlockSpec((1, block_rows), im_row),
                pl.BlockSpec((1, block_rows), im_row),
                pl.BlockSpec((_ROUTE_WORDS * KP, chunk),
                             lambda t, i, s: (0, 0)),
                pl.BlockSpec((KP, block_rows), im_row),
            ],
            out_specs=[
                pl.BlockSpec((1, block_rows), im_row),
                pl.BlockSpec(tile_shape, lambda t, i, s: (t, 0)),
            ],
            scratch_shapes=[pltpu.VMEM(tile_shape, jnp.float32)] * 3,
        )
        with jax.named_scope("tile_walk"):
            lid_out, hist = pl.pallas_call(
                functools.partial(_kernel_segment_lookahead,
                                  num_bins=num_bins, K=K, packed4=packed4,
                                  onehot_build=onehot_build, block_axis=1),
                out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32),
                           jax.ShapeDtypeStruct((F_log * num_bins, K * och),
                                                jnp.float32)],
                grid_spec=grid_spec,
                input_output_aliases={4: 0},
                compiler_params=pltpu.CompilerParams(
                    vmem_limit_bytes=fused_vmem_limit(
                        F, num_bins, 1, block_rows, packed4, targets_k=K)),
                interpret=interpret,
                name="_histogram_segment_routed",
            )(scalars, binsT, w8, frow, leaf_id.reshape(1, -1), slots_op,
              srows)
        return lid_out[0], hist.reshape(F_log, num_bins, K, och).transpose(
            2, 0, 1, 3)

    def im_data(i, s):
        return (0, jnp.minimum(s[0] + i, max_blocks - 1))

    acc_shape = (F_log * num_bins, K * och)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid_n,),
        in_specs=[
            pl.BlockSpec((F, block_rows), im_data),
            pl.BlockSpec((NUM_CHANNELS, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
            pl.BlockSpec((_ROUTE_WORDS * KP, chunk), lambda i, s: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_rows), im_data),
            pl.BlockSpec(acc_shape, lambda i, s: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM(acc_shape, jnp.float32)] * 3,
    )
    lid_out, hist = pl.pallas_call(
        functools.partial(_kernel_segment_lookahead, num_bins=num_bins,
                          K=K, packed4=packed4, onehot_build=onehot_build),
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32),
                   jax.ShapeDtypeStruct(acc_shape, jnp.float32)],
        grid_spec=grid_spec,
        # alias indices include the scalar operand: input 4 is leaf_id
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=fused_vmem_limit(F, num_bins, 1, block_rows,
                                              packed4, targets_k=K)),
        interpret=interpret,
        # the routed segment pass, widened: the trace keeps its name
        name="_histogram_segment_routed",
    )(scalars, binsT, w8, frow, leaf_id.reshape(1, -1), slots_op)
    # [F*B, K*8] -> [K, F, B, 8]
    return lid_out[0], hist.reshape(F_log, num_bins, K, och).transpose(
        2, 0, 1, 3)


def histogram_segment_lookahead(binsT: jax.Array, w8: jax.Array,
                                leaf_id: jax.Array, start_block: jax.Array,
                                n_blocks: jax.Array, target_leaf: jax.Array,
                                route: jax.Array, slots: jax.Array,
                                n_acc: jax.Array, num_bins: int,
                                block_rows: int = 0,
                                interpret: bool | None = None,
                                packed4: bool = False,
                                feature_tile_cols: int | None = None):
    """``histogram_segment_routed`` with K = 1 + len(slots) lane sets:
    returns ``(leaf_id', [K, F, B, 8])``.

    Entry 0 is the target's histogram from the routed ids, as the routed
    kernel gives it.  Entry k >= 1 is the histogram of ``slots[k-1]``
    ([_ROUTE_WORDS] i32, ``pack_lookahead_slots``): the rows of a pending
    leaf that its cached best split sends to its smaller child, summed
    over the same interval with nothing written for them.  The caller
    sees to it that such a leaf's rows lie wholly inside the interval.
    ``n_acc`` is how many of the interval's blocks accumulate:
    ``n_blocks``, or 0 for a call that only routes and returns zeros.
    Sums are f32 a block and an error-free (hi, lo) pair across blocks
    (``_kernel_segment_lookahead``), so lane set 0 agrees with the routed
    kernel to f32 rounding, not bit for bit; f32 channel stream only.
    Wide tables go tile by tile as in ``histogram_segment_routed``, each
    tile keeping its own (block sum, hi, lo) triple.
    """
    return _histogram_segment_lookahead(
        binsT, w8, leaf_id, jnp.asarray(start_block, jnp.int32),
        jnp.asarray(n_blocks, jnp.int32), target_leaf, route, slots, n_acc,
        num_bins, block_rows, interpret, packed4, onehot_build_mode(),
        _tile_rows(binsT, num_bins, packed4, feature_tile_cols))


def _kernel_frontier_routed(sref, binsT_ref, w_ref, frows_ref, lid_ref,
                            lid_out_ref, out_ref, acc_ref, *, num_bins, K,
                            packed4, onehot_build="iota", n_targets=0):
    # frows_ref: [K, rb] — the K split features' bin-row blocks
    # sref: [2 + KT + K*_ROUTE_WORDS + n_grid] =
    #   (n_blocks, pad, targets[KT], routes[K*19], block_list[n_grid])
    # KT (n_targets) decouples the histogram width from the route count:
    # the round-pass fusion histograms the K smaller children (KT == K),
    # the fused-K kernel histograms ALL 2K children of the K routes
    # (KT == 2K) so no parent gather / subtraction survives the round
    KT = n_targets or K
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # 1) K route updates — leaves are disjoint and new ids exceed every
    # routed leaf, so at most one route matches a row and application
    # order is irrelevant; invalid slots carry leaf == -1
    lid = lid_ref[...]
    frows = frows_ref[...]
    for k in range(K):
        lid = _route_block_ids(sref, 2 + KT + k * _ROUTE_WORDS,
                               frows[k:k + 1], lid, packed4)
    lid_out_ref[...] = lid

    # 2) batched accumulate of the KT targets from the UPDATED ids
    @pl.when(i < sref[0])
    def _():
        def wfn(c, chunk):
            wc = w_ref[:, pl.ds(c * chunk, chunk)]
            if w_ref.dtype == jnp.int32:
                wc = _packed_wrows(wc)
            lc = lid_out_ref[:, pl.ds(c * chunk, chunk)]
            rows = []
            for k in range(KT):
                mask = (lc == sref[2 + k]).astype(jnp.bfloat16)
                rows.append(mask * wc)
            return jnp.concatenate(rows, axis=0)

        _accumulate_block(binsT_ref, wfn, acc_ref, num_bins, packed4,
                          onehot_build)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_rows", "K",
                                    "interpret", "packed4", "onehot_build",
                                    "n_targets"))
def _histogram_frontier_routed(binsT: jax.Array, w8: jax.Array,
                               leaf_id: jax.Array, block_list: jax.Array,
                               n_blocks: jax.Array, targets: jax.Array,
                               routes: jax.Array, num_bins: int,
                               block_rows: int = 0, K: int = 0,
                               interpret: bool | None = None,
                               packed4: bool = False,
                               onehot_build: str = "iota",
                               n_targets: int = 0):
    F, n = binsT.shape
    K = K or int(routes.shape[0])
    KT = n_targets or K
    assert int(targets.shape[0]) == KT, (targets.shape, KT)
    F_log = 2 * F if packed4 else F
    CHW = int(w8.shape[0])
    och = PACKED_CHANNELS if w8.dtype == jnp.int32 else NUM_CHANNELS
    if block_rows <= 0:
        block_rows = pick_block_rows(F_log, num_bins)
    assert n % block_rows == 0, (n, block_rows)
    if interpret is None:
        interpret = _interpret_default()
    max_blocks = n // block_rows
    grid_n = jnp.clip(n_blocks, 1, max_blocks).astype(jnp.int32)
    bl = block_list.astype(jnp.int32)[:max_blocks]
    scalars = jnp.concatenate([
        jnp.stack([n_blocks.astype(jnp.int32), jnp.int32(0)]),
        targets.astype(jnp.int32), routes.astype(jnp.int32).reshape(-1),
        bl])
    blk_base = 2 + KT + K * _ROUTE_WORDS
    # the K split features' physical bin rows (routes[:, 2]), pre-sliced
    # into one [K, n] operand (whole-sublane block: Mosaic-legal)
    frows = jnp.take(binsT, routes[:, 2].astype(jnp.int32), axis=0,
                     mode="clip")

    def im_data(i, s):
        idx = jnp.minimum(i, jnp.maximum(s[0] - 1, 0))
        return (0, jnp.minimum(s[blk_base + idx], max_blocks - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid_n,),
        in_specs=[
            pl.BlockSpec((F, block_rows), im_data),
            pl.BlockSpec((CHW, block_rows), im_data),
            pl.BlockSpec((K, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
        ],
        out_specs=[
            pl.BlockSpec((1, block_rows), im_data),
            pl.BlockSpec((F_log * num_bins, KT * och),
                         lambda i, s: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((F_log * num_bins, KT * och),
                                   jnp.float32)],
    )
    lid_out, hist = pl.pallas_call(
        functools.partial(_kernel_frontier_routed, num_bins=num_bins, K=K,
                          packed4=packed4, onehot_build=onehot_build,
                          n_targets=KT),
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32),
                   jax.ShapeDtypeStruct((F_log * num_bins,
                                         KT * och), jnp.float32)],
        grid_spec=grid_spec,
        # inputs: scalars, binsT, w8, frows, leaf_id
        input_output_aliases={4: 0},
        # see _histogram_segment_routed: the K frow rows + lid streams
        # exceed the 16 MB default scoped-vmem limit at K=16 production
        # shapes — auto-sized from the computed need (the fused-K call
        # carries a KT == 2K wide accumulator, so the limit follows KT)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=fused_vmem_limit(F, num_bins, K, block_rows,
                                              packed4, targets_k=KT)),
        interpret=interpret,
        name="_histogram_frontier_routed",     # as the trace shows it
    )(scalars, binsT, w8, frows, leaf_id.reshape(1, -1))
    return lid_out[0], hist.reshape(F_log, num_bins, KT,
                                    och).transpose(2, 0, 1, 3)


def histogram_frontier_routed(binsT: jax.Array, w8: jax.Array,
                              leaf_id: jax.Array, block_list: jax.Array,
                              n_blocks: jax.Array, targets: jax.Array,
                              routes: jax.Array, num_bins: int,
                              block_rows: int = 0, K: int = 0,
                              interpret: bool | None = None,
                              packed4: bool = False):
    """Frontier variant: apply K splits' routes and histogram the K
    target leaves in one pass over the union block list.

    ``routes`` is [K, _ROUTE_WORDS] i32 (invalid slots: null_route()).
    The K split features' bin rows are pre-sliced into one [K, n]
    operand (see the fused-route header comment).  Returns
    ``(leaf_id', [K, F, B, 8])`` ([K, F, B, 4] for a packed i32 ``w8``).
    """
    return _histogram_frontier_routed(binsT, w8, leaf_id, block_list,
                                      n_blocks, targets, routes, num_bins,
                                      block_rows, K, interpret, packed4,
                                      onehot_build_mode())


def histogram_frontier_fusedk(binsT: jax.Array, w8: jax.Array,
                              leaf_id: jax.Array, block_list: jax.Array,
                              n_blocks: jax.Array, targets2: jax.Array,
                              routes: jax.Array, num_bins: int,
                              block_rows: int = 0, K: int = 0,
                              interpret: bool | None = None,
                              packed4: bool = False):
    """Frontier-K fusion: apply the round's K routes AND histogram all
    2K children in ONE pass over the union block list.

    ``routes`` is [K, _ROUTE_WORDS] i32 (invalid slots: null_route());
    ``targets2`` is [2K] i32 = (left children = the K routed parents,
    which keep their leaf id, then right children = the K new leaves),
    -1 skipping a slot.  Returns ``(leaf_id', [2K, F, B, 8])``
    ([2K, F, B, 4] for a packed i32 ``w8``), child order matching
    ``targets2`` — so the round needs NO parent histogram: both
    children come straight off the data pass and the subtraction trick
    plus both ``[L, G, B, 3]`` leaf_hist staging copies disappear.
    Bit-identical to the unfused pair (route, then
    ``histogram_frontier`` over the same 2K targets): the accumulator
    columns per channel set are independent dot products of the same
    one-hot blocks in the same chunk order.  Dynamic-grid only, like
    every fused variant.
    """
    K = K or int(routes.shape[0])
    assert int(targets2.shape[0]) == 2 * K, (targets2.shape, K)
    return _histogram_frontier_routed(binsT, w8, leaf_id, block_list,
                                      n_blocks, targets2, routes, num_bins,
                                      block_rows, K, interpret, packed4,
                                      onehot_build_mode(), n_targets=2 * K)


_FUSED_VMEM_CAP = 64 * 1024 * 1024  # ceiling for the auto-sized limit


@functools.lru_cache(maxsize=None)
def _fused_vmem_est_cached(F_phys: int, num_bins: int, K: int, KT: int,
                           block_rows: int, packed4: bool,
                           lane_padded: bool = False) -> int:
    F_log = 2 * F_phys if packed4 else F_phys
    streams = block_rows * (F_phys + K + 2 * NUM_CHANNELS + 8)
    # a feature tile is sized by its accumulator as VMEM holds it, the
    # channel lanes padded to 128 (``supported``)
    width = max(KT * NUM_CHANNELS, _LANES) if lane_padded else (
        KT * NUM_CHANNELS)
    out = F_log * num_bins * width * 4
    return 2 * (3 * streams + 3 * out)


def _fused_vmem_est(F_phys: int, num_bins: int, K: int = 1,
                    block_rows: int = 0, packed4: bool = False,
                    targets_k: int | None = None) -> int:
    """Scoped-VMEM working-set estimate (bytes) for the fused kernels.

    DELIBERATELY conservative: ~2x the plain double-buffered sum,
    calibrated so the measured K=16/F=28/rb=32768 case lands near its
    real 17.14 MB (v5e).  Shared by the ``fused_route_fits`` veto and
    the ``fused_vmem_limit`` auto-sizing so the two can never drift.
    ``targets_k`` widens the accumulator term independently of the
    route count (the fused-K kernel carries 2K channel sets over K
    routes); default = K, the round-pass fusion.  Memoized per
    (K, KT, F, row_block) shape — policy + dispatch consult it on
    every grower build and the shape set per process is tiny."""
    F_log = 2 * F_phys if packed4 else F_phys
    if block_rows <= 0:
        block_rows = pick_block_rows(F_log, num_bins)
    # a pass holds one feature tile's rows at a time (all of them where
    # the table is taken whole)
    tile = feature_tile(F_log, num_bins)
    tiled = tile < F_log
    if tiled:
        F_phys = tile // 2 if packed4 else tile
    return _fused_vmem_est_cached(F_phys, num_bins, K, targets_k or K,
                                  block_rows, bool(packed4), tiled)


def fused_vmem_limit(F_phys: int, num_bins: int, K: int = 1,
                     block_rows: int = 0, packed4: bool = False,
                     targets_k: int | None = None) -> int:
    """Auto-sized ``vmem_limit_bytes`` for the fused kernels: 2x the
    conservative working-set estimate, MB-rounded, clamped to
    [16 MB, 64 MB] — the derived replacement for the former hand-set
    64 MB override (the K=16/F=28 case gets ~34 MB; small shapes keep
    Mosaic's 16 MB default).  Recorded as the ``hist/vmem_limit_bytes``
    gauge at dispatch so traces show what the compiler was given."""
    mb = 1024 * 1024
    est = 2 * _fused_vmem_est(F_phys, num_bins, K, block_rows, packed4,
                              targets_k)
    limit = int(min(max(-(-est // mb) * mb, 16 * mb), _FUSED_VMEM_CAP))
    try:
        from ..utils.telemetry import TELEMETRY
        TELEMETRY.gauge_set("hist/vmem_limit_bytes", limit)
    except Exception:
        pass
    return limit


def fused_route_fits(F_phys: int, num_bins: int, K: int = 1,
                     block_rows: int = 0, packed4: bool = False,
                     targets_k: int | None = None) -> bool:
    """Whether the fused kernels' scoped-VMEM working set fits at this
    shape.  The small-shape self-check can't see production-shape OOMs
    (measured: K=16, F=28, rb=32768 needs 17.14 MB against Mosaic's
    16 MB default), so the auto policy consults this conservative
    estimate against the auto-limit ceiling; LIGHTGBM_TPU_FUSED_ROUTE=1
    / LIGHTGBM_TPU_FUSED_K=force bypass it for A/Bs on shapes it
    vetoes."""
    est = _fused_vmem_est(F_phys, num_bins, K, block_rows, packed4,
                          targets_k)
    return est <= int(0.9 * _FUSED_VMEM_CAP)


# build-time decisions, keyed "segment"/"frontier" — benches read this to
# report the kernel that actually ran (the env gate + fits veto make the
# bare self-check result misleading).  Values: False, True (K-target
# round-pass fusion) or the string "fusedk" (2K-children fused-K kernel).
fused_route_decisions: dict = {}


def fused_packed_optin() -> bool:
    """``LIGHTGBM_TPU_FUSED_PACKED=1``: allow the fused route+histogram
    kernels to ride the packed int16-accumulator stream.  Default OFF —
    the growers force the unfused pair whenever packed_acc is on so the
    on-chip A/B isolates one variant at a time (docs/KERNELS.md); this
    opt-in makes the combined variant reachable for its own A/B instead
    of structurally excluded."""
    import os
    return (os.environ.get("LIGHTGBM_TPU_FUSED_PACKED", "").lower()
            in ("1", "on", "true", "force"))


def fused_k_mode() -> str:
    """Raw ``LIGHTGBM_TPU_FUSED_K`` ladder: '' (off, the default) |
    'on' (self-check gated) | 'force' ('force' or a trailing '!'
    bypasses the check for on-chip A/B plumbing)."""
    import os
    env = os.environ.get("LIGHTGBM_TPU_FUSED_K", "").lower()
    if env in ("", "0", "off", "false"):
        return ""
    if env == "force" or env.endswith("!"):
        return "force"
    return "on"


def fused_k_enabled() -> bool:
    """Whether the frontier grower may use the fused-K kernel
    (``histogram_frontier_fusedk``): route + ALL-2K-children histogram
    in one pass, no parent gather / subtraction.

    Default OFF — no variant flips to default without a v5e number
    (the expected win — the route passes' ~0.07-0.2 s/iter plus one of
    the two 0.17 s/iter leaf_hist staging copies — lands in PERF_NOTES
    round 7 first).  ``1/on`` runs the one-shot bit-identity self-check
    vs the unfused pair on the live backend, memoized, with clean
    fallback; ``force``/trailing '!' bypasses.  Dynamic-grid only,
    like every fused variant."""
    global _FUSED_K_CHECK
    mode = fused_k_mode()
    if not mode:
        return False
    if not dyn_grid_enabled():
        return False
    if mode == "force":
        return True
    if _FUSED_K_CHECK is None:
        _FUSED_K_CHECK = gate_self_check("fused-K", _fused_k_self_check)
    return _FUSED_K_CHECK


def _fused_k_fallback(reason: str) -> None:
    """Requested-but-vetoed fused-K build: count it so A/B drivers can
    tell a measured off leg from a silently fallen-back force leg."""
    import sys
    try:
        from ..utils.telemetry import TELEMETRY
        TELEMETRY.counter_add("hist/fused_k_fallbacks", 1)
    except Exception:
        pass
    sys.stderr.write(f"fused-K requested but fell back: {reason}\n")


def fused_route_policy(K: int, F_log: int, num_bins: int,
                       block_rows: int, packed4: bool) -> str:
    """The growers' single dispatch policy for the fused route+histogram
    kernels.  Returns a tier: "off" | "k1" (K-target round-pass fusion,
    the kernel the unfused pair's targets match) | "fusedk" (2K-children
    fused-K kernel, frontier K > 1).

    LIGHTGBM_TPU_FUSED_K (off by default) owns the K > 1 tier: 'on'
    self-checks + consults the vmem fit at the 2K-wide carry, 'force'
    bypasses both, and a requested-but-vetoed build counts a
    ``hist/fused_k_fallbacks`` event before falling through to the
    LIGHTGBM_TPU_FUSED_ROUTE handling below.

    LIGHTGBM_TPU_FUSED_ROUTE keeps its meaning: =1 -> the K-target
    fusion wherever the kernels lower (bypasses the K policy and the
    vmem fit veto, for A/Bs); =0 -> off.  Auto: K == 1 only — on-chip
    (v5e, 2026-08-01) the K=16 K-target fusion measured 1.43 s/iter vs
    1.02-1.04 unfused at the HIGGS shape (K serial in-block route
    updates plus K frow streams cost more than the ONE union-pass
    windowed route they replace, and the subtraction still ran) while
    the K=1 segment fusion won 1.28 vs 1.43 — plus the self-check and
    the vmem fit estimate.  The fused-K tier is the re-cut that also
    deletes the parent gather + subtraction; its verdict slot is
    PERF_NOTES round 7."""
    import os
    F_phys = (F_log + 1) // 2 if packed4 else F_log
    if K > 1 and fused_k_mode():
        if not fused_k_enabled():
            _fused_k_fallback("self-check failed or dyn-grid off")
        elif (fused_k_mode() == "force"
              or fused_route_fits(F_phys, num_bins, K, block_rows,
                                  packed4, targets_k=2 * K)):
            return "fusedk"
        else:
            _fused_k_fallback("2K-wide carry fails the vmem fit veto")
    env = os.environ.get("LIGHTGBM_TPU_FUSED_ROUTE", "auto").lower()
    if env in ("0", "off", "false"):
        return "off"
    if env in ("1", "on", "true"):
        return "k1" if fused_route_available() else "off"
    if K > 1:
        return "off"
    return ("k1" if (fused_route_available()
                     and fused_route_fits(F_phys, num_bins, K, block_rows,
                                          packed4))
            else "off")


def _kernel_route_window(sref, frow_ref, lid_ref, lid_out_ref, *, packed4):
    # sref: [2 + _ROUTE_WORDS] = (start_block, n_blocks, route)
    lid_out_ref[...] = _route_block_ids(sref, 2, frow_ref[...],
                                        lid_ref[...], packed4)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "packed4"))
def route_window(binsT: jax.Array, leaf_id: jax.Array,
                 start_block: jax.Array, n_blocks: jax.Array,
                 route: jax.Array, block_rows: int,
                 interpret: bool | None = None,
                 packed4: bool = False) -> jax.Array:
    """Apply one split's route to ``leaf_id`` over the parent's block
    window, writing ONLY those blocks through an aliased input/output.

    The XLA windowed route (grower_seg.route_split_windowed) confines
    the READ side but its bucket lax.switch still materializes a fresh
    full-N leaf_id every call — the v5e trace shows 254 s32[10.5M]
    conditional copies per iteration ≈ 0.18 s/iter at the HIGGS shape.
    Here blocks outside the window are never touched (same aliasing
    contract as histogram_segment_routed).  Dynamic-grid only."""
    F, n = binsT.shape
    if interpret is None:
        interpret = _interpret_default()
    max_blocks = n // block_rows
    grid_n = jnp.clip(n_blocks, 1, max_blocks).astype(jnp.int32)
    scalars = jnp.concatenate([
        jnp.stack([start_block, n_blocks]).astype(jnp.int32),
        route.astype(jnp.int32)])
    frow = lax.dynamic_slice(binsT, (route[2].astype(jnp.int32), 0), (1, n))

    def im(i, s):
        return (0, jnp.minimum(s[0] + i, max_blocks - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid_n,),
        in_specs=[pl.BlockSpec((1, block_rows), im),
                  pl.BlockSpec((1, block_rows), im)],
        out_specs=pl.BlockSpec((1, block_rows), im),
    )
    lid_out = pl.pallas_call(
        functools.partial(_kernel_route_window, packed4=packed4),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        grid_spec=grid_spec,
        # operands: scalars, frow, leaf_id — leaf_id aliases the output
        input_output_aliases={2: 0},
        interpret=interpret,
        name="route_window",                   # as the trace shows it
    )(scalars, frow, leaf_id.reshape(1, -1))
    return lid_out[0]


_ROUTE_KERNEL_CHECK: bool | None = None


def route_kernel_available() -> bool:
    """Whether the growers should route through the aliased pallas
    window kernel instead of the XLA switch path.  =0/1 forces; auto
    runs a one-shot on-device parity check (numeric + categorical +
    missing + out-of-window retention) against the XLA route.  Needs
    the dynamic-grid dispatch."""
    global _ROUTE_KERNEL_CHECK
    import os
    env = os.environ.get("LIGHTGBM_TPU_ROUTE_KERNEL", "auto").lower()
    if env in ("0", "off", "false"):
        return False
    if not dyn_grid_enabled():
        return False
    if env in ("1", "on", "true"):
        return True
    # auto engages only on a real accelerator: the kernel exists to
    # avoid a TPU conditional copy; on the CPU interpret path it's one
    # interpreted pallas call per split, a pure slowdown
    if jax.default_backend() == "cpu":
        return False
    if _ROUTE_KERNEL_CHECK is None:
        _ROUTE_KERNEL_CHECK = gate_self_check("route-kernel",
                                              _route_kernel_self_check)
    return _ROUTE_KERNEL_CHECK


def _route_kernel_self_check() -> bool:
    """Tiny multi-block parity run of route_window against a NumPy
    re-derivation (numeric fwd/bwd-missing, categorical bitset,
    untouched blocks outside the window)."""
    import numpy as np
    rng = np.random.default_rng(11)
    F, B, rb, nblk = 4, 16, 512, 6
    n = rb * nblk
    binsT = jnp.asarray(rng.integers(0, B, (F, n)), jnp.uint8)
    lid = np.full(n, 7, np.int32)
    lid[rb:4 * rb] = np.where(rng.random(3 * rb) < 0.5, 3, 5)
    lid = jnp.asarray(lid)
    bitset = jnp.asarray(rng.integers(0, 2**32, 8, dtype=np.uint64)
                         .astype(np.uint32))

    class _M:
        feat_group = None
        feat_offset = None
        missing_type = jnp.asarray([1, 2, 2, 0], jnp.int32)
        default_bin = jnp.asarray([3, 0, 0, 0], jnp.int32)
        num_bin = jnp.full((4,), B, jnp.int32)

    # f=2 exercises the numeric MISSING_NAN branch (bin B-1 routed by
    # default_left, here False); the categorical case ignores missing
    for f, cat, dl in ((0, False, True), (1, True, True),
                       (2, False, False)):
        route = pack_route(3, 9, f, B // 2, dl, cat, bitset, _M, False)
        lid2 = route_window(binsT, lid, jnp.int32(1), jnp.int32(3),
                            route, rb)
        fcol = np.asarray(binsT[f]).astype(np.int64)
        mt = int(_M.missing_type[f])
        miss = ((mt == 1) & (fcol == int(_M.default_bin[f]))
                | (mt == 2) & (fcol == B - 1))
        if cat:
            w = np.asarray(bitset)[np.clip(fcol, 0, 255) // 32]
            go_left = (w >> (np.clip(fcol, 0, 255) % 32)) & 1 > 0
        else:
            go_left = np.where(miss, dl, fcol <= B // 2)
        exp = np.asarray(lid).copy()
        win = np.zeros(n, bool)
        win[rb:4 * rb] = True
        exp[(exp == 3) & ~go_left & win] = 9
        if not np.array_equal(np.asarray(lid2), exp):
            return False
    # packed4: the in-kernel route must unpack the split column by
    # nibble parity (both parities), on 4-bit bins
    bins4 = jnp.asarray(rng.integers(0, 15, (F, n)), jnp.uint8)
    packedT = jnp.asarray(pack_bins_4bit(bins4))

    class _M4(_M):
        num_bin = jnp.full((4,), 15, jnp.int32)
        missing_type = jnp.zeros(4, jnp.int32)
        default_bin = jnp.zeros(4, jnp.int32)

    for f in (1, 2):   # odd = high nibble, even = low
        route = pack_route(3, 9, f, 7, False, False,
                           jnp.zeros(8, jnp.uint32), _M4, True)
        lid4 = route_window(packedT, lid, jnp.int32(1), jnp.int32(3),
                            route, rb, packed4=True)
        fcol = np.asarray(bins4[f]).astype(np.int64)
        exp4 = np.asarray(lid).copy()
        win = np.zeros(n, bool)
        win[rb:4 * rb] = True
        exp4[(exp4 == 3) & (fcol > 7) & win] = 9
        if not np.array_equal(np.asarray(lid4), exp4):
            return False
    return True


_FUSED_ROUTE_CHECK: bool | None = None


def fused_route_available() -> bool:
    """Whether the growers should use the fused route+histogram kernels.

    ``LIGHTGBM_TPU_FUSED_ROUTE=0/1`` forces; default ("auto") runs a
    one-shot self-check on the live backend — the kernels must lower
    AND reproduce the separate route+histogram pair exactly, including
    untouched-block retention through the input/output alias.  Requires
    the dynamic-grid dispatch (the bucket ladder keeps the unfused
    pair).
    """
    global _FUSED_ROUTE_CHECK
    import os
    env = os.environ.get("LIGHTGBM_TPU_FUSED_ROUTE", "auto").lower()
    if env in ("0", "off", "false"):
        return False
    if not dyn_grid_enabled():
        return False
    if env in ("1", "on", "true"):
        return True
    if _FUSED_ROUTE_CHECK is None:
        _FUSED_ROUTE_CHECK = gate_self_check("fused-route",
                                             _fused_route_self_check)
    return _FUSED_ROUTE_CHECK


def _fused_route_self_check() -> bool:
    """Tiny multi-block parity run of the fused kernels vs the unfused
    pair on the real backend (numerical + categorical + missing routes,
    out-of-window retention)."""
    import numpy as np
    rng = np.random.default_rng(7)

    def _fail(leg):
        import sys
        sys.stderr.write(f"fused-route self-check FAILED leg: {leg}\n")
        return False

    # blocks of 4 chunks, so the lookahead kernel's unrolled loop runs
    F, B, rb, nblk = 4, 16, _LOOKAHEAD_UNROLL * CHUNK, 6
    n = rb * nblk
    binsT = jnp.asarray(rng.integers(0, B, (F, n)), jnp.uint8)
    grad = jnp.asarray(rng.standard_normal(n), jnp.float32)
    hess = jnp.asarray(rng.uniform(0.5, 1.5, n), jnp.float32)
    member = jnp.ones(n, jnp.float32)
    w8 = pack_channels(grad, hess, member)
    # two leaves confined to blocks [1, 4); leaf 7 elsewhere
    lid = np.full(n, 7, np.int32)
    lid[rb:4 * rb] = np.where(rng.random(3 * rb) < 0.5, 3, 5)
    lid = jnp.asarray(lid)
    bitset = jnp.asarray(rng.integers(0, 2**32, 8, dtype=np.uint64)
                         .astype(np.uint32))

    class _M:  # minimal FeatureMeta-alike for pack_route
        feat_group = None
        feat_offset = None
        missing_type = jnp.asarray([1, 2, 2, 0], jnp.int32)
        default_bin = jnp.asarray([3, 0, 0, 0], jnp.int32)
        num_bin = jnp.full((4,), B, jnp.int32)

    # f=2 exercises the numeric MISSING_NAN branch (bin B-1 routed by
    # default_left, here False); the categorical case ignores missing
    for f, cat, dl in ((0, False, True), (1, True, True),
                       (2, False, False)):
        route = pack_route(3, 9, f, B // 2, dl, cat, bitset, _M, False)
        lid2, hist = histogram_segment_routed(
            binsT, w8, lid, jnp.int32(1), jnp.int32(3), jnp.int32(9),
            route, B, rb)
        # reference: separate route + segment histogram
        fcol = np.asarray(binsT[f]).astype(np.int64)
        mt = int(_M.missing_type[f])
        miss = ((mt == 1) & (fcol == int(_M.default_bin[f]))
                | (mt == 2) & (fcol == B - 1))
        if cat:
            w = np.asarray(bitset)[np.clip(fcol, 0, 255) // 32]
            go_left = (w >> (np.clip(fcol, 0, 255) % 32)) & 1 > 0
        else:
            go_left = np.where(miss, dl, fcol <= B // 2)
        exp = np.asarray(lid).copy()
        win = np.zeros(n, bool)
        win[rb:4 * rb] = True
        exp[(exp == 3) & ~go_left & win] = 9
        if not np.array_equal(np.asarray(lid2), exp):
            return _fail(f"segment lid (cat={cat})")
        ref = histogram_segment(binsT, w8, jnp.asarray(exp), jnp.int32(1),
                                jnp.int32(3), jnp.int32(9), B, rb)
        if not np.allclose(np.asarray(hist), np.asarray(ref), atol=1e-5):
            return _fail(f"segment hist (cat={cat})")
    # packed4: the in-kernel route must unpack the split column by
    # nibble parity (both parities), on 4-bit bins
    bins4 = jnp.asarray(rng.integers(0, 15, (F, n)), jnp.uint8)
    packedT = jnp.asarray(pack_bins_4bit(bins4))

    class _M4(_M):
        num_bin = jnp.full((4,), 15, jnp.int32)
        missing_type = jnp.zeros(4, jnp.int32)
        default_bin = jnp.zeros(4, jnp.int32)

    for f in (1, 2):   # odd = high nibble, even = low
        route = pack_route(3, 9, f, 7, False, False,
                           jnp.zeros(8, jnp.uint32), _M4, True)
        lid4, hist4 = histogram_segment_routed(
            packedT, w8, lid, jnp.int32(1), jnp.int32(3), jnp.int32(9),
            route, 16, rb, packed4=True)
        fcol = np.asarray(bins4[f]).astype(np.int64)
        exp4 = np.asarray(lid).copy()
        win = np.zeros(n, bool)
        win[rb:4 * rb] = True
        exp4[(exp4 == 3) & (fcol > 7) & win] = 9
        if not np.array_equal(np.asarray(lid4), exp4):
            return _fail(f"packed4 lid (f={f})")
        ref4 = histogram_segment(packedT, w8, jnp.asarray(exp4),
                                 jnp.int32(1), jnp.int32(3), jnp.int32(9),
                                 16, rb, packed4=True)
        if not np.allclose(np.asarray(hist4), np.asarray(ref4),
                           atol=1e-5):
            return _fail(f"packed4 hist (f={f})")

    # EFB: group column carries feature at offset; out-of-range bins
    # reconstruct to the feature default
    class _ME(_M):
        feat_group = jnp.asarray([0, 0, 1, 1], jnp.int32)
        feat_offset = jnp.asarray([0, 6, 0, 6], jnp.int32)
        num_bin = jnp.full((4,), 6, jnp.int32)
        missing_type = jnp.zeros(4, jnp.int32)
        default_bin = jnp.zeros(4, jnp.int32)

    route = pack_route(3, 9, 1, 2, False, False, jnp.zeros(8, jnp.uint32),
                       _ME, False)  # feature 1 -> col 0, offset 6
    lid5, _h5 = histogram_segment_routed(
        binsT, w8, lid, jnp.int32(1), jnp.int32(3), jnp.int32(9), route,
        B, rb)
    g = np.asarray(binsT[0]).astype(np.int64)
    fcol = np.where((g >= 6) & (g < 12), g - 6, 0)
    exp5 = np.asarray(lid).copy()
    win = np.zeros(n, bool)
    win[rb:4 * rb] = True
    exp5[(exp5 == 3) & (fcol > 2) & win] = 9
    if not np.array_equal(np.asarray(lid5), exp5):
        return _fail("efb lid")

    # lookahead lane sets: the split at hand (leaf 3 -> 9) plus slots for
    # leaf 5 under a NaN-missing numeric split (smaller side right) and
    # under a categorical one (left), for the rows this very pass moves to
    # leaf 9 (slots read the UPDATED ids), and empty slots.  Lane set 0
    # must be the routed kernel's to f32 rounding (sums are kept as a
    # pair across blocks), every other one bit for bit lane set 0 of a
    # pass over that slot's rows, and a route-only call (n_acc 0) must
    # route the same and return zeros.
    route = pack_route(3, 9, 0, B // 2, True, False, bitset, _M, False)
    lid1, h1 = histogram_segment_routed(
        binsT, w8, lid, jnp.int32(1), jnp.int32(3), jnp.int32(9), route,
        B, rb)
    live = ((5, 0, 2, B // 2, False, False), (5, 1, 1, 0, True, True),
            (9, 1, 3, 5, False, False))
    KL = 8
    pad = KL - 1 - len(live)
    cols = list(zip(*live))
    slots = pack_lookahead_slots(
        jnp.asarray(cols[0] + (-1,) * pad, jnp.int32),
        jnp.asarray(cols[1] + (0,) * pad, jnp.int32),
        jnp.asarray(cols[2] + (0,) * pad, jnp.int32),
        jnp.asarray(cols[3] + (0,) * pad, jnp.int32),
        jnp.asarray(cols[4] + (False,) * pad),
        jnp.asarray(cols[5] + (False,) * pad),
        jnp.tile(bitset[None], (KL - 1, 1)), _M, False)
    lidk, hk = histogram_segment_lookahead(
        binsT, w8, lid, jnp.int32(1), jnp.int32(3), jnp.int32(9), route,
        slots, jnp.int32(3), B, rb)
    if not (np.array_equal(np.asarray(lidk), np.asarray(lid1))
            and np.allclose(np.asarray(hk[0]), np.asarray(h1), atol=1e-5)):
        return _fail("lookahead lane set 0")
    from ..models.grower import routed_left
    empty = empty_lookahead_slots(KL - 1)
    for k, (leaf, side, f, t, dl, cat) in enumerate(live, start=1):
        go = routed_left(binsT[f].astype(jnp.int32), t, dl, cat, bitset,
                         _M.missing_type[f], _M.default_bin[f],
                         _M.num_bin[f])
        member = (lid1 == leaf) & (go == bool(side))
        _, ref = histogram_segment_lookahead(
            binsT, w8, jnp.where(member, 999, lid1), jnp.int32(1),
            jnp.int32(3), jnp.int32(999), null_route(), empty,
            jnp.int32(3), B, rb)
        if not (np.asarray(ref[0]).any()
                and np.array_equal(np.asarray(hk[k]), np.asarray(ref[0]))):
            return _fail(f"lookahead lane set {k}")
    if np.asarray(hk[1 + len(live):]).any():
        return _fail("lookahead empty slots")
    lid0, h0 = histogram_segment_lookahead(
        binsT, w8, lid, jnp.int32(1), jnp.int32(3), jnp.int32(9), route,
        slots, jnp.int32(0), B, rb)
    if (not np.array_equal(np.asarray(lid0), np.asarray(lid1))
            or np.asarray(h0).any()):
        return _fail("lookahead route-only")

    # frontier: one real route + one null slot
    K = 2
    routes = jnp.stack([pack_route(5, 10, 2, 4, False, False,
                                   jnp.zeros(8, jnp.uint32), _M, False),
                        null_route()])
    targets = jnp.asarray([10, -1], jnp.int32)
    # union = leaf 5's confinement blocks [1, 4)
    bl = jnp.asarray([1, 2, 3, 0, 0, 0], jnp.int32)
    lid3, hist3 = histogram_frontier_routed(
        binsT, w8, lid, bl, jnp.int32(3), targets, routes, B, rb, K)
    fcol = np.asarray(binsT[2]).astype(np.int64)
    exp3 = np.asarray(lid).copy()
    exp3[(exp3 == 5) & (fcol > 4)] = 10
    if not np.array_equal(np.asarray(lid3), exp3):
        return _fail("frontier lid")
    ref3 = histogram_frontier(binsT, w8, jnp.asarray(exp3), bl,
                              jnp.int32(3), targets, B, rb)
    if not np.allclose(np.asarray(hist3[0]), np.asarray(ref3[0]),
                       atol=1e-5):
        return _fail("frontier hist")

    # frontier + packed4: K routes over nibble-packed rows (both
    # parities — frows are picked as col//2 and sliced per k in-kernel)
    routes4 = jnp.stack([pack_route(3, 9, 1, 7, False, False,
                                    jnp.zeros(8, jnp.uint32), _M4, True),
                         pack_route(5, 10, 2, 7, False, False,
                                    jnp.zeros(8, jnp.uint32), _M4, True)])
    lid6, hist6 = histogram_frontier_routed(
        packedT, w8, lid, bl, jnp.int32(3), jnp.asarray([9, 10], jnp.int32),
        routes4, 16, rb, 2, packed4=True)
    f1 = np.asarray(bins4[1]).astype(np.int64)
    f2 = np.asarray(bins4[2]).astype(np.int64)
    exp6 = np.asarray(lid).copy()
    exp6[(exp6 == 3) & (f1 > 7)] = 9
    exp6[(exp6 == 5) & (f2 > 7)] = 10
    if not np.array_equal(np.asarray(lid6), exp6):
        return _fail("frontier packed4 lid")
    ref6 = histogram_frontier(packedT, w8, jnp.asarray(exp6), bl,
                              jnp.int32(3), jnp.asarray([9, 10], jnp.int32),
                              16, rb, packed4=True)
    if not np.allclose(np.asarray(hist6), np.asarray(ref6), atol=1e-5):
        return _fail("frontier packed4 hist")
    return True


_FUSED_K_CHECK: bool | None = None


def _fused_k_self_check() -> bool:
    """Bit-identity of the fused-K kernel (route + ALL 2K children in
    one pass) vs the unfused pair: numpy-route the ids, then
    ``histogram_frontier`` over the SAME 2K targets.  Exact equality is
    the contract — both kernels concat the same masked channel sets
    into the same one-hot matmul in the same chunk order, so every
    accumulator column is the identical f32 dot product.  Legs:
    numeric zero-missing / NaN-missing / categorical-bitset routes,
    packed4 nibble rows (both parities), EFB group reconstruction."""
    import numpy as np
    rng = np.random.default_rng(11)

    def _fail(leg):
        import sys
        sys.stderr.write(f"fused-K self-check FAILED leg: {leg}\n")
        return False

    F, B, rb, nblk = 4, 16, 512, 6
    n = rb * nblk
    binsT = jnp.asarray(rng.integers(0, B, (F, n)), jnp.uint8)
    grad = jnp.asarray(rng.standard_normal(n), jnp.float32)
    hess = jnp.asarray(rng.uniform(0.5, 1.5, n), jnp.float32)
    w8 = pack_channels(grad, hess, jnp.ones(n, jnp.float32))
    # two leaves confined to blocks [1, 4); leaf 7 elsewhere
    lid_np = np.full(n, 7, np.int32)
    lid_np[rb:4 * rb] = np.where(rng.random(3 * rb) < 0.5, 3, 5)
    lid = jnp.asarray(lid_np)
    bitset = jnp.asarray(rng.integers(0, 2**32, 8, dtype=np.uint64)
                         .astype(np.uint32))
    bl = jnp.asarray([1, 2, 3, 0, 0, 0], jnp.int32)
    nb = jnp.int32(3)

    class _M:  # minimal FeatureMeta-alike for pack_route
        feat_group = None
        feat_offset = None
        missing_type = jnp.asarray([1, 2, 2, 0], jnp.int32)
        default_bin = jnp.asarray([3, 0, 0, 0], jnp.int32)
        num_bin = jnp.full((4,), B, jnp.int32)

    def _np_go_left(f, thr, dl, cat):
        fcol = np.asarray(binsT[f]).astype(np.int64)
        mt = int(_M.missing_type[f])
        miss = ((mt == 1) & (fcol == int(_M.default_bin[f]))
                | (mt == 2) & (fcol == B - 1))
        if cat:
            w = np.asarray(bitset)[np.clip(fcol, 0, 255) // 32]
            return (w >> (np.clip(fcol, 0, 255) % 32)) & 1 > 0
        return np.where(miss, dl, fcol <= thr)

    # K=2: route flavor under test on leaf 3 + a plain numeric route on
    # leaf 5 riding along, so the 2K=4-wide accumulate always runs;
    # f=0 is the zero-missing branch, f=2 the NaN branch (bin B-1
    # routed by default_left, here False), f=1 the categorical bitset
    for f, cat, dl in ((0, False, True), (1, True, True),
                       (2, False, False)):
        routes = jnp.stack([
            pack_route(3, 9, f, B // 2, dl, cat, bitset, _M, False),
            pack_route(5, 10, 3, B // 3, False, False,
                       jnp.zeros(8, jnp.uint32), _M, False)])
        targets2 = jnp.asarray([3, 5, 9, 10], jnp.int32)
        lid2, hist = histogram_frontier_fusedk(
            binsT, w8, lid, bl, nb, targets2, routes, B, rb, 2)
        exp = lid_np.copy()
        exp[(exp == 3) & ~_np_go_left(f, B // 2, dl, cat)] = 9
        exp[(exp == 5) & ~_np_go_left(3, B // 3, False, False)] = 10
        if not np.array_equal(np.asarray(lid2), exp):
            return _fail(f"lid (f={f}, cat={cat})")
        ref = histogram_frontier(binsT, w8, jnp.asarray(exp), bl, nb,
                                 targets2, B, rb)
        if not np.array_equal(np.asarray(hist), np.asarray(ref)):
            return _fail(f"hist (f={f}, cat={cat})")

    # packed4: both nibble parities across the K routes
    bins4 = rng.integers(0, 15, (F, n))
    packedT = jnp.asarray(pack_bins_4bit(bins4))

    class _M4(_M):
        num_bin = jnp.full((4,), 15, jnp.int32)
        missing_type = jnp.zeros(4, jnp.int32)
        default_bin = jnp.zeros(4, jnp.int32)

    routes4 = jnp.stack([pack_route(3, 9, 1, 7, False, False,
                                    jnp.zeros(8, jnp.uint32), _M4, True),
                         pack_route(5, 10, 2, 7, False, False,
                                    jnp.zeros(8, jnp.uint32), _M4, True)])
    targets2 = jnp.asarray([3, 5, 9, 10], jnp.int32)
    lid4, hist4 = histogram_frontier_fusedk(
        packedT, w8, lid, bl, nb, targets2, routes4, 16, rb, 2,
        packed4=True)
    exp4 = lid_np.copy()
    exp4[(exp4 == 3) & (bins4[1].astype(np.int64) > 7)] = 9
    exp4[(exp4 == 5) & (bins4[2].astype(np.int64) > 7)] = 10
    if not np.array_equal(np.asarray(lid4), exp4):
        return _fail("packed4 lid")
    ref4 = histogram_frontier(packedT, w8, jnp.asarray(exp4), bl, nb,
                              targets2, 16, rb, packed4=True)
    if not np.array_equal(np.asarray(hist4), np.asarray(ref4)):
        return _fail("packed4 hist")

    # EFB: group column carries feature 1 at offset 6; K=1 keeps the
    # KT=2 > K corner covered (one route, both children accumulated)
    class _ME(_M):
        feat_group = jnp.asarray([0, 0, 1, 1], jnp.int32)
        feat_offset = jnp.asarray([0, 6, 0, 6], jnp.int32)
        num_bin = jnp.full((4,), 6, jnp.int32)
        missing_type = jnp.zeros(4, jnp.int32)
        default_bin = jnp.zeros(4, jnp.int32)

    routes_e = pack_route(3, 9, 1, 2, False, False,
                          jnp.zeros(8, jnp.uint32), _ME, False)[None]
    targets_e = jnp.asarray([3, 9], jnp.int32)
    lid5, hist5 = histogram_frontier_fusedk(
        binsT, w8, lid, bl, nb, targets_e, routes_e, B, rb, 1)
    g = np.asarray(binsT[0]).astype(np.int64)
    fcol = np.where((g >= 6) & (g < 12), g - 6, 0)
    exp5 = lid_np.copy()
    exp5[(exp5 == 3) & (fcol > 2)] = 9
    if not np.array_equal(np.asarray(lid5), exp5):
        return _fail("efb lid")
    ref5 = histogram_frontier(binsT, w8, jnp.asarray(exp5), bl, nb,
                              targets_e, B, rb)
    if not np.array_equal(np.asarray(hist5), np.asarray(ref5)):
        return _fail("efb hist")
    return True


# build-time decisions, keyed "segment"/"frontier"/"plain" — benches and
# telemetry read this to report whether the packed stream actually ran
# (the env gate + self-check fallback make the bare env value misleading)
packed_acc_decisions: dict = {}

_PACKED_ACC_CHECK: bool | None = None


def packed_acc_enabled() -> bool:
    """Whether histogram passes should run the packed int16 accumulator
    stream (``LIGHTGBM_TPU_PACKED_ACC``).

    Default OFF — no variant flips to default without a v5e number.
    ``1/on`` runs the one-shot quantization-parity self-check on the
    live backend and falls back to the f32 channel path when it reports
    a mismatch (``gate_self_check``: on TPU a check that fails to lower
    raises instead); ``force`` bypasses the check for on-chip A/B
    plumbing; ``0/off``/empty disables."""
    global _PACKED_ACC_CHECK
    import os
    env = os.environ.get("LIGHTGBM_TPU_PACKED_ACC", "").lower()
    if env in ("", "0", "off", "false"):
        return False
    if env == "force":
        return True
    if _PACKED_ACC_CHECK is None:
        _PACKED_ACC_CHECK = gate_self_check("packed-acc",
                                            _packed_acc_self_check)
    return _PACKED_ACC_CHECK


def _packed_acc_self_check() -> bool:
    """One-shot parity run of the packed-accumulator stream against the
    f32 channel path on the live backend: count channel EXACT, grad/hess
    bin sums within the stochastic-rounding bound (scale x (count + 1)
    per bin), across the all/segment/frontier and packed4 legs — with a
    fractional-member leg so GOSS/bagging weights stay covered."""
    import numpy as np
    rng = np.random.default_rng(13)

    def _fail(leg):
        import sys
        sys.stderr.write(f"packed-acc self-check FAILED leg: {leg}\n")
        return False

    F, B, rb, nblk = 4, 16, 512, 4
    n = rb * nblk
    bits = packed_acc_bits()
    binsT = jnp.asarray(rng.integers(0, B, (F, n)), jnp.uint8)
    grad = jnp.asarray(rng.standard_normal(n), jnp.float32)
    hess = jnp.asarray(rng.uniform(0.5, 1.5, n), jnp.float32)
    # fractional members exercise the f32-bitcast count lane (GOSS)
    member = jnp.asarray(np.where(rng.random(n) < 0.2, 0.0,
                                  np.where(rng.random(n) < 0.3, 0.25, 1.0)
                                  ).astype(np.float32))
    w8 = pack_channels(grad, hess, member)
    w2, scales, _clips = quantize_pack_channels(grad, hess, member,
                                                bits=bits)
    sc = np.asarray(scales)

    def _bound(leg, got, ref):
        got, ref = np.asarray(got), np.asarray(ref)
        if not np.array_equal(got[..., 2], ref[..., 2]):
            return _fail(f"{leg} count")
        cnt = ref[..., 2]
        for ch, s in ((0, sc[0]), (1, sc[1])):
            if np.any(np.abs(got[..., ch] - ref[..., ch])
                      > s * (cnt + 1.0) + 1e-4):
                return _fail(f"{leg} ch{ch} bound")
        return True

    ref = unpack_hist(histogram_all(binsT, w8, B, rb))
    got = unpack_hist_packed(histogram_all(binsT, w2, B, rb), scales)
    if not _bound("all", got, ref):
        return False

    lid_np = np.full(n, 7, np.int32)
    lid_np[rb:3 * rb] = np.where(rng.random(2 * rb) < 0.5, 3, 5)
    lid = jnp.asarray(lid_np)
    refs = unpack_hist(histogram_segment(
        binsT, w8, lid, jnp.int32(1), jnp.int32(2), jnp.int32(3), B, rb))
    gots = unpack_hist_packed(histogram_segment(
        binsT, w2, lid, jnp.int32(1), jnp.int32(2), jnp.int32(3), B, rb),
        scales)
    if not _bound("segment", gots, refs):
        return False

    targets = jnp.asarray([3, 5], jnp.int32)
    bl = jnp.arange(nblk, dtype=jnp.int32)
    reff = unpack_hist(histogram_frontier(
        binsT, w8, lid, bl, jnp.int32(nblk), targets, B, rb))
    gotf = unpack_hist_packed(histogram_frontier(
        binsT, w2, lid, bl, jnp.int32(nblk), targets, B, rb), scales)
    if not _bound("frontier", gotf, reff):
        return False

    bins4 = rng.integers(0, 15, (F, n))
    packedT = jnp.asarray(pack_bins_4bit(bins4))
    ref4 = unpack_hist(histogram_all(packedT, w8, 16, rb, packed4=True))
    got4 = unpack_hist_packed(histogram_all(packedT, w2, 16, rb,
                                            packed4=True), scales)
    if not _bound("packed4", got4, ref4):
        return False
    return True


_ONEHOT_BUILD_CHECKS: dict = {}


def onehot_build_mode() -> str:
    """Resolved one-hot construction for the histogram kernels
    (``LIGHTGBM_TPU_ONEHOT_BUILD``).

    ''/'iota' -> the compare-vs-iota baseline.  'gather'/'twolevel' ->
    the alternative build, gated by a one-shot BIT-identity self-check
    against iota on the live backend (all builds feed the same matmul,
    so identity is the contract — any difference means the build is
    wrong, and the mode falls back to iota with a warning; a build that
    does not lower on TPU raises, see ``gate_self_check``).  A trailing
    '!' ('gather!') bypasses the check for
    on-chip A/Bs.  Resolved in the NON-jit public wrappers, never
    inside a jitted dispatcher, so an env change is never masked by a
    stale jit cache entry."""
    import os
    env = os.environ.get("LIGHTGBM_TPU_ONEHOT_BUILD", "").lower()
    if env in ("", "iota"):
        return "iota"
    force = env.endswith("!")
    mode = env.rstrip("!")
    if mode not in ("gather", "twolevel"):
        return "iota"
    if force:
        return mode
    if mode not in _ONEHOT_BUILD_CHECKS:
        _ONEHOT_BUILD_CHECKS[mode] = gate_self_check(
            f"one-hot build ({mode})",
            lambda: _onehot_build_self_check(mode))
    if not _ONEHOT_BUILD_CHECKS[mode]:
        return "iota"
    return mode


def _onehot_build_self_check(mode: str) -> bool:
    """Bit-identity of an alternative one-hot build vs the iota baseline
    (same [nf*B, chunk] matrix, same dot_general, same accumulation
    order => bitwise-equal f32 sums) on full/segment/frontier and
    packed4 legs."""
    import numpy as np
    rng = np.random.default_rng(17)

    def _fail(leg):
        import sys
        sys.stderr.write(f"one-hot build self-check ({mode}) FAILED "
                         f"leg: {leg}\n")
        return False

    F, B, rb, nblk = 4, 16, 512, 4
    n = rb * nblk
    binsT = jnp.asarray(rng.integers(0, B, (F, n)), jnp.uint8)
    grad = jnp.asarray(rng.standard_normal(n), jnp.float32)
    hess = jnp.asarray(rng.uniform(0.5, 1.5, n), jnp.float32)
    member = jnp.ones(n, jnp.float32)
    w8 = pack_channels(grad, hess, member)

    a = _histogram_all(binsT, w8, B, rb, onehot_build="iota")
    b = _histogram_all(binsT, w8, B, rb, onehot_build=mode)
    if not np.array_equal(np.asarray(a), np.asarray(b)):
        return _fail("all")

    lid_np = np.full(n, 7, np.int32)
    lid_np[rb:3 * rb] = np.where(rng.random(2 * rb) < 0.5, 3, 5)
    lid = jnp.asarray(lid_np)
    sa = _histogram_segment_dyn(binsT, w8, lid, jnp.int32(1), jnp.int32(2),
                                jnp.int32(3), B, rb, onehot_build="iota")
    sb = _histogram_segment_dyn(binsT, w8, lid, jnp.int32(1), jnp.int32(2),
                                jnp.int32(3), B, rb, onehot_build=mode)
    if not np.array_equal(np.asarray(sa), np.asarray(sb)):
        return _fail("segment")

    targets = jnp.asarray([3, 5], jnp.int32)
    bl = jnp.arange(nblk, dtype=jnp.int32)
    fa = _histogram_frontier_dyn(binsT, w8, lid, bl, jnp.int32(nblk),
                                 targets, B, rb, 2, onehot_build="iota")
    fb = _histogram_frontier_dyn(binsT, w8, lid, bl, jnp.int32(nblk),
                                 targets, B, rb, 2, onehot_build=mode)
    if not np.array_equal(np.asarray(fa), np.asarray(fb)):
        return _fail("frontier")

    bins4 = rng.integers(0, 15, (F, n))
    packedT = jnp.asarray(pack_bins_4bit(bins4))
    pa = _histogram_all(packedT, w8, 16, rb, packed4=True,
                        onehot_build="iota")
    pb = _histogram_all(packedT, w8, 16, rb, packed4=True,
                        onehot_build=mode)
    if not np.array_equal(np.asarray(pa), np.asarray(pb)):
        return _fail("packed4")
    return True


# self-checks of the kernels the default path selects; the rest are opt-in
# variants behind a LIGHTGBM_TPU_* knob
DEFAULT_PATH_CHECKS = ("fused_route", "route_kernel", "score_kernel")


def kernel_self_checks() -> dict:
    """Run every kernel variant self-check on the current backend (CPU:
    the interpret path; on-chip runs catch lowering drift the interpreter
    cannot).  Returns ``{name: None}`` for a check that passed, else what
    went wrong: ``"mismatch"`` or the exception, whose traceback goes to
    stderr.  No check's failure stops the others."""
    from ..models.grower_frontier import _hist_stage_self_check
    from .pallas_score import _score_kernel_self_check
    checks = [
        ("fused_route", _fused_route_self_check),
        ("route_kernel", _route_kernel_self_check),
        ("score_kernel", _score_kernel_self_check),
        ("fused_k", _fused_k_self_check),
        ("packed_acc", _packed_acc_self_check),
        ("onehot_gather", lambda: _onehot_build_self_check("gather")),
        ("onehot_twolevel", lambda: _onehot_build_self_check("twolevel")),
        ("hist_stage", _hist_stage_self_check),
    ]
    results = {}
    for name, fn in checks:
        try:
            results[name] = None if fn() else "mismatch"
        except Exception as e:  # noqa: BLE001 — reported per variant
            import sys
            import traceback
            sys.stderr.write(f"kernel self-check {name} raised:\n"
                             + traceback.format_exc()[-2000:] + "\n")
            last = (str(e).strip().splitlines() or [""])[-1]
            results[name] = f"{type(e).__name__}: {last[:200]}"
    return results


def run_kernel_self_checks(verbose: bool = True) -> int:
    """``kernel_self_checks`` with a pass/fail line per check — the
    ``verify_t1.sh --with-kernel-checks`` leg.  Returns a process exit
    code (0 = all green)."""
    results = kernel_self_checks()
    bad = [name for name, err in results.items() if err is not None]
    if verbose:
        for name, err in results.items():
            print(f"kernel self-check: {'ok' if err is None else 'FAIL'} "
                  f"{name}" + ("" if err is None else f" ({err})"))
        print(f"kernel self-checks: {'FAIL' if bad else 'PASS'}")
    return 1 if bad else 0


def leaf_histogram_pallas(binsT: jax.Array, grad: jax.Array,
                          hess: jax.Array, member: jax.Array,
                          num_bins: int, block_rows: int = 0,
                          packed4: bool = False,
                          packed_acc: bool = False,
                          bits: int = 8) -> jax.Array:
    """Drop-in [F, B, 3] leaf histogram matching ops.histogram semantics,
    computed with the full-data pallas kernel.  ``packed_acc`` runs the
    quantized int16 stream instead of the 8-channel hi/lo split — the
    per-call quantize gives this path natural per-leaf scales."""
    if packed_acc:
        w2, scales, _clips = quantize_pack_channels(grad, hess, member,
                                                    bits=bits)
        return unpack_hist_packed(
            histogram_all(binsT, w2, num_bins, block_rows,
                          packed4=packed4), scales)
    w8 = pack_channels(grad, hess, member)
    return unpack_hist(histogram_all(binsT, w8, num_bins, block_rows,
                                     packed4=packed4))


def pack_bins_4bit(binsT):
    """[F, N] u8 (bins <= 15) -> [ceil(F/2), N] u8 with feature 2i in the
    low nibble and 2i+1 in the high (Dense4bitsBin::Push layout idea,
    dense_nbits_bin.hpp:96, re-cut for the feature-major TPU stream)."""
    import numpy as np
    binsT = np.asarray(binsT)
    F = binsT.shape[0]
    if F % 2:
        binsT = np.concatenate(
            [binsT, np.zeros((1, binsT.shape[1]), binsT.dtype)])
    return (binsT[0::2] | (binsT[1::2] << 4)).astype(np.uint8)


def unpack_nibble(byte, col):
    """Logical column ``col``'s 4-bit bins from its packed byte row — the
    single place that knows the nibble convention (odd logical column =
    high nibble; inverse of pack_bins_4bit)."""
    b = byte.astype(jnp.int32)
    return jnp.where(col % 2 == 1, b >> 4, b & 15)


def slice_packed_column(binsT, col):
    """One logical column [N] i32 out of a 4-bit packed feature-major
    matrix (for a single, possibly traced, column index)."""
    byte = lax.dynamic_slice_in_dim(binsT, col // 2, 1, axis=0)[0, :]
    return unpack_nibble(byte, col)
