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
    proportional to the leaf, not the dataset.  The grid is the traced
    interval length, so no step falls outside it.

The 8 weight channels are ``[g_hi, g_lo, h_hi, h_lo, member, 0, 0, 0]``;
``unpack_hist`` folds a kernel output ``[F, B, 8]`` back to the
``[F, B, 3]`` (sum_grad, sum_hess, count) layout the split scan consumes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NUM_CHANNELS = 8
DEFAULT_BLOCK_ROWS = 16384
# inner sub-chunk of a row block: the one-hot [fblk*B, CHUNK] lives in
# VMEM only for the duration of one matmul
CHUNK = 512
# feature sub-block: keep fblk*B*CHUNK*2B (one-hot) around 2MB
_FBLK_BIN_BUDGET = 2048
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


def _accumulate_block(binsT_ref, wfn, acc_ref, num_bins, packed4=False,
                      unroll=1):
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

    The one-hot is an i32 compare against a broadcasted iota: the only
    compare width the v5e's VPU offers (16-bit iota and compares do not
    lower; docs/KERNELS.md, rejected variants).
    """
    Fp, rb = binsT_ref.shape
    F = Fp * 2 if packed4 else Fp
    B = num_bins
    fblk = max(1, _fblk(B) // (2 if packed4 else 1))
    chunk = _pick_chunk(rb)

    def one_chunk(c, carry):
        wc = wfn(c, chunk)                                  # [8, chunk]
        for p0 in range(0, Fp, fblk):
            np_ = min(fblk, Fp - p0)
            b = binsT_ref[p0:p0 + np_, pl.ds(c * chunk, chunk)]
            if packed4:
                bi = b.astype(jnp.int32)
                b = jnp.stack([bi & 15, bi >> 4], axis=1).reshape(
                    2 * np_, chunk)
            nf = b.shape[0]
            iota = lax.broadcasted_iota(jnp.int32, (nf, B, chunk), 1)
            onehot = (b.astype(jnp.int32)[:, None, :] == iota).astype(
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


def _kernel_all(binsT_ref, w_ref, out_ref, acc_ref, *, num_bins, packed4):
    # w_ref may carry MULTIPLE 8-channel sets ([8*C, rb]): the matmul
    # output widens to 8*C and each set accumulates independently — used
    # to histogram all C class-trees' roots in one pass (multiclass).
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def wfn(c, chunk):
        return w_ref[:, pl.ds(c * chunk, chunk)]

    _accumulate_block(binsT_ref, wfn, acc_ref, num_bins, packed4)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def _kernel_segment(sref, binsT_ref, w_ref, lid_ref, out_ref, acc_ref, *,
                    num_bins, packed4):
    # sref: prefetched [3] i32 = (start_block, n_blocks, target_leaf)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(i < sref[1])
    def _():
        def wfn(c, chunk):
            wc = w_ref[:, pl.ds(c * chunk, chunk)]
            lc = lid_ref[:, pl.ds(c * chunk, chunk)]
            return wc * (lc == sref[2]).astype(jnp.bfloat16)

        _accumulate_block(binsT_ref, wfn, acc_ref, num_bins, packed4)

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
                                    "packed4"))
def histogram_all(binsT: jax.Array, w8: jax.Array, num_bins: int,
                  block_rows: int = 0,
                  interpret: bool | None = None,
                  packed4: bool = False) -> jax.Array:
    """Full-data histogram: [F, Npad] bins x [8*C, Npad] channels ->
    [C, F, B, 8] (squeezed to [F, B, 8] for the common C == 1).

    ``w8`` may stack C independent 8-channel sets (multiclass batched
    roots: every class-tree's root histogram in ONE pass — C x fewer
    full-data scans, and 8*C output columns fill more of the MXU tile).
    Npad must be a multiple of ``block_rows``; pad rows must carry zero
    weight channels (the bin values there may be anything).  With
    ``packed4`` the bins hold two <=16-bin features per byte and F here
    means PHYSICAL rows; the output has 2F logical features.
    """
    F, n = binsT.shape
    F_log = 2 * F if packed4 else F
    CH = int(w8.shape[0])
    assert CH % NUM_CHANNELS == 0, CH
    C = CH // NUM_CHANNELS
    if block_rows <= 0:
        block_rows = pick_block_rows(F_log, num_bins)
    if interpret is None:
        interpret = _interpret_default()
    assert n % block_rows == 0, (n, block_rows)
    out = pl.pallas_call(
        functools.partial(_kernel_all, num_bins=num_bins, packed4=packed4),
        out_shape=jax.ShapeDtypeStruct((F_log * num_bins, CH),
                                       jnp.float32),
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((F, block_rows), lambda i: (0, i)),
            pl.BlockSpec((CH, block_rows), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((F_log * num_bins, CH),
                               lambda i: (0, 0)),
        scratch_shapes=[pltpu.VMEM((F_log * num_bins, CH), jnp.float32)],
        interpret=interpret,
    )(binsT, w8)
    if C == 1:
        return out.reshape(F_log, num_bins, CH)
    # [F*B, C*8] -> [C, F, B, 8]
    return out.reshape(F_log, num_bins, C, NUM_CHANNELS).transpose(
        2, 0, 1, 3)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_rows", "interpret",
                                    "packed4"))
def histogram_segment(binsT: jax.Array, w8: jax.Array, leaf_id: jax.Array,
                      start_block: jax.Array, n_blocks: jax.Array,
                      target_leaf: jax.Array, num_bins: int,
                      block_rows: int = 0,
                      interpret: bool | None = None,
                      packed4: bool = False) -> jax.Array:
    """Histogram of one leaf, scanning only its confinement blocks.

    ``leaf_id`` is [Npad] i32 row->leaf; rows outside the leaf (or padding,
    which must carry zero weights) contribute nothing.  DMA, compute AND
    grid length are proportional to ``n_blocks``, not N: the grid is the
    traced interval length (Mosaic accepts traced grid dims), so every
    step is in range and one kernel is compiled.  An empty interval runs
    one masked step and returns zeros.  Returns [F, B, 8] (logical
    features when ``packed4``).
    """
    F, n = binsT.shape
    F_log = 2 * F if packed4 else F
    if block_rows <= 0:
        block_rows = pick_block_rows(F_log, num_bins)
    assert n % block_rows == 0, (n, block_rows)
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

    acc_shape = (F_log * num_bins, NUM_CHANNELS)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid_n,),
        in_specs=[
            pl.BlockSpec((F, block_rows), im_data),
            pl.BlockSpec((NUM_CHANNELS, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
        ],
        out_specs=pl.BlockSpec(acc_shape, lambda i, s: (0, 0)),
        scratch_shapes=[pltpu.VMEM(acc_shape, jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_segment, num_bins=num_bins,
                          packed4=packed4),
        out_shape=jax.ShapeDtypeStruct(acc_shape, jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(scalars, binsT, w8, leaf_id.reshape(1, -1))
    return out.reshape(F_log, num_bins, NUM_CHANNELS)


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
                     num_bins, K, packed4):
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

        _accumulate_block(binsT_ref, wfn, acc_ref, num_bins, packed4)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_rows", "interpret",
                                    "packed4"))
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
    histograms — masks never match, since real leaf ids are >= 0).  The
    grid is the traced union size (one compile); ``n_blocks`` 0 runs one
    masked step and returns zeros.  Returns [K, F, B, 8] (logical
    features when ``packed4``).
    """
    F, n = binsT.shape
    K = int(targets.shape[0])
    F_log = 2 * F if packed4 else F
    if block_rows <= 0:
        block_rows = pick_block_rows(F_log, num_bins)
    assert n % block_rows == 0, (n, block_rows)
    if interpret is None:
        interpret = _interpret_default()
    max_blocks = n // block_rows
    n_blocks = jnp.asarray(n_blocks, jnp.int32)
    grid_n = jnp.clip(n_blocks, 1, max_blocks).astype(jnp.int32)
    bl = block_list.astype(jnp.int32)[:max_blocks]
    scalars = jnp.concatenate([
        jnp.stack([n_blocks, jnp.int32(0)]),
        targets.astype(jnp.int32), bl])

    def im_data(i, s):
        idx = jnp.minimum(i, jnp.maximum(s[0] - 1, 0))
        return (0, jnp.minimum(s[2 + K + idx], max_blocks - 1))

    acc_shape = (F_log * num_bins, K * NUM_CHANNELS)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid_n,),
        in_specs=[
            pl.BlockSpec((F, block_rows), im_data),
            pl.BlockSpec((NUM_CHANNELS, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
        ],
        out_specs=pl.BlockSpec(acc_shape, lambda i, s: (0, 0)),
        scratch_shapes=[pltpu.VMEM(acc_shape, jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel_frontier, num_bins=num_bins, K=K,
                          packed4=packed4),
        out_shape=jax.ShapeDtypeStruct(acc_shape, jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(scalars, binsT, w8, leaf_id.reshape(1, -1))
    # [F*B, K*8] -> [K, F, B, 8]
    return out.reshape(F_log, num_bins, K, NUM_CHANNELS).transpose(
        2, 0, 1, 3)


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
# stop matching leaf), so a block visited again (by a later feature tile,
# or by the one step an empty interval still runs) stays correct even when
# it re-reads post-write data.  Reference analog: routing rides the
# partition work the histogram already pays for
# (src/treelearner/data_partition.hpp:111).
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
                           num_bins, packed4, block_axis=0):
    # sref: [3 + _ROUTE_WORDS] = (start_block, n_blocks, target_leaf, route)
    # block_axis 1: grid (feature tiles, blocks), one tile's columns in
    # binsT_ref / acc_ref / out_ref, every tile walking the same blocks
    i = pl.program_id(block_axis)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # 1) route this block — unconditional: the update is idempotent (so
    # is a later tile's: it finds the block routed, or routes the ids it
    # prefetched before the write landed, to the same values)
    lid_out_ref[...] = _route_block_ids(sref, 3, frow_ref[...],
                                        lid_ref[...], packed4)

    # 2) accumulate the target's histogram from the UPDATED ids
    @pl.when(i < sref[1])
    def _():
        def wfn(c, chunk):
            wc = w_ref[:, pl.ds(c * chunk, chunk)]
            lc = lid_out_ref[:, pl.ds(c * chunk, chunk)]
            return wc * (lc == sref[2]).astype(jnp.bfloat16)

        _accumulate_block(binsT_ref, wfn, acc_ref, num_bins, packed4)

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
                                    "packed4", "tile_rows"))
def _histogram_segment_routed(binsT: jax.Array, w8: jax.Array,
                              leaf_id: jax.Array, start_block: jax.Array,
                              n_blocks: jax.Array, target_leaf: jax.Array,
                              route: jax.Array, num_bins: int,
                              block_rows: int = 0,
                              interpret: bool | None = None,
                              packed4: bool = False,
                              tile_rows: int = 0):
    F, n = binsT.shape
    F_log = 2 * F if packed4 else F
    if block_rows <= 0:
        block_rows = pick_block_rows(F_log, num_bins)
    assert n % block_rows == 0, (n, block_rows)
    if interpret is None:
        interpret = _interpret_default()
    if tile_rows:
        return _histogram_segment_routed_tiled(
            binsT, w8, leaf_id, start_block, n_blocks, target_leaf, route,
            num_bins, block_rows, interpret, packed4, tile_rows)
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
            pl.BlockSpec((NUM_CHANNELS, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
        ],
        out_specs=[
            pl.BlockSpec((1, block_rows), im_data),
            pl.BlockSpec((F_log * num_bins, NUM_CHANNELS),
                         lambda i, s: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((F_log * num_bins, NUM_CHANNELS),
                                   jnp.float32)],
    )
    lid_out, hist = pl.pallas_call(
        functools.partial(_kernel_segment_routed, num_bins=num_bins,
                          packed4=packed4),
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32),
                   jax.ShapeDtypeStruct((F_log * num_bins, NUM_CHANNELS),
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
    return lid_out[0], hist.reshape(F_log, num_bins, NUM_CHANNELS)


def _histogram_segment_routed_tiled(binsT, w8, leaf_id, start_block,
                                    n_blocks, target_leaf, route, num_bins,
                                    block_rows, interpret, packed4,
                                    tile_rows):
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
            pl.BlockSpec((NUM_CHANNELS, block_rows), im_row),
            pl.BlockSpec((1, block_rows), im_row),
            pl.BlockSpec((1, block_rows), im_row),
        ],
        out_specs=[
            pl.BlockSpec((1, block_rows), im_row),
            pl.BlockSpec((T_log * num_bins, NUM_CHANNELS),
                         lambda t, i, s: (t, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((T_log * num_bins, NUM_CHANNELS),
                                   jnp.float32)],
    )
    with jax.named_scope("tile_walk"):
        lid_out, hist = pl.pallas_call(
            functools.partial(_kernel_segment_routed, num_bins=num_bins,
                              packed4=packed4, block_axis=1),
            out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32),
                       jax.ShapeDtypeStruct((F_log * num_bins, NUM_CHANNELS),
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
    return lid_out[0], hist.reshape(F_log, num_bins, NUM_CHANNELS)


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
    keep their values via input/output aliasing).  A table wider than
    one accumulator holds
    (``feature_tile``) is walked tile by tile, its bin rows padded to
    whole tiles by the caller.
    """
    return _histogram_segment_routed(
        binsT, w8, leaf_id, start_block, n_blocks, target_leaf, route,
        num_bins, block_rows, interpret, packed4,
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
                              block_axis=0):
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
                          unroll=_LOOKAHEAD_UNROLL)
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
                                    "packed4", "tile_rows"))
def _histogram_segment_lookahead(binsT: jax.Array, w8: jax.Array,
                                 leaf_id: jax.Array, start_block: jax.Array,
                                 n_blocks: jax.Array, target_leaf: jax.Array,
                                 route: jax.Array, slots: jax.Array,
                                 n_acc: jax.Array, num_bins: int,
                                 block_rows: int = 0,
                                 interpret: bool | None = None,
                                 packed4: bool = False,
                                 tile_rows: int = 0):
    F, n = binsT.shape
    F_log = 2 * F if packed4 else F
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
                                  block_axis=1),
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
                          K=K, packed4=packed4),
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
    kernel to f32 rounding, not bit for bit.
    Wide tables go tile by tile as in ``histogram_segment_routed``, each
    tile keeping its own (block sum, hi, lo) triple.
    """
    return _histogram_segment_lookahead(
        binsT, w8, leaf_id, jnp.asarray(start_block, jnp.int32),
        jnp.asarray(n_blocks, jnp.int32), target_leaf, route, slots, n_acc,
        num_bins, block_rows, interpret, packed4,
        _tile_rows(binsT, num_bins, packed4, feature_tile_cols))


def _kernel_frontier_routed(sref, binsT_ref, w_ref, frows_ref, lid_ref,
                            lid_out_ref, out_ref, acc_ref, *, num_bins, K,
                            packed4):
    # frows_ref: [K, rb] — the K split features' bin-row blocks
    # sref: [2 + K + K*_ROUTE_WORDS + n_grid] =
    #   (n_blocks, pad, targets[K], routes[K*19], block_list[n_grid])
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
        lid = _route_block_ids(sref, 2 + K + k * _ROUTE_WORDS,
                               frows[k:k + 1], lid, packed4)
    lid_out_ref[...] = lid

    # 2) batched accumulate of the K targets from the UPDATED ids
    @pl.when(i < sref[0])
    def _():
        def wfn(c, chunk):
            wc = w_ref[:, pl.ds(c * chunk, chunk)]
            lc = lid_out_ref[:, pl.ds(c * chunk, chunk)]
            rows = []
            for k in range(K):
                mask = (lc == sref[2 + k]).astype(jnp.bfloat16)
                rows.append(mask * wc)
            return jnp.concatenate(rows, axis=0)

        _accumulate_block(binsT_ref, wfn, acc_ref, num_bins, packed4)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "block_rows", "K",
                                    "interpret", "packed4"))
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
    ``(leaf_id', [K, F, B, 8])``.
    """
    F, n = binsT.shape
    K = K or int(routes.shape[0])
    assert int(targets.shape[0]) == K, (targets.shape, K)
    F_log = 2 * F if packed4 else F
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
    blk_base = 2 + K + K * _ROUTE_WORDS
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
            pl.BlockSpec((NUM_CHANNELS, block_rows), im_data),
            pl.BlockSpec((K, block_rows), im_data),
            pl.BlockSpec((1, block_rows), im_data),
        ],
        out_specs=[
            pl.BlockSpec((1, block_rows), im_data),
            pl.BlockSpec((F_log * num_bins, K * NUM_CHANNELS),
                         lambda i, s: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((F_log * num_bins, K * NUM_CHANNELS),
                                   jnp.float32)],
    )
    lid_out, hist = pl.pallas_call(
        functools.partial(_kernel_frontier_routed, num_bins=num_bins, K=K,
                          packed4=packed4),
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.int32),
                   jax.ShapeDtypeStruct((F_log * num_bins,
                                         K * NUM_CHANNELS), jnp.float32)],
        grid_spec=grid_spec,
        # inputs: scalars, binsT, w8, frows, leaf_id
        input_output_aliases={4: 0},
        # see _histogram_segment_routed: the K frow rows + lid streams
        # exceed the 16 MB default scoped-vmem limit at K=16 production
        # shapes — auto-sized from the computed need
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=fused_vmem_limit(F, num_bins, K, block_rows,
                                              packed4)),
        interpret=interpret,
        name="_histogram_frontier_routed",     # as the trace shows it
    )(scalars, binsT, w8, frows, leaf_id.reshape(1, -1))
    return lid_out[0], hist.reshape(F_log, num_bins, K,
                                    NUM_CHANNELS).transpose(2, 0, 1, 3)


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
    route count (the lookahead kernel carries K lane sets over one
    route); default = K, the round-pass fusion.  Memoized per
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
    bypasses it for A/Bs on shapes it vetoes."""
    est = _fused_vmem_est(F_phys, num_bins, K, block_rows, packed4,
                          targets_k)
    return est <= int(0.9 * _FUSED_VMEM_CAP)


# build-time decisions, keyed "segment"/"frontier" — benches read this to
# report the kernel that actually ran (the env gate + fits veto make the
# bare self-check result misleading).
fused_route_decisions: dict = {}


def fused_route_policy(K: int, F_log: int, num_bins: int,
                       block_rows: int, packed4: bool) -> str:
    """The growers' single dispatch policy for the fused route+histogram
    kernels.  Returns a tier: "off" | "k1" (K-target round-pass fusion,
    the kernel the unfused pair's targets match).

    LIGHTGBM_TPU_FUSED_ROUTE: =1 -> the K-target fusion wherever the
    kernels lower (bypasses the K policy and the vmem fit veto, for
    A/Bs); =0 -> off.  Auto: K == 1 only — on-chip (v5e, 2026-08-01)
    the K=16 K-target fusion measured 1.43 s/iter vs 1.02-1.04 unfused
    at the HIGGS shape (K serial in-block route updates plus K frow
    streams cost more than the ONE union-pass windowed route they
    replace, and the subtraction still ran) while the K=1 segment
    fusion won 1.28 vs 1.43 — plus the self-check and the vmem fit
    estimate."""
    import os
    F_phys = (F_log + 1) // 2 if packed4 else F_log
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
    contract as histogram_segment_routed)."""
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
    missing + out-of-window retention) against the XLA route."""
    global _ROUTE_KERNEL_CHECK
    import os
    env = os.environ.get("LIGHTGBM_TPU_ROUTE_KERNEL", "auto").lower()
    if env in ("0", "off", "false"):
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
    untouched-block retention through the input/output alias.
    """
    global _FUSED_ROUTE_CHECK
    import os
    env = os.environ.get("LIGHTGBM_TPU_FUSED_ROUTE", "auto").lower()
    if env in ("0", "off", "false"):
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
    from .split import routed_left
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


# self-checks of the kernels the default path selects
DEFAULT_PATH_CHECKS = ("fused_route", "route_kernel", "score_kernel")


def kernel_self_checks() -> dict:
    """Run the ``DEFAULT_PATH_CHECKS`` on the current backend (CPU:
    the interpret path; on-chip runs catch lowering drift the interpreter
    cannot).  Returns ``{name: None}`` for a check that passed, else what
    went wrong: ``"mismatch"`` or the exception, whose traceback goes to
    stderr.  No check's failure stops the others."""
    from .pallas_score import _score_kernel_self_check
    checks = [
        ("fused_route", _fused_route_self_check),
        ("route_kernel", _route_kernel_self_check),
        ("score_kernel", _score_kernel_self_check),
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
                          packed4: bool = False) -> jax.Array:
    """Drop-in [F, B, 3] leaf histogram matching ops.histogram semantics,
    computed with the full-data pallas kernel."""
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
