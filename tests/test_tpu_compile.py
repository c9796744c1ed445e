"""The default path's Pallas kernels, compiled by the chip's own compiler
for a described (not attached) TPU v5e at the benchmark suite's widths.

Interpret mode cannot see what Mosaic refuses: a slice off the tiling, a
kernel over its scoped-VMEM limit.  These compiles can, at no chip time.
A compile that passes is not a chip run — nothing here executes.

The topology is described inside a fixture of THIS file only: describing
it loads libtpu, which one process at a time may do, so it must not
happen while any module is imported (the `on-chip-measurement` guide,
section 2).
"""

import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.ops import pallas_histogram as ph
from lightgbm_tpu.ops.pallas_score import score_gather_add

# (features, bins, rows) of the suite's cells: HIGGS-shape binary, GOSS
# regression, multiclass + categorical, MS-LTR-shape lambdarank
HIGGS = (28, 64, 10_500_000)
SUITE_WIDTHS = [(28, 64, 2_000_000), (36, 64, 1_000_000),
                (136, 64, 2_270_000)]
FRONTIER_K = 16


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described chip, with the persistent compile
    cache off around the module: an executable compiled for a described
    chip is written to the cache but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


class _Shapes:
    """The kernels' argument shapes for one (features, bins, rows) cell,
    as ShapeDtypeStructs placed on the described chip."""

    def __init__(self, sharding, F, B, rows):
        self.F, self.B = F, B
        self.rb = ph.pick_block_rows(F, B, rows)
        self.n = -(-rows // self.rb) * self.rb
        self._sharding = sharding

    def s(self, shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=self._sharding)

    @property
    def bins(self):
        return self.s((self.F, self.n), jnp.uint8)

    @property
    def w8(self):
        return self.s((ph.NUM_CHANNELS, self.n), jnp.bfloat16)

    @property
    def leaf_id(self):
        return self.s((self.n,), jnp.int32)

    @property
    def i32(self):
        return self.s((), jnp.int32)

    @property
    def route(self):
        return self.s((ph._ROUTE_WORDS,), jnp.int32)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _segment_dyn(sh):
    return _compile(
        lambda b, w, l, s0, nb, t: ph._histogram_segment_dyn(
            b, w, l, s0, nb, t, sh.B, sh.rb, interpret=False),
        sh.bins, sh.w8, sh.leaf_id, sh.i32, sh.i32, sh.i32)


def _all(sh):
    return _compile(
        lambda b, w: ph._histogram_all(b, w, sh.B, sh.rb, interpret=False),
        sh.bins, sh.w8)


def _segment_routed(sh, tile_rows=0):
    return _compile(
        lambda b, w, l, s0, nb, t, r: ph._histogram_segment_routed(
            b, w, l, s0, nb, t, r, sh.B, sh.rb, interpret=False,
            tile_rows=tile_rows),
        sh.bins, sh.w8, sh.leaf_id, sh.i32, sh.i32, sh.i32, sh.route)


def _segment_lookahead(sh, tile_rows=0):
    K = ph.lookahead_width(sh.F, sh.B, sh.rb, False)
    assert K >= 2
    return _compile(
        lambda b, w, l, s0, nb, t, r, sl, na: ph._histogram_segment_lookahead(
            b, w, l, s0, nb, t, r, sl, na, sh.B, sh.rb, interpret=False,
            tile_rows=tile_rows),
        sh.bins, sh.w8, sh.leaf_id, sh.i32, sh.i32, sh.i32, sh.route,
        sh.s((K - 1, ph._ROUTE_WORDS), jnp.int32), sh.i32)


def _route_window(sh):
    return _compile(
        lambda b, l, s0, nb, r: ph.route_window(b, l, s0, nb, r, sh.rb,
                                                interpret=False),
        sh.bins, sh.leaf_id, sh.i32, sh.i32, sh.route)


def _frontier_dyn(sh):
    K = FRONTIER_K
    return _compile(
        lambda b, w, l, bl, nb, t: ph._histogram_frontier_dyn(
            b, w, l, bl, nb, t, sh.B, sh.rb, K, interpret=False),
        sh.bins, sh.w8, sh.leaf_id, sh.s((sh.n // sh.rb,), jnp.int32),
        sh.i32, sh.s((K,), jnp.int32))


def _score(sh):
    return _compile(
        lambda s, l, t: score_gather_add(s, l, t, interpret=False),
        sh.s((HIGGS[2],), jnp.float32), sh.s((HIGGS[2],), jnp.int32),
        sh.s((255,), jnp.float32))


@pytest.mark.parametrize("kernel", [_all, _segment_dyn, _segment_routed,
                                    _segment_lookahead, _route_window,
                                    _frontier_dyn, _score],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_default_path_kernel_compiles_at_higgs_shape(one_chip, kernel):
    sh = _Shapes(one_chip, *HIGGS)
    assert sh.rb == 32768 and sh.n == 10_518_528
    assert "tpu_custom_call" in kernel(sh).as_text()


@pytest.mark.parametrize("F,B,rows", SUITE_WIDTHS,
                         ids=["goss", "multiclass_cat", "lambdarank"])
def test_segment_kernel_compiles_at_suite_widths(one_chip, F, B, rows):
    assert ph.supported(F, B, jnp.uint8)
    assert "tpu_custom_call" in _segment_dyn(
        _Shapes(one_chip, F, B, rows)).as_text()


@pytest.mark.parametrize("F,B", [(136, 256), (104, 256)])
def test_supported_agrees_with_compiler(one_chip, F, B):
    """MS-LTR width at the default max_bin=255, which the compiler
    refuses, and the widest 256-bin shape `supported()` admits: it sizes
    the accumulator as Mosaic lays it out (lanes padded to 128), so it
    says no where the kernel cannot compile and the XLA grower is
    selected instead of a crash on the chip.  (Feature tiles are admitted
    at 64 bins alone: below.)"""
    sh = _Shapes(one_chip, F, B, 2_270_000)
    try:
        _segment_dyn(sh)
        _segment_routed(sh)
        compiles = True
    except Exception as e:  # noqa: BLE001 — the compiler's refusal
        assert "vmem" in str(e).lower(), e
        compiles = False
    assert ph.supported(F, B, jnp.uint8) == compiles


def _tiled(kernel):
    def build(sh):
        return kernel(sh, tile_rows=ph.feature_tile(sh.F, sh.B))
    build.__name__ = kernel.__name__
    return build


@pytest.mark.parametrize("F,B,rows,tiles", [(2000, 64, 1_100_000, 16)],
                         ids=["epsilon"])
@pytest.mark.parametrize("kernel", [_segment_routed, _segment_lookahead],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_tiled_kernel_compiles_at_wide_shapes(one_chip, kernel, F, B, rows,
                                              tiles):
    """The benchmark's 2000 x 64 table (16 tiles of 128 columns, blocks of
    8192 rows): the routed and the eight-lane-set kernels compile tile by
    tile within the VMEM limit they ask for, over a table padded to whole
    tiles."""
    assert ph.feature_tiles(F, B) == tiles
    tile = ph.feature_tile(F, B)
    sh = _Shapes(one_chip, tiles * tile, B, rows)
    assert sh.rb == ph.pick_block_rows(F, B, rows)
    assert ph.lookahead_width(F, B, sh.rb, False) == 8
    assert "tpu_custom_call" in _tiled(kernel)(sh).as_text()


@pytest.mark.parametrize("F,B,rows", SUITE_WIDTHS,
                         ids=["goss", "multiclass_cat", "lambdarank"])
def test_lookahead_kernel_compiles_at_suite_widths(one_chip, F, B, rows):
    """Wherever the grower would pick more than one lane set, the kernel
    with that many compiles within the VMEM limit it asks for."""
    assert "tpu_custom_call" in _segment_lookahead(
        _Shapes(one_chip, F, B, rows)).as_text()
