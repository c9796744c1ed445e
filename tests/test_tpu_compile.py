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

import re

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


def _segment_plain(sh):
    return _compile(
        lambda b, w, l, s0, nb, t: ph.histogram_segment(
            b, w, l, s0, nb, t, sh.B, sh.rb, interpret=False),
        sh.bins, sh.w8, sh.leaf_id, sh.i32, sh.i32, sh.i32)


def _all(sh):
    return _compile(
        lambda b, w: ph.histogram_all(b, w, sh.B, sh.rb, interpret=False),
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


def _frontier(sh):
    K = FRONTIER_K
    return _compile(
        lambda b, w, l, bl, nb, t: ph.histogram_frontier(
            b, w, l, bl, nb, t, sh.B, sh.rb, interpret=False),
        sh.bins, sh.w8, sh.leaf_id, sh.s((sh.n // sh.rb,), jnp.int32),
        sh.i32, sh.s((K,), jnp.int32))


def _score(sh):
    return _compile(
        lambda s, l, t: score_gather_add(s, l, t, interpret=False),
        sh.s((HIGGS[2],), jnp.float32), sh.s((HIGGS[2],), jnp.int32),
        sh.s((255,), jnp.float32))


@pytest.mark.parametrize("kernel", [_all, _segment_plain, _segment_routed,
                                    _segment_lookahead, _route_window,
                                    _frontier, _score],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_default_path_kernel_compiles_at_higgs_shape(one_chip, kernel):
    sh = _Shapes(one_chip, *HIGGS)
    assert sh.rb == 32768 and sh.n == 10_518_528
    assert "tpu_custom_call" in kernel(sh).as_text()


@pytest.mark.parametrize("F,B,rows", SUITE_WIDTHS,
                         ids=["goss", "multiclass_cat", "lambdarank"])
def test_segment_kernel_compiles_at_suite_widths(one_chip, F, B, rows):
    assert ph.supported(F, B, jnp.uint8)
    assert "tpu_custom_call" in _segment_plain(
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
        _segment_plain(sh)
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


def _computations(text):
    """{name: instruction lines} of an optimized HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


_INSTR = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(")


def _split_loop_findings(text, table):
    """(whole-table copies, carry layouts) of the split loop in a compiled
    grower's text: the `while` body that holds the segment kernel's call
    and is itself inside a `while` body (the epoch loop).  A copy is an
    instruction there, or inside a fusion called from there, whose opcode
    is `copy` or `transpose` and whose result is `table` (an `f32[L,G,B,3]`
    type without layout); a scatter that cannot update in place shows as
    the `copy` the compiler inserts in front of it.  The layouts are those
    of the tables in the body's parameter and in its root tuple."""
    comps = _computations(text)
    body_of = {}
    for name, lines in comps.items():
        for line in lines:
            m = re.search(r" while\(.*body=%?([\w.\-]+)", line)
            if m:
                body_of[m.group(1)] = name
    loops = [b for b, parent in body_of.items() if parent in body_of
             and any("tpu_custom_call" in line for line in comps[b])]
    assert len(loops) == 1, loops
    found = []

    def walk(lines, via=""):
        for line in lines:
            m = _INSTR.match(line)
            if not m:
                continue
            name, result, op = m.groups()
            if table not in result:
                continue
            if op in ("copy", "transpose"):
                found.append(via + line.strip()[:160])
            elif op == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", line).group(1)
                walk(comps[called], via=f"{name} > ")

    lines = comps[loops[0]]
    walk(lines)
    carried = re.compile(re.escape(table) + r"\{[\d,]+")
    layouts = [carried.findall(line) for line in lines
               if " parameter(0)" in line or line.lstrip().startswith("ROOT")]
    return found, layouts


@pytest.mark.parametrize("F,rows", [(2000, 65_536), (28, 262_144)],
                         ids=["epsilon", "higgs"])
def test_split_loop_copies_no_histogram_table(one_chip, monkeypatch, F, rows):
    """The strict segment grower at 255 leaves x 64 bins, 16 feature tiles
    and one: the optimized split loop holds no copy, transpose or
    scatter-with-copy of a whole per-leaf histogram table (392 MB each at
    2000 columns; rows cut, the tables do not depend on them), and the
    tables keep one layout from the loop's parameter to its root tuple.
    Before the reads of `leaf_hist[leaf]` and `look_hist[leaf]` were pinned
    (`grower_seg._pinned_row`) the 2000-column loop held four such copies
    and the 28-column one two: a third of `epsilon63-train`'s iteration.
    The compaction outside the loop is steered onto its gather path, whose
    two-operand sort compiles in seconds where the 12-operand one takes
    minutes; the split loop's body is the same either way."""
    from lightgbm_tpu.models import grower_seg
    from lightgbm_tpu.models.grower import GrowerParams
    from lightgbm_tpu.ops.split import FeatureMeta, SplitParams
    monkeypatch.setattr(ph, "_interpret_default", lambda: False)
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_ROUTE", "1")
    monkeypatch.setattr(grower_seg, "_MAX_SORT_OPERANDS", 0)
    B, L = 64, 255
    tiles = ph.feature_tiles(F, B)
    sh = _Shapes(one_chip, tiles * ph.feature_tile(F, B) if tiles > 1 else F,
                 B, rows)
    assert sh.rb == ph.pick_block_rows(F, B)      # the cells' block rows
    params = GrowerParams(
        num_leaves=L, num_columns=F, hist_backend="pallas",
        split=SplitParams(min_sum_hessian_in_leaf=100.0, has_cat=False))
    grow = grower_seg.make_grow_tree_segment(B, params, sh.rb,
                                             wrap=lambda g: g)
    per_row = sh.s((sh.n,), jnp.float32)
    per_col = FeatureMeta(*(sh.s((F,), dt) for dt in (
        jnp.int32, jnp.int32, jnp.int32, jnp.bool_, jnp.int32, jnp.float32)))
    text = _compile(grow, sh.bins, per_row, per_row, per_row, per_col,
                    sh.s((F,), jnp.float32), sh.s((2,), jnp.uint32)).as_text()
    assert ph.fused_route_decisions["segment"]
    copies, layouts = _split_loop_findings(text, f"f32[{L},{F},{B},3]")
    assert not copies, "\n".join(copies)
    # leaf_hist and look_hist, at the parameter and at the root
    assert len(layouts) == 2 and len(layouts[0]) == 2, layouts
    assert layouts[0] == layouts[1], layouts


def test_chunk_program_with_evaluation_has_scopes_and_no_carry_copy(
        one_chip, monkeypatch):
    """The chunk program with in-scan evaluation (`boost/chunk_eval[16]`) at
    28 columns x 64 bins, 255 leaves, with a 500,000-row valid set and
    `metric: auc` (the `higgs63-train-eval` cell's; training rows cut, the
    evaluation does not depend on them): its device operations carry the
    `eval_walk` and `eval_metric` scopes, so a trace attributes their time,
    and the optimized module holds no copy or transpose of the `[C, Nv]`
    valid-score carry, which the scan threads and the program donates.  The
    compaction is steered onto its gather path as above (the 12-operand
    sort takes minutes to compile and is not what is looked at)."""
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.models import grower_seg
    rows, valid_rows, T = 65_536, 500_000, 16
    rng = np.random.default_rng(34)
    X = rng.standard_normal((rows, 28)).astype(np.float32)
    Xv = rng.standard_normal((valid_rows, 28)).astype(np.float32)
    params = {"objective": "binary", "metric": "auc", "max_bin": 63,
              "num_leaves": 255, "min_sum_hessian_in_leaf": 100,
              "verbose": -1, "tpu_histogram_backend": "pallas",
              "tpu_tree_impl": "segment", "tpu_boost_chunk": T}
    ds = lgb.Dataset(X, (X[:, 0] > 0).astype(np.float64),
                     params=dict(params))
    bst = lgb.Booster(params=params, train_set=ds)
    bst.add_valid(ds.create_valid(Xv, (Xv[:, 0] > 0).astype(np.float64)),
                  "valid_0")
    g = bst.gbdt
    assert g._use_segment and g.grower_params.hist_backend == "pallas"
    assert g.setup_inscan_eval(False) is None
    g._boost_from_average()
    g._build_fused_step()       # the kernel gates decide in interpret mode
    monkeypatch.setattr(ph, "_interpret_default", lambda: False)
    monkeypatch.setenv("LIGHTGBM_TPU_FUSED_ROUTE", "1")
    monkeypatch.setattr(grower_seg, "_MAX_SORT_OPERANDS", 0)
    carry = [jnp.asarray(np.asarray(v, np.float32)) for v in g.valid_scores]
    assert [c.shape for c in carry] == [(1, valid_rows)]
    args = (g.train_score, g._key, carry, g.bag_weight, g._device_bins(),
            g.fmeta, g._full_fmask, jnp.float32(g.shrinkage_rate),
            g._obj_arrs, g._inscan.vbins, g._inscan.arrays)
    described = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    text = g._get_chunk_fn(T, with_eval=True).lower(
        *described).compile().as_text()
    scoped = re.findall(r'op_name="jit\(chunk_run_eval\)/while/body/'
                        r'[^"]*?/(eval_walk|eval_metric)/', text)
    assert scoped.count("eval_walk") and scoped.count("eval_metric")
    copies = [line.strip()[:160] for line in text.splitlines()
              if (m := _INSTR.match(line))
              and m.group(3) in ("copy", "transpose")
              and f"f32[1,{valid_rows}]" in m.group(2)]
    assert not copies, "\n".join(copies)
