"""tools/compaction_replay.py against the grower it replays: for the same
budget the replay's counts are the grower's own ``SegStats``, tree by
tree, on the sort path and on the gather path of the compaction."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import lightgbm_tpu.models.grower_seg as gs
from lightgbm_tpu.config import Config
from lightgbm_tpu.core.dataset import TpuDataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objective import create_objective
from lightgbm_tpu.ops import pallas_histogram as ph

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import compaction_replay as cr  # noqa: E402

RB = 256
# columns: 8 sorts 7 operands; 60 packs past _MAX_SORT_OPERANDS and gathers
SHAPES = {"sort": 8, "gather": 60}


class _Grown:
    """One table of 12 whole blocks (no pad rows) and the operands of its
    segment grower, as GBDT lays them out."""

    def __init__(self, columns):
        rng = np.random.RandomState(columns)
        n = 12 * RB
        X = rng.normal(size=(n, columns))
        y = (X[:, 0] + 0.5 * X[:, 1] - 0.3 * X[:, 2] ** 2
             + 0.2 * rng.normal(size=n) > 0).astype(np.float64)
        cfg = Config(verbosity=-1, tpu_histogram_backend="pallas",
                     tpu_tree_impl="segment", tpu_row_chunk=RB,
                     num_leaves=48, min_data_in_leaf=5, objective="binary",
                     max_bin=63)
        ds = TpuDataset.from_numpy(X, y, config=cfg)
        obj = create_objective(cfg)
        obj.init(ds.metadata, ds.num_data)
        self.bst = GBDT(cfg, ds, obj)
        assert self.bst._use_segment
        self.bins = self.bst._device_bins()
        assert self.bins.shape[1] == n
        self.n, self.y, self.columns = n, y, columns
        self.path = gs.compaction_unit_costs(columns, self.bst.num_bins, n,
                                             False)["path"]

    def grow(self, monkeypatch, budget_blocks, seed):
        with monkeypatch.context() as mp:
            mp.setattr(gs, "compaction_budget_blocks",
                       lambda *a: budget_blocks)
            grower = gs.make_grow_tree_segment(
                self.bst.num_bins, self.bst.grower_params, RB)
            rng = np.random.RandomState(seed)
            g = (0.5 - self.y + 0.3 * rng.normal(size=self.n))
            h = rng.uniform(0.5, 1.5, size=self.n)
            tree, _, stats = grower(
                self.bins, jnp.asarray(g, jnp.float32),
                jnp.asarray(h, jnp.float32), jnp.ones(self.n, jnp.float32),
                self.bst.fmeta,
                jnp.ones(self.bst.fmeta.num_bin.shape[0], jnp.float32),
                jax.random.PRNGKey(seed))
        return (jax.tree_util.tree_map(np.asarray, tree),
                gs.SegStats._make(np.asarray(stats)))


@pytest.fixture(scope="module")
def grown():
    return {}


@pytest.mark.parametrize("budget_n", [9.0, 3.0, 1.0, 0.25],
                         ids=["9N", "3N", "1N", "N_over_4"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_replay_counts_are_the_growers(grown, shape, budget_n, monkeypatch):
    if shape not in grown:
        grown[shape] = _Grown(SHAPES[shape])
    case = grown[shape]
    assert case.path == shape
    K = ph.lookahead_width(case.columns, case.bst.num_bins, RB, False)
    assert K > 1
    budget = int(budget_n * 12)
    sorts = 0
    for seed in range(2):
        tree, st = case.grow(monkeypatch, budget, seed)
        assert st.compact_budget == budget and st.max_blocks == 12
        got = cr.replay_tree(cr.TreeShape.of(tree), case.n, RB, K, budget)
        assert got.splits == st.splits > K
        assert got.scanned_blocks == st.scanned_blocks
        assert got.compactions == st.compactions
        assert got.lookahead_hits == st.lookahead_hits
        assert got.route_only_blocks == st.route_only_blocks
        # the grower also fills leaves that are never split
        assert st.lookahead_hits <= got.lookahead_fills <= st.lookahead_filled
        sorts += st.compactions
    assert sorts > 0, "no compaction to replay"


def test_replay_reads_a_saved_model(tmp_path, capsys):
    """The tool's entry: a model file in, one line a budget out, the
    shape's own budget first."""
    import lightgbm_tpu as lgb
    rng = np.random.RandomState(3)
    X = rng.normal(size=(1500, 6))
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)
    bst = lgb.train({"objective": "binary", "num_leaves": 15, "max_bin": 63,
                     "verbosity": -1, "min_data_in_leaf": 5},
                    lgb.Dataset(X, y), num_boost_round=3)
    path = tmp_path / "m.txt"
    bst.save_model(str(path))
    cr.main([str(path), "--rows", "1500", "--columns", "6", "--budgets",
             "9,2"])
    import json
    head, *lines = [json.loads(x) for x in
                    capsys.readouterr().out.splitlines()
                    if x.startswith("{")]
    assert head["trees"] == 3 and head["compaction_path"] == "sort"
    assert [r["budget_N"] for r in lines] == [9.0, 2.0]
    assert all(r["splits"] == 14 for r in lines)
    assert lines[0]["scanned_N"] >= lines[1]["scanned_N"] >= 1.0
