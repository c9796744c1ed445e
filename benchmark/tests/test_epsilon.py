"""The `epsilon63` configuration and its cell come as new files found by
name; the generator is deterministic in the seed, asks the program before it
draws a row, and its rows train a model at 300 columns on which the
reference agrees with the float64 witness; the three new readers read what
the program recorded and nothing where it recorded nothing."""

import json
import os

import numpy as np
import pytest

import compare
import datagen
import reference
import witness
from manifest import HERE, ROOT, Manifest


def test_new_files_are_found_by_name():
    man = Manifest()
    cell = man.cell("epsilon63-train")
    assert cell == {k: cell[k] for k in ("name", "config", "traffic",
                                         "chips", "why")}
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("epsilon63", "train", 1)
    config = man.config("epsilon63")
    higgs = man.config("higgs63")
    assert config["params"] == higgs["params"]
    assert config["data"]["generator"] == "epsilon_proxy"
    assert (config["data"]["features"], config["data"]["rows"]) == \
        (2000, 1_100_000)
    assert config["published"] == {"rows": 400_000, "num_iterations": 500}
    assert config["reduced"] == ["rows", "num_iterations"]
    assert set(config["assumed"]) >= {"data", "precision",
                                      "min_data_in_leaf"}
    assert callable(datagen.generator("epsilon_proxy"))
    workload = man.workload("epsilon63-train")
    limits = man.workload("higgs63-train")["limits"]
    assert set(workload["limits"]) == set(limits)
    for name, limit in workload["limits"].items():
        assert limit <= limits[name], name       # none looser than higgs63's
    assert max(1, int(50 // workload["chunk_seconds"])) == 1
    mine = {m["name"] for m in man.metrics("per_layer", "epsilon63-train")}
    assert {"hist_feature_tiles", "bin_find_ms_per_column",
            "leaf_hist_gb"} <= mine
    assert {"hist_roofline", "train_mfu", "peak_hbm_gb", "bin_s"} <= mine
    for name in mine:
        assert callable(man.reader(name))
    e2e = {m["name"] for m in man.metrics("end_to_end", "epsilon63-train")}
    assert e2e == {"train_s_per_iter", "setup_s"}


def test_benchmark_json_only_gained_entries():
    """Every entry the accepted file had stands where it stood, unchanged;
    what came since follows (`HEAD`'s file is the parent's while a PR is
    uncommitted and the tree's own after; skipped where git has none)."""
    import subprocess
    try:
        old = json.loads(subprocess.run(
            ["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT,
            capture_output=True, check=True, text=True).stdout)
    except (subprocess.CalledProcessError, FileNotFoundError):
        pytest.skip("no git history in this checkout")
    new = Manifest().doc
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        assert new[key] == old[key]
    for key in ("configs", "workloads", "per_layer"):
        assert len(new[key]) >= len(old[key])
        assert new[key][:len(old[key])] == old[key]


def test_generator_is_deterministic_and_of_the_epsilon_shape():
    data = {"generator": "epsilon_proxy", "features": 300, "bins": 64,
            "noise": 0.25}
    X, y = datagen.make(data, 70_000, np.random.default_rng(2 ** 31 + 11))
    X2, y2 = datagen.make(data, 70_000, np.random.default_rng(2 ** 31 + 11))
    assert X.dtype == np.float32 and X.shape == (70_000, 300)
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    X3, _ = datagen.make(data, 1000, np.random.default_rng(5))
    assert not np.array_equal(X[:1000], X3)
    assert np.allclose((X.astype(np.float64) ** 2).sum(axis=1), 1.0,
                       atol=1e-5)                       # unit rows
    assert set(np.unique(y)) == {0.0, 1.0} and 0.45 < y.mean() < 0.55
    # the rule is dense: no handful of columns carries the label
    corr = np.abs([np.corrcoef(X[:, f], y)[0, 1] for f in range(300)])
    assert (corr > 0.01).sum() > 150 and corr.max() < 0.2


def test_generator_asks_the_program_first(monkeypatch):
    from lightgbm_tpu.ops import pallas_histogram as ph
    monkeypatch.setattr(ph, "supported", lambda *a: False)

    class NoDraw:
        def __getattr__(self, name):
            raise AssertionError(f"a row was drawn ({name})")

    with pytest.raises(SystemExit) as e:
        datagen.make({"generator": "epsilon_proxy", "features": 2000,
                      "bins": 64}, 10, NoDraw())
    assert "2000 columns x 64 bins" in str(e.value) and e.value.code != 0


@pytest.fixture(scope="module")
def trained():
    import lightgbm_tpu as lgb
    X, y = datagen.make({"generator": "epsilon_proxy", "features": 300,
                         "bins": 64, "noise": 0.25}, 6000,
                        np.random.default_rng(30))
    params = {"objective": "binary", "max_bin": 63, "num_leaves": 15,
              "learning_rate": 0.1, "min_sum_hessian_in_leaf": 5.0,
              "verbose": -1}
    bst = lgb.train(dict(params), lgb.Dataset(X, y, params=dict(params)),
                    num_boost_round=4, verbose_eval=False)
    return X, y, params, reference.parse_model(bst.model_to_string())


def test_follower_agrees_with_the_witness_on_epsilon_rows(trained):
    X, y, params, trees = trained
    assert len({int(f) for t in trees for f in t.split_feature}) > 20
    cand = reference.candidate_thresholds(trees, X.shape[1], 64)
    fol = reference.Follower(X, y, params, cand, params["num_leaves"])
    host = witness.HostFollower(X, y, params)
    try:
        for i, tree in enumerate(trees[:3]):
            facts = compare.facts_of_tree(tree,
                                          fol.init_score if i == 0 else 0.0)
            r = fol.step(tree, facts.leaf_value)
            w = host.step(tree)
            assert r.unrouted == 0
            assert np.array_equal(r.leaf_c, w["count"])
            assert compare._worst_gap(r.leaf_h, w["h"]) < 2e-7
            assert compare._worst_gap(r.leaf_g, w["g"]) < 2e-6
            assert compare._worst_gap(r.leaf_value, w["value"]) < 2e-6
            assert compare._worst_gap(facts.leaf_value, r.leaf_value) < 1e-4
        summed = fol.sum_forest(trees)
    finally:
        fol.close()
    want = sum(np.asarray(t.leaf_value)[host.leaves_of(t)] for t in trees)
    assert np.max(np.abs(summed - want)) < 1e-6


def test_new_readers_read_what_the_program_recorded():
    from lightgbm_tpu.utils.telemetry import TELEMETRY
    man = Manifest()
    ctx = {"features": 2000}
    TELEMETRY.reset()
    for name in ("hist_feature_tiles", "leaf_hist_gb"):
        assert man.reader(name)(ctx) is None        # a program without them
    assert man.reader("bin_find_ms_per_column")({"features": 0}) is None
    TELEMETRY.gauge_set("seg/feature_tiles", 16)
    TELEMETRY.gauge_set("seg/leaf_hist_bytes", 802_160_640)
    assert man.reader("hist_feature_tiles")(ctx) == 16
    assert man.reader("leaf_hist_gb")(ctx) == pytest.approx(0.80216064)
    from lightgbm_tpu.utils.phase import GLOBAL_TIMER
    with GLOBAL_TIMER.phase("bin_find"):
        pass
    ms = man.reader("bin_find_ms_per_column")(ctx)
    assert ms is not None and ms >= 0
    TELEMETRY.reset()
