"""Query groups and an objective by file: the plain lambdarank reference
(`objectives/lambdarank.py`) against a loop over pairs and against the
program's objective, the `mslr_proxy` rows, and a rehearsal of a ranking
configuration from the test manifest (`data/ranking/manifest.json`): sound
it is `correct`, and each planted fault fails by the number that names it.
An objective with neither a branch nor a file is refused before training.
"""

import math
import os

import numpy as np
import pytest

import compare
import datagen
import reference
import run
from manifest import HERE, Manifest, load_module

lambdarank = load_module(os.path.join(HERE, "objectives", "lambdarank.py"),
                         "objective_lambdarank_test")
readings_tool = load_module(os.path.join(HERE, "tools", "readings.py"),
                            "tool_readings_ranking")

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "ranking")
MANIFEST = os.path.join(DATA, "manifest.json")
CELL = "mslr-narrow-train"


# ------------------------------------------------ the reference, by the loop

def loop_over_pairs(y, score, group, params):
    """g, h and each query's loss, one pair at a time in Python floats: the
    equations of `objectives/lambdarank.py`'s docstring and nothing else."""
    sigma = float(params.get("sigmoid", 1.0))
    k = int(params.get("max_position", 20))
    norm = bool(params.get("lambdamart_norm", True))
    gain = [2.0 ** l - 1.0 for l in range(31)]
    g, h, losses = np.zeros(len(y)), np.zeros(len(y)), []
    start = 0
    for n in group:
        rows = list(range(start, start + n))
        start += n
        s = {i: float(score[i]) for i in rows}
        lab = {i: int(y[i]) for i in rows}
        by_score = sorted(rows, key=lambda i: -s[i])     # stable: row order
        pos = {i: r for r, i in enumerate(by_score)}
        best = sorted((lab[i] for i in rows), reverse=True)[:k]
        max_dcg = sum(gain[l] / math.log2(2 + r) for r, l in enumerate(best))
        inv = 1.0 / max_dcg if max_dcg > 0 else 0.0
        spread = max(s.values()) != min(s.values())
        lam_sum, loss = 0.0, 0.0
        gq, hq = {i: 0.0 for i in rows}, {i: 0.0 for i in rows}
        for i in rows:
            for j in rows:
                if lab[i] <= lab[j]:
                    continue
                delta = s[i] - s[j]
                w = ((gain[lab[i]] - gain[lab[j]]) * inv
                     * abs(1 / math.log2(2 + pos[i])
                           - 1 / math.log2(2 + pos[j])))
                loss += w * math.log1p(math.exp(-sigma * delta))
                if norm and spread:
                    w /= 0.01 + abs(delta)
                rho = 1.0 / (1.0 + math.exp(sigma * delta))
                lam = -sigma * w * rho
                hh = sigma * sigma * w * rho * (1.0 - rho)
                gq[i] += lam
                gq[j] -= lam
                hq[i] += hh
                hq[j] += hh
                lam_sum += -2.0 * lam
        factor = (math.log2(1.0 + lam_sum) / lam_sum
                  if norm and lam_sum > 0 else 1.0)
        for i in rows:
            g[i], h[i] = gq[i] * factor, hq[i] * factor
        losses.append(loss)
    return g, h, np.array(losses)


def hand_made():
    """Queries that each stress one rule: one row; all labels 0; ties (all
    scores equal, and two equal pairs among others); a query of 30 rows,
    longer than max_position 20 (and 5); two rows of one label; a long query
    with every grade."""
    rs = np.random.default_rng(5)
    queries = [
        ([3], [0.4]),                                   # one row
        ([0, 0, 0, 0], [0.3, -1.0, 2.0, 0.5]),          # no relevant row
        ([2, 0, 1, 0, 4], [0.0] * 5),                   # all tied
        ([1, 3, 0, 3, 2], [0.5, 0.5, -0.2, 0.5, 1.5]),  # some ties
        (list(rs.integers(0, 5, 30)), list(rs.normal(size=30))),
        ([1, 1], [0.2, -0.7]),                          # no pair
        (list(rs.integers(0, 5, 70)), list(rs.normal(size=70) * 3)),
    ]
    y = np.concatenate([q[0] for q in queries]).astype(np.float64)
    score = np.concatenate([q[1] for q in queries])
    group = np.array([len(q[0]) for q in queries], np.int64)
    return y, score, group


@pytest.mark.parametrize("params", [
    {}, {"lambdamart_norm": False}, {"max_position": 5},
    {"sigmoid": 2.0, "lambdamart_norm": False},
], ids=["norm", "no-norm", "max_position-5", "sigmoid-2-no-norm"])
def test_reference_is_the_loop_over_pairs(params):
    """float64 on the host against the loop: the same sums in another order,
    so to float64 rounding (1e-12 of the query's largest entry)."""
    y, score, group = hand_made()
    want_g, want_h, want_loss = loop_over_pairs(y, score, group, params)
    g, h, loss = lambdarank._per_query(y, score, params, {"group": group})
    for got, want in ((g, want_g), (h, want_h), (loss, want_loss)):
        assert np.allclose(got, want, rtol=1e-12,
                           atol=1e-12 * max(1.0, np.abs(want).max()))
    starts = np.cumsum(group) - group
    assert g[starts[0]] == 0 and np.all(g[starts[1]:starts[2]] == 0)
    assert np.any(g != 0) and np.all(h >= 0)


def test_device_float32_is_the_float64_reference():
    """`gradients` and `loss` compute in float32 on the device: the float64
    numbers to float32 rounding.  A row's g is a sum over its query's pairs
    (69 here), which XLA adds pairwise: log2(70) x 2^-24 x the terms'
    magnitudes, ~4e-7 of the query's largest entry, with the normalisation's
    log2 and one product more; 2e-6 allows that five times over (read:
    1.4e-7 for g, 1.7e-7 for h)."""
    y, score, group = hand_made()
    ex = {"group": group}
    g64, h64, l64 = lambdarank._per_query(y, score, {}, ex)
    g, h = lambdarank.gradients(y, score.astype(np.float32), {}, ex)
    assert g.dtype == np.float32 and h.dtype == np.float32
    for got, want in ((g, g64), (h, h64)):
        assert np.allclose(got, want, rtol=2e-6,
                           atol=2e-6 * np.abs(want).max())
    assert lambdarank.loss(y, score, {}, ex) == pytest.approx(l64.mean(),
                                                              rel=2e-6)
    assert lambdarank.init_score(y, {}, ex) == 0.0


def test_labels_outside_the_gain_table_are_refused():
    with pytest.raises(ValueError):
        lambdarank._per_query(np.array([0.0, 31.0]), np.zeros(2), {},
                              {"group": np.array([2])})
    with pytest.raises(ValueError):
        lambdarank._per_query(np.array([0.0, 1.5]), np.zeros(2), {},
                              {"group": np.array([2])})


@pytest.mark.parametrize("scores", ["zero", "random"])
def test_the_programs_lambdas_are_the_references(scores):
    """`objective/rank.py` (the program) against the reference on the CPU, on
    `mslr_proxy` queries (sizes up to 1,251).  Both are float32 with the
    same equations; they differ in the order of their sums over a query's
    pairs (up to 1,250 terms a row, added pairwise: log2(1250) x 2^-24 =
    6e-7 of the terms' magnitudes) and in where the normalisation
    multiplies, so each row's g and h agree to 4e-6 of its query's largest
    |g| and h (read: 1.5e-7; each against the float64 reference 3.8e-7).
    At zero scores every score ties, and both rank ties in row order."""
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    X, y, ex = datagen.make({"generator": "mslr_proxy", "features": 5},
                            6000, np.random.default_rng(2 ** 31 + 3))
    params = {"objective": "lambdarank", "verbose": -1}
    ds = lgb.Dataset(X, y, params=dict(params), group=ex["group"])
    obj = lgb.Booster(params=dict(params), train_set=ds).gbdt.objective
    score = (np.zeros(len(y), np.float32) if scores == "zero" else
             np.random.default_rng(9).normal(size=len(y)).astype(np.float32))
    pg, ph = (np.asarray(a)[:len(y)]
              for a in obj.get_gradients(jnp.asarray(score)))
    rg, rh = lambdarank.gradients(y, score, params, ex)
    q = datagen.query_of_rows(ex["group"])
    starts = np.cumsum(ex["group"]) - ex["group"]
    scale_g = np.maximum.reduceat(np.abs(rg), starts)[q]
    scale_h = np.maximum.reduceat(rh, starts)[q]
    live = scale_h > 0
    assert live.mean() > 0.9
    assert np.all(np.abs(pg - rg)[live] <= 4e-6 * scale_g[live])
    assert np.all(np.abs(ph - rh)[live] <= 4e-6 * scale_h[live])
    assert np.all(pg[~live] == 0) and np.all(rg[~live] == 0)


def test_sharded_reference_gives_the_one_device_numbers():
    """The objective's g and h go up in the blocks' layout, sharded as the
    rows are: over four devices the readings are the one device's, to the
    float64 additions of the shards' sums in another grouping (1e-6 of each
    number, or of the largest of its kind: a candidate's gain is a
    difference of G^2 / H terms of its node's size)."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("fewer than four devices")
    cost = load_module(os.path.join(HERE, "tools", "reference_cost.py"),
                       "reference_cost_ranking")
    X, y, ex = datagen.make({"generator": "mslr_proxy", "features": 12},
                            40_000, np.random.default_rng(17))
    rs = np.random.default_rng(3)
    trees = [cost.synthetic_tree(rs, 12, 15, np.linspace(-1.5, 1.5, 31))
             for _ in range(3)]
    cand = reference.candidate_thresholds(trees, 12, 64)
    params = {"objective": "lambdarank", "learning_rate": 0.1,
              "num_leaves": 15}
    one = run.follow(X, y, params, trees, cand, sum_all=trees, extras=ex)
    four = run.follow(X, y, params, trees, cand, sum_all=trees, extras=ex,
                      devices=jax.devices()[:4])
    for a, b in zip(one[1], four[1]):
        assert np.array_equal(a.leaf_c, b.leaf_c)
        for k in ("leaf_g", "leaf_h", "leaf_value", "gain", "best_gain"):
            want = getattr(a, k)
            np.testing.assert_allclose(getattr(b, k), want, rtol=1e-6,
                                       atol=1e-6 * np.abs(want).max(),
                                       err_msg=k)
        assert b.loss == pytest.approx(a.loss, rel=1e-6)
        assert b.tested_loss == pytest.approx(a.tested_loss, rel=1e-6)
    np.testing.assert_allclose(four[2], one[2], rtol=1e-6, atol=1e-7)
    assert one[3] == four[3] == 0.0


# ------------------------------------------------------------- the rows

def test_mslr_proxy_rows_groups_and_grades():
    data = {"generator": "mslr_proxy", "features": 136}
    X, y, ex = datagen.make(data, 60_000, np.random.default_rng(2 ** 31 + 9))
    X2, y2, ex2 = datagen.make(data, 60_000,
                               np.random.default_rng(2 ** 31 + 9))
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    assert np.array_equal(ex["group"], ex2["group"])
    assert X.shape == (60_000, 136) and X.dtype == np.float32
    group = ex["group"]
    assert group.dtype == np.int64 and group.sum() == 60_000
    assert group.min() >= 1 and group.max() <= 1251
    share = np.bincount(y.astype(int), minlength=5) / len(y)
    assert np.allclose(share, [0.51, 0.33, 0.13, 0.02, 0.01], atol=0.02)
    gen = load_module(os.path.join(HERE, "generators", "mslr_proxy.py"),
                      "generator_mslr_proxy_test")
    sizes = gen.query_sizes(np.random.default_rng(4), 2_000_000)
    assert 110 < sizes[:-1].mean() < 130 and sizes.max() <= 1251
    # a rehearsal's rows end with a whole query: the sizes sum to them
    assert datagen.make(data, 40_000, np.random.default_rng(1))[2][
        "group"].sum() == 40_000


def test_rows_kept_keep_their_queries():
    ex = {"group": np.array([3, 1, 4], np.int64), "other": 1}
    assert list(datagen.keep_rows(ex, np.s_[::2])["group"]) == [2, 2]
    assert list(datagen.keep_rows(ex, np.s_[:5])["group"]) == [3, 1, 1]
    assert datagen.keep_rows(ex, np.s_[:5])["other"] == 1
    assert datagen.keep_rows({}, np.s_[::2]) == {}


def test_groups_that_do_not_add_up_are_refused(tmp_path):
    (tmp_path / "generators").mkdir()
    (tmp_path / "generators" / "bad_groups.py").write_text(
        "import numpy as np\n"
        "def make(rng, rows):\n"
        "    return (np.zeros((rows, 2), np.float32), np.zeros(rows),\n"
        "            {'group': np.array([rows - 1], np.int64)})\n")
    with pytest.raises(ValueError):
        datagen.make({"generator": "bad_groups"}, 10,
                     np.random.default_rng(1), here=str(tmp_path))


# ------------------------------------------------------- the rehearsal

@pytest.fixture
def man():
    return Manifest(doc=MANIFEST)


def drive(man, seed=2 ** 31 + 21, more_readings=None):
    code, result, extras = run.run_cell(man, CELL, seed, 5.0, 0, True,
                                        more_readings=more_readings)
    assert code == 3 and result["metrics"] == {}    # a rehearsal, no metric
    return result, extras


def test_the_test_manifest_is_found():
    man = Manifest(doc=MANIFEST)
    for w in man.doc["workloads"]:
        cfg = man.config(w["config"])
        assert cfg["params"]["objective"] == "lambdarank"
        assert cfg["data"]["generator"] == "mslr_proxy"
        assert man.workload(w["name"])["limits"]
        assert "warmup_chunks" in man.traffic(w["traffic"])
    assert man.config("mslr-test")["data"]["features"] == 136
    # BENCHMARK.json holds no cell of it
    assert "mslr-test-train" not in [w["name"] for w in
                                      Manifest().doc["workloads"]]


def test_sound_rehearsal_is_correct_and_the_control_is_not(man):
    result, extras = drive(man, more_readings=readings_tool.control_and_faults)
    assert result["correct"], result["compared"]
    assert result["attempted"] == 4 and result["failed"] == 0
    limits = man.workload(CELL)["limits"]
    ctrl = dict(extras["control_bfloat16"], forest_faults=0,
                best_split_shortfall=0.0)
    assert not compare.verdict(ctrl, limits)[0]
    half = dict(extras["fault_half_rows"], forest_faults=0,
                best_split_shortfall=0.0, score_gap=0.0)
    assert not compare.verdict(half, limits)[0]
    assert extras["fault_skipped_feature"]["best_split_shortfall"] \
        > limits["best_split_shortfall"]


def test_groups_shifted_by_one_row_are_not_correct(man, monkeypatch):
    """The program's query boundaries each one row later than the rows'."""
    from lightgbm_tpu.core.metadata import Metadata
    real = Metadata.set_query

    def shifted(self, query):
        query = np.array(query, np.int64)
        query[0] += 1
        query[-1] -= 1
        return real(self, query)

    monkeypatch.setattr(Metadata, "set_query", shifted)
    result, _ = drive(man)
    assert not result["correct"]
    assert result["compared"]["hessian_sum_gap"]["value"] \
        > result["compared"]["hessian_sum_gap"]["limit"]


def test_lambdas_without_the_ndcg_weight_are_not_correct(man, monkeypatch):
    """Every pair weighs the same: RankNet's lambdas, normalised as before."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.objective import rank

    @jax.jit
    def unweighted(scores, labels, mask, inv_max_dcg, gains, sigmoid, norm):
        s = jnp.where(mask, scores, -1e30)
        pair = (mask[:, :, None] & mask[:, None, :]
                & (labels[:, :, None] > labels[:, None, :]))
        delta = s[:, :, None] - s[:, None, :]
        rho = 1.0 / (1.0 + jnp.exp(sigmoid * delta))
        lam = jnp.where(pair, -sigmoid * rho, 0.0)
        hes = jnp.where(pair, sigmoid * sigmoid * rho * (1.0 - rho), 0.0)
        total = -2.0 * jnp.sum(lam, axis=(1, 2))
        factor = jnp.where(total > 0, jnp.log2(1.0 + total)
                           / jnp.maximum(total, 1e-20), 1.0)[:, None]
        return ((jnp.sum(lam, axis=2) - jnp.sum(lam, axis=1)) * factor,
                (jnp.sum(hes, axis=2) + jnp.sum(hes, axis=1)) * factor)

    monkeypatch.setattr(rank, "_chunk_lambdas", unweighted)
    result, _ = drive(man)
    assert not result["correct"]
    assert result["compared"]["leaf_value_gap"]["value"] \
        > result["compared"]["leaf_value_gap"]["limit"]


def test_leaf_values_scaled_are_not_correct(man, monkeypatch):
    """Every leaf value of every tree 1.001 times itself as the tree is
    fetched."""
    from lightgbm_tpu.models.tree import Tree
    real = Tree.from_grown.__func__

    def scaled(cls, arrays, dataset, shrinkage):
        t = real(cls, arrays, dataset, shrinkage)
        t.leaf_value = np.array(t.leaf_value) * 1.001
        return t

    monkeypatch.setattr(Tree, "from_grown", classmethod(scaled))
    result, _ = drive(man)
    assert not result["correct"]
    assert result["compared"]["leaf_value_gap"]["value"] \
        > result["compared"]["leaf_value_gap"]["limit"]


def test_an_objective_with_no_branch_and_no_file_is_refused(man,
                                                            monkeypatch):
    import lightgbm_tpu as lgb
    config = man.config("mslr-narrow")
    config["params"]["objective"] = "regression"
    monkeypatch.setattr(Manifest, "config", lambda self, name: config)
    monkeypatch.setattr(lgb, "train", lambda *a, **k: pytest.fail("trained"))
    monkeypatch.setattr(datagen, "make",
                        lambda *a, **k: pytest.fail("rows drawn"))
    with pytest.raises(SystemExit, match="no objective 'regression'"):
        drive(man)
    for name in ("regression", None, "../reference"):
        with pytest.raises(SystemExit):
            reference.objective({"objective": name})
    assert reference.objective({"objective": "binary"}) is None
    assert reference.objective({"objective": "lambdarank"}).gradients
