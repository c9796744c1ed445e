import pytest

from lightgbm_tpu.config import Config, default_params, resolve_alias, str2map


def test_defaults():
    c = Config()
    assert c.num_iterations == 100
    assert c.learning_rate == 0.1
    assert c.num_leaves == 31
    assert c.max_bin == 255
    assert c.min_data_in_leaf == 20
    assert c.min_sum_hessian_in_leaf == 1e-3
    assert c.objective == "regression"
    assert c.boosting == "gbdt"
    assert c.tree_learner == "serial"


def test_alias_resolution():
    assert resolve_alias("n_estimators") == "num_iterations"
    assert resolve_alias("eta") == "learning_rate"
    assert resolve_alias("min_child_samples") == "min_data_in_leaf"
    assert resolve_alias("subsample") == "bagging_fraction"
    assert resolve_alias("colsample_bytree") == "feature_fraction"
    assert resolve_alias("reg_alpha") == "lambda_l1"
    assert resolve_alias("reg_lambda") == "lambda_l2"
    assert resolve_alias("random_state") == "seed"
    assert resolve_alias("workers") == "machines"


def test_aliases_apply():
    c = Config(n_estimators=50, eta=0.3, num_leaf=15)
    assert c.num_iterations == 50
    assert c.learning_rate == 0.3
    assert c.num_leaves == 15


def test_objective_aliases():
    assert Config(objective="mse").objective == "regression"
    assert Config(objective="mae").objective == "regression_l1"
    assert Config(app="binary").objective == "binary"
    assert Config(objective="softmax", num_class=3).objective == "multiclass"


def test_str2map_and_config_file_syntax():
    m = str2map("task=train objective=binary num_trees=10")
    assert m == {"task": "train", "objective": "binary", "num_trees": "10"}
    c = Config(**m)
    assert c.num_iterations == 10
    assert c.objective == "binary"


def test_type_coercion():
    c = Config(num_iterations="25", learning_rate="0.05", is_unbalance="true",
               metric="auc,binary_logloss")
    assert c.num_iterations == 25
    assert c.learning_rate == 0.05
    assert c.is_unbalance is True
    assert c.metric == ["auc", "binary_logloss"]


def test_conflicts():
    with pytest.raises(ValueError):
        Config(objective="multiclass", num_class=1)
    with pytest.raises(ValueError):
        Config(objective="binary", num_class=3)
    with pytest.raises(ValueError):
        Config(feature_fraction=0.0)
    with pytest.raises(ValueError):
        Config(tree_learner="bogus")


def test_default_params_covers_reference_set():
    # spot-check the reference's Config::parameter_set membership
    p = default_params()
    for name in ["max_cat_threshold", "cat_l2", "cat_smooth", "top_k",
                 "sparse_threshold", "snapshot_freq", "machines",
                 "tweedie_variance_power", "label_gain", "eval_at",
                 "num_machines", "gpu_use_dp", "refit_decay_rate"]:
        assert name in p, name


def test_parameters_doc_is_current():
    """docs/PARAMETERS.md is generated from the _PARAMS registry and must
    be regenerated when the registry changes (the reference keeps
    docs/Parameters.rst in sync the same way via its generator)."""
    import subprocess
    import sys
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "gen_params_doc.py"),
         "--check"], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_env_knobs_are_the_documented_ones():
    """Every `LIGHTGBM_TPU_*` name the library reads is described in a file
    under docs/, and every such name that docs/ or README.md describes as
    a knob is read somewhere in the repo.  (docs/KERNELS.md's table of
    deleted variants names their knobs as history.)"""
    import ast
    import glob
    import os
    import re

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    knob = re.compile(r"LIGHTGBM_TPU_[A-Z0-9_]+")

    def read_in(pattern):
        names = set()
        for path in glob.glob(os.path.join(root, pattern), recursive=True):
            for node in ast.walk(ast.parse(open(path).read())):
                if (isinstance(node, ast.Constant)
                        and isinstance(node.value, str)
                        and knob.fullmatch(node.value)):
                    names.add(node.value)
        return names

    def described_in(path):
        text = open(path).read()
        if path.endswith("KERNELS.md"):
            text = text.split("## Rejected variants")[0]
        return set(knob.findall(text))

    library = read_in("lightgbm_tpu/**/*.py")
    repo = library | read_in("*.py") | read_in("tools/*.py") \
        | read_in("benchmark/**/*.py")
    docs = set().union(*(described_in(p) for p in glob.glob(
        os.path.join(root, "docs", "*.md"))))
    readme = described_in(os.path.join(root, "README.md"))
    assert len(library) == 19, sorted(library)
    assert not library - docs, f"read, documented nowhere: {library - docs}"
    assert not (docs | readme) - repo, (
        f"documented, read nowhere: {(docs | readme) - repo}")
