"""`correct`: the comparison passes a sound run, fails the control (the
reference in bfloat16 in the program's place), and fails a run whose timed
path is broken underneath, once for each fault a training cell can have.

Each case drives `run.run_cell` as a benchmark run does, past the look for a
chip (`rehearse`), at a size a test can hold.  The limits are this size's
own: three times what sound runs read here, as the chip's are set from the
chip's readings.
"""

import numpy as np
import pytest

import importlib.util
import os

import compare
import run
from manifest import HERE, Manifest

_spec = importlib.util.spec_from_file_location(
    "bench_readings", os.path.join(HERE, "tools", "readings.py"))
readings_tool = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(readings_tool)

CELL = "higgs63-train"
# sound runs at the rehearsal's 40,000 rows read about 7e-6 / 3e-6 / 4e-5 /
# 3e-7 / 3e-7 / 2e-7 (leaf value, hessian, gain, shortfall, loss, score); the
# control reads 8e-3 / 5e-3 / 4e-2 / - / 2e-4 / 3e-3
LIMITS = {"count_gap": 1e-6, "unrouted_rows": 0, "forest_faults": 0,
          "leaf_value_gap": 2e-4, "hessian_sum_gap": 1e-4,
          "split_gain_gap": 1e-3, "best_split_shortfall": 1e-3,
          "loss_gap": 1e-5, "score_gap": 1e-5, "leaf_value_gap_p50": 1e-4}


@pytest.fixture
def man(monkeypatch):
    m = Manifest()
    held = dict(m.workload(CELL), limits=LIMITS)
    monkeypatch.setattr(Manifest, "workload", lambda self, name: held)
    return m


def drive(man, seed=5, control=False):
    code, result, extras = run.run_cell(
        man, CELL, seed, 5.0, 0, True,
        more_readings=readings_tool.control_and_faults if control else None)
    assert code == 3 and result["metrics"] == {}    # a rehearsal, no metric
    return result, extras


def test_sound_run_is_correct_and_the_control_is_not(man):
    result, extras = drive(man, control=True)
    assert result["correct"], result["compared"]
    assert result["attempted"] == 4 and result["failed"] == 0
    ctrl = dict(extras["control_bfloat16"], forest_faults=0,
                best_split_shortfall=0.0)
    ok, rows = compare.verdict(ctrl, LIMITS)
    assert not ok, rows
    sound = extras["numbers"]
    assert any(ctrl[k] >= 3 * sound[k] > 0
               for k in ("leaf_value_gap", "hessian_sum_gap",
                         "split_gain_gap", "loss_gap", "score_gap"))
    assert extras["fault_skipped_feature"]["best_split_shortfall"] \
        > LIMITS["best_split_shortfall"]
    half = dict(extras["fault_half_rows"], forest_faults=0,
                best_split_shortfall=0.0, score_gap=0.0)
    assert not compare.verdict(half, LIMITS)[0]
    assert half["count_gap"] > 0.3 and half["hessian_sum_gap"] > 0.3
    same = extras["fault_state_unchanged"]
    assert same["leaf_value_gap_p50"] > 100 * sound["leaf_value_gap_p50"]


def test_state_left_unchanged_is_not_correct(man, monkeypatch):
    """A step that returns its scores as it got them."""
    from lightgbm_tpu.ops import pallas_score
    monkeypatch.setenv("LIGHTGBM_TPU_SCORE_KERNEL", "1")
    monkeypatch.setattr(pallas_score, "score_gather_add",
                        lambda score, leaf_id, table, **kw: score)
    result, _ = drive(man)
    assert not result["correct"]
    assert result["compared"]["forest_faults"]["value"] > 0
    assert result["compared"]["leaf_value_gap"]["value"] > 0.1


def test_half_the_batch_left_out_is_not_correct(man, monkeypatch):
    """Every second row's gradient and hessian dropped before the grower."""
    import jax.numpy as jnp
    from lightgbm_tpu.objective.binary import BinaryLogloss
    real = BinaryLogloss.get_gradients

    def halved(self, score):
        g, h = real(self, score)
        keep = (jnp.arange(g.shape[-1]) % 2 == 0).astype(g.dtype)
        return g * keep, h * keep

    monkeypatch.setattr(BinaryLogloss, "get_gradients", halved)
    result, _ = drive(man)
    assert not result["correct"]
    assert result["compared"]["hessian_sum_gap"]["value"] > 0.3


def test_an_answer_altered_where_it_is_produced_is_not_correct(man,
                                                               monkeypatch):
    """One leaf value of every tree moved by 2% as the tree is fetched."""
    from lightgbm_tpu.models.tree import Tree
    real = Tree.from_grown.__func__

    def altered(cls, arrays, dataset, shrinkage):
        t = real(cls, arrays, dataset, shrinkage)
        t.leaf_value = np.array(t.leaf_value)
        t.leaf_value[0] *= 1.02
        return t

    monkeypatch.setattr(Tree, "from_grown", classmethod(altered))
    result, _ = drive(man)
    assert not result["correct"]
    assert result["compared"]["leaf_value_gap"]["value"] > 0.01


def test_a_threshold_altered_is_not_correct(man, monkeypatch):
    """One split threshold moved as the tree is fetched: rows change side."""
    from lightgbm_tpu.models.tree import Tree
    real = Tree.from_grown.__func__

    def altered(cls, arrays, dataset, shrinkage):
        t = real(cls, arrays, dataset, shrinkage)
        t.threshold = np.array(t.threshold)
        t.threshold[0] += 0.05
        return t

    monkeypatch.setattr(Tree, "from_grown", classmethod(altered))
    result, _ = drive(man)
    assert not result["correct"]
    assert result["compared"]["count_gap"]["value"] > 1e-3
