"""bench.py impl A/B selection logic (pure-function tests; the measurement
itself runs only on a TPU, and refuses anything else)."""

import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ab_picks_faster_when_quality_holds(monkeypatch):
    bench = _load_bench()
    monkeypatch.delenv("LIGHTGBM_TPU_IMPL", raising=False)
    base = {"per_iter": 0.5, "rows": 100, "backend": "tpu",
            "impl": "segment", "auc": 0.900}
    monkeypatch.setattr(bench, "run_tier",
                        lambda *a, **k: {"per_iter": 0.2, "rows": 100,
                                         "backend": "tpu",
                                         "impl": "frontier",
                                         "auc": 0.899})
    out = bench.maybe_ab_frontier(base, "tpu", 100, 1, 2, 60)
    assert out["impl"] == "frontier"


def test_ab_rejects_quality_regression(monkeypatch):
    bench = _load_bench()
    monkeypatch.delenv("LIGHTGBM_TPU_IMPL", raising=False)
    base = {"per_iter": 0.5, "rows": 100, "backend": "tpu",
            "impl": "segment", "auc": 0.900}
    monkeypatch.setattr(bench, "run_tier",
                        lambda *a, **k: {"per_iter": 0.2, "rows": 100,
                                         "backend": "tpu",
                                         "impl": "frontier",
                                         "auc": 0.850})
    out = bench.maybe_ab_frontier(base, "tpu", 100, 1, 2, 60)
    assert out["impl"] == "segment"


def test_ab_rejects_slower_frontier(monkeypatch):
    bench = _load_bench()
    monkeypatch.delenv("LIGHTGBM_TPU_IMPL", raising=False)
    base = {"per_iter": 0.5, "rows": 100, "backend": "tpu",
            "impl": "segment", "auc": 0.900}
    monkeypatch.setattr(bench, "run_tier",
                        lambda *a, **k: {"per_iter": 0.9, "rows": 100,
                                         "backend": "tpu",
                                         "impl": "frontier",
                                         "auc": 0.905})
    out = bench.maybe_ab_frontier(base, "tpu", 100, 1, 2, 60)
    assert out["impl"] == "segment"


def test_ab_skips_cpu_and_pinned_impl(monkeypatch):
    bench = _load_bench()
    base = {"per_iter": 0.5, "rows": 100, "backend": "cpu",
            "impl": "fused-onehot", "auc": 0.9}
    calls = []
    monkeypatch.setattr(bench, "run_tier",
                        lambda *a, **k: calls.append(1))
    assert bench.maybe_ab_frontier(base, "cpu", 100, 1, 2, 60) is base
    monkeypatch.setenv("LIGHTGBM_TPU_IMPL", "segment")
    assert bench.maybe_ab_frontier(base, "tpu", 100, 1, 2, 60) is base
    assert not calls


def test_ab_survives_child_failure(monkeypatch):
    bench = _load_bench()
    monkeypatch.delenv("LIGHTGBM_TPU_IMPL", raising=False)
    base = {"per_iter": 0.5, "rows": 100, "backend": "tpu",
            "impl": "segment", "auc": 0.9}

    def boom(*a, **k):
        raise RuntimeError("tier child rc=1")
    monkeypatch.setattr(bench, "run_tier", boom)
    assert bench.maybe_ab_frontier(base, "tpu", 100, 1, 2, 60) is base


def test_ab_skips_when_measured_backend_is_cpu(monkeypatch):
    """A tpu tier whose child silently fell back to the CPU backend must
    not trigger a second meaningless CPU A/B run."""
    bench = _load_bench()
    monkeypatch.delenv("LIGHTGBM_TPU_IMPL", raising=False)
    base = {"per_iter": 30.0, "rows": 100, "backend": "cpu",
            "impl": "fused-onehot", "auc": 0.9}
    calls = []
    monkeypatch.setattr(bench, "run_tier",
                        lambda *a, **k: calls.append(1))
    assert bench.maybe_ab_frontier(base, "tpu", 100, 1, 2, 60) is base
    assert not calls


def test_ab_chunked_picks_faster_and_pins_impl(monkeypatch):
    bench = _load_bench()
    monkeypatch.delenv("LIGHTGBM_TPU_BOOST_CHUNK", raising=False)
    monkeypatch.delenv("LIGHTGBM_TPU_IMPL", raising=False)
    base = {"per_iter": 0.5, "rows": 100, "backend": "tpu",
            "impl": "frontier", "auc": 0.900, "chunk": 1}
    seen = {}

    def fake_run_tier(*a, **k):
        seen.update(k)
        return {"per_iter": 0.3, "rows": 100, "backend": "tpu",
                "impl": "frontier", "auc": 0.900, "chunk": 4}
    monkeypatch.setattr(bench, "run_tier", fake_run_tier)
    out = bench.maybe_ab_chunked(base, "tpu", 100, 2, 4, 60)
    assert out["chunk"] == 4
    # both sides of the comparison must run the same grower
    assert seen["impl_env"] == "frontier"
    assert seen["chunk_env"] == "4"


def test_ab_chunked_skips_pinned_env_and_rejects_slower(monkeypatch):
    bench = _load_bench()
    monkeypatch.delenv("LIGHTGBM_TPU_IMPL", raising=False)
    base = {"per_iter": 0.5, "rows": 100, "backend": "cpu",
            "impl": "fused-onehot", "auc": 0.9, "chunk": 1}
    calls = []
    monkeypatch.setenv("LIGHTGBM_TPU_BOOST_CHUNK", "4")
    monkeypatch.setattr(bench, "run_tier",
                        lambda *a, **k: calls.append(1))
    assert bench.maybe_ab_chunked(base, "cpu", 100, 1, 2, 60) is base
    assert not calls
    monkeypatch.delenv("LIGHTGBM_TPU_BOOST_CHUNK")
    monkeypatch.setattr(
        bench, "run_tier",
        lambda *a, **k: {"per_iter": 0.8, "rows": 100, "backend": "cpu",
                         "impl": "fused-onehot", "auc": 0.9, "chunk": 2})
    assert bench.maybe_ab_chunked(base, "cpu", 100, 1, 2, 60) is base


def test_measurement_refuses_without_a_tpu(capsys):
    """A measurement child on another backend exits non-zero and says why;
    it produces no record to be mistaken for the chip's."""
    import pytest

    from lightgbm_tpu.utils import require_tpu
    with pytest.raises(SystemExit) as e:
        require_tpu("bench.py")
    assert "bench.py: needs a TPU; JAX found cpu" in str(e.value.code)
    assert capsys.readouterr().out == ""
