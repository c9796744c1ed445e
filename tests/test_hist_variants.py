"""Histogram kernel variants (packed accumulator, round-carry staging,
one-hot builds — ops/pallas_histogram.py r6).

Three independently env-gated variants with distinct contracts:

  * packed int16 accumulator (LIGHTGBM_TPU_PACKED_ACC): the count
    channel is EXACT, grad/hess per bin carry stochastic-rounding
    quantization error bounded by scale x (count + 1) — trained models
    must reach quality parity, not bit-identity;
  * round-carry leaf-hist staging (LIGHTGBM_TPU_HIST_STAGE): pure data
    movement, must be BIT-identical;
  * one-hot build alternatives (LIGHTGBM_TPU_ONEHOT_BUILD): same
    [nf*B, chunk] matrix into the same dot_general, must be
    BIT-identical.

Every gate falls back to the baseline path when its self-check fails.
"""

import numpy as np
import pytest

import lightgbm_tpu.ops.pallas_histogram as ph
from lightgbm_tpu.config import Config
from lightgbm_tpu.core.dataset import TpuDataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objective import create_objective


def _train(X, y, impl, monkeypatch, env=(), cat_feats=(), n_iters=3,
           **params):
    for k, v in env:
        monkeypatch.setenv(k, v)
    cfg = Config(verbosity=-1, tpu_histogram_backend="pallas",
                 tpu_tree_impl=impl, **params)
    ds = TpuDataset.from_numpy(X, y, config=cfg,
                               categorical_features=list(cat_feats))
    obj = create_objective(cfg)
    obj.init(ds.metadata, ds.num_data)
    bst = GBDT(cfg, ds, obj)
    for _ in range(n_iters):
        bst.train_one_iter()
    for k, _ in env:
        monkeypatch.delenv(k, raising=False)
    return bst


def _rand_stream(rng, n):
    grad = rng.standard_normal(n).astype(np.float32)
    hess = rng.uniform(0.5, 1.5, n).astype(np.float32)
    # fractional member exercises the f32-bitcast count lane (GOSS)
    member = np.where(rng.random(n) < 0.2, 0.0,
                      np.where(rng.random(n) < 0.3, 0.25,
                               1.0)).astype(np.float32)
    return grad, hess, member


def test_quantize_count_exact_and_error_bound(rng):
    """Count channel exact; grad/hess per-bin error within the
    stochastic-rounding bound scale x (count + 1)."""
    import jax.numpy as jnp
    nrng = np.random.default_rng(5)
    F, B, rb, n = 6, 32, 512, 2048
    binsT = jnp.asarray(nrng.integers(0, B, (F, n)), jnp.uint8)
    grad, hess, member = _rand_stream(nrng, n)
    g, h, m = map(jnp.asarray, (grad, hess, member))
    w8 = ph.pack_channels(g, h, m)
    ref = np.asarray(ph.unpack_hist(ph.histogram_all(binsT, w8, B, rb)))
    w2, scales, clips = ph.quantize_pack_channels(g, h, m)
    got = np.asarray(ph.unpack_hist_packed(
        ph.histogram_all(binsT, w2, B, rb), scales))
    assert np.array_equal(got[..., 2], ref[..., 2]), "count must be exact"
    sc = np.asarray(scales)
    cnt = ref[..., 2]
    for ch in (0, 1):
        bound = sc[ch] * (cnt + 1.0) + 1e-4
        assert np.all(np.abs(got[..., ch] - ref[..., ch]) <= bound), ch
    assert int(clips) >= 1   # saturated-lane count (max lane by scale)


def test_quantize_zero_weight_rows_stay_zero():
    """member == 0 rows (bagging/pad rows) must quantize to exact zero in
    every lane — otherwise pad rows would leak into bin 0."""
    import jax.numpy as jnp
    g = jnp.asarray([1.0, -2.0, 0.5, 3.0], jnp.float32)
    h = jnp.ones(4, jnp.float32)
    m = jnp.asarray([1.0, 0.0, 0.0, 1.0], jnp.float32)
    w2, scales, _ = ph.quantize_pack_channels(g, h, m)
    w = np.asarray(w2)
    assert w[0, 1] == 0 and w[0, 2] == 0      # packed (gq, hq) pair
    assert w[1, 1] == 0 and w[1, 2] == 0      # bitcast member


def test_packed_self_check_covers_all_legs():
    assert ph._packed_acc_self_check()


@pytest.mark.parametrize("build", ["gather", "twolevel"])
def test_onehot_builds_bit_identical(build):
    assert ph._onehot_build_self_check(build)


@pytest.mark.parametrize("build", ["gather", "twolevel"])
def test_onehot_env_routes_through_wrapper(rng, monkeypatch, build):
    """The non-jit wrappers resolve LIGHTGBM_TPU_ONEHOT_BUILD and the
    result is bitwise equal to the iota baseline."""
    import jax.numpy as jnp
    nrng = np.random.default_rng(11)
    F, B, rb, n = 4, 16, 256, 1024
    binsT = jnp.asarray(nrng.integers(0, B, (F, n)), jnp.uint8)
    g, h, m = map(jnp.asarray, _rand_stream(nrng, n))
    w8 = ph.pack_channels(g, h, m)
    base = np.asarray(ph.histogram_all(binsT, w8, B, rb))
    monkeypatch.setenv("LIGHTGBM_TPU_ONEHOT_BUILD", build)
    got = np.asarray(ph.histogram_all(binsT, w8, B, rb))
    assert np.array_equal(base, got)


def test_onehot_twolevel_requires_pow2_bins():
    """Non-power-of-two B falls back to the iota build statically (the
    high/low split only tiles cleanly for power-of-two widths) — the
    public wrapper must still run and match."""
    import jax.numpy as jnp
    nrng = np.random.default_rng(12)
    F, B, rb, n = 4, 12, 256, 1024
    binsT = jnp.asarray(nrng.integers(0, B, (F, n)), jnp.uint8)
    g, h, m = map(jnp.asarray, _rand_stream(nrng, n))
    w8 = ph.pack_channels(g, h, m)
    a = np.asarray(ph._histogram_all(binsT, w8, B, rb,
                                     onehot_build="iota"))
    b = np.asarray(ph._histogram_all(binsT, w8, B, rb,
                                     onehot_build="twolevel"))
    assert np.array_equal(a, b)


def test_staging_self_check_bit_identity():
    from lightgbm_tpu.models.grower_frontier import _hist_stage_self_check
    assert _hist_stage_self_check()


def test_staging_trained_model_bit_identical(rng, monkeypatch):
    """End-to-end: LIGHTGBM_TPU_HIST_STAGE=force through GBDT training
    must give byte-identical trees and predictions (missing values and
    a categorical feature included)."""
    n = 3000
    X = rng.normal(size=(n, 5))
    X[rng.random(size=n) < 0.1, 2] = np.nan
    X[:, 4] = rng.randint(0, 8, size=n)
    y = ((X[:, 0] + 0.4 * X[:, 1] > 0) | (X[:, 4] > 5)).astype(np.float64)
    kw = dict(objective="binary", num_leaves=15, min_data_in_leaf=5)
    base = _train(X, y, "frontier", monkeypatch,
                  env=[("LIGHTGBM_TPU_HIST_STAGE", "0")],
                  cat_feats=[4], **kw)
    staged = _train(X, y, "frontier", monkeypatch,
                    env=[("LIGHTGBM_TPU_HIST_STAGE", "force")],
                    cat_feats=[4], **kw)
    for i, (ta, tb) in enumerate(zip(base.models, staged.models)):
        assert ta.num_leaves == tb.num_leaves, i
        assert np.array_equal(ta.split_feature, tb.split_feature), i
        assert np.array_equal(ta.threshold_in_bin, tb.threshold_in_bin), i
        np.testing.assert_array_equal(ta.leaf_value, tb.leaf_value)
    np.testing.assert_array_equal(base._raw_predict(X),
                                  staged._raw_predict(X))


@pytest.mark.parametrize("impl", ["segment", "frontier"])
def test_packed_trained_model_quality_parity(rng, monkeypatch, impl):
    """Packed accumulator through GBDT training: same-quality model (not
    bit-identical — quantization may permute tie-break split order).
    Covers missing values, a categorical feature, and bagging."""
    n = 4000
    X = rng.normal(size=(n, 6))
    X[rng.random(size=n) < 0.1, 3] = np.nan
    X[:, 5] = rng.randint(0, 10, size=n)
    p = (X[:, 0] + 0.5 * X[:, 1] > 0) | (X[:, 5] > 7)
    y = p.astype(np.float64)
    kw = dict(objective="binary", num_leaves=15, min_data_in_leaf=5,
              bagging_fraction=0.8, bagging_freq=1, bagging_seed=3)
    base = _train(X, y, impl, monkeypatch,
                  env=[("LIGHTGBM_TPU_PACKED_ACC", "0")],
                  cat_feats=[5], **kw)
    packed = _train(X, y, impl, monkeypatch,
                    env=[("LIGHTGBM_TPU_PACKED_ACC", "force")],
                    cat_feats=[5], **kw)
    pb = 1.0 / (1.0 + np.exp(-base._raw_predict(X)))
    pp = 1.0 / (1.0 + np.exp(-packed._raw_predict(X)))
    acc_b = np.mean((pb > 0.5) == p)
    acc_p = np.mean((pp > 0.5) == p)
    assert acc_b > 0.9, acc_b
    assert acc_p >= acc_b - 0.01, (acc_b, acc_p)
    np.testing.assert_allclose(pp, pb, atol=0.12)


def test_packed_packed4_leg(rng, monkeypatch):
    """max_bin <= 15 (packed4 nibble layout) + packed accumulator."""
    n = 2500
    X = rng.normal(size=(n, 4))
    p = X[:, 0] - 0.6 * X[:, 2] > 0
    y = p.astype(np.float64)
    kw = dict(objective="binary", num_leaves=15, max_bin=15,
              min_data_in_leaf=5)
    packed = _train(X, y, "segment", monkeypatch,
                    env=[("LIGHTGBM_TPU_PACKED_ACC", "force")], **kw)
    assert packed.grower_params.packed4
    pp = 1.0 / (1.0 + np.exp(-packed._raw_predict(X)))
    assert np.mean((pp > 0.5) == p) > 0.9


def test_packed_acc_fallback_on_self_check_failure(monkeypatch):
    """Env =1 runs the self-check; a failing/raising check must fall
    back to the f32 path, and the failure must be memoized."""
    calls = []

    def boom():
        calls.append(1)
        raise RuntimeError("synthetic lowering failure")

    monkeypatch.setattr(ph, "_PACKED_ACC_CHECK", None)
    monkeypatch.setattr(ph, "_packed_acc_self_check", boom)
    monkeypatch.setenv("LIGHTGBM_TPU_PACKED_ACC", "1")
    assert ph.packed_acc_enabled() is False
    assert ph.packed_acc_enabled() is False
    assert len(calls) == 1, "self-check must be memoized"
    # force bypasses the (failing) check; off never consults it
    monkeypatch.setenv("LIGHTGBM_TPU_PACKED_ACC", "force")
    assert ph.packed_acc_enabled() is True
    monkeypatch.setenv("LIGHTGBM_TPU_PACKED_ACC", "0")
    assert ph.packed_acc_enabled() is False


def test_onehot_fallback_on_self_check_failure(monkeypatch):
    monkeypatch.setattr(ph, "_ONEHOT_BUILD_CHECKS", {})
    monkeypatch.setattr(ph, "_onehot_build_self_check",
                        lambda mode: False)
    monkeypatch.setenv("LIGHTGBM_TPU_ONEHOT_BUILD", "gather")
    assert ph.onehot_build_mode() == "iota"
    # trailing '!' bypasses the check (on-chip A/B plumbing)
    monkeypatch.setenv("LIGHTGBM_TPU_ONEHOT_BUILD", "gather!")
    assert ph.onehot_build_mode() == "gather"
    monkeypatch.setenv("LIGHTGBM_TPU_ONEHOT_BUILD", "nonsense")
    assert ph.onehot_build_mode() == "iota"


def test_hist_stage_fallback_on_self_check_failure(monkeypatch):
    import lightgbm_tpu.models.grower_frontier as gf
    monkeypatch.setattr(gf, "_HIST_STAGE_CHECK", None)
    monkeypatch.setattr(gf, "_hist_stage_self_check",
                        lambda: (_ for _ in ()).throw(RuntimeError("x")))
    monkeypatch.setenv("LIGHTGBM_TPU_HIST_STAGE", "1")
    assert gf.hist_stage_enabled() is False
    monkeypatch.setenv("LIGHTGBM_TPU_HIST_STAGE", "force")
    assert gf.hist_stage_enabled() is True
    monkeypatch.setenv("LIGHTGBM_TPU_HIST_STAGE", "0")
    assert gf.hist_stage_enabled() is False


def test_run_kernel_self_checks_green(capsys):
    """The verify_t1 --with-kernel-checks leg: every variant self-check
    passes on the interpret backend."""
    assert ph.run_kernel_self_checks() == 0
    out = capsys.readouterr().out
    assert "kernel self-checks: PASS" in out
    for name in ("packed_acc", "onehot_gather", "onehot_twolevel",
                 "hist_stage", "fused_route", "fused_k", "route_kernel",
                 "score_kernel"):
        assert f"ok {name}" in out, name


def test_kernel_self_checks_report_each_variant(monkeypatch):
    """One entry per variant: None for a pass, "mismatch" for a check
    that ran and disagreed, the exception for one that raised — and one
    variant's failure never stops the others."""
    def boom():
        raise ValueError("synthetic lowering failure\nShape mismatch")

    monkeypatch.setattr(ph, "_fused_k_self_check", boom)
    monkeypatch.setattr(ph, "_packed_acc_self_check", lambda: False)
    results = ph.kernel_self_checks()
    assert results["fused_k"] == "ValueError: Shape mismatch"
    assert results["packed_acc"] == "mismatch"
    assert set(ph.DEFAULT_PATH_CHECKS) <= set(results)
    assert all(err is None for name, err in results.items()
               if name not in ("fused_k", "packed_acc"))


def test_gate_self_check_raise_surfaces_on_tpu(monkeypatch):
    """On a tpu backend a self-check that raises is an error of the
    program, not a reason to take another path; off it the interpreter
    keeps falling back."""
    import pytest

    def boom():
        raise RuntimeError("synthetic lowering failure")

    assert ph.gate_self_check("x", boom) is False       # cpu: other path
    monkeypatch.setattr(ph.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="synthetic lowering failure"):
        ph.gate_self_check("x", boom)
    # through a gate: nothing is memoized, the error is heard again
    monkeypatch.setattr(ph, "_PACKED_ACC_CHECK", None)
    monkeypatch.setattr(ph, "_packed_acc_self_check", boom)
    monkeypatch.setenv("LIGHTGBM_TPU_PACKED_ACC", "1")
    for _ in range(2):
        with pytest.raises(RuntimeError, match="synthetic"):
            ph.packed_acc_enabled()


def test_gate_self_check_mismatch_warns_and_counts(monkeypatch, capsys):
    """A check that runs and reports "not equal" may select the other
    path — on every backend — but says so: one warning, one count."""
    from lightgbm_tpu.utils.telemetry import TELEMETRY

    def count():
        return TELEMETRY.stats()["counters"].get(
            "hist/self_check_fallbacks", 0)

    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(ph.jax, "default_backend", lambda b=backend: b)
        before = count()
        assert ph.gate_self_check("some-kernel", lambda: False) is False
        assert count() == before + 1
        assert (f"some-kernel self-check failed on the {backend} backend"
                in capsys.readouterr().out)
    before = count()
    assert ph.gate_self_check("some-kernel", lambda: True) is True
    assert count() == before


def test_vmem_limit_autosize():
    """Derived vmem_limit_bytes: calibrated above the measured 17.14 MB
    K=16/F=28/rb=32768 scoped need, at the 16 MB Mosaic default for
    small shapes, never past the 64 MB cap; recorded as a gauge."""
    mb = 1024 * 1024
    big = ph.fused_vmem_limit(28, 64, 16, 32768)
    assert big > int(17.14 * mb)
    assert big <= 64 * mb
    assert ph.fused_vmem_limit(4, 16, 1, 512) == 16 * mb
    from lightgbm_tpu.utils.telemetry import TELEMETRY
    gauges = getattr(TELEMETRY, "_gauges", None)
    if gauges is not None:
        assert gauges.get("hist/vmem_limit_bytes") == 16 * mb


def test_vmem_est_fused_k_and_memoized():
    """The fused-K pass carries a 2K-target accumulator: the estimate
    (and hence the auto limit) must grow with targets_k, stay clamped to
    the 64 MB cap, and the per-shape estimate is lru_cache-memoized so
    every grower build at a repeated shape skips the arithmetic."""
    mb = 1024 * 1024
    base = ph._fused_vmem_est(28, 64, 16, 32768)
    wide = ph._fused_vmem_est(28, 64, 16, 32768, targets_k=32)
    assert wide > base
    # the 2K carry at the calibration shape still fits under the cap
    assert ph.fused_vmem_limit(28, 64, 16, 32768, targets_k=32) <= 64 * mb
    info_before = ph._fused_vmem_est_cached.cache_info()
    ph._fused_vmem_est(28, 64, 16, 32768, targets_k=32)
    ph._fused_vmem_est(28, 64, 16, 32768, targets_k=32)
    info_after = ph._fused_vmem_est_cached.cache_info()
    assert info_after.misses == info_before.misses
    assert info_after.hits >= info_before.hits + 2
    # the fit veto consults the same estimate at the wide carry
    assert isinstance(ph.fused_route_fits(28, 64, 16, 32768, False,
                                          targets_k=32), bool)
