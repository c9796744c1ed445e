"""The gates of the default path's kernels (ops/pallas_histogram.py):
the self-checks they run on the live backend, what a failed check does,
and the scoped-VMEM arithmetic the fused kernels are sized by."""

import pytest

import lightgbm_tpu.ops.pallas_histogram as ph


def test_run_kernel_self_checks_green(capsys):
    """The verify_t1 --with-kernel-checks leg: every self-check of the
    default path passes on the interpret backend."""
    assert ph.run_kernel_self_checks() == 0
    out = capsys.readouterr().out
    assert "kernel self-checks: PASS" in out
    for name in ph.DEFAULT_PATH_CHECKS:
        assert f"ok {name}" in out, name


def test_kernel_self_checks_report_each_variant(monkeypatch):
    """One entry per check of the default path and no other: None for a
    pass, "mismatch" for a check that ran and disagreed, the exception
    for one that raised — and one check's failure never stops the
    others."""
    def boom():
        raise ValueError("synthetic lowering failure\nShape mismatch")

    monkeypatch.setattr(ph, "_fused_route_self_check", boom)
    monkeypatch.setattr(ph, "_route_kernel_self_check", lambda: False)
    results = ph.kernel_self_checks()
    assert tuple(results) == ph.DEFAULT_PATH_CHECKS
    assert results["fused_route"] == "ValueError: Shape mismatch"
    assert results["route_kernel"] == "mismatch"
    assert results["score_kernel"] is None


def test_gate_self_check_raise_surfaces_on_tpu(monkeypatch):
    """On a tpu backend a self-check that raises is an error of the
    program, not a reason to take another path; off it the interpreter
    keeps falling back."""
    def boom():
        raise RuntimeError("synthetic lowering failure")

    assert ph.gate_self_check("x", boom) is False       # cpu: other path
    monkeypatch.setattr(ph.jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="synthetic lowering failure"):
        ph.gate_self_check("x", boom)
    # through a gate: nothing is memoized, the error is heard again
    monkeypatch.setattr(ph, "_FUSED_ROUTE_CHECK", None)
    monkeypatch.setattr(ph, "_fused_route_self_check", boom)
    monkeypatch.delenv("LIGHTGBM_TPU_FUSED_ROUTE", raising=False)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="synthetic"):
            ph.fused_route_available()


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


def test_vmem_est_lookahead_lane_sets_and_memoized():
    """The lookahead kernel carries K lane sets over one route: the
    estimate (and hence the auto limit) must grow with targets_k, stay
    clamped to the 64 MB cap, and the per-shape estimate is
    lru_cache-memoized so every grower build at a repeated shape skips
    the arithmetic."""
    mb = 1024 * 1024
    K = ph.lookahead_width(28, 64, 32768, False)
    assert K == 8
    base = ph._fused_vmem_est(28, 64, 1, 32768)
    wide = ph._fused_vmem_est(28, 64, 1, 32768, targets_k=K)
    assert wide > base
    # the K lane sets at the cell's shape still fit under the cap
    assert ph.fused_vmem_limit(28, 64, 1, 32768, targets_k=K) <= 64 * mb
    info_before = ph._fused_vmem_est_cached.cache_info()
    ph._fused_vmem_est(28, 64, 1, 32768, targets_k=K)
    ph._fused_vmem_est(28, 64, 1, 32768, targets_k=K)
    info_after = ph._fused_vmem_est_cached.cache_info()
    assert info_after.misses == info_before.misses
    assert info_after.hits >= info_before.hits + 2
    # the fit veto consults the same estimate at the wide carry
    assert ph.fused_route_fits(28, 64, 1, 32768, False, targets_k=K)
