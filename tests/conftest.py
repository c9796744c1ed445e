"""Test harness config: run JAX on a virtual 8-device CPU mesh.

The reference has no single-process distributed test seam (SURVEY.md §4); we
get one for free by forcing the CPU platform with 8 virtual devices so the
data-/feature-parallel learners run their real collective paths in-process.
"""

import os

# Hermetic env: the perf knobs (LIGHTGBM_TPU_*) change traced shapes,
# dispatch policies and module-level defaults at import time; a knob
# leaked from a concurrently-running bench/probe (the driver runs them
# side by side) must not reconfigure the test suite.  Tests that WANT a
# knob set it explicitly via monkeypatch after import.  Test-control
# gates (not perf knobs) are kept.
_KEEP = {"LIGHTGBM_TPU_SKIP_CAPI"}
_scrubbed = [k for k in os.environ
             if k.startswith("LIGHTGBM_TPU_") and k not in _KEEP]
for _k in _scrubbed:
    del os.environ[_k]
if _scrubbed:
    import sys as _sys
    _sys.stderr.write(
        "conftest: scrubbed env knobs: " + ", ".join(sorted(_scrubbed))
        + "\n")

# Must happen before the first backend init: the env var for this process's
# own import of jax and for every child a test starts, jax.config below for
# a jax that a pytest plug-in imported before this file.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture
def tiles_of_96(monkeypatch):
    """`feature_tile` says 96 columns past 96: what 2000 x 64 does to the
    real arithmetic, at a width the CPU trains in seconds."""
    from lightgbm_tpu.models import grower_seg
    from lightgbm_tpu.ops import pallas_histogram as ph
    real = ph.feature_tile

    def small(F, B):
        return real(F, B) if F <= 96 else 96

    monkeypatch.setattr(ph, "feature_tile", small)
    monkeypatch.setattr(grower_seg, "feature_tile", small)
