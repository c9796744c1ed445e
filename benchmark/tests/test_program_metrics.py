"""The readers of the program's own counters and spans, on a registry filled
by hand: what each computes, `None` where the program recorded nothing (a
program from before these counters, or a path that does not record them), and
steady timeline entries only for the host phases."""

import pytest

from lightgbm_tpu.utils.phase import GLOBAL_TIMER
from lightgbm_tpu.utils.telemetry import TELEMETRY
from manifest import Manifest
from test_work import three_leaf_tree

NEW = ("hist_scan_ratio", "hist_grid_ratio", "compactions_per_tree",
       "host_dispatch_ms_per_iter", "host_fetch_ms_per_iter", "bin_find_s",
       "bin_quantize_s", "booster_init_s")


@pytest.fixture(autouse=True)
def clean_registry():
    GLOBAL_TIMER.reset()
    TELEMETRY.reset()
    yield
    GLOBAL_TIMER.reset()
    TELEMETRY.reset()


def read(name, ctx=None):
    return Manifest().reader(name)(ctx if ctx is not None else {"trees": []})


def spend(name, seconds, count=1):
    """`count` finished phases of `seconds` together, as PhaseTimer.phase
    leaves them."""
    with GLOBAL_TIMER._lock:
        GLOBAL_TIMER.seconds[name] += seconds
        GLOBAL_TIMER.counts[name] += count


@pytest.mark.parametrize("name", NEW)
def test_nothing_recorded_reads_none(name):
    assert read(name, {"trees": [three_leaf_tree()]}) is None


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_is_in_the_manifest(name):
    entry = [m for m in Manifest().doc["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"] == ["higgs63-train"]
    assert entry[0]["source"] in ("program_counter", "program_span")


def test_scan_ratio_is_scanned_rows_over_needed_rows():
    # two three-leaf trees need 150 visits each (test_work.py)
    trees = [three_leaf_tree()] * 2
    TELEMETRY.counter_add("seg/scanned_blocks", 9)
    TELEMETRY.counter_add("seg/trees", 2)
    TELEMETRY.gauge_set("seg/block_rows", 100)
    assert read("hist_scan_ratio", {"trees": trees}) == 900 / 300
    # the counters cover other trees than the run's: no ratio
    assert read("hist_scan_ratio", {"trees": trees[:1]}) is None
    assert read("hist_scan_ratio", {"trees": trees * 2}) is None


def test_scan_ratio_needs_the_block_size():
    TELEMETRY.counter_add("seg/scanned_blocks", 9)
    TELEMETRY.counter_add("seg/trees", 1)
    assert read("hist_scan_ratio", {"trees": [three_leaf_tree()]}) is None


def test_grid_ratio_and_compactions():
    TELEMETRY.counter_add("seg/scanned_blocks", 8)
    assert read("hist_grid_ratio") is None          # no grid counter
    TELEMETRY.counter_add("seg/grid_steps", 10)
    assert read("hist_grid_ratio") == 1.25
    assert read("compactions_per_tree") is None     # no tree count
    TELEMETRY.counter_add("seg/trees", 4)
    TELEMETRY.counter_add("seg/compactions", 6)
    assert read("compactions_per_tree") == 1.5


def test_host_phases_read_the_steady_entries_only():
    # first chunk: compile-bearing; second and third: steady; a fourth
    # compiles again (a tail chunk of another length) and is left out
    spend("compile[boost/chunk[16]]", 500.0)
    spend("chunk", 501.0)
    spend("fetch", 0.001)
    TELEMETRY.mark_iteration(15, count=16)
    assert read("host_dispatch_ms_per_iter") is None    # no steady entry
    spend("chunk", 0.032)
    spend("fetch", 0.8)
    TELEMETRY.mark_iteration(31, count=16)
    spend("chunk", 0.016)
    spend("fetch", 0.4)
    TELEMETRY.mark_iteration(47, count=16)
    spend("compile[boost/chunk[3]]", 40.0)
    spend("chunk", 41.0)
    spend("fetch", 0.4)
    TELEMETRY.mark_iteration(50, count=3)
    assert read("host_dispatch_ms_per_iter") == pytest.approx(48.0 / 32)
    assert read("host_fetch_ms_per_iter") == pytest.approx(1200.0 / 32)


def test_host_phases_without_phases_in_the_timeline():
    """A program whose timeline entries carry no `phases` key."""
    TELEMETRY.mark_iteration(15, count=16)
    TELEMETRY.mark_iteration(31, count=16)
    for entry in TELEMETRY._timeline:
        del entry["phases"]
    assert read("host_dispatch_ms_per_iter") is None
    assert read("host_fetch_ms_per_iter") is None


def test_setup_phases():
    spend("bin_find", 3.5)
    spend("bin_quantize", 20.25)
    spend("booster_init", 9.0, count=2)
    assert read("bin_find_s") == 3.5
    assert read("bin_quantize_s") == 20.25
    assert read("booster_init_s") == 4.5
