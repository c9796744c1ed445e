"""`lookahead_hit_share` on a registry filled by hand: nothing where the
program has no such counters (a program from before them), the share with
them, and its entry in the manifest."""

import pytest

from lightgbm_tpu.utils.telemetry import TELEMETRY
from manifest import Manifest


@pytest.fixture(autouse=True)
def clean_registry():
    TELEMETRY.reset()
    yield
    TELEMETRY.reset()


def read():
    return Manifest().reader("lookahead_hit_share")({"trees": []})


def test_nothing_recorded_reads_none():
    assert read() is None
    # the counters of a program from before lookahead: still nothing
    TELEMETRY.counter_add("seg/scanned_blocks", 9)
    TELEMETRY.counter_add("seg/trees", 2)
    assert read() is None


def test_share_of_splits_served():
    TELEMETRY.counter_add("seg/splits", 508)
    TELEMETRY.counter_add("seg/lookahead_hits", 254)
    TELEMETRY.counter_add("seg/lookahead_filled", 600)
    assert read() == 0.5


def test_a_path_that_fills_nothing_reads_zero():
    TELEMETRY.counter_add("seg/splits", 30)
    TELEMETRY.counter_add("seg/lookahead_hits", 0)
    assert read() == 0.0


def test_the_entry_in_the_manifest():
    entry = [m for m in Manifest().doc["per_layer"]
             if m["name"] == "lookahead_hit_share"]
    assert len(entry) == 1
    assert entry[0] == {
        "name": "lookahead_hit_share", "unit": "1/split",
        "better": "higher", "source": "program_counter", "layer": "grower",
        "moves": "train_s_per_iter", "workloads": ["higgs63-train"]}
    # appended: the entries that were there keep their places
    assert Manifest().doc["per_layer"][-1]["name"] == "lookahead_hit_share"
