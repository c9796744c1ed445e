"""The `higgs63-heldout` configuration and its cell `higgs63-train-eval`
come as new files found by name and as entries appended to `BENCHMARK.json`;
the cell rehearses to the end on the CPU with one stamp a chunk; the three
new readers read what a rehearsed run recorded, and nothing from a program
without the counters."""

import json

import run
from manifest import Manifest
# the manifest still has HEAD's entries as a prefix: the accepted check,
# collected here too so that this file alone holds the cell to it
from test_epsilon import test_benchmark_json_only_gained_entries  # noqa: F401

CELL = "higgs63-train-eval"
READERS = ("eval_walk_levels_per_tree", "eval_points_per_iter",
           "eval_replay_ms_per_iter")


def test_configuration_and_cell_are_found_by_name():
    man = Manifest()
    cell = man.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("higgs63-heldout", "train-eval", 1)
    config, higgs = man.config("higgs63-heldout"), man.config("higgs63")
    entry = {c["name"]: c for c in man.doc["configs"]}
    # a configuration with another's source and reduced keys is no new one
    assert config["source"] != higgs["source"]
    assert entry["higgs63-heldout"]["source"] == config["source"]
    assert len(config["source"]) <= 200
    assert config["reduced"] == entry["higgs63-heldout"]["reduced"] == \
        ["rows", "num_iterations"]
    # the table and the parameters are higgs63's; the metric is what is new
    assert config["data"] == higgs["data"]
    assert config["params"] == dict(higgs["params"], metric="auc")
    assert config["published"]["held_out_rows"] == 500_000
    traffic = man.traffic("train-eval")
    assert traffic["valid_rows"] == 500_000      # the published size, uncut
    assert (traffic["warmup_chunks"], traffic["extra_params"]) == (1, {})
    workload, limits = man.workload(CELL), man.workload("higgs63-train")
    assert set(workload["limits"]) == set(limits["limits"])
    for name, limit in workload["limits"].items():
        assert limit <= limits["limits"][name], name    # none looser
    assert max(1, int(50 // workload["chunk_seconds"])) == 1
    mine = {m["name"]: m for m in man.metrics("per_layer", CELL)}
    for name in READERS:
        assert mine[name]["workloads"] == [CELL]
        assert (mine[name]["layer"], mine[name]["moves"]) == \
            ("boosting loop", "train_s_per_iter")
    assert {"hist_roofline", "train_mfu", "pallas_pct", "xla_ops_pct",
            "peak_hbm_gb", "bin_s"} <= set(mine)
    for name in mine:
        assert callable(man.reader(name))


def test_readers_return_nothing_without_the_counters():
    from lightgbm_tpu.utils.phase import GLOBAL_TIMER
    from lightgbm_tpu.utils.telemetry import TELEMETRY
    GLOBAL_TIMER.reset()
    TELEMETRY.reset()
    man = Manifest()
    ctx = {"trees": [object()] * 8, "config": man.config("higgs63-heldout")}
    for name in READERS:
        assert man.reader(name)(ctx) is None


def test_cell_rehearses_with_one_stamp_a_chunk(capfd):
    """`run.py --rehearse --workload higgs63-train-eval` to the end on the
    CPU: evaluation stays in the scan, the window is one whole chunk, and
    the three readers read what the run recorded."""
    from lightgbm_tpu.utils.phase import GLOBAL_TIMER
    from lightgbm_tpu.utils.telemetry import TELEMETRY
    GLOBAL_TIMER.reset()
    TELEMETRY.reset()
    man = Manifest()
    code, result, _ = run.run_cell(man, CELL, 2 ** 31 + 34, 50.0, 0,
                                   rehearse=True)
    err = capfd.readouterr().err
    assert code == 3 and result["rehearsal"] and result["correct"]
    chunk = run.REHEARSAL["chunk"]
    assert (result["attempted"], result["failed"]) == (chunk, 0)
    assert f"train: chunk {chunk}" in err and f"= {2 * chunk} rounds" in err
    # two chunks, two stamps: one boundary between them
    stamps = err.split("chunks by the stamps ")[1].splitlines()[0]
    assert len(json.loads(stamps)) == 1
    stats = TELEMETRY.stats()
    assert not [g for g in stats["gauges"] if g.startswith("boost/inscan_")]
    assert stats["counters"]["transfer/eval_fetch_calls"] == 2
    ctx = {"trees": [object()] * (2 * chunk),
           "config": man.config("higgs63-heldout")}
    levels = man.reader("eval_walk_levels_per_tree")(ctx)
    assert 1.0 <= levels <= run.REHEARSAL["num_leaves"]
    assert man.reader("eval_points_per_iter")(ctx) == 1.0
    assert man.reader("eval_replay_ms_per_iter")(ctx) >= 0.0
    assert stats["gauges"]["eval/valid_rows"] == run.REHEARSAL["rows"] // 10
