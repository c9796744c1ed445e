"""BENCHMARK.json keeps to the contract's limits, and a configuration, a
cell, a traffic mix and a metric added as new files are found by name with
no edit to a file that is there."""

import json
import os
import re
import shutil

import numpy as np
import pytest

import datagen
from manifest import HERE, ROOT, Manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_keeps_to_the_contract():
    doc = Manifest().doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= doc["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    configs = {c["name"] for c in doc["configs"]}
    e2e = {m["name"] for m in doc["end_to_end"]}
    cells = {w["name"] for w in doc["workloads"]}
    assert "setup_s" in e2e
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        held = json.load(open(os.path.join(ROOT, c["file"])))
        assert held["reduced"] == c["reduced"]
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    assert len({(w["config"], w["traffic"]) for w in doc["workloads"]}) \
        == len(doc["workloads"])
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    layers = set()
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
        layers.add(m["layer"])
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(names) == len(set(names))
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert f"**{layer}**" in perf, f"PERF.md's layers lack {layer!r}"


def test_every_name_has_its_files():
    man = Manifest()
    for w in man.doc["workloads"]:
        assert man.config(w["config"])["params"]
        assert "warmup_chunks" in man.traffic(w["traffic"])
        wl = man.workload(w["name"])
        assert wl["chunk_seconds"] > 0 and wl["limits"]
    for m in man.doc["end_to_end"] + man.doc["per_layer"]:
        assert callable(man.reader(m["name"]))
    assert man.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        man.peaks("TPU v9 imaginary")


def test_new_files_are_found_by_name(tmp_path):
    """A later PR's configuration, traffic mix, cell, metric and generator:
    new files and new entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    before = {p: open(p).read() for p in
              (str(x) for x in (root / "benchmark").rglob("*.*")
               if x.suffix in (".json", ".py"))}
    cfg = json.load(open(root / "benchmark/configs/higgs63.json"))
    cfg["name"] = "higgs15"
    cfg["params"]["max_bin"] = 15
    cfg["data"] = {"generator": "one_hot_blocks", "groups": 3, "width": 4,
                   "rows": 1000}
    os.makedirs(root / "benchmark/generators", exist_ok=True)
    (root / "benchmark/generators/one_hot_blocks.py").write_text(
        "import numpy as np\n"
        "def make(rng, rows, groups, width):\n"
        "    hot = rng.integers(0, width, (rows, groups))\n"
        "    X = (hot[:, :, None] == np.arange(width)).reshape(rows, -1)\n"
        "    return X.astype(np.float32), (hot[:, 0] > 1).astype(np.float64)"
        "\n")
    (root / "benchmark/configs/higgs15.json").write_text(json.dumps(cfg))
    (root / "benchmark/traffic/train-eval-auc.json").write_text(json.dumps(
        {"valid_rows": 500000, "warmup_chunks": 1,
         "extra_params": {"metric": "auc"}}))
    (root / "benchmark/workloads/higgs15-train-eval.json").write_text(
        json.dumps({"chunk_seconds": 60.0, "limits": {"count_gap": 0}}))
    (root / "benchmark/metrics/gen_s.py").write_text(
        "def read(ctx):\n    return ctx['spans'].get('gen_s')\n")
    doc["configs"].append({"name": "higgs15", "source": cfg["source"],
                           "file": "benchmark/configs/higgs15.json",
                           "reduced": cfg["reduced"], "why": "narrower"})
    doc["workloads"].append({"name": "higgs15-train-eval",
                             "config": "higgs15", "traffic": "train-eval-auc",
                             "chips": 1, "why": "with a valid set"})
    doc["per_layer"].append({"name": "gen_s", "unit": "s", "better": "lower",
                             "source": "host_clock", "layer": "host",
                             "moves": "setup_s",
                             "workloads": ["higgs15-train-eval"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    man = Manifest(root=str(root), here=str(root / "benchmark"))
    cell = man.cell("higgs15-train-eval")
    assert man.config(cell["config"])["params"]["max_bin"] == 15
    assert man.traffic(cell["traffic"])["valid_rows"] == 500000
    assert man.workload(cell["name"])["chunk_seconds"] == 60.0
    names = [m["name"] for m in man.metrics("per_layer", cell["name"])]
    assert "gen_s" in names and "bin_s" in names
    assert "gen_s" not in [m["name"] for m in
                           man.metrics("per_layer", "higgs63-train")]
    assert man.reader("gen_s")({"spans": {"gen_s": 1.5}}) == 1.5
    data = man.config(cell["config"])["data"]

    def rows_of(seed):
        X, y, extras = datagen.make(data, 50, np.random.default_rng(seed),
                                    here=man.here)
        assert extras == {}         # a generator of two items: no groups
        return X, y

    X, y = rows_of(2 ** 31 + 11)
    assert X.shape == (50, 12) and X.dtype == np.float32 and len(y) == 50
    assert np.all(X.sum(axis=1) == 3) and 0 < y.sum() < 50
    assert np.array_equal(X, rows_of(2 ** 31 + 11)[0])
    assert not np.array_equal(X, rows_of(5)[0])
    # the generator that was there is found as before, a name with no file
    # is refused
    assert datagen.make(man.config("higgs63")["data"], 8,
                        np.random.default_rng(1), here=man.here)[0].shape \
        == (8, 28)
    with pytest.raises(SystemExit):
        datagen.make(dict(data, generator="no_such_rows"), 8,
                     np.random.default_rng(1), here=man.here)
    assert all(open(p).read() == text for p, text in before.items())
