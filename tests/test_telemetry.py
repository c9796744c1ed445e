"""Training telemetry subsystem (utils/telemetry.py).

Covers the four ISSUE acceptance surfaces: the Chrome trace export
schema (valid trace-event JSON with the required span names), exact
fetch-byte counters for a deterministic 2-chunk run, compaction
counters under LIGHTGBM_TPU_SEG_STATS, and the ``telemetry_level=0``
off switch (no spans, no counters, no timeline).  Plus the registry's
thread-safety / single-writer check (the reference Network keeps all
collectives on one thread; here a second writer is flagged, not
fatal), the parallel/network.py collective counters, the CLI
``metrics_out=`` path and the tools/trace_report.py digest.
"""

import json
import os
import sys
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.cli import Application
from lightgbm_tpu.parallel import network
from lightgbm_tpu.utils.phase import GLOBAL_TIMER
from lightgbm_tpu.utils.telemetry import (METRICS_SCHEMA, TELEMETRY,
                                          TelemetryRegistry)

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import trace_report  # noqa: E402


@pytest.fixture(autouse=True)
def clean_telemetry():
    """TELEMETRY is process-global: start every test from a clean window
    (reset also clears the network counters and re-reads the level)."""
    GLOBAL_TIMER.reset()
    TELEMETRY.reset()
    yield
    GLOBAL_TIMER.reset()
    TELEMETRY.reset()


def make_binary(rng, n=500, f=5):
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float64)
    return X, y


def _params(**kw):
    p = {"objective": "binary", "num_leaves": 7, "max_bin": 31,
         "min_data_in_leaf": 5, "verbose": -1}
    p.update(kw)
    return p


# ---------------------------------------------------------------- trace


def test_trace_export_schema(rng, tmp_path, monkeypatch):
    trace_path = tmp_path / "trace.json"
    monkeypatch.setenv("LIGHTGBM_TPU_TRACE_JSON", str(trace_path))
    TELEMETRY.refresh_level()
    assert TELEMETRY.level >= 2, "TRACE_JSON must force span recording"

    X, y = make_binary(rng)
    bst = lgb.train(_params(), lgb.Dataset(X, y), num_boost_round=3)

    assert trace_path.exists(), "engine.train must export the trace"
    blob = json.loads(trace_path.read_text())
    events = blob["traceEvents"]
    assert isinstance(events, list) and events
    assert blob["otherData"]["schema"] == METRICS_SCHEMA

    span_names = set()
    for ev in events:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
        assert ev["ph"] in ("X", "C", "M", "i")
        if ev["ph"] == "X":        # complete event: microsecond ts + dur
            assert {"ts", "dur", "cat"} <= set(ev)
            assert isinstance(ev["tid"], int)
            assert ev["dur"] >= 0
            span_names.add(ev["name"])
        elif ev["ph"] == "i":      # fault instant event: global scope
            assert ev["s"] == "g"
            assert ev["name"].startswith("fault/")
    assert {"boost", "grow", "fetch"} <= span_names

    # the same data is reachable through the stats API
    stats = bst.get_stats()
    assert stats["version"] == 7
    assert stats["level"] >= 2
    assert stats["spans"]["recorded"] > 0
    assert stats["spans"]["dropped"] == 0
    assert bst.train_stats["counters"] == stats["counters"]


# ------------------------------------------------------------- counters


def test_fetch_counters_exact_for_two_chunk_run(rng):
    """4 iterations at tpu_boost_chunk=2 -> exactly 2 chunk fetches, and
    the byte count matches the packed tree-buffer layout: for L leaves
    (n = L-1 internal nodes) the int32 block is 1+14n+2L words and the
    float32 block 4n+3L words (models/grower.py pack layout)."""
    L = 7
    X, y = make_binary(rng, n=600)
    bst = lgb.train(_params(num_leaves=L, tpu_boost_chunk=2),
                    lgb.Dataset(X, y), num_boost_round=4)
    stats = bst.get_stats()
    c = stats["counters"]
    assert c["transfer/fetch_calls"] == 2

    n = L - 1
    per_tree = (1 + 14 * n + 2 * L) * 4 + (4 * n + 3 * L) * 4
    assert c["transfer/fetch_bytes"] == 4 * per_tree
    assert c["transfer/h2d_bytes"] > 0

    assert stats["gauges"]["boost/chunk_size"] == 2
    timeline = stats["timeline"]
    assert sum(e["count"] for e in timeline) == 4
    # every timeline entry carries the counter deltas for its window
    assert any("transfer/fetch_bytes" in e["counters"] for e in timeline)


def test_compaction_counters_under_seg_stats(rng, monkeypatch):
    """LIGHTGBM_TPU_SEG_STATS opts into fetching the segment grower's
    device counters; the training shape scans past the compaction budget
    (9 N: about half of 39 splits scan, the lookahead lane sets serve the
    rest) so at least one compaction lands in seg/compactions, beside
    the split and lookahead counters."""
    monkeypatch.setenv("LIGHTGBM_TPU_SEG_STATS", "1")
    X, y = make_binary(rng, n=800, f=8)
    bst = lgb.train(_params(num_leaves=40, tpu_tree_impl="segment",
                            tpu_histogram_backend="pallas"),
                    lgb.Dataset(X, y), num_boost_round=3)
    c = bst.get_stats()["counters"]
    assert c.get("seg/compactions", 0) >= 1
    assert c.get("seg/scanned_blocks", 0) > 0
    assert c["seg/splits"] == sum(t.num_leaves - 1
                                  for t in bst.gbdt.models)
    assert 0 < c["seg/lookahead_hits"] <= c["seg/lookahead_filled"]
    assert c["seg/lookahead_hits"] < c["seg/splits"]
    assert c["seg/route_only_blocks"] > 0


def test_level0_adds_nothing(rng):
    X, y = make_binary(rng)
    bst = lgb.train(_params(telemetry_level=0), lgb.Dataset(X, y),
                    num_boost_round=2)
    stats = bst.get_stats()
    assert stats["level"] == 0
    assert stats["counters"] == {}
    assert stats["gauges"] == {}
    assert stats["timeline"] == []
    assert stats["spans"]["recorded"] == 0
    # v2 device-side sections record nothing at level 0 either
    assert "memory" not in stats
    assert "cost" not in stats


def test_compile_listeners_count_retraces(rng):
    X, y = make_binary(rng)
    bst = lgb.train(_params(), lgb.Dataset(X, y), num_boost_round=2)
    c = bst.get_stats()["counters"]
    # a cold 2-iteration run traces and compiles at least once
    assert c.get("compile/retraces", 0) >= 1
    assert c.get("compile/retrace_seconds", 0) > 0
    assert c.get("compile/backend_compiles", 0) >= 1


# ------------------------------------------------------- thread safety


def test_registry_thread_safety_and_writer_check(monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_TELEMETRY", "2")
    reg = TelemetryRegistry(span_capacity=64)
    nthreads, per = 8, 400
    # every writer alive at once: a thread ident is reused once its thread
    # exits, so writers that happened to run one after another would look
    # like ONE writer and the race below would never be flagged
    all_started = threading.Barrier(nthreads)

    def work():
        all_started.wait(timeout=60)
        for _ in range(per):
            reg.counter_add("t/hits")
            with reg.span("t_span"):
                pass

    threads = [threading.Thread(target=work) for _ in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()

    stats = reg.stats()
    assert stats["counters"]["t/hits"] == nthreads * per
    # single-writer check: the second thread is flagged exactly once
    assert stats["counters"]["telemetry/writer_races"] == 1
    # ring buffer: all spans counted, only the last `capacity` kept
    assert stats["spans"]["recorded"] == nthreads * per
    assert stats["spans"]["kept"] == 64
    assert stats["spans"]["dropped"] == nthreads * per - 64


# -------------------------------------------------------------- network


def test_network_allgather_obj_counters():
    def fake_allgather(blob):
        return [blob, blob]

    network.init_with_functions(lambda *a: None, fake_allgather,
                                rank=0, num_machines=2)
    try:
        out = network.allgather_obj({"mapper": 7})
        # read BEFORE dispose(): teardown resets the counters
        st = network.collective_stats()
        summary = network.collective_summary()
        timer_line = GLOBAL_TIMER.summary()
        net_stats = TELEMETRY.stats()["network"]
    finally:
        network.dispose()
    assert out == [{"mapper": 7}, {"mapper": 7}]

    assert st["allgather_obj"]["calls"] == 1
    assert st["allgather_obj"]["bytes"] > 0
    assert st["allgather_obj"]["seconds"] >= 0.0

    # rendered into the phase summary line and the stats blob
    assert "allgather_obj=1x" in summary
    assert "allgather_obj=1x" in timer_line
    assert net_stats["allgather_obj"]["calls"] == 1

    # dispose() zeroed the counters so a back-to-back run starts clean
    assert network.collective_stats() == {}
    assert "allgather_obj" not in GLOBAL_TIMER.summary()
    assert network.collective_summary() == ""


def test_network_single_writer_check():
    network.record_collective("main_kind", 10, 0.001)

    def other():
        network.record_collective("other_kind", 20, 0.002)

    t = threading.Thread(target=other)
    t.start()
    t.join()
    st = network.collective_stats()
    assert st["main_kind"]["calls"] == 1
    assert st["other_kind"]["calls"] == 1   # consistent despite the race
    assert network._coll_race_warned


def test_network_disabled_at_level0(monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_TELEMETRY", "0")
    TELEMETRY.refresh_level()
    network.record_collective("nope", 100, 1.0)
    assert network.collective_stats() == {}


def test_parallel_learner_records_collectives(rng):
    X, y = make_binary(rng, n=1000, f=8)
    bst = lgb.train(_params(num_leaves=15, tree_learner="data"),
                    lgb.Dataset(X, y), num_boost_round=3)
    net = bst.get_stats()["network"]
    assert net, "data-parallel training must record collectives"
    assert sum(v["calls"] for v in net.values()) >= 3   # one per tree
    assert sum(v["bytes"] for v in net.values()) > 0    # mesh-math estimate


# ------------------------------------------------------------- surfaces


def test_cli_metrics_out(tmp_path, rng):
    X, y = make_binary(rng, n=300)
    train = tmp_path / "train.csv"
    np.savetxt(train, np.column_stack([y, X]), delimiter=",", fmt="%.6f")
    model = tmp_path / "model.txt"
    metrics = tmp_path / "metrics.json"
    Application([
        "task=train", f"data={train}", "objective=binary",
        "num_trees=2", "num_leaves=7", f"output_model={model}",
        f"metrics_out={metrics}", "verbosity=-1",
    ]).run()
    assert metrics.exists()
    blob = json.loads(metrics.read_text())
    assert blob["schema"] == METRICS_SCHEMA
    assert blob["version"] == 7
    assert blob["phases"], "the CLI run must have recorded phases"
    assert blob["cost"]["labels"], "CLI train must harvest seam costs"
    assert blob["counters"]["transfer/fetch_calls"] >= 1


def test_trace_report_summarize(rng, tmp_path, capsys):
    X, y = make_binary(rng)
    bst = lgb.train(_params(), lgb.Dataset(X, y), num_boost_round=2)
    blob = bst.get_stats()

    text = trace_report.summarize(blob)
    assert "telemetry summary" in text
    assert "phases" in text
    assert "transfers:" in text

    # also accepts a bench record wrapping the blob under "metrics"
    record = tmp_path / "bench_record.json"
    record.write_text(json.dumps({"wall": 1.0, "metrics": blob}))
    assert trace_report.main([str(record)]) == 0
    assert "telemetry summary" in capsys.readouterr().out


# ------------------------------------------------- device-side (v2)


_FAKE_MS = {"bytes_in_use": 1 << 20, "peak_bytes_in_use": 3 << 20,
            "largest_alloc_size": 1 << 19, "bytes_limit": 1 << 30}


def _fake_mem(monkeypatch, ms=None):
    """Pretend the backend reports allocator stats (the CPU backend's
    memory_stats() is None, so the real path can't be exercised here)."""
    monkeypatch.setattr(TelemetryRegistry, "_device_memory_stats",
                        lambda self: dict(ms or _FAKE_MS))


def test_cost_section_populated_on_cpu(rng):
    """The acceptance-criteria path: a plain CPU training run harvests
    Compiled.cost_analysis() at the fused jit seams and multiplies it
    out by dispatch counts."""
    X, y = make_binary(rng)
    bst = lgb.train(_params(), lgb.Dataset(X, y), num_boost_round=3)
    stats = bst.get_stats()
    assert stats["version"] == 7
    cost = stats["cost"]
    labels = cost["labels"]
    assert "boost/gradients" in labels
    assert "grow/fused_step" in labels
    g = labels["boost/gradients"]
    assert g["compiles"] >= 1
    assert g["calls"] == 3                      # one dispatch per iter
    assert g["flops"] > 0
    assert g["flops_total"] == pytest.approx(g["flops"] * g["calls"])
    assert cost["flops_total"] == pytest.approx(
        sum(e["flops_total"] for e in labels.values()))
    assert cost["window_seconds"] > 0
    assert cost["est_flops_per_s"] > 0
    # the digest renders the cost + utilization lines from the same blob
    text = trace_report.summarize(stats)
    assert "cost (" in text
    assert "utilization:" in text


def test_chunked_run_costs_the_scan(rng):
    X, y = make_binary(rng, n=600)
    bst = lgb.train(_params(tpu_boost_chunk=2), lgb.Dataset(X, y),
                    num_boost_round=4)
    labels = bst.get_stats()["cost"]["labels"]
    assert labels["boost/chunk[2]"]["calls"] == 2
    # the whole 2-iteration scan is one program: its per-call flops
    # must dwarf a single gradient pass
    assert (labels["boost/chunk[2]"]["flops"]
            > labels.get("boost/gradients", {}).get("flops", 0))


def test_memory_absent_on_cpu_without_warnings(rng):
    """CPU memory_stats() is None -> the section is cleanly absent, no
    warnings, and the probe latches off after the first miss."""
    import warnings
    X, y = make_binary(rng)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # any warning -> failure
        bst = lgb.train(_params(), lgb.Dataset(X, y), num_boost_round=2)
        stats = bst.get_stats()
    assert "memory" not in stats
    assert TELEMETRY._mem_supported is False    # latched: later samples
    TELEMETRY.sample_memory("x")                # are one attribute check
    assert "memory" not in TELEMETRY.stats()
    assert "memory: n/a" in trace_report.summarize(stats)


def test_memory_section_when_backend_reports(rng, monkeypatch):
    _fake_mem(monkeypatch)
    X, y = make_binary(rng)
    bst = lgb.train(_params(), lgb.Dataset(X, y), num_boost_round=2)
    mem = bst.get_stats()["memory"]
    assert mem["bytes_in_use"] == _FAKE_MS["bytes_in_use"]
    assert mem["peak_bytes_in_use"] == _FAKE_MS["peak_bytes_in_use"]
    assert mem["largest_alloc"] == _FAKE_MS["largest_alloc_size"]
    assert mem["bytes_limit"] == _FAKE_MS["bytes_limit"]
    # phase boundaries attributed samples (engine wraps the loop in a
    # memory_session; utils/phase.py samples at every phase exit)
    assert mem["phases"]["session"]["samples"] >= 2
    assert "grow" in mem["phases"]
    assert "sampler" not in mem          # env knob off by default
    text = trace_report.summarize(bst.get_stats())
    assert "memory: peak 3.0MB" in text
    assert "% peak" in text


def test_mem_sampler_lifecycle(monkeypatch):
    _fake_mem(monkeypatch)
    monkeypatch.setenv("LIGHTGBM_TPU_MEM_SAMPLE_MS", "2")
    import time as _time
    with TELEMETRY.memory_session():
        thread = TELEMETRY._mem_thread
        assert thread is not None and thread.is_alive()
        deadline = _time.time() + 5.0
        while (not TELEMETRY._mem_track) and _time.time() < deadline:
            _time.sleep(0.01)
    # cleanly stopped and joined on exit
    assert TELEMETRY._mem_thread is None
    assert not thread.is_alive()
    mem = TELEMETRY.stats()["memory"]
    assert mem["sampler"]["interval_ms"] == 2.0
    assert mem["sampler"]["samples"] >= 1
    # the sampler feeds a counter track into the Chrome trace
    trace = TELEMETRY.chrome_trace()
    mem_events = [e for e in trace["traceEvents"]
                  if e["name"] == "mem/bytes_in_use"]
    assert mem_events and all(e["ph"] == "C" for e in mem_events)
    assert mem_events[0]["args"]["value"] == _FAKE_MS["bytes_in_use"]


def test_sampler_never_outlives_training_on_error(rng, monkeypatch):
    """engine.train wraps the loop in memory_session(); a callback
    exception must still stop and join the sampler thread."""
    _fake_mem(monkeypatch)
    monkeypatch.setenv("LIGHTGBM_TPU_MEM_SAMPLE_MS", "2")
    X, y = make_binary(rng)

    def boom(env):
        raise RuntimeError("callback exploded")

    with pytest.raises(RuntimeError, match="callback exploded"):
        lgb.train(_params(), lgb.Dataset(X, y), num_boost_round=5,
                  callbacks=[boom])
    assert TELEMETRY._mem_thread is None
    for t in threading.enumerate():
        assert t.name != "mem-sampler"


def test_sampler_noop_without_env(monkeypatch):
    _fake_mem(monkeypatch)
    with TELEMETRY.memory_session():
        assert TELEMETRY._mem_thread is None


def test_trace_report_handles_v1_blob():
    """Older blobs lack network/timeline/memory/cost: every section must
    render as n/a, never KeyError."""
    v1 = {"version": 1, "level": 1, "mode": "dispatch",
          "phases": {"grow": {"seconds": 1.5, "count": 3}},
          "counters": {}, "gauges": {}, "timeline": [],
          "spans": {"recorded": 0, "kept": 0, "dropped": 0,
                    "capacity": 4096}}
    text = trace_report.summarize(v1)
    assert "memory: n/a" in text
    assert "cost: n/a" in text
    # a pathologically bare blob (no sections at all) still renders
    bare = trace_report.summarize({})
    assert "phases: n/a" in bare


def test_trace_report_diff(tmp_path, capsys):
    a = {"version": 2, "phases": {"grow": {"seconds": 1.0, "count": 4},
                                  "boost": {"seconds": 0.5, "count": 4}},
         "counters": {"transfer/fetch_bytes": 1000},
         "memory": {"peak_bytes_in_use": 1 << 20, "bytes_in_use": 1000,
                    "largest_alloc": 512},
         "cost": {"flops_total": 100.0, "bytes_total": 10.0,
                  "labels": {"grow/fused_step":
                             {"calls": 4, "flops_total": 100.0}}}}
    b = {"version": 2, "phases": {"grow": {"seconds": 0.8, "count": 4}},
         "counters": {"transfer/fetch_bytes": 800},
         "cost": {"flops_total": 100.0, "bytes_total": 10.0,
                  "labels": {"grow/fused_step":
                             {"calls": 4, "flops_total": 100.0}}}}
    text = trace_report.diff(a, b)
    assert "grow: 1.000s -> 0.800s" in text
    assert "-20.0%" in text
    assert "boost: 0.500s -> n/a" in text
    assert "transfer/fetch_bytes: 1000 -> 800" in text
    assert "peak_bytes_in_use: 1.0MB -> n/a" in text

    # the CLI path: --diff a.json b.json
    pa, pb = tmp_path / "a.json", tmp_path / "b.json"
    pa.write_text(json.dumps(a))
    pb.write_text(json.dumps(b))
    assert trace_report.main(["--diff", str(pa), str(pb)]) == 0
    assert "metrics diff" in capsys.readouterr().out
    # diffing against a v1 blob (no memory/cost) stays n/a-tolerant
    assert "memory (bytes): n/a" in trace_report.diff(
        {"version": 1}, {"version": 1})


def test_profile_session_is_exception_safe(monkeypatch, tmp_path):
    """An exception inside the profiler window must still stop the
    trace (a leaked session poisons every later start_trace)."""
    from lightgbm_tpu.utils import phase

    started, stopped = [], []
    monkeypatch.setattr(phase, "maybe_start_profile",
                        lambda: started.append(1))
    monkeypatch.setattr(phase, "maybe_stop_profile",
                        lambda: stopped.append(1))
    with pytest.raises(RuntimeError):
        with phase.profile_session():
            raise RuntimeError("boom")
    assert started == [1] and stopped == [1]


# ----------------------------------------- the chunked path's own work
# (counters out of the scan, phases in the timeline and in any open
# profiler trace, named scopes in the device program)

SEG_KEYS = ("seg/scanned_blocks", "seg/grid_steps", "seg/compactions",
            "seg/trees")
# sha256 of the model string the PARENT commit (963c3c4) grows for
# _seg_run(chunk=4, rounds=8): one more scan output and HLO metadata must
# not move a bit of it
PARENT_MODEL_SHA256 = (
    "c0f98571037b0be86966ed82fa7fbe020d40df8a92de9744699bd4fad122a732")


def _seg_data(n=800):
    r = np.random.RandomState(20260925)
    X = r.normal(size=(n, 8)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0).astype(np.float64)
    return X, y


def _seg_run(chunk, rounds=8, n=800, **kw):
    X, y = _seg_data(n)
    return lgb.train(_params(num_leaves=15, tpu_tree_impl="segment",
                             tpu_histogram_backend="pallas",
                             tpu_boost_chunk=chunk, **kw),
                     lgb.Dataset(X, y), num_boost_round=rounds)


def _trees_of(bst):
    return "\n".join(line for line in bst.model_to_string().splitlines()
                     if not line.startswith(("[tpu_boost_chunk",
                                             "[telemetry_level")))


def test_chunked_seg_counters_match_per_iteration_path(monkeypatch):
    """The chunk scan carries the grower's counters out with the tree
    buffers: recorded with no env var, totals equal to what the
    per-iteration path records under LIGHTGBM_TPU_SEG_STATS for the same
    seed, and not counted as a tree fetch."""
    bst = _seg_run(chunk=4, n=2500)
    stats = bst.get_stats()
    c = stats["counters"]
    assert c["seg/trees"] == 8
    assert c["seg/scanned_blocks"] > 0 and c["seg/compactions"] >= 1
    assert c["seg/grid_steps"] >= c["seg/scanned_blocks"]
    assert c["transfer/fetch_calls"] == 2        # two chunks, trees only
    rb = bst.gbdt.grower_params.row_chunk
    assert stats["gauges"]["seg/block_rows"] == rb
    # the budget the run compacted under, as the grower derived it
    from lightgbm_tpu.models.grower_seg import compaction_budget_blocks
    assert stats["gauges"]["seg/compact_budget_blocks"] == \
        compaction_budget_blocks(8, bst.gbdt.num_bins,
                                 -(-2500 // rb) * rb, rb, False)
    # the root alone scans every block of every tree
    assert c["seg/scanned_blocks"] >= 8 * -(-2500 // rb)
    chunked = {k: c[k] for k in SEG_KEYS}

    GLOBAL_TIMER.reset()
    TELEMETRY.reset()
    monkeypatch.setenv("LIGHTGBM_TPU_SEG_STATS", "1")
    per_iter = _seg_run(chunk=4, n=2500)
    assert per_iter.gbdt.boost_chunk_size() == 1   # the env forces it
    c1 = per_iter.get_stats()["counters"]
    assert {k: c1[k] for k in SEG_KEYS} == chunked
    assert _trees_of(per_iter) == _trees_of(bst)


def test_chunked_seg_counters_off_at_level0():
    """telemetry_level=0: the same chunk program, nothing recorded, the
    same trees."""
    import jax.numpy as jnp

    def lowered(g):
        return g._chunk_fns[4].lower(
            g.train_score, g._key, g.bag_weight, g._device_bins(), g.fmeta,
            g._full_fmask, jnp.float32(g.shrinkage_rate),
            g._obj_arrs).as_text()

    off = _seg_run(chunk=4, telemetry_level=0)
    stats = off.get_stats()
    assert not [k for k in list(stats["counters"]) + list(stats["gauges"])
                if k.startswith(("seg/", "hist/", "transfer/"))]
    assert stats["timeline"] == []
    on = _seg_run(chunk=4)
    assert on.get_stats()["counters"]["seg/trees"] == 8
    assert _trees_of(off) == _trees_of(on)
    # one program at every level: the scan's counter output is always
    # there, only the host-side record is switched
    assert lowered(off.gbdt) == lowered(on.gbdt)


def _eval_run(**kw):
    X, y = _seg_data(1200)
    evals = {}
    ds = lgb.Dataset(X[:800], y[:800])
    bst = lgb.train(_params(metric="auc", tpu_boost_chunk=4, **kw), ds,
                    num_boost_round=8, verbose_eval=False,
                    valid_sets=[ds.create_valid(X[800:], y[800:])],
                    evals_result=evals)
    return bst, evals


@pytest.mark.parametrize("level", [1, 2])
def test_inscan_eval_counters_gauge_and_phase(level):
    """What the in-scan evaluation records: the walk's levels (out of the
    scan in the metric matrix's own fetch), the values delivered, the rows
    walked and the host phase of the replay."""
    bst, evals = _eval_run(telemetry_level=level)
    stats = bst.get_stats()
    c = stats["counters"]
    assert c["eval/points"] == 8 == len(evals["valid_0"]["auc"])
    assert stats["gauges"]["eval/valid_rows"] == 400
    # a level a tree at the least, never past the leaves; whole numbers
    assert 8 <= c["eval/walk_levels"] <= 8 * 7
    assert c["eval/walk_levels"] == int(c["eval/walk_levels"])
    # no transfer of its own: one eval fetch a chunk, 4 bytes an iteration
    # for the levels beside the one metric column's 4
    assert c["transfer/eval_fetch_calls"] == 2
    assert c["transfer/eval_fetch_bytes"] == 8 * (4 + 4)
    assert stats["phases"]["eval_replay"]["count"] == 2
    assert "callbacks" not in stats["phases"]


def test_dense_walk_counter_counts_every_tree(monkeypatch):
    """`eval/dense_walk_trees`: every tree of the run where the dense walk
    takes the shape, none where the gather walk does; the levels and the
    metric values the same either way."""
    from lightgbm_tpu.models import gbdt as gm
    dense, evals = _eval_run()
    c = dense.get_stats()["counters"]
    assert c["eval/dense_walk_trees"] == 8 == dense.num_trees()
    TELEMETRY.reset()
    monkeypatch.setattr(gm, "_dense_walk_plan", lambda vbins, leaves: None)
    gather, evals_gather = _eval_run()
    g = gather.get_stats()["counters"]
    assert g["eval/dense_walk_trees"] == 0
    assert g["eval/walk_levels"] == c["eval/walk_levels"]
    assert evals_gather == evals


def test_inscan_eval_names_cost_nothing_at_level0():
    """telemetry_level=0: none of the names recorded, the same model bytes
    and the same metric values."""
    off, evals_off = _eval_run(telemetry_level=0)
    stats = off.get_stats()
    assert not [k for k in list(stats["counters"]) + list(stats["gauges"])
                if k.startswith(("eval/", "transfer/"))]
    assert stats["timeline"] == []
    on, evals_on = _eval_run()
    assert on.get_stats()["counters"]["eval/points"] == 8
    assert _trees_of(off) == _trees_of(on)
    assert evals_off == evals_on


def test_timeline_entries_carry_phases():
    """Each chunk's timeline entry holds the per-phase seconds and counts
    since the last mark; the compile is a phase of the first entry
    alone, so a reader takes the steady entries."""
    bst = _seg_run(chunk=4)
    first, second = bst.get_stats()["timeline"]
    for entry in (first, second):
        assert entry["count"] == 4
        for name in ("chunk", "nonfinite_guard", "fetch"):
            assert entry["phases"][name]["count"] == 1
            assert entry["phases"][name]["seconds"] >= 0.0
    # the first chunk's trees are fetched under the second's dispatch
    assert "fetch_wait" not in first["phases"]
    for name in ("fetch_wait", "materialize"):
        assert second["phases"][name]["count"] == 1
        assert second["phases"][name]["seconds"] \
            <= second["phases"]["fetch"]["seconds"]
    compile_key = "compile[boost/chunk[4]]"
    assert first["phases"][compile_key]["seconds"] > 0.0
    assert first["phases"]["chunk"]["seconds"] \
        >= first["phases"][compile_key]["seconds"]
    assert not [k for k in second["phases"] if k.startswith("compile[")]
    # set-up's phases land in the first entry too
    assert first["phases"]["booster_init"]["count"] == 1
    assert "booster_init" not in second["phases"]
    assert first["phases"]["bin_find"]["count"] == 1


def test_phases_enter_whatever_profiler_trace_is_open(monkeypatch):
    """No program-owned capture (no LIGHTGBM_TPU_PROFILE_DIR, no
    profile_window): the phases are trace annotations all the same, so a
    trace the CALLER opened holds them."""
    import jax

    entered = []

    class Recorder:
        def __init__(self, name, **kw):
            self.name = name

        def __enter__(self):
            entered.append(self.name)
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Recorder)
    seen = []
    X, y = _seg_data()
    lgb.train(_params(num_leaves=15, tpu_boost_chunk=4), lgb.Dataset(X, y),
              num_boost_round=8, callbacks=[lambda env: seen.append(1)])
    for name in ("lgbm:chunk", "lgbm:fetch", "lgbm:booster_init",
                 "lgbm:fetch_wait", "lgbm:materialize", "lgbm:callbacks",
                 "lgbm:nonfinite_guard",
                 "lgbm:h2d_upload", "lgbm:compile[boost/chunk[4]]",
                 "lgbm:bin_find", "lgbm:bin_quantize"):
        assert name in entered, name
    assert entered.count("chunk") == 2          # the step annotations
    assert seen


def test_phase_yields_nothing_and_mode_is_constant():
    with GLOBAL_TIMER.phase("probe") as got:
        pass
    assert got is None
    assert GLOBAL_TIMER.snapshot()["probe"][1] == 1
    assert TELEMETRY.stats()["mode"] == "dispatch"
    assert GLOBAL_TIMER.summary().startswith("phases[dispatch] ")


def test_chunk_program_names_its_scopes_and_grows_the_parents_trees():
    """jax.named_scope is HLO metadata only: the compiled chunk program
    names the grower's and the step's phases, and the model of a fixed
    seed is the parent commit's, bit for bit."""
    import hashlib
    import re

    bst = _seg_run(chunk=4)
    text = bst.gbdt._chunk_fns[4].executables()[0].as_text()
    scopes = set()
    for path in re.findall(r'op_name="([^"]*)"', text):
        scopes.update(path.split("/"))
    for name in ("compact", "hist_split", "split_scan", "unpermute",
                 "hist_root", "quantize_pack", "grad", "grad_stats",
                 "score", "pack_tree"):
        assert name in scopes, name
    digest = hashlib.sha256(bst.model_to_string().encode()).hexdigest()
    assert digest == PARENT_MODEL_SHA256
