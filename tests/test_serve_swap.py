"""Hot-swap, overload-shedding and refit-loop tests (serve robustness).

The contracts under test, from ISSUE 20:

  * ``ServeSession.swap`` replaces a resident model with zero request
    failures: in-flight work completes against the version live at its
    dispatch (bit-identical to that generation's ``Booster.predict``),
    and swapping one model never retraces the executables of untouched
    residents.
  * The quality gate keeps a bad candidate out (non-finite outputs,
    holdout-metric regression, or an injected ``serve/swap`` fault at
    the flip) — the old model keeps serving bit-identically and a
    ``swap_rejected`` record lands in the health stream.
    ``rollback()`` restores the retained previous generation exactly.
  * The bounded queue sheds overload with a named
    ``ServeOverloadError`` while admitted requests still complete; an
    injected RESOURCE_EXHAUSTED at dispatch is retried at half batch
    with replies bit-identical to the unsplit dispatch.
  * ``evict()`` fails still-queued requests eagerly by name; a worker
    wedged at ``close()`` fails its futures by name instead of
    dropping them.
  * ``RefitLoop`` closes the drift→refit→gated-swap loop and survives
    faulted attempts.
"""

import json
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.serve import (RefitLoop, ServeError, ServeOverloadError,
                                ServeSession, SwapRejectedError)
from lightgbm_tpu.utils.faults import FAULTS
from lightgbm_tpu.utils.telemetry import TELEMETRY


@pytest.fixture(autouse=True)
def _clean():
    TELEMETRY.reset()
    TELEMETRY.set_config_level(1)
    TELEMETRY.install_jax_listeners()
    yield
    FAULTS.configure()


def _make(rng, n=500, f=8):
    X = rng.normal(size=(n, f))
    X[:, 3] = rng.randint(0, 6, size=n)
    X[rng.rand(n) < 0.15, 1] = np.nan
    y = (np.nan_to_num(X[:, 0] + X[:, 1]) + (X[:, 3] % 2) > 0.6
         ).astype(np.float64)
    return X, y


def _train(rng, rounds=10, n=500):
    X, y = _make(rng, n=n)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15}
    ds = lgb.Dataset(X, y, categorical_feature=[3])
    return lgb.train(params, ds, num_boost_round=rounds), X, y


def _counters():
    return TELEMETRY.stats()["counters"]


# --------------------------------------------------------- atomic swap
def test_swap_bit_identical_and_flat_retraces_for_untouched(rng):
    """Three refit→swap cycles on model A while predicting model B:
    B's compiled executables never retrace (same pack shapes, per-model
    epoch bump only), and after each flip A serves the NEW generation
    bit-identically."""
    bstA, X, y = _train(rng)
    bstB, _, _ = _train(rng, rounds=6)
    Xq = X[:48].copy()
    refB = bstB.predict(Xq)
    with ServeSession(max_batch=64, max_delay_ms=0.0) as sess:
        a = sess.load(bstA, model_id="a")
        b = sess.load(bstB, model_id="b")
        # warm one FULL cycle: executables for both models, refit's
        # one-time jits, and the in-place pack-row update.  (The
        # swapped model itself recompiles once per epoch by design —
        # the flat-retrace contract is for UNTOUCHED residents.)
        sess.predict_direct(a, Xq)
        sess.predict_direct(b, Xq)
        Xw, yw = _make(rng, n=300)
        bstA.refit(Xw, yw, decay_rate=0.3)
        sess.swap(a, bstA, gated=False)              # warmup swap
        sess.predict_direct(a, Xq)
        sess.predict_direct(b, Xq)
        for i in range(3):
            X2, y2 = _make(rng, n=300)
            bstA.refit(X2, y2, decay_rate=0.3)
            ref_new = bstA.predict(Xq)
            pause = sess.swap(a, bstA, gated=False)
            assert pause >= 0.0
            # untouched model B: bit-identical, zero retraces
            c0 = _counters().get("compile/retraces", 0)
            np.testing.assert_array_equal(refB, sess.predict_direct(b, Xq))
            assert _counters().get("compile/retraces", 0) == c0
            # A serves the freshly flipped generation exactly
            np.testing.assert_array_equal(ref_new,
                                          sess.predict_direct(a, Xq))
        assert sess.registry.epoch_of(a) == 4
        assert sess.registry.epoch_of(b) == 0
        assert len(sess.registry.swap_pauses) == 4
    assert _counters()["serve/swaps"] == 4


def test_swap_under_load_zero_failures(rng):
    """Worker threads hammer model A through the queue while the main
    thread runs 3 refit→swap cycles: zero failed replies, and every
    reply is bit-identical to SOME generation that was live (requests
    complete against the snapshot pinned at their dispatch)."""
    bstA, X, _ = _train(rng)
    Xq = X[:32].copy()
    with ServeSession(max_batch=64, max_delay_ms=0.0) as sess:
        a = sess.load(bstA, model_id="a")
        refs = [bstA.predict(Xq)]
        sess.predict(a, Xq)                          # compile before load
        errors, mismatches, stop = [], [], threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    out = sess.predict(a, Xq, timeout=30)
                except Exception as exc:             # pragma: no cover
                    errors.append(exc)
                    return
                if not any(np.array_equal(out, r) for r in refs):
                    mismatches.append(out)           # pragma: no cover
                    return

        workers = [threading.Thread(target=hammer) for _ in range(3)]
        for w in workers:
            w.start()
        try:
            for _ in range(3):
                X2, y2 = _make(rng, n=300)
                bstA.refit(X2, y2, decay_rate=0.3)
                refs.append(bstA.predict(Xq))        # before the flip
                sess.swap(a, bstA, gated=False)
        finally:
            stop.set()
            for w in workers:
                w.join(timeout=30)
        assert not errors
        assert not mismatches
        assert all(p >= 0.0 for p in sess.registry.swap_pauses)


# --------------------------------------------------------- quality gate
def test_swap_rejected_nonfinite_candidate(rng, tmp_path):
    bstA, X, _ = _train(rng)
    bad, _, _ = _train(rng, rounds=6)
    # every leaf of the first tree: whichever leaves the holdout rows reach
    # (that depends on the trained tree's shape), the scores are non-finite
    bad.gbdt.models[0].leaf_value = np.full_like(
        bad.gbdt.models[0].leaf_value, np.nan, dtype=np.float64)
    Xq = X[:24].copy()
    ref = bstA.predict(Xq)
    hpath = tmp_path / "serve_health.jsonl"
    with ServeSession(max_batch=32, max_delay_ms=0.0,
                      health_out=str(hpath)) as sess:
        a = sess.load(bstA, model_id="a")
        with pytest.raises(SwapRejectedError, match="non-finite"):
            sess.swap(a, bad, holdout=Xq)
        # the old generation never stopped serving
        np.testing.assert_array_equal(ref, sess.predict_direct(a, Xq))
        assert sess.registry.epoch_of(a) == 0
    kinds = [json.loads(line)["kind"]
             for line in hpath.read_text().splitlines()]
    assert "swap_begin" in kinds and "swap_rejected" in kinds
    assert "swap_flip" not in kinds
    assert _counters()["serve/swap_rejected"] == 1


def test_swap_rejected_metric_regression(rng):
    bstA, X, y = _train(rng)
    # a candidate fit to SHUFFLED labels: finite but strictly worse
    yr = y.copy()
    rng.shuffle(yr)
    ds = lgb.Dataset(X, yr, categorical_feature=[3])
    worse = lgb.train({"objective": "binary", "verbose": -1,
                       "num_leaves": 15}, ds, num_boost_round=10)
    Xq, yq = X[:200], y[:200]
    ref = bstA.predict(Xq)
    with ServeSession(max_batch=32, max_delay_ms=0.0) as sess:
        a = sess.load(bstA, model_id="a")
        with pytest.raises(SwapRejectedError, match="regressed"):
            sess.swap(a, worse, holdout=Xq, label=yq,
                      quality_threshold=0.05)
        np.testing.assert_array_equal(ref, sess.predict_direct(a, Xq))


def test_swap_gate_uses_replay_reservoir(rng):
    """With no explicit holdout the gate shadow-scores on the
    deterministic reservoir of recently served rows."""
    bstA, X, _ = _train(rng)
    cand, _, _ = _train(rng, rounds=8)
    with ServeSession(max_batch=32, max_delay_ms=0.0) as sess:
        a = sess.load(bstA, model_id="a")
        sess.predict_direct(a, X[:100])              # feeds the reservoir
        assert sess.registry.replay_rows(a) is not None
        sess.swap(a, cand)                           # gated, finite: flips
        np.testing.assert_array_equal(cand.predict(X[:16]),
                                      sess.predict_direct(a, X[:16]))


def test_swap_fault_at_flip_keeps_old_serving(rng):
    bstA, X, _ = _train(rng)
    cand, _, _ = _train(rng, rounds=6)
    Xq = X[:24].copy()
    ref = bstA.predict(Xq)
    with ServeSession(max_batch=32, max_delay_ms=0.0) as sess:
        a = sess.load(bstA, model_id="a")
        FAULTS.configure("serve/swap")
        with pytest.raises(SwapRejectedError, match="serve/swap"):
            sess.swap(a, cand, gated=False)
        np.testing.assert_array_equal(ref, sess.predict_direct(a, Xq))
        # the site healed: the next swap goes through
        sess.swap(a, cand, gated=False)
        np.testing.assert_array_equal(cand.predict(Xq),
                                      sess.predict_direct(a, Xq))


def test_rollback_restores_previous_generation(rng):
    bstA, X, _ = _train(rng)
    cand, _, _ = _train(rng, rounds=6)
    Xq = X[:24].copy()
    ref0 = bstA.predict(Xq)
    with ServeSession(max_batch=32, max_delay_ms=0.0) as sess:
        a = sess.load(bstA, model_id="a")
        sess.predict_direct(a, Xq)
        sess.swap(a, cand, gated=False)
        np.testing.assert_array_equal(cand.predict(Xq),
                                      sess.predict_direct(a, Xq))
        sess.rollback(a)
        np.testing.assert_array_equal(ref0, sess.predict_direct(a, Xq))
        # ping-pong: the rollback retained the swapped-in generation
        sess.rollback(a)
        np.testing.assert_array_equal(cand.predict(Xq),
                                      sess.predict_direct(a, Xq))
    assert _counters()["serve/rollbacks"] == 2


def test_rollback_without_previous_generation_errors(rng):
    bstA, _, _ = _train(rng, rounds=4)
    with ServeSession(max_batch=16, max_delay_ms=0.0) as sess:
        a = sess.load(bstA, model_id="a")
        with pytest.raises(ServeError, match="no retained"):
            sess.rollback(a)


# ------------------------------------------------------------- overload
def test_overload_sheds_excess_admits_complete(rng):
    bstA, X, _ = _train(rng)
    with ServeSession(max_batch=256, max_delay_ms=400.0,
                      max_queue_rows=8) as sess:
        a = sess.load(bstA, model_id="a")
        # 8 rows fill the bound while the 400ms coalescing window holds
        # them queued; the next submit must shed, not block or drop
        f1 = sess.submit(a, X[:8])
        with pytest.raises(ServeOverloadError, match="serve_max_queue_rows"):
            sess.submit(a, X[8:12])
        np.testing.assert_array_equal(bstA.predict(X[:8]),
                                      f1.result(timeout=30))
        # capacity freed: the queue admits again
        np.testing.assert_array_equal(bstA.predict(X[:4]),
                                      sess.predict(a, X[:4]))
    c = _counters()
    assert c["serve/shed_requests"] == 1
    assert c["serve/shed_rows"] == 4


def test_forced_shed_fault_site(rng):
    bstA, X, _ = _train(rng)
    with ServeSession(max_batch=32, max_delay_ms=0.0) as sess:
        a = sess.load(bstA, model_id="a")
        FAULTS.configure("serve/shed")
        with pytest.raises(ServeOverloadError, match="serve/shed"):
            sess.predict(a, X[:4])
        np.testing.assert_array_equal(bstA.predict(X[:4]),
                                      sess.predict(a, X[:4]))


def test_oom_retry_halves_batch_bit_identical(rng):
    """An injected RESOURCE_EXHAUSTED at dispatch: the ladder halves
    the batch, retries, and the stitched replies are bit-identical to
    the unsplit dispatch."""
    bstA, X, _ = _train(rng)
    Xq = X[:16].copy()
    ref = bstA.predict(Xq)
    with ServeSession(max_batch=16, max_delay_ms=0.0) as sess:
        a = sess.load(bstA, model_id="a")
        FAULTS.configure("serve/oom")
        np.testing.assert_array_equal(ref, sess.predict_direct(a, Xq))
        assert sess.predictor._batch_cap == 8        # sticky half
        # subsequent traffic keeps working at the reduced cap
        np.testing.assert_array_equal(ref, sess.predict_direct(a, Xq))
    c = _counters()
    assert c["serve/oom_halvings"] == 1
    ev = [e for e in TELEMETRY.stats()["faults"]["events"]
          if e.get("kind") == "serve_oom"]
    assert ev and "serve/oom" in ev[-1].get("site", "")


# --------------------------------------------------- queue degradation
def test_evict_fails_queued_requests_by_name(rng):
    bstA, X, _ = _train(rng)
    with ServeSession(max_batch=256, max_delay_ms=400.0) as sess:
        a = sess.load(bstA, model_id="a")
        fut = sess.submit(a, X[:8])                  # held by the window
        sess.evict(a)
        with pytest.raises(ServeError, match="evicted while queued"):
            fut.result(timeout=30)
    assert _counters()["serve/evicted_queued"] == 1


def test_close_wedged_worker_fails_futures_by_name(rng):
    bstA, X, _ = _train(rng)
    sess = ServeSession(max_batch=16, max_delay_ms=0.0)
    release = threading.Event()
    try:
        a = sess.load(bstA, model_id="a")
        sess.predict(a, X[:4])                       # healthy first

        def wedge(*args, **kwargs):
            release.wait(30)
            raise ServeError("released after close")

        sess.predictor.predict = wedge
        fut = sess.submit(a, X[:4])
        # wait until the worker has actually taken the batch (close()
        # would otherwise win the race and fail it as merely pending)
        for _ in range(200):
            if sess.queue._current is not None:
                break
            time.sleep(0.01)
        assert sess.queue._current is not None
        sess.queue.join_timeout_s = 0.3
        sess.close()
        with pytest.raises(ServeError, match="wedged at close"):
            fut.result(timeout=30)
        assert _counters()["serve/wedged_close"] == 1
    finally:
        release.set()


# ------------------------------------------------------------ refit loop
def _drifted_session(rng, psi_threshold=0.05):
    bst, X, y = _train(rng)
    sess = ServeSession(max_batch=256, max_delay_ms=0.0,
                        drift_detect=True,
                        drift_psi_threshold=psi_threshold)
    mid = sess.load(bst, model_id="m")
    # shift the numeric columns hard: served occupancy piles into the
    # extreme bins, PSI blows past any sane threshold
    Xs = X[:256].copy()
    Xs[:, [0, 1, 2, 5, 6, 7]] += 4.0
    ys = (np.nan_to_num(Xs[:, 0] + Xs[:, 1]) + (Xs[:, 3] % 2) > 0.6
          ).astype(np.float64)
    sess.predict_direct(mid, Xs)                     # accumulate drift
    return bst, sess, mid, Xs, ys


def test_refit_loop_requires_drift_gate(rng):
    bst, X, _ = _train(rng, rounds=4)
    with ServeSession(max_batch=16, max_delay_ms=0.0) as sess:
        sess.load(bst, model_id="m")
        with pytest.raises(ServeError, match="drift_detect"):
            RefitLoop(sess, "m", bst, lambda: None)


def test_refit_loop_drift_to_swap_end_to_end(rng):
    bst, sess, mid, Xs, ys = _drifted_session(rng)
    try:
        assert sess.drift_gate.drifted(mid)
        loop = RefitLoop(sess, mid, bst, lambda: (Xs, ys),
                         quality_threshold=5.0)
        assert loop.run_once() == "swapped"
        # the swap re-registered the drift state: with no traffic since
        # the flip, the trigger does not immediately re-fire.  (Checked
        # BEFORE any further predicts — the traffic really is shifted,
        # so new rows legitimately re-arm the gate.)
        assert loop.run_once() == "idle"
        assert loop.swaps == 1
        # the refitted generation is live and bit-identical
        np.testing.assert_array_equal(bst.predict(Xs[:16]),
                                      sess.predict_direct(mid, Xs[:16]))
        assert sess.registry.epoch_of(mid) == 1
    finally:
        sess.close()
    assert _counters()["serve/refits"] == 1


def test_refit_loop_survives_injected_fault(rng):
    bst, sess, mid, Xs, ys = _drifted_session(rng)
    try:
        lv0 = [np.array(t.leaf_value) for t in bst.gbdt.models]
        ref = sess.predict_direct(mid, Xs[:16])
        loop = RefitLoop(sess, mid, bst, lambda: (Xs, ys),
                         quality_threshold=5.0)
        FAULTS.configure("serve/refit")
        assert loop.run_once() == "fault"
        # the booster and the served model are both untouched
        for t, lv in zip(bst.gbdt.models, lv0):
            np.testing.assert_array_equal(t.leaf_value, lv)
        np.testing.assert_array_equal(ref,
                                      sess.predict_direct(mid, Xs[:16]))
        # the site healed and the drift signal is still armed
        assert loop.run_once() == "swapped"
        assert (loop.faults, loop.swaps) == (1, 1)
    finally:
        sess.close()
    assert _counters()["serve/refit_faults"] == 1


def test_refit_loop_thread_lifecycle(rng):
    bst, sess, mid, Xs, ys = _drifted_session(rng)
    try:
        loop = sess.start_refit_loop(mid, bst, lambda: (Xs, ys),
                                     poll_s=0.02, quality_threshold=5.0,
                                     max_refits=1)
        deadline = threading.Event()
        for _ in range(200):                         # ≤ 4s
            if loop.swaps >= 1:
                break
            deadline.wait(0.02)
        assert loop.swaps == 1
        assert sess.registry.epoch_of(mid) == 1
    finally:
        sess.close()                                 # stops the loop
    assert not loop._thread.is_alive()
