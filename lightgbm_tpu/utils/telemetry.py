"""Process-global training telemetry: spans, counters, gauges, a
per-iteration timeline, and Chrome trace-event export.

The reference fork's defining additions over stock LightGBM are
observability: easy_profiler trace blocks (src/main.cpp:13-39), TIMETAG
per-phase accumulators (serial_tree_learner.cpp:20-47) and network
byte/time counters (linkers.h:114-117).  This module is the TPU build's
superset of all three, layered on top of the existing ``PhaseTimer``
(utils/phase.py), which keeps its role as the per-phase accumulator and
additionally feeds every finished phase into the span ring buffer here.

Three telemetry levels gate the overhead:

  * ``0`` — off.  Every record call is a single attribute compare.
  * ``1`` — default.  Counters, gauges and the per-iteration timeline
    accumulate; phase seconds keep accruing in ``PhaseTimer``.
  * ``2`` — adds timestamped spans in a bounded ring buffer, exportable
    as Chrome trace-event JSON (load in Perfetto / chrome://tracing).

The effective level resolves lazily (env vars are read at refresh time,
not import time, so the test harness's env scrubbing and monkeypatching
behave): ``LIGHTGBM_TPU_TELEMETRY`` wins if set, else the
``telemetry_level`` config parameter, else 1; a set
``LIGHTGBM_TPU_TRACE_JSON=<path>`` forces the effective level to >= 2
and exports the trace there at the end of training (plus an atexit
backstop).

Timing caveat: device work is dispatched asynchronously, so spans and
phase seconds measure host-side dispatch (the ``mode`` field of
``stats()`` is the constant ``"dispatch"``); device time per phase comes
from a jax-profiler trace, which holds every phase as an ``lgbm:<name>``
annotation (see utils/phase.py).

Compile visibility comes from ``jax.monitoring`` listeners
(install_jax_listeners): retrace counts/seconds, backend compile
counts/seconds and compilation-cache hits/misses — cold-vs-warm cache
behavior is measurable instead of inferred from wall-clock cliffs.

The v2 schema adds two DEVICE-side sections on top of the host view:

  * ``memory`` — HBM gauges from ``device.memory_stats()`` (bytes in
    use, peak, largest allocation, the device byte limit), sampled at
    phase boundaries (utils/phase.py) and optionally by a low-rate
    background thread (``LIGHTGBM_TPU_MEM_SAMPLE_MS``, off by default)
    whose samples feed a ``mem/*`` counter track in the Chrome trace.
    Backends whose ``memory_stats()`` returns ``None`` (CPU) cleanly
    omit the section.  Reading allocator stats never syncs the device.
  * ``cost`` — static XLA ``Compiled.cost_analysis()`` (flops, bytes
    accessed, transcendentals) harvested once per compiled executable
    at the jit seams (utils/jitcost.py), keyed by function label and
    multiplied out by call counts, so ``stats()`` can report
    estimated FLOPs/s and bytes/s for the measured window.

The v4 schema adds MEASURED device time: an opt-in ``timing`` section
(``device_timing=`` config parameter / ``LIGHTGBM_TPU_DEVICE_TIMING``
env) fed by utils/jitcost.py, which times every instrumented jit
dispatch wall-to-ready (sync on the returned buffers) and accumulates
per-label count/total/mean/p50/p99 plus the dispatch GAP (host overhead
between consecutive dispatches of the same label).  Dividing the v2
``cost`` section's static FLOPs/bytes by the measured seconds yields
real utilization next to the estimated one.  The section also records
the jax-profiler capture artifact (path + iteration window) when a
``profile_window=START:END`` capture ran (utils/phase.py).

The v3 schema adds the STREAMING run-health layer: every blob carries
top-level ``schema`` and ``telemetry_level`` keys (so tools can branch
without sniffing sections), and — when a run writes a health stream —
a ``health`` digest section.  The stream itself (``HealthStream``, one
process-global ``HEALTH``) is an append-only JSONL file
(``health_out=`` config parameter / ``LIGHTGBM_TPU_HEALTH_JSONL`` env)
written at eval/chunk cadence while training runs, so a 5-hour job is
legible while it is alive, not only after its ``finally`` flush.  Each
record is a single ``os.write`` to an ``O_APPEND`` descriptor, so
records never tear even when a signal kills the process mid-run;
``resume=true`` compacts records past the snapshot iteration and keeps
appending, yielding ONE contiguous stream across kill+resume.  Consume
it live with ``tools/run_monitor.py``.

The v5 schema adds the SERVE observability plane: every request through
the micro-batching queue (serve/queue.py) records its lifecycle stage
walls (``serve/t_queue`` → ``serve/t_coalesce`` → ``serve/t_dispatch``
→ ``serve/t_reply``) through :meth:`record_dispatch`, feeds one
completed-request sample into a bounded sliding window here
(:meth:`serve_request_done`), and ``stats()`` gains a ``serve`` section
with the last-10s QPS and end-to-end p50/p99
(:meth:`serve_window_stats`).  The serve plane additionally streams its
own health JSONL (``serve/health.py``, the same O_APPEND never-torn
writer as training, ``serve_start``/``serve_window``/``serve_admit``/
``serve_fault``/``serve_summary`` record kinds) — deliberately a
SEPARATE ``HealthStream`` instance, so serving a model can never touch
a training run's stream or its models.

The v6 schema adds the FLEET observability plane (lightgbm_tpu/obs/):
every health record carries a paired ``{wall_ts, mono_ts}`` clock stamp
(:func:`clock_pair`), traces embed ``mono_epoch``/``wall_epoch``/rank
anchors so ``tools/fleet_trace.py`` can merge per-rank traces onto one
skew-corrected timeline, and ``obs/fleet.py`` kv-allgathers per-rank
per-collective enter/duration tables to split collective wall into
*wait* (skew-corrected idle before the slowest rank arrives) vs *work*
(transfer/reduce) seconds — the ``dist/wait_s``/``dist/work_s`` counter
pair, a named straggler rank per window (``dist_window`` records), and
the ``fleet`` stats section.  All of it is host-side timing and IO:
trained models stay byte-identical with the plane on or off.
"""

from __future__ import annotations

import atexit
import json
import os
import threading
import time
from collections import defaultdict, deque
from contextlib import contextmanager
from typing import Any, Dict, Optional

METRICS_SCHEMA = "lightgbm_tpu.metrics/v7"
METRICS_VERSION = 7
HEALTH_SCHEMA = "lightgbm_tpu.health/v1"
HEALTH_ENV = "LIGHTGBM_TPU_HEALTH_JSONL"
TIMING_ENV = "LIGHTGBM_TPU_DEVICE_TIMING"
SPAN_CAPACITY = 65536
TIMELINE_CAPACITY = 8192
MEM_TRACK_CAPACITY = 16384
FAULT_CAPACITY = 512
# bounded per-label reservoir backing the p50/p99 dispatch quantiles
TIMING_SAMPLE_CAPACITY = 4096
# serve sliding window: width of the stats() serve section and the
# capacity of the (t_done, latency) completed-request ring behind it
SERVE_WINDOW_S = 10.0
SERVE_SAMPLE_CAPACITY = 65536

# jax.monitoring event name -> (count counter, seconds counter)
_JAX_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("compile/retraces", "compile/retrace_seconds"),
    "/jax/core/compile/backend_compile_duration":
        ("compile/backend_compiles", "compile/backend_compile_seconds"),
}
# jax.monitoring count-only event -> counter
_JAX_COUNT_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile/cache_hits",
    "/jax/compilation_cache/cache_misses": "compile/cache_misses",
}


def clock_pair() -> Dict[str, float]:
    """The v6 record timestamp pair: ``wall_ts`` (``time.time()``, for
    humans and cross-restart ordering) and ``mono_ts``
    (``time.monotonic()``, for merge ordering — NTP steps and clock
    slew never reorder it).  Cross-rank, ``mono_ts`` values live on
    per-host clocks with arbitrary epochs; ``obs/clockskew.py``
    estimates the per-rank offsets that map them onto one timeline."""
    return {"wall_ts": round(time.time(), 6),
            "mono_ts": round(time.monotonic(), 6)}


class HealthStream:
    """Append-only JSONL run-health stream (schema ``HEALTH_SCHEMA``).

    Record kinds:

      * ``start`` / ``resume`` — stream (re)opened; ``resume`` carries
        the snapshot iteration the run continues from.
      * ``iter`` — one boosting iteration: dispatched chunk size,
        per-tree shape stats (leaves, depth, split-gain sum/max),
        per-class gradient/hessian stats (min/max/l2/nonfinite — folded
        into the chunk scan, zero extra dispatches), and the HBM gauge
        when the backend reports allocator stats.
      * ``eval`` — train/valid metric values at the eval cadence.
      * ``snapshot`` — a resumable snapshot was written.
      * ``fault`` — mirror of every ``TELEMETRY.fault_event``.
      * ``summary`` — stream closed (``aborted`` marks a crash/signal).

    Every record is one ``os.write`` to an ``O_APPEND`` descriptor —
    atomic on POSIX regular files at these sizes, so a SIGKILL between
    records never leaves a torn line.  On resume the existing file is
    compacted first (iteration-scoped records at/past the snapshot
    iteration are dropped via tmp + ``os.replace``), so a killed run
    whose pipeline had materialized past the snapshot re-emits those
    iterations exactly once and the stream stays contiguous.
    """

    # record kinds scoped to an iteration index: these are dropped at/
    # past the snapshot iteration when a resumed run compacts the file
    _ITER_SCOPED = ("iter", "eval", "snapshot")

    def __init__(self) -> None:
        self._lock = threading.RLock()
        self._path = ""
        self._fd: Optional[int] = None
        self._t0 = time.perf_counter()
        self._records = 0
        self._by_kind: Dict[str, int] = defaultdict(int)
        self._last_iter: Optional[Dict[str, Any]] = None
        self._nonfinite_total = 0

    # ------------------------------------------------------------- config
    @staticmethod
    def resolve_path(config=None) -> str:
        """Stream destination: the env var wins over the ``health_out``
        config parameter; "" = no stream."""
        env = os.environ.get(HEALTH_ENV, "")
        if env:
            return env
        if config is not None:
            return str(getattr(config, "health_out", "") or "")
        return ""

    @property
    def active(self) -> bool:
        return self._fd is not None

    # ---------------------------------------------------------- lifecycle
    def open(self, path: str, resume_iter: Optional[int] = None,
             meta: Optional[Dict[str, Any]] = None,
             start_kind: Optional[str] = None) -> None:
        """Open (or, with ``resume_iter``, compact-and-continue) the
        stream and write the ``start``/``resume`` record.  An IO failure
        is survivable: logged, and the stream stays inactive.
        ``start_kind`` renames the opening record (the serve plane's
        private stream opens with ``serve_start``)."""
        from .log import log_warning
        with self._lock:
            if self._fd is not None:
                self.close(summary=False)
            self._path = ""
            self._records = 0
            self._by_kind = defaultdict(int)
            self._last_iter = None
            self._nonfinite_total = 0
            self._t0 = time.perf_counter()
            try:
                resuming = (resume_iter is not None
                            and os.path.exists(path))
                if resuming:
                    self._compact_for_resume(path, int(resume_iter))
                flags = os.O_WRONLY | os.O_CREAT | os.O_APPEND
                if not resuming:
                    flags |= os.O_TRUNC
                self._fd = os.open(path, flags, 0o644)
            except OSError as e:
                self._fd = None
                log_warning(f"could not open health stream {path}: {e}")
                return
            self._path = path
            rec: Dict[str, Any] = {
                "kind": ("resume" if resuming
                         else (start_kind or "start")),
                "schema": HEALTH_SCHEMA,
                "ts": round(time.time(), 3),
                "pid": os.getpid(),
            }
            rec.update(clock_pair())
            if resuming:
                rec["iter"] = int(resume_iter)
            if meta:
                rec.update(meta)
            self._ingest(rec)
            self._write(rec)

    def _compact_for_resume(self, path: str, resume_iter: int) -> None:
        """Drop iteration-scoped records at/past the snapshot iteration
        (the resumed run re-emits them) and any stale ``summary``;
        re-ingest the survivors so the digest covers the whole run."""
        kept = []
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue                    # torn/corrupt line
                kind = rec.get("kind")
                if kind == "summary":
                    continue
                if (kind in self._ITER_SCOPED
                        and int(rec.get("iter", -1)) >= resume_iter):
                    continue
                kept.append((line, rec))
        d = os.path.dirname(os.path.abspath(path))
        tmp = os.path.join(
            d, f".{os.path.basename(path)}.tmp.{os.getpid()}")
        with open(tmp, "w") as fh:
            for line, _ in kept:
                fh.write(line + "\n")
            fh.flush()
            os.fsync(fh.fileno())
        os.replace(tmp, path)
        for _, rec in kept:
            self._ingest(rec)

    def close(self, summary: bool = True, aborted: bool = False,
              extra: Optional[Dict[str, Any]] = None) -> None:
        """Write the ``summary`` record (unless suppressed) and release
        the descriptor.  ``extra`` fields are merged into the summary
        (e.g. the trainer's top-K feature importances).  The digest
        state stays readable afterwards so a post-run ``stats()`` still
        carries the ``health`` section."""
        with self._lock:
            if self._fd is None:
                return
            if summary:
                rec: Dict[str, Any] = {
                    "kind": "summary",
                    "ts": round(time.time(), 3),
                    "records": self._records + 1,
                    "aborted": bool(aborted),
                }
                rec.update(clock_pair())
                if self._last_iter is not None:
                    rec["iterations"] = int(self._last_iter["iter"]) + 1
                if self._nonfinite_total:
                    rec["nonfinite_total"] = self._nonfinite_total
                if extra:
                    rec.update(extra)
                self._ingest(rec)
                self._write(rec)
            fd, self._fd = self._fd, None
            try:
                os.close(fd)
            except OSError:
                pass

    def reset(self) -> None:
        """Drop the stream and the digest state (test/bench windows)."""
        with self._lock:
            self.close(summary=False)
            self._path = ""
            self._records = 0
            self._by_kind = defaultdict(int)
            self._last_iter = None
            self._nonfinite_total = 0

    # ------------------------------------------------------------ records
    def record(self, kind: str, fields: Optional[Dict[str, Any]] = None,
               ) -> None:
        """Append one record; no-op when the stream is closed.  ``t`` is
        stamped as seconds since the stream opened unless provided."""
        with self._lock:
            if self._fd is None:
                return
            rec: Dict[str, Any] = {"kind": kind}
            if fields:
                rec.update(fields)
            rec.setdefault("t", round(time.perf_counter() - self._t0, 6))
            for k, v in clock_pair().items():
                rec.setdefault(k, v)
            self._ingest(rec)
            self._write(rec)

    def _ingest(self, rec: Dict[str, Any]) -> None:
        self._records += 1
        self._by_kind[rec.get("kind", "?")] += 1
        if rec.get("kind") == "iter":
            self._last_iter = rec
            for sec in ("grad", "hess"):
                nf = (rec.get(sec) or {}).get("nonfinite")
                if nf:
                    self._nonfinite_total += int(sum(nf))

    def _write(self, rec: Dict[str, Any]) -> None:
        line = json.dumps(rec, separators=(",", ":")) + "\n"
        try:
            os.write(self._fd, line.encode())
        except OSError as e:
            # a full disk must degrade the stream, not kill training
            from .log import log_warning
            log_warning(f"health stream write to {self._path} failed "
                        f"({e}); stream disabled for the rest of the run")
            try:
                os.close(self._fd)
            except OSError:
                pass
            self._fd = None

    # ------------------------------------------------------------- digest
    def summary_section(self) -> Optional[Dict[str, Any]]:
        """The ``health`` section of ``stats()``: stream path, record
        counts by kind, the last ``iter`` record, and nonfinite totals.
        ``None`` when this process never opened a stream."""
        with self._lock:
            if not self._path:
                return None
            out: Dict[str, Any] = {
                "schema": HEALTH_SCHEMA,
                "path": self._path,
                "active": self._fd is not None,
                "records": self._records,
                "by_kind": dict(self._by_kind),
            }
            if self._last_iter is not None:
                out["last_iter"] = dict(self._last_iter)
            if self._nonfinite_total:
                out["nonfinite_total"] = self._nonfinite_total
            return out


HEALTH = HealthStream()


class TelemetryRegistry:
    """Thread-safe registry of counters, gauges, spans and the
    per-iteration timeline.  One process-global instance (``TELEMETRY``)
    exists; tests may construct private ones."""

    def __init__(self, span_capacity: int = SPAN_CAPACITY) -> None:
        self._lock = threading.RLock()
        self._counters: Dict[str, float] = defaultdict(float)
        self._gauges: Dict[str, float] = {}
        # (ts_us, dur_us, name, tid_label, args|None)
        self._spans: deque = deque(maxlen=span_capacity)
        self._spans_recorded = 0
        self._timeline: deque = deque(maxlen=TIMELINE_CAPACITY)
        self._iter_snapshot: Dict[str, float] = {}
        # GLOBAL_TIMER's (seconds, count) per phase at the last mark
        self._phase_snapshot: Dict[str, Any] = {}
        self._epoch = time.perf_counter()
        self._config_level: Optional[int] = None
        self._jax_listeners_installed = False
        # single-writer race check, analogous to the reference Network's
        # single-thread CHECK: the first writer thread claims the stream;
        # a second one is recorded (and warned about) once, not fatal
        self._writer: Optional[int] = None
        self._race_flagged = False
        # ------ device memory (HBM) accounting ------
        # tri-state support flag: None = unknown, False = backend has no
        # memory_stats (CPU) — once False, sampling short-circuits
        self._mem_supported: Optional[bool] = None
        self._mem_device = None
        self._mem_last: Optional[int] = None
        self._mem_peak = 0
        self._mem_largest = 0
        self._mem_limit: Optional[int] = None
        self._mem_phase: Dict[str, Dict[str, int]] = {}
        # (t_offset_s, bytes_in_use) from the background sampler, for
        # the Chrome-trace mem/* counter track
        self._mem_track: deque = deque(maxlen=MEM_TRACK_CAPACITY)
        self._mem_thread: Optional[threading.Thread] = None
        self._mem_stop: Optional[threading.Event] = None
        self._mem_interval_ms = 0.0
        # which tier the binned training matrix lives in ("resident" /
        # "spill", models/gbdt.py); None until a run resolves it
        self._data_tier: Optional[str] = None
        # ------ XLA cost analysis (per jit-seam label) ------
        self._costs: Dict[str, Dict[str, float]] = {}
        # ------ measured per-dispatch timing (opt-in, v4) ------
        # label -> {count, total_s, samples, last_end, gap_count,
        # gap_total_s}; fed by utils/jitcost.py only when ``timing_on``
        self._timing: Dict[str, Dict[str, Any]] = {}
        self._config_timing = False
        # the jax-profiler capture artifact (utils/phase.py): path and,
        # for windowed captures, the iteration span
        self._profile_capture: Optional[Dict[str, Any]] = None
        # ------ serve sliding window (v5) ------
        # (t_done rel epoch, end-to-end latency) of completed serve
        # requests; serve/queue.py appends one sample per reply and
        # serve_window_stats() folds the trailing SERVE_WINDOW_S into
        # live QPS/p50/p99 — the bound makes a long-lived server's
        # memory flat no matter how much traffic it absorbs
        self._serve_done: deque = deque(maxlen=SERVE_SAMPLE_CAPACITY)
        # ------ fault / recovery narration ------
        # every injected fault, rollback, retry and salvage lands here so
        # the metrics blob can explain a degraded run; recorded at EVERY
        # level (faults are rare and load-bearing, unlike hot-path spans)
        self._faults: deque = deque(maxlen=FAULT_CAPACITY)
        self._fault_counts: Dict[str, float] = defaultdict(float)
        self._level = self._resolve_level()
        # plain attribute (not a property): the hot-path off-switch in
        # utils/jitcost.py stays one attribute compare
        self.timing_on = self._resolve_timing()

    # ------------------------------------------------------------- level
    def _resolve_level(self) -> int:
        env = os.environ.get("LIGHTGBM_TPU_TELEMETRY", "")
        if env != "":
            try:
                lvl = int(env)
            except ValueError:
                lvl = 1
        elif self._config_level is not None:
            lvl = self._config_level
        else:
            lvl = 1
        if os.environ.get("LIGHTGBM_TPU_TRACE_JSON"):
            lvl = max(lvl, 2)
        return max(0, min(2, lvl))

    def refresh_level(self) -> int:
        """Re-read env/config into the cached level (the hot-path gate is
        one attribute compare; refresh happens at setup boundaries)."""
        self._level = self._resolve_level()
        self.timing_on = self._resolve_timing()
        return self._level

    @property
    def level(self) -> int:
        return self._level

    def set_config_level(self, level) -> None:
        """Bind the ``telemetry_level`` config parameter (env wins)."""
        try:
            self._config_level = int(level)
        except (TypeError, ValueError):
            self._config_level = None
        self.refresh_level()

    def _resolve_timing(self) -> bool:
        """Measured-dispatch timing is an opt-in on TOP of level >= 1
        (jitcost's level gate already short-circuits below that):
        ``LIGHTGBM_TPU_DEVICE_TIMING`` wins over the ``device_timing``
        config parameter."""
        if self._level < 1:
            return False
        env = os.environ.get(TIMING_ENV, "")
        if env != "":
            return env.strip().lower() not in ("0", "false", "off", "no")
        return bool(self._config_timing)

    def set_config_timing(self, flag) -> None:
        """Bind the ``device_timing`` config parameter (env wins)."""
        self._config_timing = bool(flag)
        self.timing_on = self._resolve_timing()

    # ----------------------------------------------------- writer check
    def _note_writer(self) -> None:
        ident = threading.get_ident()
        if self._writer is None:
            self._writer = ident
        elif self._writer != ident and not self._race_flagged:
            self._race_flagged = True
            self._counters["telemetry/writer_races"] += 1
            from .log import log_warning
            log_warning("telemetry written from multiple threads; counts "
                        "stay consistent (locked) but span/timeline "
                        "ordering may interleave")

    # -------------------------------------------------- counters/gauges
    def counter_add(self, name: str, value: float = 1) -> None:
        if self._level < 1:
            return
        with self._lock:
            self._note_writer()
            self._counters[name] += value

    def gauge_set(self, name: str, value: float) -> None:
        if self._level < 1:
            return
        with self._lock:
            self._note_writer()
            self._gauges[name] = value

    def gauge_get(self, name: str, default=None):
        with self._lock:
            return self._gauges.get(name, default)

    # -------------------------------------------------------------- spans
    def record_span(self, name: str, t0: float, dur: float,
                    args: Optional[dict] = None,
                    tid: Optional[str] = None) -> None:
        """Record one finished span; ``t0`` is a time.perf_counter()
        value, ``dur`` seconds.  No-op below level 2."""
        if self._level < 2:
            return
        label = tid or threading.current_thread().name
        with self._lock:
            self._note_writer()
            self._spans_recorded += 1
            self._spans.append(((t0 - self._epoch) * 1e6, dur * 1e6,
                                name, label, args or None))

    @contextmanager
    def span(self, name: str, **args):
        """Context-managed span (host-side dispatch window; see module
        docstring for the async caveat)."""
        if self._level < 2:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.record_span(name, t0, time.perf_counter() - t0,
                             args or None)

    # ----------------------------------------------------------- timeline
    def mark_iteration(self, iteration: int, count: int = 1) -> None:
        """Close one timeline entry: iteration index (the last iteration
        when ``count`` > 1, i.e. a boosting chunk), the wall offset since
        reset, and the counter deltas and per-phase seconds and counts
        (``phases``, from the global PhaseTimer) since the previous
        mark.  A compile shows as a ``compile[label]`` phase of the
        entry that paid it, so a reader takes the steady entries alone."""
        if self._level < 1:
            return
        from .phase import GLOBAL_TIMER
        now = GLOBAL_TIMER.snapshot()
        with self._lock:
            phases = {}
            for name, (sec, cnt) in now.items():
                sec0, cnt0 = self._phase_snapshot.get(name, (0.0, 0))
                if cnt != cnt0:
                    phases[name] = {"seconds": round(sec - sec0, 9),
                                    "count": cnt - cnt0}
            self._phase_snapshot = now
            self._note_writer()
            deltas = {}
            for k, v in self._counters.items():
                d = v - self._iter_snapshot.get(k, 0)
                if d:
                    deltas[k] = round(d, 9) if isinstance(d, float) else d
            self._iter_snapshot = dict(self._counters)
            self._timeline.append(
                {"iter": int(iteration), "count": int(count),
                 "t": round(time.perf_counter() - self._epoch, 6),
                 "counters": deltas, "phases": phases})

    # -------------------------------------------------------------- faults
    def fault_event(self, kind: str, site: str = "", detail: str = "",
                    iteration: Optional[int] = None) -> None:
        """Record one fault/recovery event (``injected``, ``oom_degrade``,
        ``nonfinite_rollback``, ``snapshot_io``, ``resume``,
        ``collective_retry``, ``partial_save`` ...).  Unlike counters and
        spans this records at every telemetry level: faults are rare and
        explain why a run degraded, so they must never be gated away."""
        with self._lock:
            self._note_writer()
            self._fault_counts[kind] += 1
            ev: Dict[str, Any] = {
                "kind": kind,
                "t": round(time.perf_counter() - self._epoch, 6),
            }
            if site:
                ev["site"] = site
            if detail:
                ev["detail"] = detail
            if iteration is not None:
                ev["iter"] = int(iteration)
            self._faults.append(ev)
        # mirror into the health stream (its own lock; no nesting back
        # into this registry) so a live monitor sees faults as they land
        if HEALTH.active:
            fields: Dict[str, Any] = {"fault": kind}
            if site:
                fields["site"] = site
            if detail:
                fields["detail"] = detail
            if iteration is not None:
                fields["iter"] = int(iteration)
            HEALTH.record("fault", fields)

    def _faults_section(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            if not self._faults and not self._fault_counts:
                return None
            return {"counts": dict(self._fault_counts),
                    "events": [dict(e) for e in self._faults]}

    # ------------------------------------------------------ jax.monitoring
    def install_jax_listeners(self) -> None:
        """Register jax.monitoring listeners for compile/retrace/cache
        events.  Idempotent; jax offers no unregistration, so callbacks
        stay bound to this (process-global) registry and self-gate on the
        current level."""
        if self._jax_listeners_installed:
            return
        self._jax_listeners_installed = True
        try:
            from jax import monitoring
        except ImportError:      # pragma: no cover - jax is a hard dep
            return

        def on_event(event, **kw):
            name = _JAX_COUNT_EVENTS.get(event)
            if name is not None:
                self.counter_add(name)

        def on_duration(event, duration, **kw):
            names = _JAX_DURATION_EVENTS.get(event)
            if names is None:
                return
            self.counter_add(names[0])
            self.counter_add(names[1], float(duration))
            if self._level >= 2:
                now = time.perf_counter()
                self.record_span(event.rsplit("/", 1)[-1],
                                 now - float(duration), float(duration),
                                 tid="jax-compile")

        monitoring.register_event_listener(on_event)
        monitoring.register_event_duration_secs_listener(on_duration)

    # ------------------------------------------------------- device memory
    def _device_memory_stats(self) -> Optional[Dict[str, Any]]:
        """Raw ``memory_stats()`` of the default device, or ``None`` on
        backends that do not report allocator stats (CPU).  The first
        ``None`` latches ``_mem_supported = False`` so later samples are
        a single attribute compare.  Reading allocator stats is a local
        runtime query — it never blocks on in-flight device work."""
        if self._mem_supported is False:
            return None
        try:
            if self._mem_device is None:
                import jax
                self._mem_device = jax.local_devices()[0]
            ms = self._mem_device.memory_stats()
        except Exception:
            ms = None
        if not ms:
            self._mem_supported = False
            return None
        self._mem_supported = True
        return ms

    def sample_memory(self, phase: Optional[str] = None) -> None:
        """Fold one allocator snapshot into the memory gauges; ``phase``
        attributes the bytes-in-use high-water mark to a named phase
        (called at phase boundaries by utils/phase.py).  No-op below
        level 1 or on backends without memory stats."""
        if self._level < 1 or self._mem_supported is False:
            return
        ms = self._device_memory_stats()
        if ms is None:
            return
        in_use = int(ms.get("bytes_in_use", 0))
        peak = int(ms.get("peak_bytes_in_use", in_use))
        # no _note_writer here: the background sampler is an EXPECTED
        # second thread; gauges are simple maxes under the lock
        with self._lock:
            if "bytes_limit" in ms:
                self._mem_limit = int(ms["bytes_limit"])
            self._mem_largest = max(self._mem_largest,
                                    int(ms.get("largest_alloc_size", 0)))
            self._mem_last = in_use
            self._mem_peak = max(self._mem_peak, peak, in_use)
            if phase:
                e = self._mem_phase.setdefault(
                    phase, {"bytes_in_use_max": 0, "samples": 0})
                e["bytes_in_use_max"] = max(e["bytes_in_use_max"], in_use)
                e["samples"] += 1

    def start_mem_sampler(self) -> None:
        """Start the background HBM sampler thread when
        ``LIGHTGBM_TPU_MEM_SAMPLE_MS`` requests one (off by default).
        Idempotent; the thread is a daemon and additionally bounded by
        stop_mem_sampler, so it can never outlive the training window
        it was started for."""
        if self._level < 1 or self._mem_thread is not None:
            return
        raw = os.environ.get("LIGHTGBM_TPU_MEM_SAMPLE_MS", "")
        try:
            interval_ms = float(raw)
        except ValueError:
            interval_ms = 0.0
        if interval_ms <= 0:
            return
        stop = threading.Event()

        def run() -> None:
            while not stop.wait(interval_ms / 1000.0):
                if self._mem_supported is False:
                    return          # nothing to sample; exit quietly
                self.sample_memory()
                ms = self._mem_last
                if ms is not None:
                    with self._lock:
                        self._mem_track.append(
                            (time.perf_counter() - self._epoch, ms))

        self._mem_stop = stop
        self._mem_interval_ms = interval_ms
        self._mem_thread = threading.Thread(target=run, name="mem-sampler",
                                            daemon=True)
        self._mem_thread.start()

    def stop_mem_sampler(self) -> None:
        """Stop and join the background sampler (idempotent)."""
        t, stop = self._mem_thread, self._mem_stop
        self._mem_thread = None
        self._mem_stop = None
        if stop is not None:
            stop.set()
        if t is not None:
            t.join(timeout=5.0)

    @contextmanager
    def memory_session(self):
        """Device-memory window around a training run: one boundary
        sample on entry and exit, plus the opt-in background sampler —
        exception-safe, so an error mid-training never leaks the
        sampler thread."""
        self.sample_memory("session")
        self.start_mem_sampler()
        try:
            yield
        finally:
            self.stop_mem_sampler()
            self.sample_memory("session")

    def device_memory_budget(self) -> Optional[int]:
        """The device allocator's reported capacity (``bytes_limit``) or
        None on backends without memory stats — the denominator of the
        out-of-core admission check (models/gbdt.py)."""
        ms = self._device_memory_stats()
        if not ms:
            return None
        limit = ms.get("bytes_limit")
        return int(limit) if limit else None

    def set_data_tier(self, tier: Optional[str]) -> None:
        """Record which tier the binned matrix lives in ("resident" /
        "spill").  Like fault_event this records at every level: a tier
        transition explains a run's performance cliff and must never be
        gated away."""
        with self._lock:
            self._data_tier = tier

    def data_tier(self) -> Optional[str]:
        with self._lock:
            return self._data_tier

    def memory_gauges(self) -> Optional[Dict[str, int]]:
        """Cheap HBM gauge for per-iteration health records: the last
        and peak bytes-in-use already sampled at phase boundaries — no
        fresh allocator query, so the hot path stays untouched.  None on
        backends without memory stats."""
        with self._lock:
            if self._mem_last is None:
                return None
            return {"bytes_in_use": self._mem_last,
                    "peak_bytes_in_use": self._mem_peak}

    def _memory_section(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            # a spilled run surfaces its tier even on backends without
            # allocator stats (CPU tests); a resident run on such a
            # backend keeps the section cleanly absent, as before
            if self._mem_last is None and self._data_tier != "spill":
                return None
            out: Dict[str, Any] = {}
            if self._mem_last is not None:
                out.update({
                    "bytes_in_use": self._mem_last,
                    "peak_bytes_in_use": self._mem_peak,
                    "largest_alloc": self._mem_largest,
                    "phases": {k: dict(v)
                               for k, v in self._mem_phase.items()},
                })
                if self._mem_limit is not None:
                    out["bytes_limit"] = self._mem_limit
                if self._mem_interval_ms > 0:
                    out["sampler"] = {"interval_ms": self._mem_interval_ms,
                                      "samples": len(self._mem_track)}
            if self._data_tier is not None:
                out["data_tier"] = self._data_tier
            return out

    # --------------------------------------------------- XLA cost analysis
    def record_cost(self, label: str, analysis: Dict[str, float]) -> None:
        """Bind one compiled executable's static cost analysis to a jit
        seam label (utils/jitcost.py harvests it once per compile).  The
        per-call numbers become the increment applied by cost_call."""
        if self._level < 1:
            return
        with self._lock:
            e = self._costs.setdefault(label, {
                "flops": 0.0, "bytes_accessed": 0.0,
                "transcendentals": 0.0, "calls": 0, "compiles": 0,
                "flops_total": 0.0, "bytes_total": 0.0})
            e["flops"] = float(analysis.get("flops", 0.0))
            e["bytes_accessed"] = float(analysis.get("bytes_accessed", 0.0))
            e["transcendentals"] = float(
                analysis.get("transcendentals", 0.0))
            # executable working set (memory_analysis), when available
            for k in ("temp_bytes", "argument_bytes", "output_bytes"):
                if k in analysis:
                    e[k] = float(analysis[k])
            e["compiles"] += 1

    def cost_working_set(self) -> int:
        """Largest per-executable working set (argument + temp + output
        bytes) among the cost-instrumented seams, from XLA's
        memory_analysis — 0 when nothing compiled yet.  Feeds the
        out-of-core admission check alongside the bin-matrix bytes."""
        with self._lock:
            best = 0
            for e in self._costs.values():
                ws = int(e.get("argument_bytes", 0)
                         + e.get("temp_bytes", 0)
                         + e.get("output_bytes", 0))
                best = max(best, ws)
            return best

    def cost_call(self, label: str, count: int = 1) -> None:
        """Count ``count`` dispatches of a cost-instrumented seam; the
        running totals use the label's CURRENT per-call cost, so they
        stay exact across recompiles at new shapes."""
        if self._level < 1:
            return
        with self._lock:
            e = self._costs.get(label)
            if e is None:
                return
            e["calls"] += count
            e["flops_total"] += e["flops"] * count
            e["bytes_total"] += e["bytes_accessed"] * count

    def _cost_section(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            if not self._costs:
                return None
            labels = {k: dict(v) for k, v in self._costs.items()}
            elapsed = time.perf_counter() - self._epoch
        flops_total = sum(e["flops_total"] for e in labels.values())
        bytes_total = sum(e["bytes_total"] for e in labels.values())
        out: Dict[str, Any] = {
            "labels": labels,
            "window_seconds": round(elapsed, 6),
            "flops_total": flops_total,
            "bytes_total": bytes_total,
        }
        if elapsed > 0:
            out["est_flops_per_s"] = flops_total / elapsed
            out["est_bytes_per_s"] = bytes_total / elapsed
        return out

    # ------------------------------------------- measured dispatch timing
    def record_dispatch(self, label: str, start: float, end: float) -> None:
        """Fold one measured wall-to-ready dispatch window (two
        ``time.perf_counter()`` values) into the per-label timing
        accumulators.  utils/jitcost.py calls this only when
        ``timing_on`` — the sync that produced ``end`` already happened.
        The gap accumulators measure host overhead between consecutive
        dispatches of the SAME label (end of one to start of the next)."""
        wall = max(0.0, end - start)
        with self._lock:
            e = self._timing.get(label)
            if e is None:
                e = self._timing[label] = {
                    "count": 0, "total_s": 0.0,
                    "samples": deque(maxlen=TIMING_SAMPLE_CAPACITY),
                    "last_end": None, "gap_count": 0, "gap_total_s": 0.0}
            e["count"] += 1
            e["total_s"] += wall
            e["samples"].append(wall)
            last_end = e["last_end"]
            if last_end is not None and start > last_end:
                e["gap_count"] += 1
                e["gap_total_s"] += start - last_end
            e["last_end"] = end

    def dispatch_seconds_total(self) -> float:
        """Sum of every label's measured wall-to-ready dispatch seconds.
        Zero until ``device_timing`` ran; deltas of this around a work
        window (the sched plane brackets each time slice with it) give
        that window's measured device-seconds without walking the
        per-label ``timing`` section."""
        with self._lock:
            return float(sum(e["total_s"] for e in self._timing.values()))

    def record_profile_capture(self, info: Dict[str, Any]) -> None:
        """Attach a jax-profiler capture's artifact location (and, for
        windowed captures, the iteration span) to the ``timing`` section.
        Recorded at every level: a capture the user asked for must be
        findable from the blob."""
        with self._lock:
            self._profile_capture = dict(info)

    @staticmethod
    def _quantile(sorted_vals, q: float) -> float:
        if not sorted_vals:
            return 0.0
        idx = min(len(sorted_vals) - 1,
                  int(round(q * (len(sorted_vals) - 1))))
        return float(sorted_vals[idx])

    # --------------------------------------------- serve sliding window
    def serve_request_done(self, latency_s: float,
                           end: Optional[float] = None) -> None:
        """Fold one completed serve request (end-to-end enqueue→reply
        latency) into the sliding window.  ``end`` is the reply's
        ``time.perf_counter()`` stamp (defaults to now)."""
        if self._level < 1:
            return
        t = (end if end is not None else time.perf_counter()) \
            - self._epoch
        with self._lock:
            self._serve_done.append((t, max(0.0, float(latency_s))))

    def serve_window_stats(self, window_s: float = SERVE_WINDOW_S,
                           now: Optional[float] = None,
                           ) -> Optional[Dict[str, Any]]:
        """Live serve rates over the trailing ``window_s`` seconds:
        request count, QPS and end-to-end p50/p99.  ``None`` when no
        request completed inside the window (distinguishes an idle
        server from one that never served)."""
        t_now = (now if now is not None else time.perf_counter()) \
            - self._epoch
        cutoff = t_now - window_s
        with self._lock:
            lat = sorted(lt for (t, lt) in self._serve_done
                         if t >= cutoff)
        if not lat:
            return None
        return {"window_s": float(window_s),
                "requests": len(lat),
                "qps": round(len(lat) / window_s, 3),
                "p50_s": round(self._quantile(lat, 0.50), 9),
                "p99_s": round(self._quantile(lat, 0.99), 9)}

    def _timing_section(self) -> Optional[Dict[str, Any]]:
        """The v4 ``timing`` section: per-label measured dispatch wall
        (count/total/mean/p50/p99/max + gap stats) and, for labels with
        cost analysis, measured FLOP/s and B/s — static work divided by
        MEASURED seconds, next to the blob-level estimated rates.  The
        quantiles come from a bounded per-label sample reservoir
        (``TIMING_SAMPLE_CAPACITY`` newest samples).  ``None`` when
        timing never ran and no profiler capture was taken."""
        with self._lock:
            if not self._timing and self._profile_capture is None:
                return None
            entries = {k: (dict(v), sorted(v["samples"]))
                       for k, v in self._timing.items()}
            costs = {k: dict(v) for k, v in self._costs.items()}
            capture = (dict(self._profile_capture)
                       if self._profile_capture is not None else None)
            enabled = bool(self.timing_on)
        labels: Dict[str, Any] = {}
        total_s = 0.0
        flops_timed = bytes_timed = 0.0
        have_cost = False
        for name, (e, samples) in entries.items():
            n = e["count"]
            lab: Dict[str, Any] = {
                "count": n,
                "total_s": round(e["total_s"], 6),
                "mean_s": round(e["total_s"] / n, 9) if n else 0.0,
                "p50_s": round(self._quantile(samples, 0.50), 9),
                "p99_s": round(self._quantile(samples, 0.99), 9),
                "max_s": round(samples[-1], 9) if samples else 0.0,
            }
            if e["gap_count"]:
                lab["gap_count"] = e["gap_count"]
                lab["gap_total_s"] = round(e["gap_total_s"], 6)
                lab["gap_mean_s"] = round(
                    e["gap_total_s"] / e["gap_count"], 9)
            c = costs.get(name)
            if c is not None and e["total_s"] > 0:
                lab["measured_flops_per_s"] = \
                    c["flops_total"] / e["total_s"]
                lab["measured_bytes_per_s"] = \
                    c["bytes_total"] / e["total_s"]
                flops_timed += c["flops_total"]
                bytes_timed += c["bytes_total"]
                have_cost = True
            labels[name] = lab
            total_s += e["total_s"]
        out: Dict[str, Any] = {"enabled": enabled or bool(labels)}
        if labels:
            out["labels"] = labels
            out["total_s"] = round(total_s, 6)
            if have_cost and total_s > 0:
                out["measured_flops_per_s"] = flops_timed / total_s
                out["measured_bytes_per_s"] = bytes_timed / total_s
        if capture is not None:
            out["profile"] = capture
        return out

    # ------------------------------------------------------------- output
    def stats(self) -> Dict[str, Any]:
        """Versioned stats dict: phases (from the global PhaseTimer),
        counters, gauges, network collective counters, the per-iteration
        timeline, span-buffer occupancy, and — when available — the
        device-side ``memory`` (HBM gauges) and ``cost`` (XLA cost
        analysis) sections.  ``memory`` is omitted on backends whose
        ``memory_stats()`` returns None; ``cost`` is omitted when no
        instrumented seam compiled in the window.  v3 adds top-level
        ``schema``/``telemetry_level`` keys and, when the run wrote a
        health stream, its ``health`` digest section.  v4 adds the
        ``timing`` section (measured per-dispatch wall + profiler
        capture info), present only when device timing ran or a
        profiler capture was taken.  v5 adds the ``serve`` section:
        the sliding-window QPS/p50/p99 of the serve plane, present
        only when a request completed inside the window.  v6 adds the
        ``fleet`` section — cross-rank collective wait-vs-work
        attribution (per-rank wait seconds, slowest-rank histogram,
        clock-offset table) — present only when the fleet observability
        plane synced at least one window.  v7 adds the ``drift``
        section — per-model serve-traffic drift vs training baseline
        (per-feature PSI, score-shift JS, the gate threshold) — present
        only when a drift window synced, so earlier blobs keep their
        v6 shape."""
        import sys
        from .phase import GLOBAL_TIMER
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            timeline = list(self._timeline)
            recorded = self._spans_recorded
            kept = len(self._spans)
            capacity = self._spans.maxlen
        phases = {name: {"seconds": round(sec, 6), "count": cnt}
                  for name, (sec, cnt) in GLOBAL_TIMER.snapshot().items()}
        network: Dict[str, Any] = {}
        net = sys.modules.get("lightgbm_tpu.parallel.network")
        if net is not None and hasattr(net, "collective_stats"):
            network = net.collective_stats()
        out: Dict[str, Any] = {
            "schema": METRICS_SCHEMA,
            "version": METRICS_VERSION,
            "level": self._level,
            "telemetry_level": self._level,
            "mode": "dispatch",
            "phases": phases,
            "counters": counters,
            "gauges": gauges,
            "network": network,
            "timeline": timeline,
            "spans": {"recorded": recorded, "kept": kept,
                      "dropped": recorded - kept, "capacity": capacity},
        }
        memory = self._memory_section()
        if memory is not None:
            out["memory"] = memory
        cost = self._cost_section()
        if cost is not None:
            out["cost"] = cost
        timing = self._timing_section()
        if timing is not None:
            out["timing"] = timing
        serve = self.serve_window_stats()
        if serve is not None:
            out["serve"] = serve
        faults = self._faults_section()
        if faults is not None:
            out["faults"] = faults
        health = HEALTH.summary_section()
        if health is not None:
            out["health"] = health
        fleet_mod = sys.modules.get("lightgbm_tpu.obs.fleet")
        if fleet_mod is not None and hasattr(fleet_mod, "fleet_section"):
            fleet = fleet_mod.fleet_section()
            if fleet is not None:
                out["fleet"] = fleet
        drift_mod = sys.modules.get("lightgbm_tpu.obs.drift")
        if drift_mod is not None and hasattr(drift_mod, "drift_section"):
            drift = drift_mod.drift_section()
            if drift is not None:
                out["drift"] = drift
        return out

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (the ``{"traceEvents": [...]}`` object
        form): one complete ("X") event per span, one counter ("C") event
        per timeline counter delta, plus thread-name metadata."""
        with self._lock:
            spans = list(self._spans)
            timeline = list(self._timeline)
            mem_track = list(self._mem_track)
            faults = [dict(e) for e in self._faults]
        pid = os.getpid()
        events = []
        tids: Dict[str, int] = {}

        def tid_of(label: str) -> int:
            if label not in tids:
                tids[label] = len(tids) + 1
                events.append({"name": "thread_name", "ph": "M",
                               "pid": pid, "tid": tids[label],
                               "args": {"name": label}})
            return tids[label]

        for ts, dur, name, label, args in spans:
            ev = {"name": name, "cat": "lightgbm_tpu", "ph": "X",
                  "ts": round(ts, 3), "dur": round(dur, 3),
                  "pid": pid, "tid": tid_of(label)}
            if args:
                ev["args"] = args
            events.append(ev)
        for entry in timeline:
            ts = entry["t"] * 1e6
            for cname, delta in entry["counters"].items():
                events.append({"name": cname, "ph": "C", "pid": pid,
                               "tid": 0, "ts": round(ts, 3),
                               "args": {"value": delta}})
        # background HBM samples as their own counter track
        for t_off, in_use in mem_track:
            events.append({"name": "mem/bytes_in_use", "ph": "C",
                           "pid": pid, "tid": 0,
                           "ts": round(t_off * 1e6, 3),
                           "args": {"value": in_use}})
        # fault/recovery events as globally-scoped instants, so a
        # degradation is visible at a glance on the trace timeline
        for ev in faults:
            args = {k: v for k, v in ev.items() if k not in ("kind", "t")}
            events.append({"name": f"fault/{ev['kind']}",
                           "cat": "lightgbm_tpu", "ph": "i", "s": "g",
                           "pid": pid, "tid": 0,
                           "ts": round(ev["t"] * 1e6, 3),
                           "args": args})
        # clock anchors: event ``ts`` values are µs since ``_epoch`` (a
        # perf_counter instant).  ``mono_epoch``/``wall_epoch`` pin that
        # instant on the monotonic and wall clocks so fleet_trace.py can
        # map per-rank traces onto one skew-corrected timeline.
        now_pc = time.perf_counter()
        other: Dict[str, Any] = {
            "schema": METRICS_SCHEMA,
            "mono_epoch": round(time.monotonic() - (now_pc - self._epoch),
                                6),
            "wall_epoch": round(time.time() - (now_pc - self._epoch), 6),
        }
        import sys
        dist = sys.modules.get("lightgbm_tpu.parallel.distributed")
        if dist is not None and getattr(dist, "is_active", lambda: False)():
            other["rank"] = dist.rank()
            other["world"] = dist.world()
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": other}

    def export_trace(self, path: str) -> None:
        try:
            with open(path, "w") as fh:
                json.dump(self.chrome_trace(), fh)
        except OSError as e:
            from .log import log_warning
            log_warning(f"could not write trace JSON to {path}: {e}")

    def maybe_export_trace(self) -> None:
        """Write the Chrome trace to ``LIGHTGBM_TPU_TRACE_JSON`` if set.
        Called at the end of training and (backstop) at process exit."""
        path = os.environ.get("LIGHTGBM_TPU_TRACE_JSON")
        if path:
            self.export_trace(path)

    def metrics_blob(self) -> Dict[str, Any]:
        """The versioned JSON blob written by the CLI ``metrics_out=``
        parameter and embedded in bench results."""
        blob = {"schema": METRICS_SCHEMA}
        blob.update(self.stats())
        return blob

    # -------------------------------------------------------------- reset
    def reset(self) -> None:
        """Clear all recorded data (not the config level or installed
        listeners) and re-zero the time base; also resets the network
        collective counters so a measurement window starts clean."""
        import sys
        from .phase import GLOBAL_TIMER
        self.stop_mem_sampler()
        phases_now = GLOBAL_TIMER.snapshot()
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._spans.clear()
            self._spans_recorded = 0
            self._timeline.clear()
            self._iter_snapshot = {}
            self._phase_snapshot = phases_now
            self._epoch = time.perf_counter()
            self._writer = None
            self._race_flagged = False
            self._mem_supported = None
            self._mem_device = None
            self._mem_last = None
            self._mem_peak = 0
            self._mem_largest = 0
            self._mem_limit = None
            self._mem_phase = {}
            self._mem_track.clear()
            self._mem_interval_ms = 0.0
            self._data_tier = None
            self._costs = {}
            self._timing = {}
            self._serve_done.clear()
            self._profile_capture = None
            self._faults.clear()
            self._fault_counts.clear()
        net = sys.modules.get("lightgbm_tpu.parallel.network")
        if net is not None and hasattr(net, "reset_collective_stats"):
            net.reset_collective_stats()
        drift_mod = sys.modules.get("lightgbm_tpu.obs.drift")
        if drift_mod is not None and hasattr(drift_mod, "reset"):
            drift_mod.reset()
        HEALTH.reset()
        self.refresh_level()


TELEMETRY = TelemetryRegistry()

# an exception that unwinds past the training loop must not lose an
# almost-complete trace: export whatever was recorded at process exit
atexit.register(TELEMETRY.maybe_export_trace)
