"""Per-phase wall-clock accounting for the training loop.

The reference ships three tracing mechanisms — easy_profiler blocks
(src/main.cpp:13-39), TIMETAG per-phase accumulators printed at learner
destruction (src/treelearner/serial_tree_learner.cpp:20-47), and network
byte/time counters (src/network/linkers.h:114-117).  This module is the
TPU build's equivalent of the TIMETAG accumulators: named phases
accumulate wall-clock across iterations and are printed on demand
(bench.py prints them every run; ``Log`` prints at verbosity>=debug).
Each finished phase is also recorded as a span in the telemetry
registry (utils/telemetry.py), which adds counters, a per-iteration
timeline and Chrome trace export on top.

Because device work is dispatched asynchronously, a phase's wall time
measures host-side dispatch only.  Device time per phase comes from a
jax-profiler trace: every phase is also a
``jax.profiler.TraceAnnotation("lgbm:<name>")`` and every chunk dispatch
a ``StepTraceAnnotation`` (models/gbdt.py), so whatever capture is open
— the program's own below, or one the caller started with
``jax.profiler.start_trace`` — holds the host phases on the device
trace's clock, nested by the host thread's stack, and the device
program's operations carry the ``jax.named_scope`` path of the phase
that issued them (docs/OBSERVABILITY.md).  With no capture open an
annotation is a flag test.

Profiler capture comes in two shapes: the original all-or-nothing
session (``LIGHTGBM_TPU_PROFILE_DIR`` wraps the whole train loop) and
the windowed programmatic capture (``profile_window=START:END`` config
parameter / ``LIGHTGBM_TPU_PROFILE_WINDOW`` env), which opens the
``jax.profiler`` trace only for that boosting-iteration span — a
multi-hour run yields a viewable-sized artifact of exactly the steady
state (or exactly the suspect iterations).  The artifact path and
actual window land in the metrics blob's ``timing`` section.
"""

from __future__ import annotations

import os
import threading
import time
from collections import defaultdict
from contextlib import contextmanager
from typing import Dict, Optional, Tuple

import jax


class PhaseTimer:
    """Accumulates (count, seconds) per named phase.  Thread-safe: the
    accumulators are guarded by a lock (phases themselves may overlap
    freely across threads; each contributes its own wall window)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.seconds: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)

    @contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        # the host phase structure, on the clock of any open profiler
        # trace (looked up per call: tests patch the annotation class)
        with jax.profiler.TraceAnnotation(f"lgbm:{name}"):
            try:
                yield
            finally:
                dur = time.perf_counter() - t0
                with self._lock:
                    self.seconds[name] += dur
                    self.counts[name] += 1
                from .telemetry import TELEMETRY
                TELEMETRY.record_span(name, t0, dur)
                TELEMETRY.sample_memory(name)

    def reset(self) -> None:
        with self._lock:
            self.seconds.clear()
            self.counts.clear()

    def snapshot(self) -> Dict[str, Tuple[float, int]]:
        """Consistent {name: (seconds, count)} copy."""
        with self._lock:
            return {name: (sec, self.counts[name])
                    for name, sec in self.seconds.items()}

    def summary(self) -> str:
        snap = self.snapshot()
        total = sum(sec for sec, _ in snap.values())
        parts = []
        for name, (sec, n) in sorted(snap.items(), key=lambda kv: -kv[1][0]):
            parts.append(f"{name}={sec:.3f}s/{n}")
        out = f"phases[dispatch] total={total:.3f}s " + " ".join(parts)
        # append the network collective counters (linkers.h:114-117
        # equivalent) when the parallel machinery has been used
        import sys
        net = sys.modules.get("lightgbm_tpu.parallel.network")
        if net is not None and hasattr(net, "collective_summary"):
            net_line = net.collective_summary()
            if net_line:
                out += " | " + net_line
        # and the fleet plane's cross-rank wait/work split when it
        # attributed at least one window this run
        fleet = sys.modules.get("lightgbm_tpu.obs.fleet")
        if fleet is not None and hasattr(fleet, "summary_line"):
            fleet_line = fleet.summary_line()
            if fleet_line:
                out += " | " + fleet_line
        return out


# process-global timer used by GBDT unless one is injected
GLOBAL_TIMER = PhaseTimer()

_profile_session: Optional[object] = None

WINDOW_ENV = "LIGHTGBM_TPU_PROFILE_WINDOW"
DEFAULT_PROFILE_DIR = "lightgbm_tpu.profile"


class ProfileWindow:
    """Windowed programmatic jax-profiler capture.

    ``profile_window=START:END`` (env ``LIGHTGBM_TPU_PROFILE_WINDOW``
    wins) arms ONE capture per training run over the half-open boosting-
    iteration span ``[START, END)``.  The train loops call
    ``clamp_step`` (so a chunk dispatch never straddles a window
    boundary — chunk size never changes the model, PR 1 parity, so the
    clamp only affects dispatch granularity) and then ``step(i)`` before
    dispatching iteration ``i``; the window opens/closes itself at the
    boundaries.  ``close()`` in the profile_session finally guarantees
    an exception mid-window cannot leak an open jax profiler session
    (which would poison every later ``start_trace`` in the process).
    The artifact dir comes from ``LIGHTGBM_TPU_PROFILE_DIR`` when set,
    else ``lightgbm_tpu.profile``; the dir + actual captured span are
    recorded into the metrics blob's ``timing`` section.
    """

    def __init__(self) -> None:
        self.start = 0
        self.end = 0
        self.dir = ""
        self.is_open = False
        self._armed = False
        self._done = False
        self._opened_at = 0
        self._last_iter = 0

    def configure(self, config=None) -> bool:
        """(Re-)arm from the env/config spec; returns True when a
        window is armed.  A malformed spec warns and disables the
        window rather than failing the run."""
        self._armed = False
        self._done = False
        self.is_open = False
        spec = os.environ.get(WINDOW_ENV, "")
        if not spec and config is not None:
            spec = str(getattr(config, "profile_window", "") or "")
        if not spec:
            return False
        try:
            a, _, b = spec.partition(":")
            start, end = int(a), int(b)
        except ValueError:
            start, end = 0, 0
        if end <= start or start < 0:
            from .log import log_warning
            log_warning(f"bad profile_window spec {spec!r} (want "
                        "START:END with END > START >= 0); profiler "
                        "window disabled")
            return False
        self.start, self.end = start, end
        self.dir = (os.environ.get("LIGHTGBM_TPU_PROFILE_DIR")
                    or DEFAULT_PROFILE_DIR)
        self._armed = True
        return True

    def clamp_step(self, iteration: int, step: int) -> int:
        """Clamp a chunk step so the next dispatch stops at the nearest
        upcoming window boundary."""
        if not self._armed or self._done:
            return step
        for boundary in (self.start, self.end):
            if iteration < boundary:
                return min(step, boundary - iteration)
        return step

    def step(self, iteration: int) -> None:
        """Advance to ``iteration`` (about to be dispatched): opens the
        trace entering the window, closes it leaving."""
        if not self._armed or self._done:
            return
        self._last_iter = iteration
        if self.is_open:
            if iteration >= self.end:
                self._close(iteration)
        elif self.start <= iteration < self.end:
            jax.profiler.start_trace(self.dir)
            self.is_open = True
            self._opened_at = iteration

    def _close(self, iteration: int) -> None:
        # clear the open marker FIRST: if stop_trace raises, the finally
        # close() must not call it again on an already-broken session
        self.is_open = False
        self._done = True
        jax.profiler.stop_trace()
        from .telemetry import TELEMETRY
        TELEMETRY.record_profile_capture({
            "dir": self.dir, "kind": "window",
            "window": [int(self._opened_at), int(iteration)],
            "requested": [int(self.start), int(self.end)]})

    def close(self) -> None:
        """Force-close an open window and disarm (profile_session
        finally): the capture then covers up to the last stepped
        iteration."""
        if self.is_open:
            self._close(min(self.end, self._last_iter + 1))
        self._armed = False


PROFILE_WINDOW = ProfileWindow()


def maybe_start_profile() -> None:
    """Start a jax-profiler trace if LIGHTGBM_TPU_PROFILE_DIR is set."""
    global _profile_session
    path = os.environ.get("LIGHTGBM_TPU_PROFILE_DIR")
    if path and _profile_session is None:
        jax.profiler.start_trace(path)
        _profile_session = path


def maybe_stop_profile() -> None:
    global _profile_session
    if _profile_session is not None:
        # clear the session marker FIRST: if stop_trace raises, a retry
        # must not call it again on an already-broken session
        path, _profile_session = _profile_session, None
        jax.profiler.stop_trace()
        from .telemetry import TELEMETRY
        TELEMETRY.record_profile_capture({"dir": path, "kind": "session"})


@contextmanager
def profile_session(config=None):
    """Exception-safe profiler window: an error mid-training must not
    leak an open jax profiler trace session (which would poison every
    later start_trace in the process).  A configured
    ``profile_window=START:END`` span takes over from the all-or-nothing
    LIGHTGBM_TPU_PROFILE_DIR session — the window owns the capture and
    the train loop drives it via PROFILE_WINDOW.step()."""
    windowed = PROFILE_WINDOW.configure(config)
    if not windowed:
        maybe_start_profile()
    try:
        yield
    finally:
        if windowed:
            PROFILE_WINDOW.close()
        else:
            maybe_stop_profile()
