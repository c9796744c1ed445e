"""Distributed "network" state: the TPU-native stand-in for the reference's
static Network class (include/LightGBM/network.h:102-297, src/network/).

The reference builds a TCP/MPI mesh from a machine list and hand-rolls
Bruck/recursive-halving/ring collectives (network.cpp:115-434).  On TPU the
runtime owns transport and algorithm selection: collectives are XLA ops over
a `jax.sharding.Mesh` spanning ICI (and DCN for multi-host).  This module
keeps the reference's API seam — init/rank/num_machines/dispose — and holds
the process-wide mesh used by the parallel tree learners.

Multi-host: run one process per host under `jax.distributed.initialize`;
`jax.devices()` then spans all hosts and the same mesh covers DCN, which is
the TPU equivalent of the reference's machine list + socket handshake
(linkers_socket.cpp:23-230).  `parallel/distributed.py` owns the init
lifecycle, barriers, snapshot election and preemption flow; this module
owns the mesh, the per-collective counters, and the hardened host-level
collective seam (`allgather_obj` with configurable retries / backoff /
per-attempt timeout via `collective_retries=` / `collective_timeout_s=`).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

from ..utils.log import LightGBMError, log_info, log_warning

_mesh: Optional["jax.sharding.Mesh"] = None
_mesh_fingerprint: Optional[tuple] = None
_injected: Optional[dict] = None

MACHINES_AXIS = "machines"

# Retry policy for host-level collectives (and the distributed-init
# handshake): total extra attempts, per-attempt wall budget, first
# backoff.  The defaults preserve the historical retry-once behavior;
# configure() rebinds them from `collective_retries=` /
# `collective_timeout_s=` at the same lifecycle point as
# FAULTS.configure.
_policy = {"retries": 1, "timeout_s": 120.0, "backoff_s": 0.05}


def configure(config) -> None:
    """Bind the collective retry policy from a Config (clamped sane)."""
    retries = int(getattr(config, "collective_retries", 1))
    timeout_s = float(getattr(config, "collective_timeout_s", 120.0))
    _policy["retries"] = max(0, retries)
    _policy["timeout_s"] = max(0.001, timeout_s)


def collective_policy() -> tuple:
    """(retries, timeout_s, backoff_s) currently in force."""
    return _policy["retries"], _policy["timeout_s"], _policy["backoff_s"]

# ---------------------------------------------------------------------------
# Per-collective counters: calls, payload bytes, wall seconds — the TPU
# equivalent of the reference Linkers byte/time counters (linkers.h:114-117).
# For XLA collectives launched from jitted growers the bytes are the static
# mesh-math estimate and the seconds are the HOST DISPATCH wall of the
# enclosing grow call (device execution is asynchronous); for host-side
# collectives (allgather_obj) both are measured for real.
_coll_lock = threading.Lock()
_collectives: Dict[str, Dict[str, float]] = {}
_coll_writer: Optional[int] = None
_coll_race_warned = False
# v6 fleet plane: per-kind window of (call_index, enter mono_ts,
# seconds) samples since the last take_collective_window().  Every rank
# issues collectives in the same order, so (kind, call_index) pairs the
# same logical collective across ranks after a kv-allgather — that pair
# is what obs/fleet.py splits into wait vs work seconds.  Bounded per
# kind; the call index keeps pairing correct even after drops.
_COLL_WINDOW_CAP = 4096
_coll_window: Dict[str, list] = {}


def record_collective(kind: str, nbytes: float = 0,
                      seconds: float = 0.0, calls: int = 1,
                      enter_mono: Optional[float] = None) -> None:
    """Accumulate one collective's stats under ``kind``.  Thread-safe,
    with the reference Network's single-writer check relaxed to a
    warning (include/LightGBM/network.h keeps all collectives on one
    thread; here a second writer is flagged, not fatal).

    ``enter_mono`` — the ``time.monotonic()`` instant this rank ENTERED
    the collective (before any peer wait) — additionally feeds the
    fleet-plane attribution window; callers that cannot observe entry
    (async device dispatch) omit it and stay out of the window."""
    global _coll_writer, _coll_race_warned
    from ..utils.telemetry import TELEMETRY
    if TELEMETRY.level < 1:
        return
    with _coll_lock:
        ident = threading.get_ident()
        if _coll_writer is None:
            _coll_writer = ident
        elif _coll_writer != ident and not _coll_race_warned:
            _coll_race_warned = True
            log_warning("network collectives recorded from multiple "
                        "threads; the reference keeps Network "
                        "single-threaded — counters stay consistent but "
                        "per-kind attribution may interleave")
        st = _collectives.setdefault(
            kind, {"calls": 0, "bytes": 0, "seconds": 0.0})
        idx = int(st["calls"])
        st["calls"] += int(calls)
        st["bytes"] += int(nbytes)
        st["seconds"] += float(seconds)
        if enter_mono is not None:
            win = _coll_window.setdefault(kind, [])
            win.append((idx, round(float(enter_mono), 6),
                        round(float(seconds), 6)))
            if len(win) > _COLL_WINDOW_CAP:
                del win[: len(win) - _COLL_WINDOW_CAP]
    if enter_mono is not None and TELEMETRY.level >= 2:
        # span for the fleet trace merge: flow arrows join the per-rank
        # net/<kind> spans of the same (kind, seq) across lanes
        now = time.perf_counter()
        TELEMETRY.record_span(f"net/{kind}", now - float(seconds),
                              float(seconds), tid="net",
                              args={"seq": idx, "bytes": int(nbytes)})


def take_collective_window() -> Dict[str, list]:
    """Drain and return this rank's attribution window:
    ``{kind: [(call_index, enter_mono, seconds), ...]}``.  Samples
    recorded after this call land in the next window, so synchronized
    callers (obs/fleet.py syncs at iteration barriers) see aligned
    windows on every rank."""
    with _coll_lock:
        out = {k: list(v) for k, v in _coll_window.items() if v}
        _coll_window.clear()
    return out


def collective_stats() -> Dict[str, Dict[str, float]]:
    """{kind: {calls, bytes, seconds}} copy (rounded for JSON)."""
    with _coll_lock:
        return {k: {"calls": int(v["calls"]), "bytes": int(v["bytes"]),
                    "seconds": round(v["seconds"], 6)}
                for k, v in _collectives.items()}


def collective_summary() -> str:
    """One-line rendering for the phase summary; empty when no
    collective ran."""
    stats = collective_stats()
    if not stats:
        return ""
    parts = [f"{k}={v['calls']}x/{v['bytes'] / 1e6:.1f}MB/"
             f"{v['seconds']:.3f}s" for k, v in sorted(stats.items())]
    return "net " + " ".join(parts)


def reset_collective_stats() -> None:
    global _coll_writer, _coll_race_warned
    with _coll_lock:
        _collectives.clear()
        _coll_window.clear()
        _coll_writer = None
        _coll_race_warned = False


def _device_fingerprint(devices) -> tuple:
    """Identity + order of a device list — what the mesh's collective
    layout assumptions are actually keyed on."""
    return tuple((getattr(d, "process_index", 0), getattr(d, "id", i))
                 for i, d in enumerate(devices))


def init(num_machines: int = 0) -> "jax.sharding.Mesh":
    """Build (or rebuild) the 1-D device mesh over the `machines` axis.

    Always re-queries ``jax.devices()`` so a second init after
    ``dispose()`` — possibly under a NEW ``jax.distributed`` world size —
    builds a fresh mesh instead of reusing stale device ordering."""
    global _mesh, _mesh_fingerprint
    devices = jax.devices()
    if num_machines <= 0:
        num_machines = len(devices)
    if num_machines > len(devices):
        log_warning(f"num_machines={num_machines} > available devices "
                    f"({len(devices)}); clamping")
        num_machines = len(devices)
    _mesh = jax.sharding.Mesh(np.asarray(devices[:num_machines]),
                              (MACHINES_AXIS,))
    _mesh_fingerprint = _device_fingerprint(devices)
    log_info(f"Initialized TPU collective mesh with {num_machines} devices")
    return _mesh


def init_from_machines(machines: str, num_machines: int = 1) -> None:
    """Reference-API shim: LGBM_NetworkInit(machines, port, ...) — the
    machine list is advisory on TPU (the runtime already knows the slice)."""
    init(num_machines)


def init_with_functions(reduce_scatter_fn: Callable, allgather_fn: Callable,
                        rank: int, num_machines: int) -> None:
    """External-collective injection seam (network.h:123,
    LGBM_NetworkInitWithFunctions c_api.cpp:1572) — used by tests to fake
    multi-machine runs in one process."""
    global _injected
    _injected = {"reduce_scatter": reduce_scatter_fn,
                 "allgather": allgather_fn,
                 "rank": rank, "num_machines": num_machines}


def injected() -> Optional[dict]:
    return _injected


def mesh() -> "jax.sharding.Mesh":
    """The process-wide mesh, rebuilt if the visible device set changed
    since it was created (e.g. a fresh ``jax.distributed`` world came up
    after ``dispose()``) — collectives over a mesh of dead/reordered
    devices would silently misroute."""
    global _mesh
    if _mesh is None:
        return init()
    if _device_fingerprint(jax.devices()) != _mesh_fingerprint:
        log_warning("visible device set changed since the mesh was "
                    "built; rebuilding the collective mesh")
        spanned_all = int(_mesh.devices.size) == len(_mesh_fingerprint)
        return init(0 if spanned_all else int(_mesh.devices.size))
    return _mesh


def num_machines() -> int:
    if _injected is not None:
        return _injected["num_machines"]
    return mesh().devices.size


def rank() -> int:
    if _injected is not None:
        return _injected["rank"]
    return jax.process_index()


def binning_world() -> tuple:
    """(world, rank) for host-level distributed bin finding
    (dataset_loader.cpp:933-1034).  Machine count here means PROCESSES
    (hosts) — a single process driving 8 local devices gains nothing from
    sharding host-side binning, so the mesh size is deliberately not used.

    jax.process_count() would INITIALIZE the backend; dataset loading is
    pure host work and must not take the device, so multi-process is only
    consulted when jax.distributed was explicitly initialized."""
    if _injected is not None:
        return _injected["num_machines"], _injected["rank"]
    try:
        initialized = jax.distributed.is_initialized()
    except AttributeError:
        # distributed state unreadable: silently reporting world=1 on a
        # real multi-process run would desynchronize bin mappers across
        # hosts, so if any multi-process launch marker is in the
        # environment this is fatal, not a warning
        import os

        def _multi(var: str) -> bool:
            val = os.environ.get(var, "")
            if not val:
                return False
            if var in ("SLURM_JOB_NUM_NODES", "OMPI_COMM_WORLD_SIZE"):
                try:
                    return int(val) > 1   # 1-node/1-rank runs are serial
                except ValueError:
                    return True
            if var == "TPU_WORKER_HOSTNAMES":
                return "," in val         # single-host pod slice is serial
            return True                    # coordinator address present

        markers = [v for v in (
            "JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
            "MEGASCALE_COORDINATOR_ADDRESS", "TPU_WORKER_HOSTNAMES",
            "SLURM_JOB_NUM_NODES", "OMPI_COMM_WORLD_SIZE",
        ) if _multi(v)]
        if markers:
            raise LightGBMError(
                "cannot determine the multi-process world for distributed "
                "bin finding (jax distributed-state API unavailable) but "
                f"multi-process launch markers are set ({markers}); "
                "refusing to fit bin mappers per-host — use "
                "network.init_with_functions to inject the topology")
        log_warning("could not inspect jax.distributed state; assuming a "
                    "single-process run for bin finding")
        return 1, 0
    if not initialized:
        return 1, 0
    return jax.process_count(), jax.process_index()


def allgather_obj(obj):
    """Allgather a picklable object across binning ranks; returns the list
    of every rank's object (self included), rank-ordered.

    Uses the injected allgather when tests fake a multi-machine run
    (init_with_functions), else the coordination-service KV transport
    when a ``jax.distributed`` world is up (works on every backend and
    turns a dead peer into an error naming the missing rank — see
    ``distributed.kv_allgather_bytes``), else
    jax.experimental.multihost_utils over DCN, else identity.

    Transient failures are retried under the configured policy
    (``collective_retries=`` extra attempts, exponential backoff,
    per-attempt budget from ``collective_timeout_s=``; default retry
    once), each retry recorded as a ``collective_retry`` fault event:
    host-level allgather runs over DCN during data loading, where a
    single hiccup should not kill a long job.  Exhausting the attempts
    propagates — a dead link is not transient.  The retry path is
    exercised deterministically via the ``collective/allgather`` fault
    site, probed per attempt."""
    from ..utils.retry import retry_call
    from ..utils.telemetry import TELEMETRY
    retries, _timeout_s, backoff_s = collective_policy()

    def _on_retry(_k, e):
        TELEMETRY.fault_event("collective_retry",
                              site="collective/allgather", detail=str(e))

    return retry_call(lambda: _allgather_obj_once(obj),
                      attempts=1 + retries, backoff_s=backoff_s,
                      fatal=(LightGBMError,), on_retry=_on_retry,
                      label="allgather_obj")


def _allgather_obj_once(obj):
    import pickle

    from ..utils.faults import FAULTS
    from . import distributed
    FAULTS.maybe_raise("collective/allgather")   # probed per attempt
    distributed.probe_slow()                     # injected straggler delay
    blob = pickle.dumps(obj)
    t0 = time.perf_counter()
    enter = time.monotonic()
    if _injected is not None:
        out = [pickle.loads(b) for b in _injected["allgather"](blob)]
        record_collective("allgather_obj", len(blob),
                          time.perf_counter() - t0, enter_mono=enter)
        return out
    if distributed.is_active():
        # coordinator KV transport: backend-agnostic (XLA's CPU backend
        # has no cross-process computations) with real per-call
        # deadlines and missing-rank attribution
        blobs = distributed.kv_allgather_bytes(
            blob, timeout_s=collective_policy()[1], label="allgather_obj")
        out = [pickle.loads(b) for b in blobs]
        record_collective("allgather_obj",
                          sum(len(b) for b in blobs),
                          time.perf_counter() - t0, enter_mono=enter)
        return out
    if jax.process_count() == 1:
        return [obj]
    from jax.experimental import multihost_utils
    arr = np.frombuffer(blob, np.uint8)
    sizes = multihost_utils.process_allgather(
        np.asarray([arr.size], np.int64))
    maxn = int(np.max(sizes))
    pad = np.zeros(maxn, np.uint8)
    pad[: arr.size] = arr
    gathered = multihost_utils.process_allgather(pad)
    out = [pickle.loads(gathered[i, : int(sizes[i])].tobytes())
           for i in range(gathered.shape[0])]
    record_collective("allgather_obj", maxn, time.perf_counter() - t0,
                      enter_mono=enter)
    return out


def probe_dispatch_collective(kind: Optional[str]) -> None:
    """Deterministic fault probe at the eager dispatch seam of an
    in-jit device collective (the grower's reduce-scatter/allgather/psum
    runs INSIDE compiled programs where an injected Python exception
    cannot fire, and donated carries cannot be re-dispatched — so the
    fault site probes just before dispatch).  The site is named after
    the canonical data-parallel histogram reduce-scatter and fires for
    whichever grower collective is active.  Retried under the
    configured policy like any transient DCN hiccup; a spec that never
    heals (``x*``) exhausts the attempts and propagates."""
    site = "collective/reduce_scatter" if kind else None
    from ..utils.faults import FAULTS, KNOWN_SITES
    if site not in KNOWN_SITES or not FAULTS.enabled:
        return
    from ..utils.retry import retry_call
    from ..utils.telemetry import TELEMETRY
    retries, _timeout_s, backoff_s = collective_policy()

    def _on_retry(_k, e):
        TELEMETRY.fault_event("collective_retry", site=site,
                              detail=str(e))

    retry_call(lambda: FAULTS.maybe_raise(site),
               attempts=1 + retries, backoff_s=backoff_s,
               fatal=(LightGBMError,), on_retry=_on_retry, label=site)


def dispose() -> None:
    """Tear down the mesh/injection AND the collective counters —
    back-to-back runs in one process (tests, notebooks) must not leak
    the previous run's call/byte totals into the next stats() blob.
    Also shuts down a ``jax.distributed`` client that THIS process's
    lifecycle layer created, so a later ``init()`` can bring up a fresh
    world under a new size (an externally initialized world is left
    alone)."""
    global _mesh, _mesh_fingerprint, _injected
    _mesh = None
    _mesh_fingerprint = None
    _injected = None
    reset_collective_stats()
    from . import distributed
    distributed.shutdown_owned()
