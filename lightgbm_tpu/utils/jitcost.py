"""Cost-instrumented jit dispatch for the training hot-path seams.

``cost_jit(label, jitted)`` wraps an already-``jax.jit``-ed callable so
that the first dispatch at each input signature goes through the AOT
path (``jitted.lower(*args).compile()``): the resulting executable's
static XLA ``cost_analysis()`` — flops, bytes accessed, transcendentals
— is harvested ONCE into the telemetry registry under ``label``, and
the compiled executable itself is cached and used for every later call
at that signature, so nothing compiles twice.  Every dispatch bumps the
label's call count, which multiplies the per-call cost out into the
``cost`` section of the metrics blob (telemetry.stats()).

With measured device timing enabled (``device_timing=`` config knob /
``LIGHTGBM_TPU_DEVICE_TIMING`` env), each dispatch is additionally
timed wall-to-ready: the wrapper blocks on the returned buffers and
records the window into the telemetry ``timing`` section (per-label
count/total/mean/p50/p99 + the host gap between consecutive dispatches
of the same label).  ``block_until_ready`` only synchronizes — values,
and therefore models, are unchanged — but it does serialize the async
pipeline, so timing is an opt-in measurement mode, never a default.
Under an outer trace the tracer passthrough below returns before the
timing gate, so timing latches off exactly like the AOT fallback; with
timing off the extra cost is one attribute compare.

Gating and fallbacks keep the wrapper invisible when it cannot help:

  * telemetry level 0 — one attribute compare, then the plain jitted
    call (identical to the uninstrumented seam);
  * called under an outer trace (the fused/chunked paths close over the
    grower INSIDE a jit) — tracers pass straight through to the wrapped
    function, which inlines as usual;
  * keyword arguments, non-array leaves, or a backend/executable that
    rejects AOT compile or cost analysis — the plain jitted call, with
    the failure latched so it is not retried per iteration.

The wrapped callable's attributes (e.g. the parallel growers'
``_collective_kind`` tags) remain reachable through ``__getattr__``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

# sentinel distinct from None (None caches "AOT failed; use plain jit")
_UNSEEN = object()


def _leaf_sig(leaf) -> Optional[Tuple]:
    """Hashable signature of one flattened argument leaf, or None when
    the leaf is not a committed array-like (a varying Python scalar
    would otherwise mint a new executable per call).  Sharding is part
    of the signature: a compiled executable only accepts the shardings
    it was lowered with (the distributed learners call the same seams
    with mesh-sharded operands)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return None
    try:
        sharding = hash(getattr(leaf, "sharding", None))
    except TypeError:
        return None
    return (tuple(shape), str(dtype),
            bool(getattr(leaf, "weak_type", False)), sharding)


def harvest_cost(compiled) -> Dict[str, float]:
    """Normalize ``Compiled.cost_analysis()`` (a dict, or a list with
    one dict per module on older jax) into the keys the telemetry
    registry stores.  Also folds in ``memory_analysis()`` sizes when
    the executable exposes them (argument/output/temp bytes — the
    executable's working set, distinct from traffic)."""
    analysis = compiled.cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    analysis = analysis or {}
    out = {
        "flops": float(analysis.get("flops", 0.0)),
        "bytes_accessed": float(analysis.get("bytes accessed", 0.0)),
        "transcendentals": float(analysis.get("transcendentals", 0.0)),
    }
    try:
        mem = compiled.memory_analysis()
        out["temp_bytes"] = float(getattr(mem, "temp_size_in_bytes", 0.0))
        out["argument_bytes"] = float(
            getattr(mem, "argument_size_in_bytes", 0.0))
        out["output_bytes"] = float(
            getattr(mem, "output_size_in_bytes", 0.0))
    except Exception:
        pass
    return out


class CostJit:
    """See module docstring.  One instance per jit seam."""

    def __init__(self, label: str, jitted) -> None:
        self._label = label
        self._fn = jitted
        self._can_aot = hasattr(jitted, "lower")
        # signature -> compiled executable (None = AOT failed, use the
        # plain jitted dispatch for this signature)
        self._compiled: Dict[Any, Any] = {}

    def __getattr__(self, name: str):
        return getattr(self._fn, name)

    def executables(self) -> list:
        """The executables compiled at this seam so far, one per input
        signature (``.as_text()`` shows the kernels and collectives the
        compiler put in)."""
        return [c for c in self._compiled.values() if c is not None]

    def _aot_compile(self, args, key):
        from .phase import GLOBAL_TIMER
        from .telemetry import TELEMETRY
        # a phase of its own, nested in whatever phase dispatched: a
        # reader takes the compile out of a cold first ``chunk``
        with GLOBAL_TIMER.phase(f"compile[{self._label}]"):
            try:
                compiled = self._fn.lower(*args).compile()
                TELEMETRY.record_cost(self._label, harvest_cost(compiled))
            except Exception:
                compiled = None
        self._compiled[key] = compiled
        return compiled

    def __call__(self, *args, **kwargs):
        from .telemetry import TELEMETRY
        if TELEMETRY.level < 1 or not self._can_aot or kwargs:
            return self._fn(*args, **kwargs)
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(args)
        sigs = []
        for leaf in leaves:
            if isinstance(leaf, jax.core.Tracer):
                # under an outer trace: inline into the caller's jaxpr
                return self._fn(*args)
            sig = _leaf_sig(leaf)
            if sig is None:
                return self._fn(*args)
            sigs.append(sig)
        key = (treedef, tuple(sigs))
        entry = self._compiled.get(key, _UNSEEN)
        if entry is _UNSEEN:
            entry = self._aot_compile(args, key)
        TELEMETRY.cost_call(self._label)
        if not TELEMETRY.timing_on:
            if entry is None:
                return self._fn(*args)
            try:
                return entry(*args)
            except (TypeError, ValueError):
                # executable rejected the call (e.g. a sharding/layout
                # facet the signature key missed) BEFORE running —
                # nothing was donated; latch plain-jit dispatch for
                # this signature
                self._compiled[key] = None
                return self._fn(*args)
        # measured dispatch timing: wall from dispatch to buffers ready
        # (the plain-jit fallback is a real dispatch too, so it is timed
        # under the same label)
        import time
        t0 = time.perf_counter()
        if entry is None:
            out = self._fn(*args)
        else:
            try:
                out = entry(*args)
            except (TypeError, ValueError):
                self._compiled[key] = None
                out = self._fn(*args)
        jax.block_until_ready(out)
        TELEMETRY.record_dispatch(self._label, t0, time.perf_counter())
        return out


def cost_jit(label: str, jitted) -> CostJit:
    """Wrap a jitted callable for per-label cost accounting."""
    return CostJit(label, jitted)
