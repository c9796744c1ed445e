from .log import (LightGBMError, Timer, check, log_debug, log_fatal, log_info,
                  log_warning, register_log_callback, set_verbosity)

__all__ = ["LightGBMError", "Timer", "check", "log_debug", "log_fatal",
           "log_info", "log_warning", "register_log_callback",
           "set_verbosity", "cpu_subprocess_env",
           "enable_jax_compilation_cache", "maybe_enable_compile_cache",
           "require_tpu"]


def require_tpu(who: str) -> dict:
    """For a measurement script's process: exit non-zero unless JAX's
    default backend is a TPU, else return the device stamp every record
    carries.  A time taken on another backend is not a measurement of this
    system, so nothing falls back to one."""
    import sys

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"{who}: needs a TPU; JAX found {devices[0].platform} "
                 f"({devices[0].device_kind}). No record is produced on "
                 f"another backend.")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def cpu_subprocess_env(n_virtual_devices: int = 0) -> dict:
    """Environment for a child process that must run JAX on the CPU
    platform, with ``n_virtual_devices`` host devices when > 0.  The one
    recipe the tests and the multichip dry run share to start a CPU
    child."""
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    flags = " ".join(f for f in flags.split()
                     if "xla_force_host_platform_device_count" not in f)
    if n_virtual_devices > 0:
        flags = (flags + " --xla_force_host_platform_device_count="
                 f"{n_virtual_devices}").strip()
    env["XLA_FLAGS"] = flags
    return env


def enable_jax_compilation_cache(cache_dir: str | None = None) -> str:
    """Turn on JAX's persistent executable cache, so a cold compile is
    paid once and later processes reuse it; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there
    and no directory is set in code.  Otherwise the cache goes to
    ``cache_dir`` (the ``compile_cache=<dir>`` knob routes a path here)
    or, unnamed, to the fixed ``<checkout>/.jax_cache`` — never to a
    temporary or per-process path: the path is part of the cache's key,
    so a directory that moves never hits.  A directory that cannot be
    created raises."""
    import os

    import jax
    from jax.experimental.compilation_cache import compilation_cache

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        # utils/ -> lightgbm_tpu/ -> checkout root
        placed = cache_dir or os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    os.makedirs(placed, exist_ok=True)
    # cache EVERY executable: warm-up is dominated by many medium-size
    # compiles (kernels, fused_step variants), so none is too small to keep
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax checks-and-latches cache usability at the FIRST compile of the
    # process and initializes the cache at most once, so enabling (or
    # re-pointing) it after any earlier compile would silently do nothing
    # without a reset here
    compilation_cache.reset_cache()
    return placed


def maybe_enable_compile_cache(config) -> None:
    """Honor the ``compile_cache=`` config knob: off by default; a truthy
    value ("1"/"true"/"on"/"default") turns on the persistent XLA
    compilation cache at its default location, any other non-empty
    string is taken as the cache directory (enable_jax_compilation_cache:
    ``JAX_COMPILATION_CACHE_DIR``, where set, wins over both).  Hits and
    misses land in the compile/cache_hits|cache_misses telemetry counters
    (the jax monitoring bridge already subscribes to them)."""
    cc = str(getattr(config, "compile_cache", "") or "").strip()
    if not cc or cc.lower() in ("0", "false", "off", "no"):
        return
    if cc.lower() in ("1", "true", "on", "yes", "default"):
        enable_jax_compilation_cache()
    else:
        enable_jax_compilation_cache(cache_dir=cc)
