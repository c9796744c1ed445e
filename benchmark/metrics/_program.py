"""What the readers of the program's own counters and spans share: the
registry of the process that trained (`lightgbm_tpu.utils.telemetry`), read
the way `run.py` reads its compile counters.  Everything here returns `None`
where the program recorded nothing under that name, as a program from before
these counters and spans does; nothing raises."""

from lightgbm_tpu.utils.telemetry import TELEMETRY


def counter(name):
    return TELEMETRY.stats()["counters"].get(name)


def gauge(name):
    return TELEMETRY.stats()["gauges"].get(name)


def phase_seconds(name, per_count=False):
    """Seconds the program spent in phase `name` over the whole process, or
    the mean of its entries."""
    ph = TELEMETRY.stats()["phases"].get(name)
    if not ph or not ph["count"]:
        return None
    return ph["seconds"] / ph["count"] if per_count else ph["seconds"]


def steady_ms_per_iter(name):
    """Milliseconds an iteration of phase `name` over the steady timeline
    entries: those after the first that paid no compile."""
    steady = [e for e in TELEMETRY.stats()["timeline"][1:]
              if name in e.get("phases", {})
              and not any(k.startswith("compile[") for k in e["phases"])]
    iters = sum(e["count"] for e in steady)
    if not iters:
        return None
    return 1e3 * sum(e["phases"][name]["seconds"] for e in steady) / iters
