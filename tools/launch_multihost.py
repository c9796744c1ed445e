"""Launch an N-process CPU-backend ``jax.distributed`` run on localhost.

The 2-process CPU harness is how the multi-host robustness layer
(`parallel/distributed.py`) is *tested* rather than asserted: real
``jax.distributed.initialize`` against a real coordination service,
real KV-store collectives and barriers, real process death — just
without a TPU pod.  Used by the slow-marked tests in
tests/test_distributed.py and runnable by hand:

    python tools/launch_multihost.py --hosts 2 -- \
        python -m lightgbm_tpu train.conf output_model=/tmp/m{rank}.txt

``{rank}`` in any argv token expands to the process's host rank.  Each
child gets JAX_PLATFORMS=cpu, an even share of virtual CPU devices (the
parent initialises no backend and needs no chip), and the
LIGHTGBM_TPU_COORDINATOR_ADDRESS/
_NUM_HOSTS/_HOST_RANK env vars that drive
``distributed.maybe_initialize``.

The module API (`launch`) additionally takes per-rank argv lists — the
preemption tests arm the ``dist/preempt`` fault site on ONE rank only —
and per-rank extra env, and can deliver a late SIGKILL to a chosen rank
to simulate a host dying mid-run.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional, Sequence

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def free_port() -> int:
    """An OS-assigned free TCP port (racy by nature, fine for tests)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def rank_env(rank: int, num_hosts: int, port: int,
             devices_per_host: int = 2,
             extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Child-process environment for one host rank."""
    from lightgbm_tpu.utils import cpu_subprocess_env
    env = cpu_subprocess_env(n_virtual_devices=devices_per_host)
    # children may run from any cwd (tests chdir into tmp dirs)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["LIGHTGBM_TPU_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
    env["LIGHTGBM_TPU_NUM_HOSTS"] = str(num_hosts)
    env["LIGHTGBM_TPU_HOST_RANK"] = str(rank)
    if extra:
        env.update(extra)
    return env


class MultihostRun:
    """Handle over the fleet: per-rank Popen objects + helpers."""

    def __init__(self, procs: List[subprocess.Popen], port: int):
        self.procs = procs
        self.port = port

    def kill_rank(self, rank: int) -> None:
        """SIGKILL one host — the uncoordinated death the barrier
        timeouts exist for."""
        self.procs[rank].kill()

    def wait(self, timeout_s: float = 300.0) -> List[int]:
        """Wait for every rank; returns return codes (rank order)."""
        deadline = time.monotonic() + timeout_s
        codes = []
        for p in self.procs:
            left = max(0.1, deadline - time.monotonic())
            try:
                codes.append(p.wait(timeout=left))
            except subprocess.TimeoutExpired:
                p.kill()
                codes.append(p.wait())
        return codes

    def terminate_all(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()


def launch(argvs: Sequence[Sequence[str]],
           devices_per_host: int = 2,
           port: Optional[int] = None,
           extra_env: Optional[Sequence[Optional[Dict[str, str]]]] = None,
           cwds: Optional[Sequence[Optional[str]]] = None,
           stdouts: Optional[Sequence] = None) -> MultihostRun:
    """Spawn ``len(argvs)`` host processes, one per rank.

    ``argvs[r]`` is rank r's full argv (``{rank}`` tokens substituted);
    ``extra_env[r]`` merges rank-specific env on top (e.g. a
    LIGHTGBM_TPU_FAULTS spec armed on one rank only); ``cwds[r]`` is
    rank r's working directory (tests run each rank in its own dir with
    identical relative-path argv, keeping saved models byte-comparable
    across runs); ``stdouts[r]`` is a per-rank log file object (stderr
    is folded in).
    """
    num_hosts = len(argvs)
    port = port or free_port()
    procs = []
    for r, argv in enumerate(argvs):
        env = rank_env(r, num_hosts, port,
                       devices_per_host=devices_per_host,
                       extra=(extra_env[r] if extra_env else None))
        argv = [str(a).replace("{rank}", str(r)) for a in argv]
        out = stdouts[r] if stdouts else None
        procs.append(subprocess.Popen(
            argv, env=env, cwd=(cwds[r] if cwds else None),
            stdout=out, stderr=(subprocess.STDOUT if out else None)))
    return MultihostRun(procs, port)


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="run COMMAND once per host rank under a localhost "
                    "jax.distributed world ({rank} expands in args)")
    ap.add_argument("--hosts", type=int, default=2)
    ap.add_argument("--devices-per-host", type=int, default=2)
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="command to run (prefix with -- )")
    args = ap.parse_args()
    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("no command given")
    run = launch([cmd] * args.hosts,
                 devices_per_host=args.devices_per_host)
    codes = run.wait(timeout_s=args.timeout)
    for r, c in enumerate(codes):
        print(f"rank {r}: exit {c}")
    return max(abs(c) for c in codes)


if __name__ == "__main__":
    sys.exit(main())
