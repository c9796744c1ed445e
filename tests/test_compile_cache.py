"""Persistent compilation cache behavior (utils.enable_jax_compilation_cache).

The warm-start wall-clock lever (VERDICT r4 item 3): executables must
survive process boundaries through the on-disk cache so a second run
skips recompilation.  Where the cache lives is decided in that one helper:
``JAX_COMPILATION_CACHE_DIR`` where set, else a named directory, else the
fixed ``<checkout>/.jax_cache``.

Every case runs in a child process: the cache directory and its latch are
process-wide jax state.
"""

import os
import subprocess
import sys

import pytest

from lightgbm_tpu.utils import cpu_subprocess_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, cache_env: str | None, timeout: float = 600):
    """Run ``code`` in a CPU child whose ``JAX_COMPILATION_CACHE_DIR`` is
    ``cache_env`` (None = unset, whatever the caller's environment)."""
    env = cpu_subprocess_env()
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-800:]
    return proc.stdout


# the helper's answer, the directory jax ends up with, and every directory
# the helper set in code
_WHERE = """
import jax
set_in_code = []
_update = jax.config.update
def spy(name, value):
    if name == "jax_compilation_cache_dir":
        set_in_code.append(value)
    _update(name, value)
jax.config.update = spy
from lightgbm_tpu.utils import enable_jax_compilation_cache
got = enable_jax_compilation_cache({arg})
print("GOT", got)
print("JAX", jax.config.jax_compilation_cache_dir)
print("SET", set_in_code)
"""


def _where(stdout: str) -> dict:
    return dict(line.split(" ", 1) for line in stdout.splitlines()
                if line.split(" ", 1)[0] in ("GOT", "JAX", "SET"))


@pytest.mark.parametrize("named", [False, True], ids=["default", "named"])
def test_env_cache_dir_wins_and_none_is_set_in_code(tmp_path, named):
    """With JAX_COMPILATION_CACHE_DIR set the cache stays there — over the
    default and over a directory the caller names — and the program sets
    no directory in code."""
    env_dir, other = str(tmp_path / "from_env"), str(tmp_path / "named")
    out = _where(_run(_WHERE.format(arg=repr(other) if named else ""),
                      cache_env=env_dir))
    assert out == {"GOT": env_dir, "JAX": env_dir, "SET": "[]"}
    assert os.path.isdir(env_dir) and not os.path.exists(other)


def test_unset_env_gives_checkout_jax_cache():
    """Without the variable the cache is at the fixed <checkout>/.jax_cache
    — never a temporary or per-process path."""
    fixed = os.path.join(REPO, ".jax_cache")
    out = _where(_run(_WHERE.format(arg=""), cache_env=None))
    assert out == {"GOT": fixed, "JAX": fixed, "SET": repr([fixed])}


def test_named_cache_dir_used_when_env_unset(tmp_path):
    """The compile_cache=<dir> knob's path: a named directory is used when
    the environment does not place the cache."""
    named = str(tmp_path / "named")
    out = _where(_run(_WHERE.format(arg=repr(named)), cache_env=None))
    assert out == {"GOT": named, "JAX": named, "SET": repr([named])}
    assert os.path.isdir(named)


def test_bad_cache_dir_is_heard(tmp_path):
    """A cache directory that cannot be created raises; it is not
    swallowed into a run that silently compiles everything again."""
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    code = """
from lightgbm_tpu.utils import enable_jax_compilation_cache
try:
    enable_jax_compilation_cache()
except OSError as e:
    print("RAISED", type(e).__name__)
"""
    assert "RAISED" in _run(code, cache_env=str(blocker / "cache"))


def test_persistent_compile_cache_round_trip(tmp_path):
    """The persistent executable cache must actually store and re-serve
    compiles across processes (the warm-start wall-clock lever, VERDICT
    r4 item 3): a second identical training process must HIT the cache
    populated by the first, not recompile.  The cache is placed through
    JAX_COMPILATION_CACHE_DIR in the child's environment."""
    code = """
from lightgbm_tpu.utils import enable_jax_compilation_cache
enable_jax_compilation_cache()
import numpy as np
import lightgbm_tpu as lgb
from lightgbm_tpu.utils.telemetry import TELEMETRY
rng = np.random.RandomState(0)
X = rng.normal(size=(2000, 6))
y = (X[:, 0] > 0).astype(float)
bst = lgb.train({"objective": "binary", "verbose": -1,
                 "num_leaves": 15}, lgb.Dataset(X, y),
                num_boost_round=2, verbose_eval=False)
print("TRAINED", float(bst.predict(X[:1]).item()))
print("HITS", int(TELEMETRY.stats()["counters"].get("compile/cache_hits", 0)))
"""
    for run in range(2):
        stdout = _run(code, cache_env=str(tmp_path))
        assert "TRAINED" in stdout
        hits = int(stdout.split("HITS", 1)[1].split()[0])
        entries = {p.name for p in tmp_path.glob("*")}
        assert entries, f"run {run}: no cache entries written"
        if run == 0:
            first = entries
        else:
            # the second process re-used the first's executables: hits,
            # and no (or almost no) new entries — a cold second process
            # that recompiled everything would roughly double the dir
            assert hits > 0
            new = entries - first
            assert len(new) <= max(2, len(first) // 4), (
                len(first), len(new))
