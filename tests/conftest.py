"""Test configuration: hold JAX to a virtual 8-device CPU mesh.

The tests say nothing about the device: they run scheduler, collective and
sharding logic without accelerators (reference: python/ray/tests/conftest.py),
with Pallas kernels in interpret mode. `tests/test_tpu_compile.py` compiles
for a described TPU, and `chip_smoke.py` is the run on the chip.
"""

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

os.environ.setdefault("RAY_TPU_TESTING", "1")
# Subprocesses (workers) also come up on CPU jax with 8 virtual devices.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

# Persistent XLA compilation cache, shared by the driver AND every spawned
# worker/actor process (env is inherited). The suite compiles the same tiny
# llama/train programs in dozens of fresh actor processes; on a small CI
# box those duplicate compiles dominate wall-clock (~40% of a cluster-test's
# runtime measured). jax keys entries by program + compile options + backend
# and falls back to compiling on any cache miss/corruption, so this is
# purely a speedup. Placed from outside where JAX_COMPILATION_CACHE_DIR is
# set; otherwise one fixed directory inside the checkout (the path is part
# of the cache's key, so a directory that moves never hits).
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(_ROOT, ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.2")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

sys.path.insert(0, _ROOT)

import pytest  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _unload_what_earlier_files_compiled():
    """A process maps every program it compiles and keeps the maps until the
    programs go, and the kernel allows a process `vm.max_map_count` of them
    (65,530): the map that fails then is XLA's, a segmentation fault inside
    `compile`, which xdist reports as the FAILURE of whatever test was
    running. Read in one process on PR 60's tree: tests/test_llm_unified.py
    leaves 56,825 maps, tests/test_llm_kimi_linear.py behind it stands at
    61,662 after its server's warm-up and dies at 64,180 two tests later
    (CHANGES.md, PR 61). So every test file starts from none: a file builds
    its own runners and jitted steps, nothing compiled in memory is shared
    between two files, and what is shared comes back from the compile cache
    on disk."""
    if "jax" in sys.modules:
        import gc

        import jax

        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def cpu_jax():
    import jax

    jax.config.update("jax_platforms", "cpu")
    assert jax.default_backend() == "cpu"
    assert len(jax.devices()) == 8
    return jax


@pytest.fixture
def pickle_sanitizer():
    """Scoped pickle observation: `w = pickle_sanitizer.window()` opens a
    window (`with w: ...`) during which every pickle.dumps/loads in the
    process is attributed to its call site; `w.assert_zero_pickle()` is
    the steady-state proof. Replaces per-test counter_snapshot plumbing."""
    from ray_tpu.analysis.sanitizers import PickleSanitizer

    san = PickleSanitizer()
    try:
        yield san
    finally:
        san.close()


@pytest.fixture
def lock_sanitizer():
    """Wraps threading.Lock for the test; locks created inside the window
    are tracked and `san.assert_no_inversions()` fails on any cross-thread
    lock-order cycle, reporting both acquisition stacks."""
    from ray_tpu.analysis.sanitizers import LockOrderSanitizer

    with LockOrderSanitizer() as san:
        yield san


@pytest.fixture
def fake_memory_pressure(tmp_path, monkeypatch):
    """(mem_file, marker) for the OOM tests. The raylet's monitor reads the
    node's usage from mem_file (its test hook). A task's first attempt
    writes its pid into marker and 0.99 into mem_file, then hangs until the
    monitor kills it. Real memory is freed when its holder dies, so the fake
    is too: a watcher here puts 0.10 back once that pid is gone. (Left to
    the retry's first line, the monitor's next tick, a second later, could
    find the file still at 0.99 and kill every retry on a loaded host.)"""
    import threading
    import time

    mem_file = str(tmp_path / "mem_frac")
    marker = str(tmp_path / "attempt_marker")
    with open(mem_file, "w") as f:
        f.write("0.10")
    monkeypatch.setenv("RAY_TPU_MEMORY_MONITOR_TEST_FILE", mem_file)
    stop = threading.Event()

    def gone(pid: int) -> bool:
        try:
            with open(f"/proc/{pid}/stat") as f:
                return f.read().rpartition(") ")[2][0] in "ZX"
        except OSError:
            return True

    def relieve():
        while not stop.wait(0.01):
            try:
                with open(marker) as f:
                    pid = int(f.read())
            except (OSError, ValueError):
                continue
            if gone(pid):
                with open(mem_file, "w") as f:
                    f.write("0.10")
                return

    watcher = threading.Thread(target=relieve, name="fake-memory-relief",
                               daemon=True)
    watcher.start()
    yield mem_file, marker
    stop.set()
    watcher.join(5)
