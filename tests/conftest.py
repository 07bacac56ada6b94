"""Test harness: a virtual 8-device CPU mesh.

The analog of the reference's ``mpirun -np 8`` single-host oversubscription
(SURVEY.md §5): the grid logic is identical at any scale, so host-only runs
exercise every code path.  Configured BEFORE the backend initializes.
"""
from functools import partial
from pathlib import Path

import jax

jax.config.update("jax_platform_name", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

from elemental_tpu import Grid  # noqa: E402
from elemental_tpu.core.compile_cache import enable_compile_cache  # noqa: E402

# Persistent XLA compilation cache: the suite's time is compiles, identical
# run to run, and the mmap guard below counts on the cache to make the
# recompiles it forces cheap.  A whole tier-1 run from an empty directory
# writes 23,000 entries (154 MB; 43,000 and 211 MB while the blocked
# drivers' result tests still walked eagerly, PERF.md 7); keep it out of the
# checkout (JAX_COMPILATION_CACHE_DIR), and start the one whole run from an
# empty one.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

# vm.max_map_count guard: every LoadedExecutable the suite compiles holds
# mmapped JIT code pages, and one full-suite process accumulates tens of
# thousands of mappings -- once the kernel cap (default 65530) is reached
# XLA segfaults inside compile/deserialize.  The guard below watches this
# process's mapping count after each test and drops jax's compilation
# caches (releasing every executable's mappings) well before the cap; the
# persistent compile cache above turns the forced recompiles into cheap
# deserializes, so the cost is seconds per trip, not minutes.  The cap
# sits ~9.5k below the kernel limit (no single test compiles anywhere
# near that many executables): each trip costs ~8s plus a deserialize
# cascade, so spurious trips are wall-time the whole suite pays.
_MAPS_SOFT_CAP = 56_000


def _n_mappings() -> int:
    try:
        n = 0
        with open("/proc/self/maps", "rb") as f:
            while True:
                chunk = f.read(1 << 20)
                if not chunk:
                    return n
                n += chunk.count(b"\n")
    except OSError:            # non-Linux: no /proc, no known map cap
        return 0


@pytest.fixture(autouse=True)
def _cap_executable_mappings():
    yield
    if _n_mappings() > _MAPS_SOFT_CAP:
        jax.clear_caches()


def compiled(driver, **options):
    """``driver`` with its options bound, as ONE compiled program
    (``DistMatrix`` is a pytree): how a tier-1 case runs a blocked driver
    it wants the RESULT of, and how every cell and every user runs one.
    Walked eagerly, each redistribute, view, matmul and mask of each step
    is an executable of its own shapes, five hundred for nine steps.  The
    eager walk is for what only it can show: phase timers and tracers
    ticking, a fault injected at a step, the ABFT recovery, host loops."""
    return jax.jit(partial(driver, **options))


@pytest.hookimpl(trylast=True)
def pytest_collection_modifyitems(items):
    """``test_chip_compile.py``'s whole-program compiles are the suite's
    longest cases by far and sort at the end of the collection, where the
    ``-n 6 --dist load`` run hands them out two at a time to one worker
    while five idle.  Spread them through the first half of the collection
    at an even stride: xdist's ``load`` scheduler hands out CONSECUTIVE
    items (a first batch of ``items // workers // 4``, refills of half a
    worker's share of what is left), so forty in a row anywhere land on
    one worker, and at a stride each batch holds three or four."""
    long_file = Path(__file__).with_name("test_chip_compile.py")
    long = [item for item in items if item.path == long_file]
    rest = [item for item in items if item.path != long_file]
    if not long or not rest:
        return
    stride = max(1, len(rest) // 2 // len(long))
    spread = []
    for i, item in enumerate(long):
        spread += [item, *rest[i * stride:(i + 1) * stride]]
    items[:] = spread + rest[len(long) * stride:]


@pytest.fixture(scope="session", params=[(2, 4), (4, 2), (1, 8), (8, 1)],
                ids=lambda rc: f"grid{rc[0]}x{rc[1]}")
def any_grid(request):
    r, c = request.param
    return Grid(jax.devices()[: r * c], height=r)


@pytest.fixture(scope="session", params=[(2, 4), (1, 8)],
                ids=lambda rc: f"grid{rc[0]}x{rc[1]}")
def two_grids(request):
    """A generic 2-D grid plus one degenerate (stride-1) grid: the cheap
    tier for blocked-algorithm tests (the full 4-grid sweep stays on the
    core redistribution conformance)."""
    r, c = request.param
    return Grid(jax.devices()[: r * c], height=r)


@pytest.fixture(scope="session")
def grid24():
    return Grid(jax.devices(), height=2)


@pytest.fixture
def redist_counter():
    """Scoped redistribute/panel_spread call counter: yields a fresh
    Counter active for this test only (see engine.redist_counts) -- no
    clear()-and-hope on the module global, no state leaking between
    tests."""
    from elemental_tpu.redist.engine import redist_counts
    with redist_counts() as c:
        yield c


@pytest.fixture(scope="session")
def grid42():
    return Grid(jax.devices(), height=4)
