"""Test environment: force JAX onto CPU with 8 virtual devices so mesh /
sharding tests run without TPU hardware (SURVEY.md §4 test strategy).

Must run before the first `import jax` anywhere in the test session.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import gc  # noqa: E402
import sys  # noqa: E402

import pytest  # noqa: E402

# The C++ host core builds itself on first use (``make`` into one file). In
# a fresh checkout six workers importing tests/test_native.py at once each
# start that build, and a worker that loads the file while another's linker
# is still writing it ("file too short") reports no library for good: its
# seven tests skip. The session's first process builds it before any worker
# starts; a worker's own ``make`` is then a no-op.
if not os.environ.get("PYTEST_XDIST_WORKER"):
    from vnsum_tpu import native as _native  # noqa: E402

    _native.available()

# ``slow`` today means "outside tier-1": the driver's command deselects the
# marker (``-m 'not slow'``), so nothing runs these fourteen modules — the
# dense path's own tests, 141 test functions — unless somebody names them.
# They were set apart forty PRs ago for a one-core host; what each costs and
# whether it still passes, run alone, is ROADMAP.md D19 (PR 58: thirteen
# pass, one has rotted). Bringing them in is a count and a clock that a
# later issue decides with those numbers.
_SLOW_MODULES = {
    "test_backend_engine",
    "test_backend_long_context",
    "test_graft_entry",
    "test_model_convert",
    "test_model_gemma",
    "test_model_llama",
    "test_model_phi",
    "test_model_quant",
    "test_ops_decode",
    "test_ops_flash",
    "test_parallel_distributed",
    "test_parallel_train",
    "test_pipeline_weights_dir",
    "test_train_checkpoint",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__ in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)


# What ends a long-lived worker is its count of memory mappings (ROADMAP.md
# D18): every executable XLA:CPU loads is a few hundred of them, JAX's
# in-process caches keep every one, and at ``vm.max_map_count`` the loader's
# next ``mmap`` fails inside a compile — a segmentation fault or an abort in
# whatever test happens to run then. So a worker gives them back: at every
# module's end, and inside a module once it holds a quarter of the limit.


def _mappings() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f)
    except OSError:          # no /proc: nothing to count, nothing to shed
        return 0


def _mappings_allowed() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530         # the kernel's default


_SHED_ABOVE = _mappings_allowed() // 4


def shed_compiled_programs() -> None:
    """Give back every executable this process loaded: JAX's in-process
    caches, then the cycles that keep an executable alive. What survives —
    a jitted function, an engine, an array — compiles again when called."""
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="module", autouse=True)
def _shed_at_a_modules_end():
    """Set up before a module's own fixtures and so torn down after them;
    the family harness's engines go first: nothing else holds them."""
    yield
    harness = sys.modules.get("family_harness")
    if harness is not None:
        harness.forget_engines()
    shed_compiled_programs()


@pytest.fixture(autouse=True)
def _shed_at_a_quarter_of_the_limit():
    yield
    if _mappings() > _SHED_ABOVE:
        shed_compiled_programs()


@pytest.fixture(scope="session")
def cpu_mesh8():
    import jax
    from vnsum_tpu.parallel.mesh import make_mesh

    assert len(jax.devices("cpu")) == 8
    return make_mesh({"data": 2, "model": 2, "seq": 2}, platform="cpu")
