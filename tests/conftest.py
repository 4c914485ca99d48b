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
# Each xdist worker compiles into a directory of its own. JAX's file cache
# writes an entry in place (no rename, no lock), so a sibling worker that
# reads the same key mid-write deserializes a truncated executable: the
# worker of the driver's six-worker run that held
# test_model_nemotron_h.py::test_engine_generates_and_counts_scan_cells_and_experts
# died of a segmentation fault in compilation_cache.get_executable_and_time
# (the test passes alone and beside its file's others). With the variable
# set, core/jax_cache.py sets no directory and JAX's own handling stands; a
# child process a test starts inherits its worker's directory.
_worker = os.environ.get("PYTEST_XDIST_WORKER")
if _worker:
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
            __file__))), ".jax_cache", _worker))

import pytest  # noqa: E402

# Heavy JAX-compile modules: every test in these files traces + compiles real
# model programs, which dominates wall-clock on a 1-core host (full suite
# >10 min there). The remaining files are the FAST tier — host logic plus
# tiny-encoder compiles — and finish in ~2.5 min:
#   python -m pytest -m "not slow"
# The full hermetic suite stays the CI default (plain `pytest`).
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


@pytest.fixture(scope="session")
def cpu_mesh8():
    import jax
    from vnsum_tpu.parallel.mesh import make_mesh

    assert len(jax.devices("cpu")) == 8
    return make_mesh({"data": 2, "model": 2, "seq": 2}, platform="cpu")
