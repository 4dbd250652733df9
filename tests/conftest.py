"""Test configuration: force an 8-device virtual CPU platform BEFORE jax init.

This mirrors the reference's multi-node-without-a-cluster strategy (SURVEY.md
§4): the same mesh code that runs on a v5e-8 slice runs here on 8 virtual CPU
devices, so every distributed test (DDP, SyncBN, TP, PP, ring attention)
executes real collectives in-process.
"""

import gc
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from apex_tpu.utils.platform import enable_compile_cache  # noqa: E402

# Persistent compilation cache: the suite is compile-bound on this box
# (hundreds of small shard_map programs), and the cache is keyed by HLO
# hash, so re-runs of unchanged tests skip XLA entirely. The directory is
# JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache.
enable_compile_cache()

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run tests marked slow (the full-coverage suite)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy compile-bound test excluded from the default fast "
        "suite; enable with --runslow or RUN_SLOW=1")


def pytest_collection_modifyitems(config, items):
    """Default run = fast subset (the ref's L0 sanity tier); --runslow or
    RUN_SLOW=1 = full cross-product (the ref's L1 nightly tier)."""
    if config.getoption("--runslow") or os.environ.get("RUN_SLOW") == "1":
        return
    skip = pytest.mark.skip(reason="slow: use --runslow or RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def mesh8():
    """A dp=8 mesh over the 8 virtual devices."""
    from apex_tpu.parallel.mesh import build_mesh

    return build_mesh(tp=1, pp=1, sp=1)


@pytest.fixture(autouse=True, scope="module")
def _drop_compiled_programs():
    """Every loaded executable holds memory maps (~90 per test), and one
    pytest process runs ~900 tests: near test 730 it reaches
    ``vm.max_map_count`` (65530) and the next executable load segfaults
    inside jaxlib. Dropping the in-process caches between modules keeps the
    count bounded; the persistent cache makes the cross-module recompiles
    cheap."""
    yield
    jax.clear_caches()
    gc.collect()


@pytest.fixture(autouse=True)
def _reset_parallel_state():
    yield
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
