"""Test configuration: run everything on a simulated 8-device CPU platform.

SURVEY.md §4: the reference can only test distributed behavior on real
multi-GPU nodes; the TPU build does better by unit-testing DP/SyncBN
semantics on a virtual CPU mesh.  The XLA flag must be set before jax
initializes its backends, hence the top-of-conftest placement.

Two modes:
* default — the full suite on the virtual CPU mesh (the CI gate);
* ``APEX_TPU_TESTS=1`` — a *kernel-validation* mode that leaves the
  default device on the real TPU and runs ONLY the ``tpu``-marked tests;
  everything else is skipped because the CPU-mesh pinning is global and
  mixed-device runs produce spurious failures.  It complements, not
  replaces, a default-mode run.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# APEX_TPU_TESTS=1 leaves the platform choice alone so the ``tpu``-marked
# kernel tests (test_pallas_tpu.py) exercise the Mosaic kernels on chip.
# Otherwise the suite is held to the CPU BEFORE jax is imported: with the
# variable unset, the first ``jax.devices()`` initialises every backend,
# the TPU included, and a chip belongs to one process at a time — a test
# run must never take it by accident.
_ON_CHIP = bool(os.environ.get("APEX_TPU_TESTS"))
if not _ON_CHIP:
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    skip = pytest.mark.skip(
        reason="TPU kernel test: set APEX_TPU_TESTS=1 on a TPU host")
    skip_cpu = pytest.mark.skip(
        reason="CPU-mesh test: run without APEX_TPU_TESTS (on-chip mode "
               "keeps the TPU default device, which breaks tests built "
               "around the virtual CPU mesh)")
    run_on_chip = _ON_CHIP and jax.default_backend() == "tpu"
    for item in items:
        if "tpu" in item.keywords and not run_on_chip:
            item.add_marker(skip)
        elif "tpu" not in item.keywords and run_on_chip:
            item.add_marker(skip_cpu)


@pytest.fixture(scope="session", autouse=True)
def _isolated_tune_cache(tmp_path_factory):
    """Hermetic suite vs the kernel autotuner (ISSUE 14): registered
    kernels consult the per-device tune config cache at dispatch time,
    whose default location is ``~/.cache/apex_tpu`` — a developer who
    ran ``python -m apex_tpu.tune`` locally would otherwise have every
    interpret-mode kernel test silently dispatch THEIR cached blocks
    instead of the shipped defaults.  Point the env override at an
    empty per-session tmpdir (an explicit APEX_TPU_TUNE_CACHE — e.g. an
    on-chip validation run exercising a real cache — still wins)."""
    if not os.environ.get("APEX_TPU_TUNE_CACHE"):
        os.environ["APEX_TPU_TUNE_CACHE"] = str(
            tmp_path_factory.mktemp("tune_cache") / "tune_configs.json")
    yield


@pytest.fixture
def cpu_mesh():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices("cpu")[:8]), ("data",))
