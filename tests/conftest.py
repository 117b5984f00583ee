"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is validated on
8 virtual CPU devices (`__graft_entry__.dryrun_multichip` runs the same way under
JAX_PLATFORMS=cpu).

Setting KOORD_TPU_TESTS=1 keeps the session on the real accelerator instead,
enabling tests marked `requires_tpu` (compiled — non-interpret — Pallas
kernel parity on hardware, tests/test_tpu_hardware.py); those auto-skip on
every other backend, so hardware coverage is systematic when a chip is
present and harmless when not.

Tier-1 runs on the CPU; the chip's entry point is chip_smoke.py. The
variables are set here, before the first `import jax`, because a JAX
backend cannot change platform once it has started.
"""

import os

_ON_TPU = os.environ.get("KOORD_TPU_TESTS") == "1"

if not _ON_TPU:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

if not _ON_TPU:
    jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "requires_tpu: compiled-kernel parity on real TPU hardware; "
        "auto-skipped unless the session backend is tpu "
        "(KOORD_TPU_TESTS=1)",
    )


def pytest_collection_modifyitems(config, items):
    if _ON_TPU and jax.default_backend() == "tpu":
        return
    skip = pytest.mark.skip(
        reason="requires real TPU backend (run with KOORD_TPU_TESTS=1)")
    for item in items:
        if "requires_tpu" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual cpu devices, got {len(devs)}"
    return devs
