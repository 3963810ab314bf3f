"""Test harness: run everything on a virtual 8-device CPU mesh.

The reference's tests fork N processes with real NCCL (tests/unit/common.py);
on TPU we can do better — XLA's host platform simulates N devices in one
process, so sharding/collective tests run anywhere. Must set flags before
jax initializes.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"     # the env var alone suffices
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tier — convergence runs, pipeline "
        "engine end-to-ends, HF-parity suites (run by default; the fast "
        "tier is -m 'not slow', ~3 min on the 8-device CPU mesh)")


@pytest.fixture
def mesh8():
    from deepspeed_tpu.parallel.topology import build_mesh
    return build_mesh()  # 8-way data parallel by default


@pytest.fixture
def tmp_ds_config(tmp_path):
    """Write a ds_config dict to a json file, return its path."""
    import json

    def _write(config: dict) -> str:
        p = tmp_path / "ds_config.json"
        p.write_text(json.dumps(config))
        return str(p)

    return _write
