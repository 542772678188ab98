"""Test harness configuration.

Forces JAX onto the host CPU platform with 8 virtual devices BEFORE jax is
imported anywhere, so every test exercises real multi-device sharding and
collectives without TPU hardware (the analog of the reference's
@distributed_test process spawner, tests/unit/common.py:14-100 — but using
XLA's simulated multi-device instead of forked NCCL processes).
"""

import os

# Force CPU even when the outer environment points at a TPU platform —
# unit tests must exercise the virtual 8-device mesh deterministically
# (and must not take the chip from whoever holds it). The config update
# below also covers a jax that something imported before this file.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def tmp_config_file(tmp_path):
    """Write a config dict to a temp JSON file, return its path."""
    import json

    def _write(config_dict, name="ds_config.json"):
        path = tmp_path / name
        path.write_text(json.dumps(config_dict))
        return str(path)

    return _write
