"""Test configuration: force a virtual 8-device CPU mesh.

Mirrors the reference's test strategy of simulating multi-node on one host
(SURVEY §4: CommunicationTestDistBase launches --nnode=N against 127.0.0.1);
on TPU the analogue is XLA's forced host-platform device count, giving every
distributed test an 8-device mesh without hardware.
"""
import os

# Must OVERRIDE (not setdefault): on a machine with a chip JAX defaults to
# the TPU; unit tests want the virtual 8-device CPU mesh. Set before jax is
# first imported — the env var is authoritative.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the budgeted tier-1 run (-m 'not slow')",
    )
