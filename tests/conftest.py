"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Tests run on the CPU: the sharding tests need 8 devices, which exist
only as virtual CPU devices, and nothing tier-1 asserts is a device
number.  The chip is reached through chip_smoke.py and the benchmark
(dssbench/).  Must run before jax import.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# jax may already have been imported (and have read an older
# JAX_PLATFORMS) by a plugin: pin the platform at the config level
jax.config.update("jax_platforms", "cpu")


import pytest


@pytest.fixture(scope="session")
def keypair():
    """One RS256 keypair per test session (PEM private, PEM public).
    Skips the requesting test when `cryptography` (an optional
    dependency — auth is disableable) is not installed."""
    pytest.importorskip("cryptography")
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric import rsa

    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    priv = key.private_bytes(
        serialization.Encoding.PEM,
        serialization.PrivateFormat.PKCS8,
        serialization.NoEncryption(),
    )
    pub = key.public_key().public_bytes(
        serialization.Encoding.PEM,
        serialization.PublicFormat.SubjectPublicKeyInfo,
    )
    return priv, pub


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running acceptance tests (tier-1 CI runs -m 'not "
        "slow'; the dedicated CI jobs run them unfiltered)",
    )
