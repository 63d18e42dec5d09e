"""Test configuration: the CPU backend with an 8-device virtual mesh.

Tests run on the CPU: multi-chip sharding is validated on virtual devices,
and tests/test_v5e_compile.py compiles for a described v5e without a chip.
The chip is reached only through the chip tool (``python chip_smoke.py``).
The platform is pinned with jax.config.update before the first backend use
(this conftest executes before any test module imports jax), so no
environment can move the suite onto an accelerator.
"""

import importlib.util
import os
import pathlib

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running (nightly) tests, excluded from tier-1's "
        "-m 'not slow' run",
    )


@pytest.fixture
def graft_entry():
    """The repo root's ``__graft_entry__`` module (the dryrun's entry points
    and its hard-semantics documents), loaded from its file."""
    spec = importlib.util.spec_from_file_location(
        "__graft_entry__",
        pathlib.Path(__file__).parent.parent / "__graft_entry__.py",
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
