"""Pytest bootstrap: tests run on the CPU, on an 8-device virtual mesh.

CI has no chip: every mesh/sharding test runs on 8 virtual CPU devices, and
the kernels run in Pallas interpret mode.  (On the chip the program runs
through `python chip_smoke.py` and the daemon's default platform; the
described-chip compiles in tests/test_chip_compile.py need no chip.)  These
env vars must be set before the first JAX backend initializes.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # override the ambient TPU platform
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# In case jax was imported before this file ran (its env-read defaults are
# then already set), pin the platform through the config too: the backend
# initializes lazily, so the update still lands.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_addoption(parser):
    parser.addoption(
        "--long",
        action="store_true",
        default=False,
        help="run the scaled-up fuzz schedules (50+ seeds x 500+ writes; "
        "see tests/test_parity_fuzz.py and PARITY.md).  CRDT_LONG=1 in the "
        "environment does the same for bare `pytest` invocations.",
    )


def pytest_configure(config):
    if os.environ.get("CRDT_LONG"):
        config.option.long = True
    config.addinivalue_line(
        "markers",
        "slow: long-running end-to-end checks (tier-1 runs -m 'not slow')",
    )
