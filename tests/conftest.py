"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax loads.

This is the rebuild's version of the reference's local-mode Spark trick
(SURVEY.md §4): the whole distributed surface — mesh, shardings, the
control/data planes — is exercised on one box with no TPU pod.
"""

import os

# Must happen before any `import jax` anywhere in the test process, and
# before any node subprocess is spawned (children inherit this environ at
# exec and read it when THEY import jax — see utils.util.cpu_only_env).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent compilation cache: OFF for the suite and for every child it
# starts, through the switch JAX itself reads. The programs' entry points
# turn the cache on (utils.util.enable_compile_cache: the directory in
# JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache), so without this
# every node and smoke subprocess a test starts would write there.
#
# History of the pin: under jaxlib 0.4.36 (found in PR 15's tier-1) a
# MULTI-DEVICE/sharded CPU executable restored from the persistent cache
# corrupted the heap when executed more than once (glibc "corrupted
# double-linked list"). Re-run in PR 21 under jax/jaxlib 0.9.0 — the
# sharded llama train step on the 8-device (data=2, fsdp=4) CPU mesh,
# min compile time 0 so everything is cached, one process to write the
# cache and two more to run 6 steps each from it: both cache-hit runs
# (`Persistent compilation cache hit for 'jit_step'`) exited 0 with
# losses identical to the compile run. The corruption does NOT reproduce
# on this installation. The suite still compiles without the cache in
# this PR: turning it on for 840 tests is its own change, with its own
# timing evidence.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402

# tfsan witness lifecycle (no-op unless TFOS_TFSAN=1): thin delegating
# hooks, because pytest honors `pytest_plugins` only in the rootdir
# conftest and this one lives under tests/.
from tests.plugins import tfsan as _tfsan_plugin  # noqa: E402


def pytest_configure(config):
    _tfsan_plugin.configure(config)


def pytest_sessionfinish(session, exitstatus):
    _tfsan_plugin.sessionfinish(session, exitstatus)


@pytest.fixture(scope="session")
def mesh8():
    from tensorflowonspark_tpu.compute.mesh import make_mesh

    return make_mesh({"data": 2, "fsdp": 4})


@pytest.fixture(scope="session")
def mesh_dp():
    from tensorflowonspark_tpu.compute.mesh import make_mesh

    return make_mesh({"data": 8})
