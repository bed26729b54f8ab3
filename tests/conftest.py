"""Test configuration: run the whole suite on a virtual 8-device CPU mesh.

Mirrors the reference's backend-profile testing (reference pom.xml:123-150,
test-nd4j-native profile; Spark tests' local[N] master at BaseSparkTest.java:90):
the same tests validate single-device math and multi-device sharding without TPU
hardware. MUST set env vars before jax import.
"""
import os
import re
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# the session's own compile-cache root (JAX's persistent cache, and under it
# the repo's executable store): no test reads what another process left
# behind. The directory object lives as long as the session.
_SESSION_CACHE = tempfile.TemporaryDirectory(prefix="dl4j-test-cache-")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _SESSION_CACHE.name
_flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# JAX's own persistent cache stays off in the suite: its compiles are mostly
# one of a kind, and serializing the long ones to disk costs more than the few
# hits save (a sample of three files ran 4% slower with it on). The repo's
# executable store is what the tests exercise; it does not depend on this
# switch.
jax.config.update("jax_enable_compilation_cache", False)
assert jax.default_backend() == "cpu" and len(jax.devices()) == 8, (
    "test suite requires the virtual 8-device CPU mesh; backends were initialized "
    f"before conftest could force them (got {jax.devices()})")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 gate (-m 'not slow')")
    # the native runtime is built here, once, before any worker of a
    # distributed run imports the test files that ask whether it loads
    if not hasattr(config, "workerinput"):
        from deeplearning4j_tpu import nativert
        nativert.get_runtime()


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session", autouse=True)
def _lock_order_witness():
    """Opt-in runtime lock-order witness (DL4J_LOCK_WITNESS=1).

    Patches threading.Lock/RLock for the whole session so every lock the
    suites construct records its acquisition order, then asserts at
    teardown that no two locks were ever taken in both orders — the
    dynamic complement to the static lock-order rule. Off by default:
    ./runtests.sh lock turns it on for the threaded serving suites.
    """
    if os.environ.get("DL4J_LOCK_WITNESS") != "1":
        yield
        return
    from deeplearning4j_tpu.lint import witness
    witness.reset()
    witness.install()
    try:
        yield
    finally:
        witness.uninstall()
        witness.assert_acyclic()


@pytest.fixture(autouse=True)
def _compile_cache_isolation(tmp_path, monkeypatch):
    """Point the executable store at a per-test tmp dir. Without this a
    warm entry from one test would turn another test's expected cold compile
    into a disk hit — the compile-storm tests in particular pin that
    recompiles really happen. (JAX read the variable at import, so its own
    cache stays in the session directory.)"""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xcache"))
