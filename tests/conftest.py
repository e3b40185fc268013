import os
import sys

# src-layout import without install
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Multi-device test infra: REPRO_FORCE_DEVICES=N provisions an N-way host-
# platform device mesh by setting XLA_FLAGS *before anything imports jax*
# (conftest runs ahead of test-module collection, so this is early enough;
# once the backend initialises the flag is frozen). Without the env var the
# default stays 1 device — smoke tests and benches must see 1 device; only
# launch/dryrun.py forces 512, and the sharded-scan differential tests
# (tests/test_scan_sharded.py) opt in via the `device_mesh` fixture below,
# skipping cleanly when the mesh is unavailable.
_FORCE = os.environ.get("REPRO_FORCE_DEVICES")
if _FORCE:
    if "jax" in sys.modules:  # too late to grow the device count
        raise RuntimeError(
            "REPRO_FORCE_DEVICES set but jax was imported before conftest; "
            "host-platform device count can no longer be forced")
    os.environ["XLA_FLAGS"] = " ".join(
        [os.environ.get("XLA_FLAGS", ""),
         f"--xla_force_host_platform_device_count={int(_FORCE)}"]).strip()

import pytest  # noqa: E402  (after the env fix-up on purpose)

#: device requirement for the multidevice marker / fixture — the CI job and
#: the differential tests agree on an 8-way (data=4, model=2) mesh
MULTIDEVICE_COUNT = 8


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "multidevice: needs an 8-way device mesh "
        "(run with REPRO_FORCE_DEVICES=8; skipped otherwise)")
    config.addinivalue_line(
        "markers",
        "faults: fault-injection / guard-pipeline / crash-safety tests "
        "(ISSUE 7); CI runs them as a dedicated job via `-m faults`")


def pytest_collection_modifyitems(config, items):
    """Skip `multidevice` tests up front when the mesh cannot exist. Gates on
    the *actual* device count, so the suite runs both under
    REPRO_FORCE_DEVICES=8 and on real 8+-device hardware with the env var
    unset. jax is imported only when multidevice tests were collected — and
    collecting them imported it (module-level) anyway."""
    if not any("multidevice" in item.keywords for item in items):
        return
    import jax
    if jax.device_count() >= MULTIDEVICE_COUNT:
        return
    skip = pytest.mark.skip(
        reason=f"needs {MULTIDEVICE_COUNT} devices, have "
               f"{jax.device_count()}: run with "
               f"REPRO_FORCE_DEVICES={MULTIDEVICE_COUNT}")
    for item in items:
        if "multidevice" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def device_mesh():
    """An 8-way (data=4, model=2) host-platform mesh for the sharded-scan
    differential tests; skips cleanly when the devices are missing (e.g.
    REPRO_FORCE_DEVICES unset, or a partial forced count)."""
    import jax
    if jax.device_count() < MULTIDEVICE_COUNT:
        pytest.skip(f"needs {MULTIDEVICE_COUNT} devices, have "
                    f"{jax.device_count()}: run with "
                    f"REPRO_FORCE_DEVICES={MULTIDEVICE_COUNT}")
    from repro.core.scan_sharded import staleness_mesh
    mesh = staleness_mesh(model=2)
    assert mesh is not None and mesh.devices.size >= MULTIDEVICE_COUNT
    return mesh


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described TPU v5e:2x2 topology, as a sharding to lower
    and compile for: the TPU compiler installed with JAX compiles for a chip
    that is described, not attached, and nothing runs. Described here,
    never at import, since only one process at a time may load the TPU
    library. The persistent compilation cache is off around its tests: an
    executable compiled for a described chip cannot be read back. Skips
    only where no topology can be described."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()
