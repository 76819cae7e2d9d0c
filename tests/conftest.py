"""Test harness setup.

Forces JAX onto the host CPU backend with 8 virtual devices BEFORE jax is
imported anywhere, so mesh/sharding tests emulate a multi-chip TPU slice
without hardware (see SURVEY.md §4's test plan).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # override even if the env preset a TPU
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Persistent compilation cache: the suite compiles dozens of model/mesh
# variants; caching them across runs cuts wall-clock several-fold.  Same
# placement rule as the program (JAX_COMPILATION_CACHE_DIR, else
# <repo>/.jax_cache), so CLI children the tests spawn share it.
from sat_tpu.utils.compile_cache import enable as _enable_cache  # noqa: E402

_enable_cache(jax)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (pytest -m 'not slow')",
    )


@pytest.fixture(autouse=True)
def _fault_injection_inert():
    """Fault injection must be opt-in per test: no SAT_FI_* variable may
    leak in from the environment or out of a test, and the armed/consumed
    bookkeeping resets so injection counts never bleed between tests."""
    from sat_tpu.resilience import faultinject

    stray = [k for k in os.environ if k.startswith(faultinject.ENV_PREFIX)]
    assert not stray, f"fault-injection env leaked into the test run: {stray}"
    assert faultinject.FaultPlan.from_env().inert
    faultinject.reset_io_faults()
    yield
    for k in [k for k in os.environ if k.startswith(faultinject.ENV_PREFIX)]:
        del os.environ[k]
    faultinject.reset_io_faults()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def coco_fixture(tmp_path_factory):
    """A tiny synthetic COCO-captions dataset with real image files."""
    from tests.fixtures import make_coco_fixture

    root = tmp_path_factory.mktemp("coco")
    return make_coco_fixture(str(root))
