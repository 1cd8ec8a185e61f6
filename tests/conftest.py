"""Test harness config.

Mirrors the reference's two-profile test strategy (SURVEY.md §4: the
same suite runs under -P test-nd4j-native and -P test-nd4j-cuda-8.0):
tests run on the jax CPU backend with 8 virtual devices so multi-chip
sharding paths (pjit over a Mesh) are exercised without TPU hardware;
set DL4J_TPU_TEST_PLATFORM=tpu to run the same suite on the chip
(tests/run_tpu_profile.sh, through the chip tool).
"""

import os

# Both settings must be in the environment before jax is first imported.
_platform = os.environ.get("DL4J_TPU_TEST_PLATFORM", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
if _platform == "cpu":
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running; excluded from the tier-1 command"
    )
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (seed via "
        "DL4J_TPU_CHAOS_SEED; run standalone with scripts/run_chaos.sh "
        "— fast and CPU-only, so they ALSO run under tier-1)"
    )


@pytest.fixture
def rng():
    return np.random.RandomState(12345)


@pytest.fixture(autouse=True)
def _reset_pallas_dispatch():
    """``ops.dispatch`` caches DL4J_TPU_PALLAS once per process; any
    test that monkeypatches the env must not leak a stale cache into
    (or inherit one from) its neighbours, so re-read around each test."""
    from deeplearning4j_tpu.ops import dispatch

    dispatch.reset_for_tests()
    yield
    dispatch.reset_for_tests()


def assert_params_match(net_a, net_b) -> None:
    """Param-tree equality across two engines/paths: bitwise on the
    CPU profile (identical programs -> identical bits), small-tolerance
    on TPU, where two mathematically identical programs may fuse or
    tile differently (and matmuls default to bf16-input precision), so
    bit-equality is not the contract — numerical agreement is."""
    import jax

    tpu = jax.default_backend() == "tpu"
    for ln in net_a.params:
        for pn in net_a.params[ln]:
            a = np.asarray(net_a.params[ln][pn])
            b = np.asarray(net_b.params[ln][pn])
            if tpu:
                np.testing.assert_allclose(
                    a, b, rtol=5e-3, atol=1e-5,
                    err_msg=f"{ln}/{pn}",
                )
            else:
                np.testing.assert_array_equal(a, b, err_msg=f"{ln}/{pn}")


def pallas_interpret() -> bool:
    """Pallas tests run interpret-mode on CPU and the REAL kernels on
    the TPU profile (the point of the -P test-nd4j-cuda analog run)."""
    import jax

    return jax.default_backend() != "tpu"


def kernel_tols():
    """(rtol, atol) for kernel-vs-reference comparisons: tight on CPU
    (f32 throughout), bf16-scale on TPU, where the MXU truncates f32
    matmul inputs to bf16 at default precision (eps ~7.8e-3) — for
    both the kernel AND the XLA reference, in independently-rounded
    ways."""
    import jax

    if jax.default_backend() == "tpu":
        return 2e-2, 8e-3
    return 2e-4, 2e-5


def dispatch_counts():
    """``pallas_dispatch_total`` as a Counter keyed ``(kernel, mode)``:
    subtract two readings for what a traced call metered."""
    import collections

    from deeplearning4j_tpu.observability.metrics import default_registry

    family = default_registry().get("pallas_dispatch_total")
    return collections.Counter() if family is None else \
        collections.Counter(
            {k: int(v.value) for k, v in family._children.items()})


def require_devices(n: int) -> None:
    """Skip a multi-device test when the active backend has fewer
    devices (the TPU profile runs on one real chip; the CPU profile
    provisions 8 virtual devices — reference analog: Spark local-mode
    tests sizing executors to the machine)."""
    import jax

    if len(jax.devices()) < n:
        pytest.skip(
            f"needs {n} devices, have {len(jax.devices())} on "
            f"{jax.default_backend()}"
        )
