"""Test fixtures.

Mirrors the reference's python/ray/tests/conftest.py pattern:
``ray_start_regular`` (:419) boots a real single-node cluster per test
module; ``ray_start_cluster`` (:500) yields a multi-raylet Cluster.

JAX tests run on a virtual 8-device CPU mesh
(xla_force_host_platform_device_count) so multi-chip sharding is exercised
without TPU hardware.
"""

import os

# Force the CPU backend even on a machine with a chip: libtpu is installed,
# so a JAX left to choose takes the TPU, which belongs to one process at a
# time — and the workers, forkservers and child interpreters the suite
# starts inherit this environment. The suite exercises sharding semantics
# on the 8-device virtual mesh and kernels in interpret mode (what the
# chip's compiler accepts is tests/test_tpu_compile.py; the chip itself is
# chip_smoke.py, not pytest). The config update covers a jax that some
# plugin imported, and so latched, before conftest ran.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    (os.environ.get("XLA_FLAGS", "") +
     " --xla_force_host_platform_device_count=8").strip())

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


class FakeClock:
    """Deterministic monotonic clock: tests inject it as the engine /
    fleet / autoscaler ``clock`` and advance time explicitly, so
    deadline-expiry and autoscaler-hysteresis behavior is exercised in
    microseconds of wall time instead of real sleeps (the hold windows
    involved are seconds to minutes)."""

    def __init__(self, start: float = 0.0):
        self.t = float(start)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        assert dt >= 0, "monotonic clocks do not rewind"
        self.t += dt


@pytest.fixture
def fake_clock():
    return FakeClock()


@pytest.fixture(scope="session")
def nano_olmoe():
    """(cfg, params) of a nano sparse model with OLMoE's layer: q/k norm,
    8 experts, 2 a token, weights not renormalised, float32. The engine
    identity tests take it as their second model family."""
    import jax.numpy as jnp

    from ray_tpu.models import MoeConfig, moe_init

    cfg = MoeConfig.nano_moe(n_experts=8, top_k=2, qk_norm=True,
                             norm_topk_prob=False, dtype=jnp.float32,
                             remat=False)
    return cfg, moe_init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module", autouse=True)
def _fresh_metric_registry():
    """Each test module starts from an empty process-local metric
    registry (util.metrics.reset_registry): counters/gauges recorded by
    an earlier module would otherwise leak into a later module's
    snapshots()/prometheus_text() assertions, making pass/fail depend
    on collection order. The serving state registry gets the same
    treatment — engines registered (weakly) by one module must not
    appear in another module's list_engines()."""
    from ray_tpu.util.metrics import reset_registry
    from ray_tpu.util.metrics_history import reset_global_history
    from ray_tpu.util.state.serving import reset_serving_state

    reset_registry()
    reset_serving_state()
    reset_global_history()
    yield
    # Every compiled XLA:CPU program holds several memory mappings, and
    # jit caches keep every program a module compiled alive. One pytest
    # process runs the whole suite, and past vm.max_map_count (65,530
    # here) the next compile dies with SIGSEGV inside the backend.
    jax.clear_caches()


# Multi-device pattern for sharded-engine tests: the session itself IS
# the forced multi-device world — the XLA_FLAGS line above sets
# --xla_force_host_platform_device_count=8 BEFORE jax initializes, so
# every test process already sees 8 virtual CPU devices and a tp mesh
# is just a subset of jax.devices(). No subprocess spawn is needed (the
# re-exec pattern __graft_entry__._reexec_with_cpu_world uses exists
# only for callers whose jax backend initialized BEFORE the flag could
# be set — never the case under this conftest). New fixtures that need
# devices should build on cpu_mesh_devices below, not re-exec.
@pytest.fixture(scope="session")
def tp_mesh(cpu_mesh_devices):
    """Factory fixture: ``tp_mesh(n)`` -> a ``{"tp": n}`` serving mesh
    over the first n virtual CPU devices, for DecodeEngine(mesh=...).
    (Engines can also just take ``tp=n`` — the factory exists for
    tests that pre-build or share a mesh across engines.)"""
    from ray_tpu.parallel import create_mesh

    def make(n: int):
        return create_mesh({"tp": n}, cpu_mesh_devices[:n])

    return make


@pytest.fixture(scope="module")
def ray_start_regular():
    import ray_tpu

    ctx = ray_tpu.init(num_cpus=4,
                       ignore_reinit_error=True)
    yield ctx
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu._private.cluster_utils import Cluster

    cluster = Cluster()
    created = []

    def factory():
        created.append(cluster)
        return cluster

    yield factory
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    for c in created:
        c.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, (
        "tests need xla_force_host_platform_device_count=8; got "
        f"{len(devices)}")
    return devices
