"""Compiles for a described TPU v5e at the widths users run (no chip needed).

The TPU compiler is installed here and compiles for a chip that is described,
not attached, so these catch what interpret mode cannot: refused lowerings,
unaligned tiles, VMEM/SMEM over-allocation, and programs that do not fit the
16 GB of one v5e chip.  Nothing runs, so they say nothing about results or
times.  The topology is described inside a fixture, never at import: only
one process at a time may load the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import baselines, compress, executor
from repro.core.acpd import MethodConfig
from repro.kernels import ops
from repro.kernels.sdca_inner import sdca_inner_pallas
from repro.kernels.topk_filter import topk_filter_pallas

# rcv1.binary (LIBSVM) train split: d = 47,236 features; 20,240 of its
# 20,242 rows over K = 16 workers.  URL is d = 3,231,961.
K, N_K, D_RCV1, D_URL = 16, 1265, 47_236, 3_231_961
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _key(one_chip):
    key = jax.eval_shape(lambda: jax.random.key(0))
    return jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)


@pytest.mark.parametrize("d", [D_RCV1, D_URL], ids=["rcv1", "url"])
def test_topk_filter_compiles(one_chip, d):
    compiled = topk_filter_pallas.lower(_spec(one_chip, (d,)), 1000).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_k,d", [(968, 2048), (40, D_RCV1)],
                         ids=["d2048", "rcv1"])
def test_sdca_kernel_compiles_at_widest_admitted_shape(one_chip, n_k, d):
    """The widest multiple-of-8 partition the wrapper admits at this d, one
    visit per coordinate; one step wider is refused by the wrapper."""
    assert ops.sdca_vmem_elements(n_k, d) <= ops._SDCA_VMEM_BUDGET
    assert ops.sdca_vmem_elements(n_k + 8, d) > ops._SDCA_VMEM_BUDGET
    s = lambda *shape, dt=jnp.float32: _spec(one_chip, shape, dt)
    compiled = sdca_inner_pallas.lower(
        s(K, d), s(K, n_k), s(K, n_k, d), s(K, n_k), s(K, n_k), 1e-3,
        K * n_k, float(K), s(K, n_k, dt=jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _rcv1_operands(one_chip):
    s = lambda *shape, dt=jnp.float32: _spec(one_chip, shape, dt)
    return (_key(one_chip), s(K, N_K, D_RCV1), s(K, N_K), s(K, N_K),
            s(), s(dt=jnp.int32), s(), s())


def test_lockstep_scan_fits_one_chip_at_rcv1_width(one_chip):
    method = MethodConfig(name="CoCoA+", protocol="cocoa_plus", B=K,
                          H=N_K, sigma_prime=float(K))
    compiled = executor._lockstep_scan.lower(
        *_rcv1_operands(one_chip), loss="ridge", num_steps=N_K,
        solver=executor.lockstep_solver(method), length=5).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, mem


def test_lag_scan_compiles_at_rcv1_width(one_chip):
    method = baselines.acpd_lag(K, D_RCV1, B=8, T=10, H=N_K)
    R = method.T
    s = lambda *shape, dt: _spec(one_chip, shape, dt)
    with jax.enable_x64(True):
        compiled = executor._lag_scan.lower(
            *_rcv1_operands(one_chip), s(dt=jnp.float32),
            s(R + 1, K, dt=jnp.float64), s(R, dt=jnp.int64),
            s(dt=jnp.int64), s(dt=jnp.int64), s(dt=jnp.float64),
            s(dt=jnp.float64), s(K, dt=jnp.float64), loss="ridge",
            num_steps=N_K, comp=compress.for_method(method, D_RCV1),
            length=R, lag_window=method.lag_window,
            dense_reply_bytes=0).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES, mem
