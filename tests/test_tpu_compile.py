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
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.api import sweep
from repro.core import baselines, compress, engine, executor
from repro.core.acpd import MethodConfig
from repro.kernels import sdca_inner
from repro.kernels.topk_filter import topk_filter_pallas

# rcv1.binary (LIBSVM) train split: d = 47,236 features; 20,240 of its
# 20,242 rows over K = 16 workers.  URL is d = 3,231,961.
K, N_K, D_RCV1, D_URL = 16, 1265, 47_236, 3_231_961
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _key(one_chip):
    key = jax.eval_shape(lambda: jax.random.key(0))
    return jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)


@pytest.mark.parametrize("d", [D_RCV1, D_URL], ids=["rcv1", "url"])
def test_topk_filter_compiles(one_chip, d):
    compiled = topk_filter_pallas.lower(_spec(one_chip, (d,)), 1000).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _pass_operands(one_chip, K, n_k, d, H):
    s = lambda *shape, dt=jnp.float32: _spec(one_chip, shape, dt)
    return (s(d), s(n_k), s(K, n_k, d), s(dt=jnp.int32), s(n_k), s(n_k),
            1e-4, K * n_k, float(K), s(H, dt=jnp.int32))


def _widest_d():
    lanes = sdca_inner.LANES
    return sdca_inner.VMEM_BUDGET_BYTES // sdca_inner.vmem_bytes(lanes) * lanes


@pytest.mark.parametrize("n_k,d,H,wider", [
    (8, "widest", 8, (sdca_inner.LANES, 0)),
    (24_000, 256, sdca_inner.SMEM_BUDGET - 4 * 24_000, (0, 1)),
], ids=["vmem", "smem"])
def test_sdca_kernel_compiles_at_widest_admitted_shape(one_chip, n_k, d, H,
                                                       wider):
    """The widest d (VMEM) and the longest pass (SMEM) the kernel admits
    compile; one step past either is refused."""
    d = _widest_d() if d == "widest" else d
    assert sdca_inner.fits("ridge", n_k, d, H)
    assert not sdca_inner.fits("ridge", n_k, d + wider[0], H + wider[1])
    compiled = sdca_inner.sdca_pass.lower(
        *_pass_operands(one_chip, K, n_k, d, H)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("Kx,n_k,d", [(K, N_K, D_RCV1), (K, 1264, D_RCV1),
                                      (3, 13, 1000), (8, 13, 640)])
def test_sdca_kernel_reads_x_in_its_default_layout(one_chip, Kx, n_k, d):
    """The kernel views X with the axis the TPU tiles second, so XLA hands
    it over as it lies: no relayout copy of X.  X's buffer holds whole
    (8, 128) tiles, so the tile block and the padded lanes that a fetch
    moves lie inside it whatever n_k, K and d are."""
    x = jax.ShapeDtypeStruct((Kx, n_k, d), jnp.float32, sharding=one_chip)
    copy = jax.jit(lambda a: a * 2).lower(x).compile()
    layout = copy.input_formats[0][0]
    by_worker = layout.layout.major_to_minor == (1, 0, 2)
    assert by_worker == sdca_inner._tiled_by_worker(Kx, n_k)
    assert layout.layout.tiling == ((sdca_inner.SUBLANES, sdca_inner.LANES),)
    major, tiled = (n_k, Kx) if by_worker else (Kx, n_k)
    tiles = -(-tiled // sdca_inner.SUBLANES) * sdca_inner.SUBLANES
    assert copy.memory_analysis().argument_size_in_bytes \
        == 4 * major * tiles * sdca_inner._padded(d)
    compiled = sdca_inner.sdca_pass.lower(
        *_pass_operands(one_chip, Kx, n_k, d, n_k)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * n_k * d


def _worker_rounds(one_chip, n_k, d, B=8):
    """``_worker_rounds_fused`` for B of K=16 workers, one pass of n_k
    steps each, the ACPD group traffic of the benchmark's gap cells."""
    s = lambda *shape, dt=jnp.float32: _spec(one_chip, shape, dt)
    method = baselines.acpd(K, d, B=B, T=10, rho_d=1000, H=n_k)
    return engine._worker_rounds_fused.lower(
        _key(one_chip), s(K, d), s(K, n_k), s(K, d), s(K, n_k, d), s(K, n_k),
        s(K, n_k), s(B, dt=jnp.int32), s(), s(dt=jnp.int32), s(), s(),
        loss="ridge", num_steps=n_k, comp=compress.for_method(method, d))


def test_worker_rounds_run_the_kernel_at_rcv1_width(one_chip):
    """At RCV1 width the worker program runs its passes on the SDCA kernel
    and copies no worker's block of X (239 MB each) out of the stacked X."""
    compiled = _worker_rounds(one_chip, N_K, D_RCV1).compile()
    assert "tpu_custom_call" in compiled.as_text()
    block = 4 * N_K * D_RCV1
    assert compiled.memory_analysis().temp_size_in_bytes < block // 20


def test_worker_rounds_bypass_the_kernel_at_url_width(one_chip):
    """URL's w_eff, v and row buffers would need 233 MB of VMEM: the worker
    program keeps the jnp pass over X[k]."""
    assert not sdca_inner.fits("ridge", 32, D_URL, 32)
    lowered = _worker_rounds(one_chip, 32, D_URL)
    assert "tpu_custom_call" not in lowered.as_text()


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


@pytest.mark.parametrize("chips,cells,batch,kernel", [
    (1, 1, "vmap", True), (1, 2, "map", True), (1, 4, "vmap", False),
    (4, 8, "vmap", False)], ids=["vmap-1", "map-2", "vmap-4",
                                 "vmap-8-cells-4chips"])
def test_lag_sweep_compiles_at_rcv1_width(topo, monkeypatch, chips, cells,
                                          batch, kernel):
    """``run_sweep`` of lag maps or vmaps the lag scan over cells (sharded
    over four chips by cells).  A cell that runs alone, mapped or vmapped
    over one, runs its passes on the SDCA kernel and copies no worker's
    block of X; cells vmapped together keep the vectorised jnp pass."""
    method = baselines.acpd_lag(K, D_RCV1, B=8, T=10, H=N_K)
    R = method.T
    if chips == 1:
        sharding = SingleDeviceSharding(topo.devices[0])
    else:  # the sweep builds its mesh from jax.devices(): hand it the chips
        mesh = Mesh(topo.devices[:chips], ("cells",))
        monkeypatch.setattr(sweep.mesh_lib, "make_sweep_mesh",
                            lambda n, axis: mesh)
        sharding = NamedSharding(mesh, PartitionSpec())
    s = lambda *shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=sharding)
    key = jax.eval_shape(lambda: jax.vmap(jax.random.key)(jnp.arange(cells)))
    f64, i64 = jnp.float64, jnp.int64
    with jax.enable_x64(True):
        compiled = sweep._lag_sweep_scan.lower(
            jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=sharding),
            s(K, N_K, D_RCV1), s(K, N_K), s(K, N_K), s(), s(dt=jnp.int32),
            s(cells), s(cells), s(), s(cells, R + 1, K, dt=f64),
            s(R, dt=i64), s(dt=i64), s(dt=i64), s(cells, dt=f64),
            s(cells, dt=f64), s(cells, K, dt=f64), s(2, dt=jnp.int32),
            loss="ridge", num_steps=N_K,
            comp=compress.for_method(method, D_RCV1), length=R,
            lag_window=method.lag_window, dense_reply_bytes=0,
            batch=batch, n_shards=chips).compile()
    assert ("tpu_custom_call" in compiled.as_text()) is kernel
    if kernel:
        block = 4 * N_K * D_RCV1
        assert compiled.memory_analysis().temp_size_in_bytes < block // 2
