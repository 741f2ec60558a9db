"""Real-width compiles of the main-path Pallas kernels for a TPU v5e.

No chip is needed: the TPU compiler is installed, and it compiles for a
v5e chip that is described, not attached. This catches what interpret mode
cannot — block shapes the (8, 128) tiling refuses, casts Mosaic lacks,
fast memory a kernel would overrun — at the paper's SIFT1M operating point
(1M rows, M=16, K=256; fs4 M=32, K=16). Each compiled program must hold a
``tpu_custom_call``: the kernel itself, not a fallback.

The topology is described inside a module-scoped fixture (never at import:
only one process at a time may load the TPU library) and the persistent
compilation cache is off around the compiles (an entry written for a
described chip cannot be read back without one).
"""

import importlib

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

N_1M = 1_000_000


def _kernel_module(name):
    return importlib.import_module(f"repro.kernels.{name}")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    was_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs outside
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler here
            jax.config.update("jax_enable_compilation_cache", was_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()


def _compile_has_kernel(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_pq_pairwise_compiles(one_chip):
    pqp = _kernel_module("pq_pairwise")
    _compile_has_kernel(pqp.pq_pairwise,
                        _spec(one_chip, (8192, 16, 8), jnp.float32),
                        _spec(one_chip, (16, 256, 8), jnp.float32))


def test_pq_pairwise_kmeans_vmap_compiles(one_chip):
    """As pq/kmeans.py calls it: one flat space per subspace, vmapped."""
    pqp = _kernel_module("pq_pairwise")
    fn = jax.vmap(lambda x, c: pqp.pq_pairwise(x[:, None, :], c[None]))
    _compile_has_kernel(fn, _spec(one_chip, (16, 8192, 8), jnp.float32),
                        _spec(one_chip, (16, 256, 8), jnp.float32))


@pytest.mark.parametrize("width", [64, 256])
def test_hop_adc_compiles(one_chip, width):
    hop = _kernel_module("hop_adc")
    _compile_has_kernel(hop.hop_adc,
                        _spec(one_chip, (N_1M + 1, 16), jnp.uint8),
                        _spec(one_chip, (64, width), jnp.int32),
                        _spec(one_chip, (64, 16, 256), jnp.float32))


@pytest.mark.parametrize("width", [64, 256])
def test_hop_adc_fs_compiles(one_chip, width):
    hop = _kernel_module("hop_adc")
    _compile_has_kernel(lambda c, i, l: hop.hop_adc_fs(c, i, l, m=32),
                        _spec(one_chip, (N_1M + 1, 16), jnp.uint8),
                        _spec(one_chip, (64, width), jnp.int32),
                        _spec(one_chip, (64, 32, 16), jnp.uint8))


def test_adc_scan_batch_compiles(one_chip):
    adc = _kernel_module("adc_scan")
    _compile_has_kernel(adc.adc_scan_batch,
                        _spec(one_chip, (N_1M, 16), jnp.uint8),
                        _spec(one_chip, (64, 16, 256), jnp.float32))


def test_adc_scan_fs_compiles(one_chip):
    adcfs = _kernel_module("adc_scan_fs")
    _compile_has_kernel(adcfs.adc_scan_fs,
                        _spec(one_chip, (N_1M, 16), jnp.uint8),
                        _spec(one_chip, (64, 32, 16), jnp.uint8))
