"""Compile rehearsal for a described TPU v5e at the cells' own shapes.

No chip is needed: the TPU compiler compiles for a v5e that is described,
not attached. The hop kernels at the cells' shapes, and the whole vmapped
beam of each cell with its kernel inside, must compile and hold a
``tpu_custom_call``. The topology is described inside a fixture, never at
import (only one process at a time may load the TPU library), and the
persistent compilation cache is off around the compiles.
"""

import importlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

N = 100_000


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    was_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
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


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


def test_hop_adc_u8_at_batch1024(one_chip):
    hop_adc = importlib.import_module("repro.kernels.hop_adc")
    text = _compiled_text(hop_adc.hop_adc,
                          _spec(one_chip, (N + 1, 16), jnp.uint8),
                          _spec(one_chip, (1024, 64), jnp.int32),
                          _spec(one_chip, (1024, 16, 256), jnp.float32))
    assert "tpu_custom_call" in text


def test_hop_adc_fs_at_online16(one_chip):
    hop_adc = importlib.import_module("repro.kernels.hop_adc")
    fn = lambda p, i, lut: hop_adc.hop_adc_fs(p, i, lut, m=48)  # noqa: E731
    text = _compiled_text(fn, _spec(one_chip, (N + 1, 24), jnp.uint8),
                          _spec(one_chip, (16, 64), jnp.int32),
                          _spec(one_chip, (16, 48, 16), jnp.uint8))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("layout,q,m,k", [("u8", 1024, 16, 256),
                                          ("fs4", 16, 48, 16)])
def test_cell_beam_compiles(one_chip, monkeypatch, layout, q, m, k):
    """The beam of each cell as ``HybridEngine`` runs it: the code table
    closed in as a constant, the compiled hop kernel in the loop. JAX here
    sees the CPU, so the kernels' dispatch is told it is on a TPU."""
    from repro.kernels import ops
    from repro.pq.pack import QuantizedLUT
    from repro.search import beam

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    rng = np.random.default_rng(0)
    width = m if layout == "u8" else (m + 1) // 2
    codes = rng.integers(0, 256, (N + 1, width), dtype=np.uint8)
    packed = layout == "fs4"
    dist_fn = beam.make_adc_dist_fn(jnp.asarray(codes), packed=packed,
                                    backend="pallas")
    if packed:
        luts = QuantizedLUT(_spec(one_chip, (q, m, 16), jnp.uint8),
                            _spec(one_chip, (q,), jnp.float32),
                            _spec(one_chip, (q,), jnp.float32))
    else:
        luts = _spec(one_chip, (q, m, k), jnp.float32)
    fn = lambda nb, lut: beam.beam_search(nb, jnp.int32(0), lut, dist_fn,  # noqa: E731
                                          h=64, max_steps=512)
    text = _compiled_text(fn, _spec(one_chip, (N, 64), jnp.int32), luts)
    assert "tpu_custom_call" in text
