"""The registry cell's kernel compiled for the chip it runs on, without
the chip: ``gN`` at ``(1024, 8, 131072)``, the whole batch a device-resident
caller launches, and at the slab a call from host memory streams it in
(``gf2kernels._slab_stripes``), for r = 1, 2 and 3 output rows through the
TPU compiler for a described v5e (nothing runs, nothing is timed).  What the Pallas interpreter cannot show -- a block shape Mosaic
declines, a launch that does not fit the device -- fails here.  One
file: the worker that is given it loads the TPU library, inside the
fixture, and no other does.

Since PR 50 also the one program a call over objects of unequal size
launches: k=10 at ``(1, 10, _slab_lanes(10))``, r = 4 and r = 2, by the
engine the shape selects on a TPU backend.
"""

from __future__ import annotations

import os

import pytest

B, K, L = 1024, 8, 131072       # benchmark/configs/rs_k8m3_registry_codec.json
HBM_BYTES = 16e9                # benchmark/peaks.json, "TPU v5 lite"


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def compile_gN(one_chip, monkeypatch, batch, rows):
    import jax
    import jax.numpy as jnp
    import ceph_tpu.ops.gf2kernels as g

    # the CPU backend of a test run would build the interpreter's kernel
    monkeypatch.setattr(g, "_interpret", lambda: False)
    cfg = g._g2_cfg(K)
    plan = g._gN_plan(K, batch, L, cfg)
    assert plan == (2, g.LANE_TILE)         # two stripes a step, 8192 lanes
    group, tile = plan
    # the maker, not ``_compiled_batch_gN``: nothing built here is cached
    fn = g._make_pallas_batch_fn_gN(8 * rows, K, batch, L, group, tile,
                                    cfg["unpack"], cfg["mm"], cfg["pack"])
    w = jax.ShapeDtypeStruct((group * 8 * rows, 8 * group * K), jnp.int8,
                             sharding=one_chip)
    xd = jax.ShapeDtypeStruct((batch, K, L), jnp.uint8, sharding=one_chip)
    compiled = fn.lower(w, xd).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == batch * rows * L
    assert mem.argument_size_in_bytes >= batch * K * L
    return mem


@pytest.mark.parametrize("rows", [1, 2, 3])
def test_gN_compiles_for_the_v5e_at_the_cells_shape(one_chip, rows,
                                                    monkeypatch):
    mem = compile_gN(one_chip, monkeypatch, B, rows)
    # the whole launch (1 GiB in, the rows out, the compiler's padded copy
    # of the result) leaves most of the chip's memory free
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES / 4


@pytest.mark.parametrize("rows", [1, 2, 3])
def test_gN_compiles_for_the_v5e_at_the_slabs_shape(one_chip, rows,
                                                    monkeypatch):
    import ceph_tpu.ops.gf2kernels as g

    slab = g._slab_stripes(B, K, L)
    assert slab * K * L == g.SLAB_BYTES and B % slab == 0
    mem = compile_gN(one_chip, monkeypatch, slab, rows)
    # three slabs live on the device at most (one uploading, one in the
    # kernel, one copying out): arguments, outputs and temporaries of all
    # three are a hundredth of the chip's memory, where the whole batch
    # at once is a tenth
    assert 3 * (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) < HBM_BYTES / 64


@pytest.mark.parametrize("rows", [4, 2])
def test_the_k10_slab_of_lanes_compiles_for_the_v5e(one_chip, rows,
                                                    monkeypatch):
    """benchmark/configs/cauchy_k10m4_registry_codec.json: every slab of
    a call over objects of unequal size is ``(1, 10, 3350528)``, an
    encode's r = 4 and a decode of two's r = 2."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import ceph_tpu.ops.gf2kernels as g

    monkeypatch.setattr(g, "_interpret", lambda: False)
    monkeypatch.setattr(g, "_want_pallas", lambda: True)
    k = 10
    lanes = g._slab_lanes(k)
    assert lanes == 3350528 and lanes % g.LANE_TILE == 0
    matrix = np.arange(1, rows * k + 1, dtype=np.uint8).reshape(rows, k)
    assert g.batch_engine(matrix, 1, k, lanes) == "v1"
    # the maker, not ``_compiled_batch``: nothing built here is cached
    fn = g._make_pallas_batch_fn(8 * rows, k, 1, lanes, g._pick_tile(lanes))
    w = jax.ShapeDtypeStruct((8 * rows, 8 * k), jnp.int8, sharding=one_chip)
    xd = jax.ShapeDtypeStruct((1, k, lanes), jnp.uint8, sharding=one_chip)
    compiled = fn.lower(w, xd).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= k * lanes
    assert mem.output_size_in_bytes >= rows * lanes
    # three slabs live on the device at most
    assert 3 * (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) < HBM_BYTES / 64
