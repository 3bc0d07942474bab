"""The registry cell's kernel compiled for the chip it runs on, without
the chip: ``gN`` at ``(1024, 8, 131072)`` for r = 1, 2 and 3 output rows
through the TPU compiler for a described v5e (nothing runs, nothing is
timed).  What the Pallas interpreter cannot show -- a block shape Mosaic
declines, a launch that does not fit the device -- fails here.  One
file: the worker that is given it loads the TPU library, inside the
fixture, and no other does.
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


@pytest.mark.parametrize("rows", [1, 2, 3])
def test_gN_compiles_for_the_v5e_at_the_cells_shape(one_chip, rows,
                                                    monkeypatch):
    import jax
    import jax.numpy as jnp
    import ceph_tpu.ops.gf2kernels as g

    # the CPU backend of a test run would build the interpreter's kernel
    monkeypatch.setattr(g, "_interpret", lambda: False)
    cfg = g._g2_cfg(K)
    plan = g._gN_plan(K, B, L, cfg)
    assert plan == (2, g.LANE_TILE)         # two stripes a step, 8192 lanes
    group, tile = plan
    # the maker, not ``_compiled_batch_gN``: nothing built here is cached
    fn = g._make_pallas_batch_fn_gN(8 * rows, K, B, L, group, tile,
                                    cfg["unpack"], cfg["mm"], cfg["pack"])
    w = jax.ShapeDtypeStruct((group * 8 * rows, 8 * group * K), jnp.int8,
                             sharding=one_chip)
    xd = jax.ShapeDtypeStruct((B, K, L), jnp.uint8, sharding=one_chip)
    compiled = fn.lower(w, xd).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == B * rows * L
    assert mem.argument_size_in_bytes >= B * K * L
    # the whole launch (1 GiB in, the rows out, the compiler's padded copy
    # of the result) leaves most of the chip's memory free
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES / 4
