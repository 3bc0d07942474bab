"""Sharded erasure coding over a jax device Mesh.

Dataflow (the TPU-native rendering of the EC write fan-out,
src/osd/ECBackend.cc:1467 -> MOSDECSubOpWrite per shard):

  * stripes shard across the 'stripe' mesh axis (data parallel: each PG
    batch is independent, like PGs are independent in RADOS);
  * the k data chunks shard across the 'shard' mesh axis (the analog of
    chunk shards living on k+m distinct OSDs);
  * parity needs all k chunks: an all_gather over 'shard' rides ICI --
    this is the communication the reference does with messenger fan-out;
  * each 'shard' row computes a slice of the m parity rows
    (reduce-style split), so compute is balanced across the axis.

The same module drives dryrun_multichip (virtual CPU mesh) and real
multi-chip runs: only the mesh construction differs.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..gf import build_decode_matrix, gen_rs_matrix
from ..ops.gf2kernels import bitmatrix_i8


def make_mesh(n_devices: int | None = None, shard_axis: int = 2) -> Mesh:
    """(stripe, shard) mesh over the first n devices."""
    devs = jax.devices()
    n = n_devices or len(devs)
    devs = np.asarray(devs[:n])
    shard = shard_axis if n % shard_axis == 0 else 1
    return Mesh(devs.reshape(n // shard, shard), ("stripe", "shard"))


def make_data_mesh(n_devices: int | None = None) -> Mesh:
    """1-D ('stripe',) mesh over the first n visible devices.

    The live OSD data plane (parallel/mesh_codec.py) partitions only
    the stripe-batch axis: every stripe is independent, so the sharded
    encode/decode needs ZERO collectives -- each chip computes the
    parity of its batch slice and a multi-chip slice behaves like one
    giant codec.  A single device degenerates to a 1-device mesh on
    the identical code path (how the CPU tier-1 suite exercises it,
    and why ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
    gives the real 8-way program on a laptop)."""
    devs = jax.devices()
    n = min(n_devices or len(devs), len(devs))
    # lint: disable=device-path-host-sync -- marshals the DEVICE LIST into the Mesh, once at construction; no batch data flows here
    return Mesh(np.asarray(devs[:n]), ("stripe",))


def _gf_matmul_bits(w_i8: jnp.ndarray, data_u8: jnp.ndarray,
                    scope: str = "gf_encode") -> jnp.ndarray:
    """(8r,8k) x (k,N) -> (r,N); same math as ops.gf2kernels.  The
    scope names these operations in a device trace: ``gf_encode``
    for encode and rmw, ``gf_decode`` where the caller reconstructs."""
    with jax.named_scope(scope):
        k, n = data_u8.shape
        d = data_u8.astype(jnp.int32)
        planes = [((d >> s) & 1) for s in range(8)]
        bits = jnp.stack(planes, axis=1).reshape(8 * k, n).astype(
            jnp.int8)
        acc = jax.lax.dot_general(
            w_i8, bits, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32) & 1
        r = w_i8.shape[0] // 8
        b = acc.reshape(r, 8, n)
        shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
        return (b << shifts).sum(axis=1).astype(jnp.uint8)


def _sharded_gf_apply(mesh: Mesh, matrix: np.ndarray,
                      x: jnp.ndarray) -> jnp.ndarray:
    """Apply a GF(2^8) matrix to shard-axis-scattered chunks: every
    device all_gathers the input shards over 'shard' (the ICI hop),
    computes ITS slice of the output rows, and the row slices
    reassemble on the shard axis.  The shared scaffolding under both
    the parity encode and the recovery decode."""
    r = matrix.shape[0]
    w = jnp.asarray(bitmatrix_i8(matrix))
    n_shard = mesh.shape["shard"]
    r_pad = ((r + n_shard - 1) // n_shard) * n_shard
    w_full = jnp.zeros((8 * r_pad, w.shape[1]),
                       jnp.int8).at[:8 * r].set(w)

    def block(w_local, chunks):
        gathered = jax.lax.all_gather(
            chunks, "shard", axis=1, tiled=True)
        bl, kk, ll = gathered.shape
        flat = gathered.transpose(1, 0, 2).reshape(kk, bl * ll)
        rows = _gf_matmul_bits(w_local, flat)
        return rows.reshape(-1, bl, ll).transpose(1, 0, 2)

    out = shard_map(
        block, mesh=mesh,
        in_specs=(P("shard", None), P("stripe", "shard", None)),
        out_specs=P("stripe", "shard", None),
    )(w_full, x)
    return out[:, :r]


def sharded_encode(mesh: Mesh, encode_matrix: np.ndarray, k: int,
                   data: jnp.ndarray) -> jnp.ndarray:
    """(B, k, L) -> (B, m, L) with B over 'stripe' and k over 'shard'.

    Requires B % mesh.stripe == 0 and k % mesh.shard == 0.
    """
    return _sharded_gf_apply(mesh, encode_matrix[k:], data)


def sharded_ec_step(mesh: Mesh, encode_matrix: np.ndarray,
                    decode_matrix: np.ndarray, decode_index: list[int],
                    erasures: list[int], k: int, data: jnp.ndarray):
    """One full EC pipeline step under jit: encode -> degrade -> recover.

    Returns (parity, recovered, global_crc_like_checksum).  The checksum
    psum over 'stripe' is the analog of the commit-ack reduction (all
    shards confirm before the client reply, ECCommon.cc:789).
    """
    parity = sharded_encode(mesh, encode_matrix, k, data)
    full = jnp.concatenate([data, parity], axis=1)
    survivors = full[:, jnp.asarray(decode_index), :]
    wdec = jnp.asarray(bitmatrix_i8(decode_matrix))

    def dec_block(w_local, chunks):
        bl, kk, ll = chunks.shape
        flat = chunks.transpose(1, 0, 2).reshape(kk, bl * ll)
        rec = _gf_matmul_bits(w_local, flat)
        return rec.reshape(-1, bl, ll).transpose(1, 0, 2)

    dec = shard_map(
        dec_block, mesh=mesh,
        in_specs=(P(None, None), P("stripe", None, None)),
        out_specs=P("stripe", None, None),
    )
    recovered = dec(wdec, survivors)

    def checksum_block(p):
        s = jnp.sum(p.astype(jnp.uint32))
        return jax.lax.psum(s, "stripe")[None]

    csum = shard_map(
        checksum_block, mesh=mesh,
        in_specs=(P("stripe", None, None),),
        out_specs=P("stripe"),
    )(recovered)
    return parity, recovered, csum


def sharded_rmw(mesh: Mesh, encode_matrix: np.ndarray, k: int,
                old_parity: jnp.ndarray,
                delta: jnp.ndarray) -> jnp.ndarray:
    """Partial-stripe read-modify-write parity update (the sharded
    rendering of ECCommon.cc:704-789's RMW pipeline): GF(2^8) codes
    are linear, so new_parity = old_parity XOR encode(new XOR old)
    touches only the changed bytes' encode -- no full-stripe re-read.
    ``delta`` is (B, k, L) with zeros outside the written range; the
    encode rides the same (stripe, shard) mesh + ICI all_gather as the
    full-stripe path.
    """
    pdelta = sharded_encode(mesh, encode_matrix, k, delta)
    return jnp.bitwise_xor(old_parity, pdelta)


def sharded_cross_recovery(mesh: Mesh, decode_matrix: np.ndarray,
                           survivors: jnp.ndarray) -> jnp.ndarray:
    """Reconstruct erased shards when the SURVIVORS are sharded over
    the 'shard' mesh axis -- each device holds only its slice, so the
    reconstruction needs a cross-chip all_gather over ICI first (the
    network reads ECBackend recovery issues to the surviving OSDs,
    ECCommon.cc recovery reads), then decodes locally.  Survivors:
    (B, k, L), k divisible by the shard axis.
    """
    return _sharded_gf_apply(mesh, decode_matrix, survivors)


# -- LRC over mesh sub-axes --------------------------------------------------
#
# The locality structure of an LRC code (ec/plugins/lrc.py; reference
# src/erasure-code/lrc/ErasureCodeLrc.h:47-134) maps onto the device mesh:
# each local group lives on one slice of the 'group' axis.  Encoding the
# global parities needs all k data chunks once (all_gather over 'group',
# the ICI hop); local parities and -- the whole point -- single-shard
# REPAIR are computed entirely inside the group's mesh slice with no
# collective at all.  This is the TPU rendering of "repair reads stay
# inside the failure domain".


def lrc_make_mesh(n_devices: int, n_groups: int) -> Mesh:
    """(stripe, group) mesh: group axis carries the LRC local groups."""
    devs = np.asarray(jax.devices()[:n_devices])
    return Mesh(devs.reshape(n_devices // n_groups, n_groups),
                ("stripe", "group"))


def lrc_sharded_encode(mesh: Mesh, k: int, m: int, l: int,
                       data: jnp.ndarray) -> jnp.ndarray:
    """LRC k/m/l encode over a (stripe, group) mesh.

    ``data`` is (B, n_groups, kg, L): group-major data chunks, sharded
    P('stripe', 'group', None, None).  Returns (B, n_groups, kg+mg+1, L)
    full group-major chunk layout (data + global parity slots + local
    parity), same sharding.  Byte-identical to the host `lrc` plugin's
    encode for the k/m/l profile.
    """
    lgc = (k + m) // l
    kg, mg = k // lgc, m // lgc
    gen_g = gen_rs_matrix(k + m, k)          # global layer
    gen_l = gen_rs_matrix(l + 1, l)          # local layers (m=1)
    wg = jnp.asarray(bitmatrix_i8(gen_g[k:]))      # (8m, 8k)
    wl = jnp.asarray(bitmatrix_i8(gen_l[l:]))      # (8, 8l)

    def block(wg_all, wl_all, chunks):
        # chunks: (B_loc, 1, kg, L) = my group's data shard
        bl, _, _, ll = chunks.shape
        gidx = jax.lax.axis_index("group")
        # ICI hop: every group needs all k data chunks for its global
        # parity rows
        gathered = jax.lax.all_gather(
            chunks, "group", axis=1, tiled=True)   # (B_loc, lgc, kg, L)
        flat = gathered.reshape(bl, k, ll).transpose(1, 0, 2) \
                       .reshape(k, bl * ll)
        # my mg rows of the global parity (rows gidx*mg ..)
        wg_mine = jax.lax.dynamic_slice_in_dim(
            wg_all, gidx * 8 * mg, 8 * mg, axis=0)
        gp = _gf_matmul_bits(wg_mine, flat)        # (mg, B*L)
        gp = gp.reshape(mg, bl, ll).transpose(1, 0, 2)  # (B_loc, mg, L)
        # local parity over my l = kg+mg chunks, no collective
        mine = chunks[:, 0]                        # (B_loc, kg, L)
        lchunks = jnp.concatenate([mine, gp], axis=1)   # (B_loc, l, L)
        lflat = lchunks.transpose(1, 0, 2).reshape(l, bl * ll)
        lp = _gf_matmul_bits(wl_all, lflat)
        lp = lp.reshape(1, bl, ll).transpose(1, 0, 2)
        out = jnp.concatenate([mine, gp, lp], axis=1)  # (B_loc, l+1, L)
        return out[:, None]

    fn = shard_map(
        block, mesh=mesh,
        in_specs=(P(None, None), P(None, None),
                  P("stripe", "group", None, None)),
        out_specs=P("stripe", "group", None, None),
    )
    return fn(wg, wl, data)


def lrc_sharded_local_repair(mesh: Mesh, k: int, m: int, l: int,
                             lost_local_pos: int,
                             chunks: jnp.ndarray) -> jnp.ndarray:
    """Repair ONE lost chunk per group from the group's surviving l
    chunks -- no collective: the repair never leaves the mesh slice.

    ``chunks``: (B, n_groups, l+1, L) group-major layout from
    lrc_sharded_encode; ``lost_local_pos`` in [0, l+1) names the lost
    position within every group (the dry run loses the same local slot
    in each group; per-group positions would shard the decode matrix).
    Returns (B, n_groups, 1, L): the reconstructed chunk per group.
    """
    gen_l = gen_rs_matrix(l + 1, l)
    dec, idx = build_decode_matrix(gen_l, l, [lost_local_pos])
    wd = jnp.asarray(bitmatrix_i8(dec))            # (8, 8l)
    sel = jnp.asarray(idx)

    def block(wd_all, chunks_):
        bl, _, _, ll = chunks_.shape
        mine = chunks_[:, 0]                       # (B_loc, l+1, L)
        srcs = mine[:, sel]                        # (B_loc, l, L)
        flat = srcs.transpose(1, 0, 2).reshape(l, bl * ll)
        rec = _gf_matmul_bits(wd_all, flat)
        return rec.reshape(1, bl, ll).transpose(1, 0, 2)[:, None]

    fn = shard_map(
        block, mesh=mesh,
        in_specs=(P(None, None), P("stripe", "group", None, None)),
        out_specs=P("stripe", "group", None, None),
    )
    return fn(wd, chunks)
