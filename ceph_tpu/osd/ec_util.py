"""EC stripe math: logical object space <-> per-shard chunk space.

Mirrors src/osd/ECUtil.h stripe_info_t (:27-117): a pool-wide
stripe_width = k * chunk_size; a logical object offset maps to
(stripe index, chunk offset); shard s of an object holds the
concatenation of that object's chunk s across all stripes.
ECUtil::encode/decode (:21,134) drive the plugin per whole stripe.
"""

from __future__ import annotations

from typing import Mapping

import numpy as np

from ..common.tracing import section


def parse_stripe_unit(codec, value) -> int:
    """Validate a profile's stripe_unit (OSDMonitor.cc:7782-7813
    prepare_pool_stripe_width mirror): it must parse as a positive
    integer and divide evenly into codec-aligned chunks, or the pool's
    stripe geometry silently diverges from what the profile claims.
    Raises ValueError with the reference's spirit of message.
    """
    try:
        su = int(value)
    except (TypeError, ValueError):
        raise ValueError(
            f"stripe_unit {value!r} is not an integer") from None
    if su <= 0:
        raise ValueError(f"stripe_unit {su} must be > 0")
    align = codec.get_alignment()
    if su % align:
        raise ValueError(
            f"stripe_unit {su} must be a multiple of the codec "
            f"alignment {align} (the codec would round chunks up and "
            f"desync the stripe geometry)")
    return su


class StripeInfo:
    def __init__(self, k: int, m: int, stripe_width: int) -> None:
        assert stripe_width % k == 0, (stripe_width, k)
        self.k = k
        self.m = m
        self.stripe_width = stripe_width
        self.chunk_size = stripe_width // k

    @classmethod
    def for_codec(cls, codec, stripe_unit: int = 4096) -> "StripeInfo":
        """Build a StripeInfo whose chunk_size matches the codec's
        aligned get_chunk_size — the same adjustment pool creation does
        (OSDMonitor::prepare_pool_stripe_width, OSDMonitor.cc:7782).
        """
        k = codec.get_data_chunk_count()
        m = codec.get_coding_chunk_count()
        chunk = codec.get_chunk_size(stripe_unit * k)
        return cls(k, m, chunk * k)

    def _check_codec(self, codec) -> None:
        # codecs align chunks up (SIMD_ALIGN); a mismatched stripe_width
        # would slice shard buffers at the wrong boundaries
        cs = codec.get_chunk_size(self.stripe_width)
        assert cs == self.chunk_size, (
            f"stripe_width {self.stripe_width} gives codec chunk_size "
            f"{cs}, StripeInfo expects {self.chunk_size}; build via "
            f"StripeInfo.for_codec")

    # -- offset maps (ECUtil.h:58-96) ---------------------------------------
    def logical_to_prev_stripe_offset(self, offset: int) -> int:
        return offset - (offset % self.stripe_width)

    def logical_to_next_stripe_offset(self, offset: int) -> int:
        rem = offset % self.stripe_width
        return offset if rem == 0 else offset + self.stripe_width - rem

    def aligned_logical_offset_to_chunk_offset(self, offset: int) -> int:
        assert offset % self.stripe_width == 0, offset
        return (offset // self.stripe_width) * self.chunk_size

    def chunk_aligned_logical_offset_to_chunk_offset(
            self, offset: int) -> int:
        return self.aligned_logical_offset_to_chunk_offset(
            self.logical_to_prev_stripe_offset(offset))

    def aligned_chunk_offset_to_logical_offset(self, offset: int) -> int:
        assert offset % self.chunk_size == 0, offset
        return (offset // self.chunk_size) * self.stripe_width

    def object_size_to_shard_size(self, size: int) -> int:
        """On-shard bytes for a logical object of `size` bytes."""
        return self.aligned_logical_offset_to_chunk_offset(
            self.logical_to_next_stripe_offset(size))

    def offset_len_to_stripe_bounds(
            self, offset: int, length: int) -> tuple[int, int]:
        """Expand [offset, offset+length) to stripe-aligned bounds."""
        start = self.logical_to_prev_stripe_offset(offset)
        end = self.logical_to_next_stripe_offset(offset + length)
        return start, end - start

    # -- stripe encode/decode drivers (ECUtil.cc:21,134) --------------------
    def encode(self, codec, data: bytes) -> dict[int, np.ndarray]:
        """Encode whole stripes of `data` into k+m shard buffers.

        `data` must be stripe-aligned (pad first).  Each shard buffer is
        the concatenation of its chunk across stripes.
        """
        self._check_codec(codec)
        assert len(data) % self.stripe_width == 0, len(data)
        n_stripes = len(data) // self.stripe_width
        want = set(range(self.k + self.m))
        shards: dict[int, list[np.ndarray]] = {i: [] for i in want}
        for s in range(n_stripes):
            stripe = data[s * self.stripe_width:(s + 1) * self.stripe_width]
            encoded = codec.encode(want, stripe)
            for i in want:
                # lint: disable=device-path-host-sync -- scalar host fallback for codecs without batch entry points
                shards[i].append(np.asarray(encoded[i], dtype=np.uint8))
        return {i: (np.concatenate(bufs) if bufs
                    else np.zeros(0, np.uint8))
                for i, bufs in shards.items()}

    async def encode_async(self, codec, data: bytes, batcher=None,
                           with_crc: bool = False):
        """Batched analog of encode(): every stripe of ``data`` rides
        ONE launch of the CodecBatcher's engine, shared with other
        concurrently-submitting ops (cross-PG coalescing).
        Byte-identical to encode(); without a batcher, or for a codec
        its engine cannot launch, encode() itself serves.

        With ``with_crc`` the result is ``(shards, crcs)`` where
        ``crcs[i]`` is the CRC32C of shard i's whole buffer: per-stripe
        chunk CRCs come back from the codec launch itself (or one host
        batched pass on fallback) and are folded across the stripe axis
        with the GF(2) combine -- the write path stamps them without
        ever re-hashing shard bytes.
        """
        if batcher is None or not batcher.supports(codec):
            if batcher is not None:
                batcher.note_fallback()
            shards = self.encode(codec, data)
            if not with_crc:
                return shards
            return shards, self._shard_crcs(shards)
        self._check_codec(codec)
        assert len(data) % self.stripe_width == 0, len(data)
        n = len(data) // self.stripe_width
        if n == 0:
            out0 = {i: np.zeros(0, np.uint8)
                    for i in range(self.k + self.m)}
            if not with_crc:
                return out0
            return out0, self._shard_crcs(out0)
        arr = np.frombuffer(data, np.uint8).reshape(
            n, self.k, self.chunk_size)
        if with_crc:
            parity, chunk_crcs = await batcher.encode(codec, arr,
                                                      with_crc=True)
        else:
            parity = await batcher.encode(codec, arr)
        # shard placement honors the codec's chunk remapping: data
        # chunk i lives at position chunk_index(i), parity row r at the
        # r-th coding position (layered codes like lrc interleave
        # coding positions between data groups; identity-mapped codecs
        # reduce to out[i]=data_i, out[k+r]=parity_r exactly as before)
        cpos = self.coding_positions(codec)
        out: dict[int, np.ndarray] = {}
        for i in range(self.k):
            out[codec.chunk_index(i)] = np.ascontiguousarray(
                arr[:, i]).reshape(-1)
        for r in range(self.m):
            out[cpos[r]] = np.ascontiguousarray(
                parity[:, r]).reshape(-1)
        if not with_crc:
            return out
        from ..ops.crc32c_batch import fold_chunk_crcs
        folded = fold_chunk_crcs(chunk_crcs, self.chunk_size)
        # folded column order is the launch order (data 0..k-1, then
        # parity rows); re-key by shard position like `out`
        crcs = {codec.chunk_index(i): int(folded[i])
                for i in range(self.k)}
        for r in range(self.m):
            crcs[cpos[r]] = int(folded[self.k + r])
        return out, crcs

    @staticmethod
    def _shard_crcs(shards: dict[int, np.ndarray]) -> dict[int, int]:
        """Whole-shard CRCs in one batched pass (fallback path)."""
        from ..ops.crc32c_batch import crc32c_batch
        ids = sorted(shards)
        crcs = crc32c_batch([shards[i] for i in ids])
        return {i: int(c) for i, c in zip(ids, crcs)}

    async def decode_async(self, codec,
                           shard_bufs: Mapping[int, np.ndarray],
                           want: set[int] | None = None,
                           batcher=None, recovering: bool = False
                           ) -> dict[int, np.ndarray]:
        """Batched analog of decode(): all stripes' reconstructions in
        one launch, grouped in the batcher by erasure
        signature (the DecodeTableCache keying) so concurrent recovery
        reads with the same down-shard pattern coalesce.
        ``recovering`` (a shard rebuilt for a peer) puts the stack of
        the survivors under the ``recovery.payload`` section."""
        from ..gf.matrices import decode_index_for
        want = (set(self.data_positions(codec)) if want is None
                else set(want))
        have = set(shard_bufs)
        k, m = self.k, self.m
        erasures = sorted(i for i in range(k + m) if i not in have)
        if batcher is None or not batcher.supports(codec):
            if batcher is not None:
                batcher.note_fallback()
            return self.decode(codec, shard_bufs, want)
        self._check_codec(codec)
        lens = {len(b) for b in shard_bufs.values()}
        assert len(lens) == 1, lens
        shard_len = lens.pop()
        assert shard_len % self.chunk_size == 0, shard_len
        n = shard_len // self.chunk_size
        cs = self.chunk_size
        if n == 0:
            return {i: np.zeros(0, np.uint8) for i in want}
        if want <= have or not erasures:
            # lint: disable=device-path-host-sync -- view-normalizes gathered/cache-resident ndarrays (no copy, no transfer)
            return {i: np.asarray(shard_bufs[i], dtype=np.uint8)
                    for i in want}
        if hasattr(codec, "decode_plan"):
            # layered/regenerating codecs (ec/linear_codec.py) pick
            # their OWN sources -- the LRC local group is fewer than k
            # chunks, which the positional decode-index contract below
            # cannot express -- and pack (sources, lost) into the
            # batcher's grouping extra so same-pattern repairs share a
            # launch
            plan = codec.decode_plan(set(want), have)
            if plan is not None:
                src, lost = plan
                survivors = np.stack(
                    # lint: disable=device-path-host-sync -- the single input marshal: gathered buffers stacked once for the launch
                    [np.asarray(shard_bufs[p], dtype=np.uint8)
                     .reshape(n, cs) for p in src], axis=1)
                rec = await batcher.decode(
                    codec, codec.pack_decode_extra(src, lost),
                    survivors)
                out2: dict[int, np.ndarray] = {}
                for i in want:
                    if i in shard_bufs:
                        # lint: disable=device-path-host-sync -- view passthrough of gathered shards alongside decoded ones
                        out2[i] = np.asarray(shard_bufs[i],
                                             dtype=np.uint8)
                    else:
                        out2[i] = np.ascontiguousarray(
                            rec[:, lost.index(i)]).reshape(-1)
                return out2
            return self.decode(codec, shard_bufs, want)
        if len(erasures) > m or len(have) < k:
            # unrecoverable: let the per-stripe driver raise its
            # canonical IOError
            return self.decode(codec, shard_bufs, want)
        decode_index = decode_index_for(k, set(erasures))

        def stack() -> np.ndarray:
            return np.stack(
                # lint: disable=device-path-host-sync -- the single input marshal: network/cache-resident buffers stacked once for the launch
                [np.asarray(shard_bufs[i], dtype=np.uint8).reshape(n, cs)
                 for i in decode_index], axis=1)          # (n, k, cs)

        if recovering:
            with section("recovery.payload"):
                survivors = stack()
        else:
            survivors = stack()
        rec = await batcher.decode(codec, tuple(erasures), survivors)
        out: dict[int, np.ndarray] = {}
        for i in want:
            if i in shard_bufs:
                # lint: disable=device-path-host-sync -- view passthrough of gathered/cache-resident shards alongside decoded ones
                out[i] = np.asarray(shard_bufs[i], dtype=np.uint8)
            else:
                out[i] = np.ascontiguousarray(
                    rec[:, erasures.index(i)]).reshape(-1)
        return out

    async def reconstruct_logical_async(
            self, codec, shard_bufs: Mapping[int, np.ndarray],
            batcher=None) -> bytes:
        dpos = self.data_positions(codec)
        data_shards = await self.decode_async(codec, shard_bufs,
                                              want=set(dpos),
                                              batcher=batcher)
        return self.interleave_logical(codec, data_shards)

    @staticmethod
    def data_positions(codec) -> list[int]:
        """Shard ids hosting data chunks 0..k-1 (mapped codes like lrc
        place data at chunk_index(i), not i)."""
        k = codec.get_data_chunk_count()
        idx = getattr(codec, "chunk_index", None)
        return [idx(i) if idx else i for i in range(k)]

    @classmethod
    def coding_positions(cls, codec) -> list[int]:
        """Shard ids hosting coding chunks, ascending (the order the
        batched encode entry points emit parity rows in)."""
        dpos = set(cls.data_positions(codec))
        n = codec.get_chunk_count()
        return [p for p in range(n) if p not in dpos]

    def decode(self, codec, shard_bufs: Mapping[int, np.ndarray],
               want: set[int] | None = None) -> dict[int, np.ndarray]:
        """Reconstruct shard buffers (possibly all) from available shards.

        Every shard buffer covers the same chunk range; decode runs
        per-stripe through the plugin and reconcatenates.
        """
        self._check_codec(codec)
        want = (set(self.data_positions(codec)) if want is None
                else set(want))
        lens = {len(b) for b in shard_bufs.values()}
        assert len(lens) == 1, lens
        shard_len = lens.pop()
        assert shard_len % self.chunk_size == 0, shard_len
        n_stripes = shard_len // self.chunk_size
        out: dict[int, list[np.ndarray]] = {i: [] for i in want}
        for s in range(n_stripes):
            lo, hi = s * self.chunk_size, (s + 1) * self.chunk_size
            # lint: disable=device-path-host-sync -- scalar host fallback (unrecoverable-stripe error path)
            chunks = {i: np.asarray(b[lo:hi], dtype=np.uint8)
                      for i, b in shard_bufs.items()}
            decoded = codec.decode(want, chunks)
            for i in want:
                out[i].append(decoded[i])
        return {i: (np.concatenate(bufs) if bufs
                    else np.zeros(0, np.uint8))
                for i, bufs in out.items()}

    def reconstruct_logical(self, codec,
                            shard_bufs: Mapping[int, np.ndarray]) -> bytes:
        """Rebuild the logical byte stream from shard buffers."""
        dpos = self.data_positions(codec)
        data_shards = self.decode(codec, shard_bufs, want=set(dpos))
        return self.interleave_logical(codec, data_shards)

    def interleave_logical(self, codec,
                           data_shards: Mapping[int, np.ndarray]) -> bytes:
        dpos = self.data_positions(codec)
        shard_len = len(next(iter(data_shards.values())))
        n_stripes = shard_len // self.chunk_size
        if n_stripes == 0 or not dpos:
            return b""
        # one materialization for the whole stream: stacking to
        # (n_stripes, k, cs) puts bytes in stripe-major interleave
        # order, vs the old per-stripe-per-shard asarray+tobytes hop
        stacked = np.stack(
            [data_shards[p].reshape(n_stripes, self.chunk_size)
             for p in dpos], axis=1)
        return stacked.tobytes()
