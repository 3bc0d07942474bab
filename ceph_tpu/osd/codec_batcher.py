"""Per-OSD cross-PG EC codec micro-batching.

The whole thesis of the TPU plugin is that erasure-code math amortizes
when many stripes share one MXU launch (ceph_tpu/ops/gf2kernels.py),
but the OSD data path naturally produces work one op at a time: each
ECBackend write re-encodes its own stripe run, each reconstruction
decodes its own object.  Dispatched per op, every launch pays the
device round trip and the batch dimension stays 1 -- slower than the
host path for small writes.

The CodecBatcher is the aggregation stage in between: every ECBackend
on an OSD (across ALL its PGs) submits encode/decode work here, the
batcher coalesces stripe sets from concurrently in-flight ops into
single launches, and fans results back to per-op futures
byte-identically.  The role analog in the reference is the RMW
pipelining of src/osd/ECCommon.cc:704-789 -- there the overhead
amortized is the read-modify-write round trip, here it is the
accelerator launch.

Mechanics:

  * submissions are grouped by codec *profile signature* (the encode
    matrix bytes + (k, m), plus the erasure pattern for decodes --
    the same keying as the DecodeTableCache) so stripes from
    different PGs, even different pools with the same profile, share
    a launch;
  * ragged tails are padded to a common (B, k, L): the GF matmul is
    column-independent, so zero-padding the lane axis and slicing the
    result back is byte-exact, and the batch axis is rounded up to a
    power-of-two bucket so the jit cache stays bounded
    (the engine's ``pad_batch``);
  * a group flushes when it reaches ``max_batch`` stripes, when the
    event loop completes a pass with no new submissions (the Nagle-off
    fast path: nothing else is going to coalesce, launch now), or on a
    short timer backstop;
  * every batch launches through ONE engine, by default the sharded
    data plane (parallel/mesh_codec.MeshCodec): one shard_map-compiled
    launch partitions the stripe-batch axis over every visible device
    with donated stripe buffers and the CRC side-path fused into the
    same program -- a single device is just a 1-device mesh, so the
    code path is identical from laptop CPU to a full slice.  The
    batcher knows queues, padding and futures; what a launch is made
    of is the engine's business;
  * a codec the engine cannot launch (isa/jerasure host plugins,
    layered codes with chunk remapping) takes the per-op path --
    ``supports`` asks the engine, and the call site gates on it;
  * the launch spine is DOUBLE-BUFFERED: a flush marshals its batch on
    host (pad, stack, stage) and hands it to a single-slot launch
    driver instead of launching inline, so batch N+1's host staging
    overlaps launch N's device time -- the dispatch/materialize split
    (``out_np=False`` launches, one ``np.asarray`` at completion) is
    what opens the window, and the engine's donation contract makes
    the buffer handoff safe.  The shutdown drain and a full staging
    queue run the same three functions (marshal, dispatch, complete)
    inline.

Occupancy is surfaced as perf counters (``perf dump`` -> "ec_batch"):
batches launched, a stripes-per-batch histogram, padding waste, and
flush-reason counts, so the bench can report achieved batch sizes.
Three wait counters, integer microseconds summed over launches, say
where a launch's time goes: ``queue_wait_us`` (a group's first
submission to its dispatch; also by kind, ``<kind>_queue_wait_us``, as
``stripes`` is by ``<kind>_stripes`` and ``batches`` by
``<kind>_launches``: a window that mixes clients' encodes with a
repair's decodes or a scrub's digests reads each kind's own; a
digest's stripes are its rows, one whole shard each), ``overlap_us``
(dispatch return to completion entry: the launch is in flight while
the loop does other work) and ``materialize_us`` (the ``np.asarray`` that blocks the
loop's thread until the device is done).
Pipeline occupancy (staged batches, overlap windows, staging-full
stalls) lands in the OSD-wide "ec_pipeline" set.
"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from ..common.tracing import section

STRIPE_HIST_BUCKETS = [1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0,
                       256.0, 512.0]


def codec_signature(codec, kind: str, extra: tuple) -> tuple:
    """Launch-compatibility key: submissions with the same signature
    compute with the same coefficient matrix and may share a batch.
    Decode submissions fold in the DecodeTableCache signature (the
    erasure pattern picks the decode matrix)."""
    if kind == "decode" and hasattr(codec, "decode_signature"):
        extra = (codec.decode_signature(extra),) + extra
    return (kind, codec.k, codec.m,
            codec.encode_matrix.tobytes(), extra)


class _Group:
    """One pending batch: submissions awaiting a shared launch."""

    __slots__ = ("codec", "kind", "extra", "items", "n_stripes", "task",
                 "t_first")

    def __init__(self, codec, kind: str, extra: tuple) -> None:
        self.codec = codec
        self.kind = kind     # "encode" | "decode" | "rmw" | "digest"
        self.extra = extra   # decode: erasure tuple; digest: (lane,)
        self.items: list[tuple[np.ndarray, asyncio.Future, bool]] = []
        self.n_stripes = 0
        self.task: asyncio.Task | None = None
        self.t_first = time.perf_counter_ns()   # queue_wait_us starts


class _Staged:
    """One marshaled batch parked between staging and launch: the
    host work (padding, stacking, CRC wants) is DONE; only the device
    dispatch and the post-launch fan-out remain."""

    __slots__ = ("grp", "reason", "batch", "old_batch", "want_crc",
                 "lane", "total", "b", "payload", "t_dispatched")

    def __init__(self, grp, reason, batch, old_batch, want_crc,
                 lane, total, b, payload) -> None:
        self.grp = grp
        self.reason = reason
        self.batch = batch
        self.old_batch = old_batch
        self.want_crc = want_crc
        self.lane = lane
        self.total = total
        self.b = b
        self.payload = payload
        self.t_dispatched = 0       # perf_counter_ns at _dispatch's return


class CodecBatcher:
    """Asyncio micro-batching stage for EC codec launches.

    ``await encode(codec, stripes)`` with stripes shaped (n, k, L)
    resolves to the (n, m, L) parity chunks; ``await decode(codec,
    erasures, survivors)`` with survivors shaped (n, k, L) in
    decode-index order resolves to the (n, len(erasures), L) recovered
    chunks.  Results are byte-identical to per-stripe codec.encode /
    codec.decode.

    ``engine`` launches the batches: ``supports(codec)``,
    ``pad_batch(total)`` and ``encode`` / ``decode`` / ``rmw`` /
    ``digest`` with MeshCodec's signatures.  Left out, it is a
    MeshCodec over every visible device, built on first use (a
    replicated-only OSD never pays the jax import).
    """

    def __init__(self, *, max_batch: int = 64,
                 flush_timeout: float = 0.002,
                 eager_flush: bool = True, perf=None,
                 engine=None, staging_depth: int = 4,
                 pipe_perf=None) -> None:
        self.max_batch = max(1, int(max_batch))
        self.flush_timeout = float(flush_timeout)
        self.eager_flush = bool(eager_flush)
        self.perf = perf
        # double-buffered launch spine: staged batches queue here and
        # a single driver task launches them, so the NEXT batch's host
        # marshal overlaps the current launch's device time.  Depth
        # bounds parked host memory; a flush finding the queue full
        # launches inline (a counted stall, never an unbounded queue).
        self.staging_depth = max(1, int(staging_depth))
        self.pipe_perf = pipe_perf
        from collections import deque
        self._staged: deque[_Staged] = deque()
        self._drive_task: asyncio.Task | None = None
        # All knobs are SNAPSHOT here -- no config object is retained
        # and nothing is looked up per batch (from_config + the
        # test_mesh_codec assertion).
        self._engine = engine
        self._groups: dict[tuple, _Group] = {}
        self._closed = False
        if perf is not None:
            perf.hist_register("stripes_per_batch", STRIPE_HIST_BUCKETS)

    @classmethod
    def from_config(cls, conf, perf=None,
                    pipe_perf=None) -> "CodecBatcher | None":
        """Construction-time snapshot of every batcher knob (the hot
        launch loop must never call ``conf.get``).
        Returns None when EC batching is disabled."""
        if not conf.get("osd_ec_batch_enabled", True):
            return None
        return cls(
            max_batch=int(conf.get("osd_ec_batch_max", 64)),
            flush_timeout=float(conf.get("osd_ec_batch_timeout",
                                         0.002)),
            eager_flush=bool(conf.get("osd_ec_batch_eager_flush",
                                      True)),
            staging_depth=int(conf.get("osd_pipeline_staging_depth",
                                       4)),
            perf=perf, pipe_perf=pipe_perf)

    @property
    def engine(self):
        """The launch engine every batch goes through."""
        if self._engine is None:
            from ..parallel.mesh_codec import MeshCodec
            self._engine = MeshCodec(perf=self.perf)
        return self._engine

    # -- capability gate ----------------------------------------------------
    def supports(self, codec) -> bool:
        """The engine can launch this codec's stripes; otherwise the
        caller takes the per-op path (and says so: ``note_fallback``)."""
        return self.engine.supports(codec)

    # -- submission ---------------------------------------------------------
    async def encode(self, codec, stripes: np.ndarray,
                     with_crc: bool = False):
        """(n, k, L) data chunks -> (n, m, L) parity chunks.

        With ``with_crc`` the result is ``(parity, crcs)`` where crcs
        is (n, k+m) uint32 -- the CRC32C of every data and parity chunk
        of every stripe, computed in the launch itself where the engine
        fuses them (no host re-scan of bytes the accelerator just
        touched) and by one host ``crc32c_rows`` pass otherwise.
        Callers fold them into whole-shard CRCs with
        ``fold_chunk_crcs``.
        """
        return await self._submit("encode", codec, stripes, (),
                                  want_crc=with_crc)

    async def decode(self, codec, erasures: tuple[int, ...],
                     survivors: np.ndarray) -> np.ndarray:
        """(n, k, L) surviving chunks (decode-index order, the same
        contract as ``decode_batch``) -> (n, len(erasures), L)."""
        return await self._submit("decode", codec, survivors,
                                  tuple(int(e) for e in erasures))

    async def rmw(self, codec, old_parity: np.ndarray,
                  delta: np.ndarray) -> np.ndarray:
        """Delta-encoded partial-stripe parity update: (n, m, L) old
        parity + (n, k, L) data delta (zeros outside the written
        range) -> (n, m, L) new parity = old XOR encode(delta), by GF
        linearity.  Coalesces across concurrently-submitting ops like
        encode/decode; the old-parity device buffer is donated and
        ALIASED in place (MeshCodec.rmw), so the update never holds
        two parity copies."""
        old_parity = np.ascontiguousarray(old_parity, np.uint8)
        assert old_parity.ndim == 3, old_parity.shape
        return await self._submit("rmw", codec, delta, (),
                                  old=old_parity)

    async def digest(self, rows: np.ndarray, lengths) -> np.ndarray:
        """CRC32C (default seed) of whole buffers, all of them in ONE
        launch of the engine's digest program: a deep scrub's resident
        shards (osd/scrub.py).  ``rows`` is (n, lane) as
        ``ops/crc32c_batch.digest_rows`` lays buffers out (each at the
        end of a power-of-two row), ``lengths`` the buffers' own
        lengths, folded in here on the host; -> (n,) uint32.  A kind
        of its own beside encode, decode and rmw: ``digest_launches``,
        ``digest_stripes`` (rows) and ``digest_queue_wait_us`` count
        it, the staged driver and the ``device_wait`` section carry
        it, and concurrent submissions of one lane share a launch."""
        from ..ops.crc32c_batch import digest_finish
        regs = await self._submit("digest", None, rows[:, None, :],
                                  (rows.shape[1],))
        return digest_finish(regs, lengths)

    def note_fallback(self) -> None:
        """A caller took the per-op path for a non-batch codec."""
        if self.perf is not None:
            self.perf.inc("fallback_ops")

    def note_rmw(self, delta: bool) -> None:
        """A partial-stripe write run took the delta path (rmw launch)
        or fell back to a full re-encode."""
        if self.perf is not None:
            self.perf.inc("rmw_delta_runs" if delta
                          else "rmw_full_runs")

    async def _submit(self, kind: str, codec, arr: np.ndarray,
                      extra: tuple, want_crc: bool = False, old=None):
        arr = np.ascontiguousarray(arr, dtype=np.uint8)
        assert arr.ndim == 3, arr.shape
        key = (kind, extra) if codec is None \
            else codec_signature(codec, kind, extra)
        grp = self._groups.get(key)
        if grp is None:
            grp = self._groups[key] = _Group(codec, kind, extra)
        loop = asyncio.get_event_loop()
        fut = loop.create_future()
        grp.items.append((arr, fut, want_crc, old))
        grp.n_stripes += arr.shape[0]
        if self._closed:
            # late straggler during shutdown: nothing to wait for
            self._flush(key, "close")
        elif grp.n_stripes >= self.max_batch:
            self._flush(key, "full")
        elif grp.task is None:
            grp.task = loop.create_task(self._linger(key, grp))
        return await fut

    # -- flush policy --------------------------------------------------------
    async def _linger(self, key: tuple, grp: _Group) -> None:
        """Wait for co-submitters, then flush.  The group grows while
        other runnable tasks reach their submit points; one full event
        loop pass with no growth means the queue drained."""
        loop = asyncio.get_event_loop()
        deadline = loop.time() + self.flush_timeout
        try:
            while True:
                n0 = grp.n_stripes
                await asyncio.sleep(0)
                if self._groups.get(key) is not grp:
                    return               # flushed by the size threshold
                if grp.n_stripes != n0:
                    continue             # still coalescing
                if self.eager_flush:
                    self._flush(key, "drain")
                    return
                now = loop.time()
                if now >= deadline:
                    self._flush(key, "timer")
                    return
                await asyncio.sleep(min(self.flush_timeout / 4,
                                        deadline - now))
        except asyncio.CancelledError:
            pass

    def _flush(self, key: tuple, reason: str) -> None:
        grp = self._groups.pop(key, None)
        if grp is None or not grp.items:
            return
        if self._closed:
            self._run_batch(grp, reason)
            return
        # marshal NOW (this is exactly the host staging that
        # overlaps the in-flight launch), park the batch, and let the
        # driver launch it.  A full staging queue degrades to an inline
        # launch -- bounded memory, and the stall is counted so the
        # bench can see when the depth knob binds.
        if len(self._staged) >= self.staging_depth:
            self._pcount("stage_stalls")
            self._run_batch(grp, reason)
            return
        self._staged.append(self._marshal(grp, reason))
        self._pcount("staged_batches")
        if self._drive_task is None or self._drive_task.done():
            self._drive_task = asyncio.ensure_future(self._drive())

    def _pcount(self, key: str, by: int = 1) -> None:
        if self.pipe_perf is not None:
            self.pipe_perf.inc(key, by)

    async def _drive(self) -> None:
        """The staged launch driver: one in-flight launch at a time.

        Dispatch is asynchronous (``out_np=False`` launches return
        device futures), so the yield between dispatch and completion
        is the overlap window -- co-submitting tasks run there and
        marshal batch N+1 while N executes on device."""
        while self._staged:
            st = self._staged.popleft()
            try:
                handle = self._dispatch(st)
            except Exception as e:
                self._fail(st, e)
                continue
            # overlap window: let submitters stage the next batch
            # while this launch is in flight on device.  Only yield
            # when someone could actually use the window (a parked
            # batch or a coalescing group) -- an unconditional yield
            # would add a scheduling pass to EVERY launch completion,
            # which under a saturated loop is pure latency.
            if self._staged or self._groups:
                await asyncio.sleep(0)
            try:
                self._complete(st, handle)
            except Exception as e:
                self._fail(st, e)

    def _drain_staged(self) -> None:
        """Synchronously launch everything parked (shutdown path): no
        staged batch may outlive the batcher -- an orphaned batch is a
        wedged op."""
        if self._drive_task is not None:
            self._drive_task.cancel()
            self._drive_task = None
        while self._staged:
            st = self._staged.popleft()
            try:
                self._complete(st, self._dispatch(st))
            except Exception as e:
                self._fail(st, e)

    @staticmethod
    def _fail(st: "_Staged", e: Exception) -> None:
        for _, fut, _, _ in st.grp.items:
            if not fut.done():
                fut.set_exception(e)

    def flush_all(self, reason: str = "close") -> None:
        for key in list(self._groups):
            self._flush(key, reason)

    def close(self) -> None:
        """Launch whatever is pending so in-flight ops complete, then
        refuse further coalescing (a straggler launches at once, on
        its own)."""
        self._closed = True
        self.flush_all("close")
        self._drain_staged()

    # -- the launch ----------------------------------------------------------
    @staticmethod
    def _host_chunk_crcs(data: np.ndarray,
                         out: np.ndarray) -> np.ndarray:
        """Chunk CRCs of a launch that did not fuse them (the flat
        dialect): still ONE batched pass over all chunks, never
        per-buffer."""
        from ..ops.crc32c_batch import crc32c_rows
        b, k, lane = data.shape
        r = out.shape[1]
        crcs = crc32c_rows(np.concatenate(
            [data.reshape(b * k, lane), out.reshape(b * r, lane)]))
        return np.concatenate([crcs[:b * k].reshape(b, k),
                               crcs[b * k:].reshape(b, r)], axis=1)

    def _run_batch(self, grp: _Group, reason: str) -> None:
        """Marshal -> dispatch -> complete inline (shutdown drain, full
        staging queue).  The staged driver runs the SAME three
        functions with a yield between dispatch and complete."""
        st = self._marshal(grp, reason)
        try:
            self._complete(st, self._dispatch(st))
        except Exception as e:
            self._fail(st, e)

    def _marshal(self, grp: _Group, reason: str) -> _Staged:
        """Host staging: pad and stack the coalesced submissions into
        one (b, k, lane) launch batch (plus the old-parity batch for
        rmw).  This is the work that overlaps the in-flight launch."""
        with section("batcher.marshal"):
            return self._marshal_batch(grp, reason)

    def _marshal_batch(self, grp: _Group, reason: str) -> _Staged:
        items = grp.items
        k = items[0][0].shape[1]
        lane = max(a.shape[2] for a, _, _, _ in items)
        total = sum(a.shape[0] for a, _, _, _ in items)
        b = self.engine.pad_batch(total)
        payload = sum(a.size for a, _, _, _ in items)
        if len(items) == 1 and b == total:
            batch = items[0][0]
        else:
            batch = np.zeros((b, k, lane), np.uint8)
            row = 0
            for a, _, _, _ in items:
                n, _, l = a.shape
                batch[row:row + n, :, :l] = a
                row += n
        old_batch = None
        if grp.kind == "rmw":
            # the old-parity side rides the same padding: zero delta
            # rows encode to zero, so padded parity passes through
            m_dim = items[0][3].shape[1]
            if len(items) == 1 and b == total:
                old_batch = items[0][3]
            else:
                old_batch = np.zeros((b, m_dim, lane), np.uint8)
                row = 0
                for a, _, _, old in items:
                    n, _, l = a.shape
                    old_batch[row:row + n, :, :l] = old
                    row += n
        want_crc = any(w for _, _, w, _ in items)
        return _Staged(grp, reason, batch, old_batch, want_crc,
                       lane, total, b, payload)

    def _dispatch(self, st: _Staged) -> tuple:
        """Device dispatch WITHOUT materialization: the launch returns
        device futures (``out_np=False``), so control comes back to
        the event loop while the device works.  Returns (out, crcs);
        ``_complete`` pays the single asarray."""
        if self.perf is not None:
            waited = (time.perf_counter_ns() - st.grp.t_first) // 1000
            self.perf.inc("queue_wait_us", waited)
            self.perf.inc(f"{st.grp.kind}_queue_wait_us", waited)
        with section("batcher.dispatch"):
            handle = self._dispatch_launch(st)
        st.t_dispatched = time.perf_counter_ns()
        return handle

    def _dispatch_launch(self, st: _Staged) -> tuple:
        """ONE launch for the whole coalesced batch, fused CRCs riding
        it when wanted.  A launch failure fails the batch's waiters
        (``_fail``): it is never retried another way."""
        grp, engine = st.grp, self.engine
        crcs = None
        if grp.kind == "digest":
            out = engine.digest(st.batch.reshape(st.b, st.lane),
                                out_np=False)
        elif grp.kind == "rmw":
            out = engine.rmw(grp.codec, st.old_batch, st.batch,
                             out_np=False)
        elif grp.kind == "decode":
            out = engine.decode(grp.codec, grp.extra, st.batch,
                                out_np=False)
        elif st.want_crc:
            out, crcs = engine.encode(grp.codec, st.batch,
                                      with_crc=True, out_np=False)
            if crcs is not None and self.perf is not None:
                self.perf.inc("crc_fused_launches")
        else:
            out = engine.encode(grp.codec, st.batch, out_np=False)
        return out, crcs

    def _complete(self, st: _Staged, handle: tuple) -> None:
        """Materialize the launch (the single post-launch host hop),
        fan results back to the per-op futures, bump the counters."""
        t_in = time.perf_counter_ns()
        out, crcs = handle
        with section("device_wait.materialize"):
            # lint: disable=device-path-host-sync -- the single post-launch materialization
            out = np.asarray(out)
            if crcs is not None:
                # lint: disable=device-path-host-sync -- the single post-launch materialization (fused CRC side output)
                crcs = np.asarray(crcs)
        if self.perf is not None:
            self.perf.inc("overlap_us", (t_in - st.t_dispatched) // 1000)
            self.perf.inc("materialize_us",
                          (time.perf_counter_ns() - t_in) // 1000)
        grp, items = st.grp, st.grp.items
        if crcs is None and st.want_crc:
            crcs = self._host_chunk_crcs(st.batch, out)
            if self.perf is not None:
                self.perf.inc("crc_host_batches")
        row = 0
        lane = st.lane
        for a, fut, w, _ in items:
            n, _, l = a.shape
            if not fut.done():
                res = out[row:row + n] if grp.kind == "digest" \
                    else out[row:row + n, :, :l]
                if w:
                    item_crcs = crcs[row:row + n]
                    if l < lane:
                        # chunk CRCs were computed at the padded lane
                        # width; zero-extension is invertible, so strip
                        # it instead of re-hashing the bytes
                        from ..ops.crc32c_batch import crc32c_strip_zeros
                        item_crcs = crc32c_strip_zeros(item_crcs,
                                                       lane - l)
                    fut.set_result((res, item_crcs))
                else:
                    fut.set_result(res)
            row += n
        if self.perf is not None:
            self.perf.inc("batches")
            self.perf.inc(f"{grp.kind}_launches")
            self.perf.inc("stripes", st.total)
            self.perf.inc(f"{grp.kind}_stripes", st.total)
            self.perf.inc("ops_coalesced", len(items))
            self.perf.inc("pad_waste_bytes",
                          st.b * st.batch.shape[1] * lane - st.payload)
            self.perf.inc(f"flush_{st.reason}")
            self.perf.hist_sample("stripes_per_batch", st.total)
