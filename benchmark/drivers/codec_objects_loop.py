"""``ceph_erasure_code_benchmark``'s encode and decode loops through the
registry's plugin over objects of unequal size, many objects a call,
host buffers in and host buffers out (BASELINE.json configuration 3).

Set-up makes the codec the way the tool does, by name and profile alone
(``registry().factory(plugin, profile)``), draws the pool of payloads
from ``--seed`` (``config.objects_per_size`` objects of each of
``config.object_bytes``, one flat uint8 array an object, their order in
a call shuffled from ``--seed``), times the host plugin and the native
GF product on the same profile and mix (facts, never metrics), encodes
the pool through the plugin to make the decode inputs (an object's
``(k+m, L_i)`` chunk map: its data chunks by the reference's chunking,
its parity the plugin's), and runs one whole cycle as warm-up.  The
window is a closed loop, one op in flight, of the traffic's cycle:

    parity = codec.encode_objects(objects)                # encode
    lost = codec.decode_objects(erased, chunk_maps)       # decode

and an op is finished when its last output byte is readable on the
host.  The erased ids of every decode are drawn from ``--seed``.  The
window closes at the end of the cycle in which ``--seconds`` pass.

``correct`` is decided after the window on what the timed ops returned:
``check_encodes`` encodes and ``check_decodes`` decodes, drawn from
``--seed``, keep ``check_objects_per_size`` objects of every size of
their output, which are held to ``reference/codec.py`` and
``reference/codec_objects.py`` (and the encodes to the host ``isa``
plugin's bytes); one of the kept encodes keeps its whole output, which
is held to the reference's product object by object; and the bytes past
an object's end in its last data chunk, read back from a decode that
erased that chunk, are held to zero.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from benchmark.drivers import codec_loop
from benchmark.drivers.codec_loop import MIB, SECTIONS, draw_erasures, \
    make_codec
from benchmark.harness import (HarnessError, Trace, counter_delta, elapsed,
                               flatten, percentile, say)
from benchmark.readers import layer_time, span_time
from benchmark.reference import codec as ref
from benchmark.reference import codec_objects as ref_objects

# counters a window may leave untouched and a metric still divides by
COUNTERS = ("objects", "lanes", "lanes_launched", "lanes_padded",
            "table_hits", "table_misses")


def require_program() -> None:
    """What the cell needs of the program, asked before anything is
    built or compiled: the registry path's sections and the plugin's
    entry points over objects of unequal size."""
    from ceph_tpu.ec.plugins.tpu import ErasureCodeTpu

    codec_loop.require_program()
    for name in ("encode_objects", "decode_objects"):
        if not hasattr(ErasureCodeTpu, name):
            raise HarnessError(
                f"the tpu plugin has no batch entry point over objects of "
                f"unequal size (ErasureCodeTpu.{name})")


def sizes_of(cfg: dict) -> list[int]:
    """The sizes of a payload's objects, ascending."""
    return [int(size) for size in sorted(cfg["object_bytes"])
            for _ in range(int(cfg["objects_per_size"]))]


def call_order(seed: int, count: int) -> np.ndarray:
    """Position in the call -> object of the payload."""
    return np.random.default_rng([seed, 0x0BDE]).permutation(count)


def payload(seed: int, index: int, cfg: dict) -> list[np.ndarray]:
    """Payload ``index`` of the pool: one flat uint8 array an object,
    sizes ascending, cut from one buffer drawn from the seed."""
    sizes = sizes_of(cfg)
    total = sum(sizes)
    words = np.random.default_rng([seed, 0xC0DEC, index]).integers(
        0, 1 << 64, -(-total // 8), dtype=np.uint64)
    flat = words.view(np.uint8)[:total]
    ends = np.cumsum(sizes).tolist()
    return [flat[end - size:end] for size, end in zip(sizes, ends)]


def chunk_maps(k: int, objects: list[np.ndarray],
               parity: list[np.ndarray]) -> list[np.ndarray]:
    """Per object its ``(k+m, L_i)`` chunk map in one buffer a payload:
    the data chunks as the tool chunks the object (the reference's
    ``chunks_of``), the parity as given."""
    n = k + parity[0].shape[0]
    lanes = sum(p.shape[1] for p in parity)
    flat = np.empty(n * lanes, np.uint8)
    maps, at = [], 0
    for obj, par in zip(objects, parity):
        length = par.shape[1]
        mine = flat[at:at + n * length].reshape(n, length)
        mine[:k] = ref.chunks_of(k, obj.tobytes())
        mine[k:] = par
        maps.append(mine)
        at += n * length
    return maps


def host_rates(isa, cfg: dict, mix: dict,
               objects: list[np.ndarray]) -> dict[str, float]:
    """MiB/s of user bytes of the host plugin (``isa``: numpy table
    lookups) on one thread, through the same registry, object by object
    over ``host_objects_per_size`` objects of every size (the cell's mix
    of an encode and a decode of 2), and of ``native/gf8.cc``'s product
    with the encode rows over the chunks of
    ``host_native_objects_per_size`` objects of every size laid side by
    side.  Neither is Intel ISA-L."""
    from ceph_tpu import native

    profile = cfg["profile"]
    k, n = int(profile["k"]), int(profile["k"]) + int(profile["m"])
    want = set(range(n))
    per = int(cfg["objects_per_size"])

    def some(count: int) -> list[np.ndarray]:
        return [obj for lo in range(0, len(objects), per)
                for obj in objects[lo:lo + min(count, per)]]
    few = [obj.tobytes() for obj in some(int(mix["host_objects_per_size"]))]
    t0 = time.perf_counter()
    encoded = [isa.encode(want, obj) for obj in few]
    t_enc = time.perf_counter() - t0
    count = next(e for kind, e in mix["cycle"] if kind == "decode")
    t0 = time.perf_counter()
    for i, chunks in enumerate(encoded):
        lost = {(i + j) % n for j in range(count)}     # walking round
        isa.decode(want, {j: chunks[j] for j in range(n) if j not in lost})
    t_dec = time.perf_counter() - t0
    more = some(int(mix["host_native_objects_per_size"]))
    rows = np.concatenate([ref.chunks_of(k, obj.tobytes()) for obj in more],
                          axis=1)
    t0 = time.perf_counter()
    native.gf8_matmul(isa.encode_matrix[k:], rows)
    t_nat = time.perf_counter() - t0
    done = sum(len(obj) for obj in few) / MIB
    return {"host_isa_mibps": 2 * done / (t_enc + t_dec),
            "host_isa_encode_mibps": done / t_enc,
            "host_isa_decode_mibps": done / t_dec,
            "host_native_mibps": sum(obj.size for obj in more) / MIB / t_nat}


def report_ops(sl: dict, ops: list[tuple[str, list[int]]]) -> None:
    """The slice's sections op by op: an op's pieces end with the
    ``registry.prepare`` that cuts its result into views, behind its
    ``registry.copy_out``; what no section covers between two ops goes
    to the later one."""
    split: list[dict[str, float]] = [{}]
    closing = False
    for start, end, name in sl["pieces"]:
        label = (name or span_time.UNCOVERED).removeprefix(SECTIONS[0])
        split[-1][label] = split[-1].get(label, 0.0) + end - start
        if closing and name == "registry.prepare":
            split.append({})
            closing = False
        elif name == "registry.copy_out":
            closing = True
    for (kind, erased), times in zip(ops, split):
        say(f"  {kind}{' of ' + str(erased) if erased else ''}: "
            f"{1e3 * sum(times.values()):.1f} ms = " + ", ".join(
                f"{label} {1e3 * secs:.1f}" for label, secs in
                sorted(times.items(), key=lambda kv: -kv[1])))


def run(cell, seed: int, seconds: float, traced: bool, meter) -> dict:
    require_program()
    cfg, mix = cell.config, cell.traffic
    profile = cfg["profile"]
    k, m = int(profile["k"]), int(profile["m"])
    n = k + m
    cycle = [(kind, int(e)) for kind, e in mix["cycle"]]
    ref_profile = {"k": k, "m": m, "technique": profile["technique"]}
    sizes = sizes_of(cfg)
    count, op_bytes = len(sizes), sum(sizes)
    lanes_a_call = sum(ref.chunk_bytes(k, size) for size in sizes)

    # ---- set-up: the codec, the pool, the host's rates, the decode inputs ----
    codec, isa = make_codec(profile), make_codec(profile, "isa")
    perf = codec.perf
    if any(codec.get_chunk_size(size) != ref.chunk_bytes(k, size)
           for size in set(sizes)):
        raise HarnessError(
            f"the plugin's chunk of an object is not the tool's (k chunks "
            f"of ceil(size / k) rounded up to {ref.ISA_ALIGNMENT}) at "
            f"k={k} for some size of {sorted(set(sizes))}")
    order = call_order(seed, count)
    t0 = time.perf_counter()
    drawn = [payload(seed, i, cfg) for i in range(int(mix["pool_payloads"]))]
    # a payload's objects in the call's order
    pool = [[objects[j] for j in order] for objects in drawn]
    placed = [sizes[j] for j in order]      # size at each position
    say(f"pool of {len(pool)} payloads of {count} objects "
        f"({int(cfg['objects_per_size'])} of each of "
        f"{sorted(set(sizes))} bytes, {op_bytes / MIB:.0f} MiB each, their "
        f"order in the call shuffled) drawn from the seed in "
        f"{time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    host = host_rates(isa, cfg, mix, drawn[0])
    say(f"host side of the same profile and mix on one thread in "
        f"{time.perf_counter() - t0:.1f}s (facts, not metrics; neither is "
        f"Intel ISA-L): plugin isa (numpy table lookups) object by object "
        f"{host['host_isa_mibps']:.1f} MiB/s over the cell's mix (encode "
        f"{host['host_isa_encode_mibps']:.1f}, decode "
        f"{host['host_isa_decode_mibps']:.1f}); native/gf8.cc's product "
        f"with the encode rows {host['host_native_mibps']:.1f} MiB/s")
    t0 = time.perf_counter()
    maps = [chunk_maps(k, objects, codec.encode_objects(objects))
            for objects in pool]
    say(f"decode inputs: the pool encoded through the plugin in "
        f"{time.perf_counter() - t0:.1f}s ({meter.hits} cache hits, "
        f"{meter.misses} misses, {meter.programs} backend compiles so far)")

    draw = draw_erasures(seed, n)
    pick = np.random.default_rng([seed, 0xC0FFEE]).random(1 << 16)
    rng = np.random.default_rng([seed, 0x57A1])
    at_size: dict[int, list[int]] = {}
    for position, size in enumerate(placed):
        at_size.setdefault(size, []).append(position)
    sample = sorted(
        int(p) for positions in at_size.values() for p in rng.choice(
            positions, min(int(mix["check_objects_per_size"]),
                           len(positions)), replace=False))
    quota = {"encode": int(mix["check_encodes"]),
             "decode": int(mix["check_decodes"])}
    kept: dict[str, list[dict]] = {kind: [] for kind in quota}
    op_ms: dict[str, list[float]] = {kind: [] for kind in quota}
    ran: list[tuple[str, list[int]]] = []      # every kept op, in order
    handed: dict[int, int] = {}     # output rows -> lanes handed in
    whole: dict | None = None       # the lowest-ranked encode, all of it
    tail: dict | None = None        # the first decode that erased chunk k-1
    failed = done = place = 0       # place: the next op's in the cycle

    def one_op(keep: bool) -> None:
        """The next op of the cycle, as the tool's loop runs it."""
        nonlocal failed, done, place, whole, tail
        kind, erasures = cycle[place % len(cycle)]
        which = place % len(pool)
        place += 1
        erased = draw(erasures) if kind == "decode" else []
        t1 = time.perf_counter()
        try:
            if kind == "encode":
                out = codec.encode_objects(pool[which])
            else:
                out = codec.decode_objects(erased, maps[which])
        except Exception as e:          # the op failed: counted, with its time
            out = None
            if keep:
                failed += 1
                if failed <= 3:
                    say(f"op {done + failed} ({kind} {erased}) raised {e!r}")
        dt = time.perf_counter() - t1
        if not keep:
            return
        op_ms[kind].append(1e3 * dt)
        ran.append((kind, erased))
        if out is None:
            return
        index, done = done, done + 1
        rows = erasures or m
        handed[rows] = handed.get(rows, 0) + lanes_a_call
        rank = pick[index % len(pick)]
        if kind == "encode" and (whole is None or rank < whole["rank"]):
            whole = {"rank": rank, "payload": which, "out": out}
        if kind == "decode" and tail is None and k - 1 in erased:
            tail = {"index": index, "payload": which, "erased": erased,
                    "sample": [out[p][erased.index(k - 1)].copy()
                               for p in sample]}
        mine = kept[kind]
        if len(mine) == quota[kind] and rank >= mine[-1]["rank"]:
            return
        mine.append({"rank": rank, "index": index, "payload": which,
                     "erased": erased,
                     "sample": [out[p].copy() for p in sample]})
        mine.sort(key=lambda rec: rec["rank"])
        del mine[quota[kind]:]

    t0, programs0 = time.perf_counter(), meter.programs
    for _ in range(int(mix["warmup_cycles"]) * len(cycle)):
        one_op(keep=False)
    say(f"warm-up: {int(mix['warmup_cycles'])} cycle(s) of {len(cycle)} ops "
        f"in {time.perf_counter() - t0:.1f}s, {meter.programs - programs0} "
        f"backend compiles in them; engines so far: " + ", ".join(
            f"{key.removeprefix('engine_')} x{val}"
            for key, val in sorted(perf.dump().items())
            if key.startswith("engine_")))

    # ---- the window ---------------------------------------------------------
    facts: dict = {}
    trace = Trace(cell.name) if traced else None
    t_open = time.perf_counter()
    setup_s = elapsed()
    cpu0, programs0, perf0 = time.process_time(), meter.programs, perf.dump()
    while (time.perf_counter() - t_open < seconds
           or (done + failed) % len(cycle)
           or (traced and "slice.ops" not in facts)):
        if traced and "slice.ops" not in facts \
                and done + failed == int(mix["trace_after_cycles"]) * len(cycle):
            # a slice of whole cycles, behind the encode that follows the
            # window's first (kept whole, its lease held: an arena miss)
            before, first = dict(handed), len(ran)
            t1 = time.perf_counter()
            trace.start()
            say(f"profiler started in {time.perf_counter() - t1:.2f}s")
            with trace.mark():
                for _ in range(int(mix["trace_cycles"]) * len(cycle)):
                    one_op(keep=True)
            t1 = time.perf_counter()
            trace.stop()
            say(f"profiler stopped and trace written in "
                f"{time.perf_counter() - t1:.2f}s")
            for rows, lanes in handed.items():
                facts[f"slice.codec.lanes_r{rows}"] = \
                    lanes - before.get(rows, 0)
            facts["slice.ops"] = int(mix["trace_cycles"]) * len(cycle)
            sl = layer_time.load(trace.file(), SECTIONS)
            if sl is not None:
                span_time.report(sl, "registry.launch")
                report_ops(sl, ran[first:])
            continue
        one_op(keep=True)
    window_s = time.perf_counter() - t_open
    cpu_s = time.process_time() - cpu0
    compiles = meter.programs - programs0
    counter_delta("window.ec_registry", perf0, perf.dump(), facts)
    for key in COUNTERS:
        facts.setdefault(f"window.ec_registry.{key}", 0)

    # ---- correct: outside the window ----------------------------------------
    t_check = time.perf_counter()
    parity_differs = isa_differs = recovered_differs = checked = 0
    lanes_differing = lanes = tail_nonzero = tails = 0
    for rec in kept["encode"]:
        objects = [pool[rec["payload"]][p] for p in sample]
        want = ref_objects.parity_of_objects(ref_profile, objects)
        for got, obj, par in zip(rec["sample"], objects, want):
            parity_differs += int((got != par).any(axis=1).sum()) \
                if got.shape == par.shape else m
            host_chunks = isa.encode(set(range(n)), obj.tobytes())
            isa_differs += sum(
                not np.array_equal(got[r], host_chunks[k + r])
                for r in range(m))
        checked += len(sample)
    if whole is not None:
        want = ref_objects.parity_of_objects(ref_profile,
                                             pool[whole["payload"]])
        lanes_differing = sum(not np.array_equal(got, par)
                              for got, par in zip(whole["out"], want))
        lanes = len(want)
    tail_from = "no decode"
    if tail is None and done:
        # no timed decode erased the last data chunk: one more, untimed
        erased = [k - 1, n - 1]
        out = codec.decode_objects(erased, maps[0])
        tail = {"index": None, "payload": 0, "erased": erased,
                "sample": [out[p][0].copy() for p in sample]}
    if tail is not None:
        tail_from = (f"timed op {tail['index']}" if tail["index"] is not None
                     else "an untimed decode after the window (no timed "
                          "decode erased that chunk)")
        for got, p in zip(tail["sample"], sample):
            tail_nonzero += ref_objects.tail_nonzero(k, placed[p], k - 1, got)
            tails += 1
    for rec in kept["decode"]:
        given = [maps[rec["payload"]][p] for p in sample]
        objects = [pool[rec["payload"]][p] for p in sample]
        # the decode's inputs were made by the program in set-up: the
        # sampled objects' parity is held to the reference here
        want = ref_objects.parity_of_objects(ref_profile, objects)
        for got, stripe, par in zip(rec["sample"], given, want):
            parity_differs += int((stripe[k:] != par).any(axis=1).sum())
            lost = ref.recovered(ref_profile, stripe, rec["erased"])
            recovered_differs += int(
                ((got != lost) | (got != stripe[rec["erased"]]))
                .any(axis=1).sum()) if got.shape == lost.shape \
                else len(rec["erased"])
        checked += len(sample)
    sampled = {kind: [rec["index"] for rec in recs]
               for kind, recs in kept.items()}
    whole_sample = all(len(recs) == quota[kind]
                       for kind, recs in kept.items())
    correct = (done > 0 and failed == 0 and whole_sample and lanes > 0
               and tails > 0 and parity_differs == 0 and isa_differs == 0
               and recovered_differs == 0 and lanes_differing == 0
               and tail_nonzero == 0)
    say(f"correct={correct}: parity_differs {parity_differs} (limit 0), "
        f"isa_differs {isa_differs} (limit 0), recovered_differs "
        f"{recovered_differs} (limit 0) over {len(sample)} objects "
        f"({int(mix['check_objects_per_size'])} of every size) of each of "
        f"the ops {sampled} of the window's {done} against the reference "
        f"and the host isa plugin; lanes_differing {lanes_differing} "
        f"(limit 0) of {lanes} objects of one whole encode against the "
        f"reference's product; tail_nonzero {tail_nonzero} (limit 0) bytes "
        f"past the end of {tails} objects in chunk {k - 1} as recovered by "
        f"{tail_from}; the sample "
        f"{'holds' if whole_sample else 'LACKS'} {quota['encode']} encodes "
        f"and {quota['decode']} decodes; in "
        f"{time.perf_counter() - t_check:.1f}s")

    w = {key.removeprefix("window.ec_registry."): val
         for key, val in facts.items()
         if key.startswith("window.ec_registry.")}
    mibps = done * op_bytes / MIB / window_s
    if done:
        for kind, ms in op_ms.items():
            if ms:
                say(f"{kind}s in window: {len(ms)}, {min(ms):.1f} / "
                    f"{statistics.median(ms):.1f} / {statistics.fmean(ms):.1f}"
                    f" / {percentile(ms, 95):.1f} / {max(ms):.1f} ms (min / "
                    f"median / mean / p95 / max); "
                    f"{op_bytes / MIB / (statistics.fmean(ms) / 1e3):.1f} "
                    f"MiB/s of user bytes while one runs")
        say(f"ops in window: {done} of {count} objects, {op_bytes} bytes "
            f"({len(op_ms['encode'])} encodes, {len(op_ms['decode'])} "
            f"decodes with the {failed} that failed, whole cycles of "
            f"{len(cycle)}), {mibps:.1f} MiB/s = "
            f"{mibps / 1024:.3f} GiB/s of user bytes, "
            f"{mibps / host['host_isa_mibps']:.2f}x the host isa plugin "
            f"and {mibps / host['host_native_mibps']:.2f}x native/gf8.cc "
            f"on one thread; compiles_in_window {compiles} (must be 0)")
        say(f"the tool's line: {window_s:.6f}\t{done * op_bytes // 1024}")
    say("ec_registry over the window: " + ", ".join(
        f"{key} {val}" for key, val in sorted(w.items())))

    flatten("config", cfg, facts)
    facts.update({f"run.{key}": val for key, val in host.items()})
    facts.update({"check.parity_differs": parity_differs,
                  "check.isa_differs": isa_differs,
                  "check.recovered_differs": recovered_differs,
                  "check.lanes_differing": lanes_differing,
                  "check.tail_nonzero": tail_nonzero,
                  "check.objects": checked, "check.lanes": lanes,
                  "check.tails": tails,
                  "run.ops": done, "run.cpu_s": cpu_s,
                  "run.window_s": window_s,
                  "run.objects_a_call": count,
                  "run.lanes_a_call": lanes_a_call,
                  "run.compiles_in_window": compiles,
                  "window.encodes": len(op_ms["encode"]),
                  "window.decodes": len(op_ms["decode"])})
    end_to_end = {"setup_s": setup_s}
    if done:
        end_to_end.update(client_mibps=mibps, op_p95_ms=percentile(
            op_ms["encode"] + op_ms["decode"], 95))
    return {"correct": correct, "attempted": done + failed,
            "failed": failed, "end_to_end": end_to_end, "facts": facts,
            "trace_file": trace.file() if traced else None}
