"""``ceph_erasure_code_benchmark``'s encode and decode loops through the
registry's plugin, many stripes a call, host buffers in and host
buffers out.

Set-up makes the codec the way the tool does, by name and profile alone
(``registry().factory(plugin, profile)``), draws the pool of payloads
from ``--seed`` (``config.batch`` objects of ``config.object_bytes``
each, as ``(batch, k, stripe_unit)`` data chunks in host memory), times
the host plugin and the native GF product on the same profile (facts,
never metrics), encodes the pool through the plugin to make the decode
inputs, and runs one whole cycle as warm-up.  The window is a closed
loop, one op in flight, of the traffic's cycle:

    parity = codec.encode_batch(data, out_np=True)              # encode
    lost = codec.decode_stripes(erased, chunks, out_np=True)    # decode

and an op is finished when its last output byte is readable on the
host.  The erased ids of every decode are drawn from ``--seed``.  The
window closes at the end of the cycle in which ``--seconds`` pass.

``correct`` is decided after the window on what the timed ops returned:
``check_encodes`` encodes and one decode of each erasure count, drawn
from ``--seed``, keep ``check_stripes`` stripes of their output, which
are held to ``reference/codec.py`` (and the encodes to the host ``isa``
plugin's bytes); one of the kept encodes keeps its whole output, which
is held to the reference's product lane by lane.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from benchmark.harness import (HarnessError, Trace, counter_delta, elapsed,
                               flatten, percentile, say)
from benchmark.readers import layer_time, span_time
from benchmark.reference import codec as ref

MIB = float(1 << 20)
SECTIONS = ("registry.",)               # what a traced slice lists


def require_program() -> None:
    """What the cell needs of the program, asked before anything is
    built or compiled: the registry path's sections and counters and
    the batch decode from a chunk map."""
    from ceph_tpu.common import tracing
    from ceph_tpu.ec.plugins.tpu import ErasureCodeTpu

    if "registry" not in tracing.SECTION_LAYERS:
        raise HarnessError("the program's registry path has no sections "
                           "(no 'registry' among tracing.SECTION_LAYERS)")
    if not hasattr(ErasureCodeTpu, "decode_stripes"):
        raise HarnessError("the tpu plugin has no batch decode from a "
                           "chunk map (ErasureCodeTpu.decode_stripes)")


def make_codec(profile: dict, plugin: str | None = None):
    """The plugin by name and profile alone, as the tool makes it."""
    from ceph_tpu.ec import registry

    return registry().factory(plugin or profile["plugin"], {
        "k": str(profile["k"]), "m": str(profile["m"]),
        "technique": profile["technique"]})


def payload(seed: int, index: int, cfg: dict) -> np.ndarray:
    """Payload ``index`` of the pool: ``batch`` objects as (batch, k,
    stripe_unit) data chunks (an object is its k chunks end to end)."""
    profile = cfg["profile"]
    shape = (int(cfg["batch"]), int(profile["k"]),
             int(profile["stripe_unit"]))
    words = np.random.default_rng([seed, 0xC0DEC, index]).integers(
        0, 1 << 64, int(np.prod(shape)) // 8, dtype=np.uint64)
    return words.view(np.uint8).reshape(shape)


def draw_erasures(seed: int, n: int):
    """An endless draw of erased ids for decodes, from the seed alone."""
    rng = np.random.default_rng([seed, 0xE7A5])

    def draw(count: int) -> list[int]:
        return sorted(int(e) for e in rng.choice(n, count, replace=False))
    return draw


def host_rates(isa, cfg: dict, mix: dict,
               data: np.ndarray) -> dict[str, float]:
    """MiB/s of user bytes of the host plugin (``isa``: numpy table
    lookups) on one thread, through the same registry, at the tool's
    shape (whole objects, the cell's mix of encodes and decodes) and at
    BASELINE.json config 1's (4 KiB chunks, encode), and of
    ``native/gf8.cc``'s product with the encode rows on the same bytes.
    Neither is Intel ISA-L."""
    from ceph_tpu import native

    profile = cfg["profile"]
    k, n = int(profile["k"]), int(profile["k"]) + int(profile["m"])
    want = set(range(n))
    size = int(cfg["object_bytes"])
    flat = data.reshape(-1)
    objects = [flat[i * size:(i + 1) * size].tobytes()
               for i in range(min(int(mix["host_objects_1m"]),
                                  data.shape[0]))]
    t0 = time.perf_counter()
    encoded = [isa.encode(want, obj) for obj in objects]
    t_enc = time.perf_counter() - t0
    counts = [e for kind, e in mix["cycle"] if kind == "decode"]
    t0 = time.perf_counter()
    for i, chunks in enumerate(encoded):
        # the cell's erasure counts in turn, the erased ids walking round
        lost = {(i + j) % n for j in range(counts[i % len(counts)])}
        isa.decode(want, {j: chunks[j] for j in range(n) if j not in lost})
    t_dec = time.perf_counter() - t0
    small = k * 4096
    few = min(int(mix["host_objects_4k"]), flat.size // small)
    t0 = time.perf_counter()
    for i in range(few):
        isa.encode(want, flat[i * small:(i + 1) * small].tobytes())
    t_4k = time.perf_counter() - t0
    stripes = data[:int(mix["host_native_stripes"])]
    rows = np.ascontiguousarray(stripes.transpose(1, 0, 2)).reshape(k, -1)
    t0 = time.perf_counter()
    native.gf8_matmul(isa.encode_matrix[k:], rows)
    t_nat = time.perf_counter() - t0
    done = len(objects) * size / MIB
    return {"host_isa_mibps": 2 * done / (t_enc + t_dec),
            "host_isa_encode_mibps": done / t_enc,
            "host_isa_decode_mibps": done / t_dec,
            "host_isa_4k_chunks_encode_mibps": few * small / MIB / t_4k,
            "host_native_mibps": rows.size / MIB / t_nat}


def report_ops(sl: dict, ops: list[tuple[str, list[int]]]) -> None:
    """The slice's sections op by op (``span_time.report`` gives them
    summed): an op's pieces end with its ``registry.copy_out``, what no
    section covers between two ops goes to the later one."""
    split: list[dict[str, float]] = [{}]
    for start, end, name in sl["pieces"]:
        label = (name or span_time.UNCOVERED).removeprefix(SECTIONS[0])
        split[-1][label] = split[-1].get(label, 0.0) + end - start
        if name == "registry.copy_out":
            split.append({})
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
    n, unit, batch = k + m, int(profile["stripe_unit"]), int(cfg["batch"])
    cycle = [(kind, int(e)) for kind, e in mix["cycle"]]
    op_bytes = batch * int(cfg["object_bytes"])
    ref_profile = {"k": k, "m": m, "technique": profile["technique"]}

    # ---- set-up: the codec, the pool, the host's rates, the decode inputs ----
    codec, isa = make_codec(profile), make_codec(profile, "isa")
    perf = codec.perf
    t0 = time.perf_counter()
    pool = [payload(seed, i, cfg) for i in range(int(mix["pool_payloads"]))]
    if (codec.get_chunk_size(int(cfg["object_bytes"])) != unit
            or ref.chunk_bytes(k, int(cfg["object_bytes"])) != unit
            or not np.array_equal(ref.chunks_of(k, pool[0][0].tobytes()),
                                  pool[0][0])):
        raise HarnessError(
            f"stripe_unit {unit} is not the chunk of a "
            f"{cfg['object_bytes']}-byte object at k={k}: a payload's "
            f"(k, stripe_unit) rows are not the tool's chunks of an object")
    say(f"pool of {len(pool)} payloads of {batch} x {cfg['object_bytes']} "
        f"bytes ({op_bytes / MIB:.0f} MiB each) drawn from the seed in "
        f"{time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    host = host_rates(isa, cfg, mix, pool[0])
    say(f"host side of the same profile on one thread in "
        f"{time.perf_counter() - t0:.1f}s (facts, not metrics; neither is "
        f"Intel ISA-L): plugin isa (numpy table lookups) at "
        f"{cfg['object_bytes']}-byte objects {host['host_isa_mibps']:.1f} "
        f"MiB/s over the cell's mix (encode "
        f"{host['host_isa_encode_mibps']:.1f}, decode "
        f"{host['host_isa_decode_mibps']:.1f}), at 4 KiB chunks encode "
        f"{host['host_isa_4k_chunks_encode_mibps']:.1f}; native/gf8.cc's "
        f"product with the encode rows {host['host_native_mibps']:.1f} MiB/s")
    t0 = time.perf_counter()
    chunks = [np.concatenate([data, codec.encode_batch(data, out_np=True)],
                             axis=1) for data in pool]
    say(f"decode inputs: the pool encoded through the plugin in "
        f"{time.perf_counter() - t0:.1f}s ({meter.hits} cache hits, "
        f"{meter.misses} misses, {meter.programs} backend compiles so far)")

    draw = draw_erasures(seed, n)
    pick = np.random.default_rng([seed, 0xC0FFEE]).random(1 << 16)
    stripe_ids = np.sort(np.random.default_rng([seed, 0x57A1]).choice(
        batch, min(int(mix["check_stripes"]), batch), replace=False))
    quota = {("encode", 0): int(mix["check_encodes"])}
    quota.update({key: 1 for key in cycle if key[0] == "decode"})
    kept: dict[tuple, list[dict]] = {key: [] for key in quota}
    op_ms: dict[tuple, list[float]] = {key: [] for key in cycle}
    ran: list[tuple[str, list[int]]] = []      # every kept op, in order
    handed: dict[int, int] = {}     # output rows -> stripes handed in
    whole: dict | None = None       # the lowest-ranked encode, all of it
    failed = done = place = 0       # place: the next op's in the cycle

    def one_op(keep: bool) -> None:
        """The next op of the cycle, as the tool's loop runs it."""
        nonlocal failed, done, place, whole
        kind, count = cycle[place % len(cycle)]
        which = place % len(pool)
        place += 1
        erased = draw(count) if kind == "decode" else []
        t1 = time.perf_counter()
        try:
            if kind == "encode":
                out = codec.encode_batch(pool[which], out_np=True)
            else:
                out = codec.decode_stripes(erased, chunks[which], out_np=True)
        except Exception as e:          # the op failed: counted, with its time
            out = None
            if keep:
                failed += 1
                if failed <= 3:
                    say(f"op {done + failed} ({kind} {erased}) raised {e!r}")
        dt = time.perf_counter() - t1
        if not keep:
            return
        op_ms[(kind, count)].append(1e3 * dt)
        ran.append((kind, erased))
        if out is None:
            return
        index, done = done, done + 1
        rows = count or m
        handed[rows] = handed.get(rows, 0) + batch
        rank = pick[index % len(pick)]
        if kind == "encode" and (whole is None or rank < whole["rank"]):
            whole = {"rank": rank, "payload": which, "out": out}
        mine = kept[(kind, count)]
        if len(mine) == quota[(kind, count)] and rank >= mine[-1]["rank"]:
            return
        mine.append({"rank": rank, "index": index, "payload": which,
                     "erased": erased, "sample": out[stripe_ids]})
        mine.sort(key=lambda rec: rec["rank"])
        del mine[quota[(kind, count)]:]

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
        if traced and done + failed == len(cycle):
            # the second cycle onwards: a slice of whole cycles
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
            for rows, stripes in handed.items():
                facts[f"slice.codec.stripes_r{rows}"] = \
                    stripes - before.get(rows, 0)
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

    # ---- correct: outside the window ----------------------------------------
    t_check = time.perf_counter()
    parity_differs = isa_differs = recovered_differs = checked = 0
    lanes_differing = lanes = 0
    for rec in kept[("encode", 0)]:
        data = pool[rec["payload"]][stripe_ids]
        want = ref.parity_of(ref_profile, data)
        parity_differs += int((rec["sample"] != want).any(axis=2).sum())
        for got, stripe in zip(rec["sample"], data):
            host_chunks = isa.encode(set(range(n)), stripe.tobytes())
            isa_differs += sum(
                not np.array_equal(got[r], host_chunks[k + r])
                for r in range(m))
        checked += len(stripe_ids)
    if whole is not None:
        want = ref.parity_of(ref_profile, pool[whole["payload"]])
        lanes_differing = int((whole["out"] != want).any(axis=(1, 2)).sum())
        lanes = batch
    for rec in (rec for key, recs in kept.items() if key[0] == "decode"
                for rec in recs):
        given = chunks[rec["payload"]][stripe_ids]
        # the decode's inputs were made by the program in set-up: the
        # sampled stripes' parity is held to the reference here
        parity_differs += int((given[:, k:] != ref.parity_of(
            ref_profile, given[:, :k])).any(axis=2).sum())
        for got, stripe in zip(rec["sample"], given):
            want = ref.recovered(ref_profile, stripe, rec["erased"])
            recovered_differs += int(
                ((got != want) | (got != stripe[rec["erased"]]))
                .any(axis=1).sum())
        checked += len(stripe_ids)
    sampled = {key: [rec["index"] for rec in recs]
               for key, recs in kept.items()}
    whole_sample = all(len(recs) == quota[key] for key, recs in kept.items())
    correct = (done > 0 and failed == 0 and whole_sample and lanes > 0
               and parity_differs == 0 and isa_differs == 0
               and recovered_differs == 0 and lanes_differing == 0)
    say(f"correct={correct}: parity_differs {parity_differs} (limit 0), "
        f"isa_differs {isa_differs} (limit 0), recovered_differs "
        f"{recovered_differs} (limit 0) over {len(stripe_ids)} stripes of "
        f"each of the ops {sampled} of the window's {done} against the "
        f"reference and the host isa plugin; lanes_differing "
        f"{lanes_differing} (limit 0) of {lanes} stripes of one whole "
        f"encode against the reference's product; the sample "
        f"{'holds' if whole_sample else 'LACKS'} {quota[('encode', 0)]} "
        f"encodes and a decode of each erasure count; in "
        f"{time.perf_counter() - t_check:.1f}s")

    w = {key.removeprefix("window.ec_registry."): val
         for key, val in facts.items()
         if key.startswith("window.ec_registry.")}
    mibps = done * op_bytes / MIB / window_s
    by_kind = {kind: [t for key, ms in op_ms.items() if key[0] == kind
                      for t in ms] for kind in ("encode", "decode")}
    if done:
        for name, ms in [*((kind + "s", ms) for kind, ms in by_kind.items()),
                         *((f"decodes of {count} erased", ms)
                           for (kind, count), ms in op_ms.items()
                           if kind == "decode")]:
            if ms:
                say(f"{name} in window: {len(ms)}, {min(ms):.1f} / "
                    f"{statistics.median(ms):.1f} / {statistics.fmean(ms):.1f}"
                    f" / {percentile(ms, 95):.1f} / {max(ms):.1f} ms (min / "
                    f"median / mean / p95 / max); "
                    f"{op_bytes / MIB / (statistics.fmean(ms) / 1e3):.1f} "
                    f"MiB/s of user bytes while one runs")
        say(f"ops in window: {done} of {batch} x {cfg['object_bytes']} bytes "
            f"({len(by_kind['encode'])} encodes, {len(by_kind['decode'])} "
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
                  "check.stripes": checked, "check.lanes": lanes,
                  "run.ops": done, "run.cpu_s": cpu_s,
                  "run.window_s": window_s,
                  "run.compiles_in_window": compiles,
                  "window.encodes": len(by_kind["encode"]),
                  "window.decodes": len(by_kind["decode"])})
    end_to_end = {"setup_s": setup_s}
    if done:
        end_to_end.update(client_mibps=mibps, op_p95_ms=percentile(
            by_kind["encode"] + by_kind["decode"], 95))
    return {"correct": correct, "attempted": done + failed,
            "failed": failed, "end_to_end": end_to_end, "facts": facts,
            "trace_file": trace.file() if traced else None}
