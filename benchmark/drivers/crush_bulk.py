"""Back-to-back bulk placement: ``VectorCrush.map_pgs`` over one bounded
launch at a time, one pool's PGs a call, as ``mon/pg_mapping.py``
consumes it (placement seeds up, OSD ids back on the host).

The map is built from the configuration's own description (the plain
reference's ``UniformTree``), handed to the program as plain buckets
and a rule.  After the window, every lane of ``check_calls`` of the
timed calls, drawn from the seed, is held to the reference mapper.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness import Trace, elapsed, flatten, say
from benchmark.reference.crush import ITEM_NONE, UniformTree, hash32_2


def build_program_map(tree: UniformTree, cfg: dict):
    """The program's CrushMap from the configuration: buckets as data,
    one replicated chooseleaf-firstn rule, the stated tunables."""
    from ceph_tpu.crush.builder import replicated_rule
    from ceph_tpu.crush.types import Bucket, CrushMap

    cm = CrushMap()
    for key, val in cfg["tunables"].items():
        setattr(cm.tunables, key, val)
    for b in tree.buckets():
        cm.add_bucket(Bucket(id=b["id"], type=b["type"], items=b["items"],
                             item_weights=b["item_weights"]),
                      f"b{b['id']}")
    cm.add_rule(replicated_rule(0, -1, choose_type=int(
        cfg["rule"]["failure_domain_type"]), leaf=True))
    return cm


def run(cell, seed: int, seconds: float, traced: bool, meter) -> dict:
    from ceph_tpu.crush.vectorized import VectorCrush

    cfg, mix = cell.config, cell.traffic
    lanes, replicas = int(mix["pg_num"]), int(cfg["rule"]["replicas"])
    tree = UniformTree(cfg["tree"]["fanouts"], int(cfg["tree"]["osd_weight"]))
    weights = [int(cfg["tree"]["osd_weight"])] * tree.n_osds
    mapper = VectorCrush(build_program_map(tree, cfg), 0)
    rng = np.random.default_rng([seed, 0xC205])
    order: list[int] = []

    def pps(pool: int) -> np.ndarray:
        """A pool's placement seeds, kept to the 31 bits map_pgs takes."""
        return (hash32_2(np.arange(lanes), pool) & np.uint32(0x7FFFFFFF)
                ).astype(np.int64)

    def seeds() -> np.ndarray:
        """The next pool's seeds.  Every run maps the same pools, in an
        order drawn from --seed and again from the top when all are
        done: how many passes of the mapper's retry loop a call needs
        depends on its seeds, so the work is the same for every seed."""
        if not order:
            order.extend(1 + rng.permutation(int(mix["pools"])))
        return pps(int(order.pop()))

    t0 = time.perf_counter()
    for _ in range(int(mix["warmup_calls"])):
        mapper.map_pgs(pps(0), replicas, weights)
    say(f"mapper for {tree.n_osds} OSDs compiled or loaded and warmed in "
        f"{time.perf_counter() - t0:.1f}s ({meter.hits} cache hits, "
        f"{meter.misses} misses)")

    # ---- the window ---------------------------------------------------------
    calls: list[tuple[np.ndarray, np.ndarray]] = []
    facts: dict = {}
    trace = Trace(cell.name) if traced else None
    t_open = time.perf_counter()
    setup_s = elapsed()
    cpu0, programs0 = time.process_time(), meter.programs
    call_ms = []
    while time.perf_counter() - t_open < seconds:
        if traced and len(calls) == 1:
            # the second call onwards: one steady slice of whole calls
            t1 = time.perf_counter()
            trace.start()
            say(f"profiler started in {time.perf_counter() - t1:.2f}s")
            with trace.mark():
                for _ in range(int(mix["trace_calls"])):
                    xs = seeds()
                    calls.append((xs, mapper.map_pgs(xs, replicas, weights)))
            t1 = time.perf_counter()
            trace.stop()
            say(f"profiler stopped and trace written in "
                f"{time.perf_counter() - t1:.2f}s")
            facts["slice.launches"] = int(mix["trace_calls"])
            continue
        xs = seeds()
        t1 = time.perf_counter()
        calls.append((xs, mapper.map_pgs(xs, replicas, weights)))
        call_ms.append(1e3 * (time.perf_counter() - t1))
    window_s = time.perf_counter() - t_open
    cpu_s = time.process_time() - cpu0
    compiles = meter.programs - programs0

    # ---- correct: outside the window ----------------------------------------
    t_check = time.perf_counter()
    pick = np.random.default_rng([seed, 0xC0FFEE]).choice(
        len(calls), size=min(int(mix["check_calls"]), len(calls)),
        replace=False)
    wrong = checked = 0
    for c in pick:
        xs, got = calls[int(c)]
        want = tree.map_pgs(xs, replicas, weights)
        wrong += int((np.asarray(got, np.int64) != want).any(axis=1).sum())
        checked += len(xs)
    unplaced = sum(int((got == ITEM_NONE).sum()) for _, got in calls)
    correct = checked > 0 and wrong == 0
    say(f"correct={correct}: lanes_differing {wrong} (limit 0) of {checked} "
        f"lanes in {len(pick)} of the window's {len(calls)} calls against "
        f"the reference mapper, in {time.perf_counter() - t_check:.1f}s; "
        f"replica slots left unplaced {unplaced}")
    say(f"calls in window: {len(calls)} of {lanes} lanes, untraced ones "
        f"{min(call_ms, default=0):.1f} to {max(call_ms, default=0):.1f} ms;"
        f" compiles_in_window {compiles} (must be 0)")

    flatten("config", cfg, facts)
    facts.update({"run.ops": len(calls), "run.cpu_s": cpu_s,
                  "run.window_s": window_s})
    return {"correct": correct, "attempted": len(calls), "failed": 0,
            "end_to_end": {"setup_s": setup_s,
                           "mappings_per_s": len(calls) * lanes / window_s},
            "facts": facts,
            "trace_file": trace.file() if traced else None}
