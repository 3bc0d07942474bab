"""An expansion stepped through OSDMap epochs, one at a time: what the
monitor, every OSD and every client's Objecter do at each weight step
while an operator brings new racks in (or drains them).

Set-up builds the configuration's 1000-OSD OSDMap with its two pools,
adds the new racks at CRUSH weight 0 through the program's own ``osd
crush`` command code (``crush.builder.crush_command``: add-bucket,
move, add; one structure-changing epoch), prepares the cycle's 32
CRUSH maps through the same code (16 ``reweight-subtree`` steps up on
the five racks, 16 back down; the five commands of a step one epoch)
and applies one whole cycle as warm-up.  The window is a closed loop of
epochs in the cycle's order, entered at a step drawn from ``--seed``:

    prev = osdmap.peek_placement_cache()
    osdmap.apply_incremental(inc)       # carries the step's CRUSH map
    cur = osdmap.placement_cache()      # PGMapping.build: both pools
    moved = cur.delta(prev)

and an epoch is finished when its delta is in hand.  The window closes
at the end of the cycle in which ``--seconds`` pass, so every run
applies whole cycles of the same 32 epochs.

After the window ``check_epochs`` of its epochs, drawn from the seed,
are held to ``reference/crush_expand.py``: both pools' whole tables (up
and acting of every PG) and the delta against the epoch before.
"""

from __future__ import annotations

import dataclasses
import statistics
import time

import numpy as np

from benchmark.harness import (HarnessError, Trace, counter_delta, elapsed,
                               flatten, percentile, say)
from benchmark.readers import layer_time, span_time
from benchmark.reference import crush_expand as ref

ROOT = -1
STAGES = ("launch", "ingest", "delta")
SECTIONS = ("placement.", "device_wait.")      # what a traced slice lists


def require_program() -> None:
    """What the cell needs of the program, asked before anything is
    built or compiled: the ``osd crush`` command code, bucket tables
    that are operands of the mapper's program (with constants in their
    place every weight step is a compile of half a minute)."""
    import jax
    from ceph_tpu.crush import builder
    from ceph_tpu.crush.vectorized import VectorCrush

    commands = getattr(builder, "CRUSH_COMMANDS", None)
    if commands is None or not hasattr(builder, "crush_command"):
        raise HarnessError("the program has no osd crush command code "
                           "(crush.builder.crush_command)")
    missing = {"osd crush add-bucket", "osd crush move", "osd crush add",
               "osd crush reweight-subtree"} - set(commands)
    if missing:
        raise HarnessError(f"the program lacks {sorted(missing)}")
    leaves = jax.tree_util.tree_leaves(
        VectorCrush(builder.build_two_level_map(2, 2), 0))
    if not leaves or not all(isinstance(x, jax.Array) for x in leaves):
        raise HarnessError(
            "the mapper's bucket tables are constants of its compiled "
            "program, not operands (VectorCrush is no pytree of device "
            "tables): every weight step would compile")


# -- the deployment, on both sides ---------------------------------------------

def expansion_commands(cfg: dict) -> list[tuple[str, dict]]:
    """The ``osd crush`` commands that add the new racks at weight 0,
    in the order the configuration states."""
    tree, grow = cfg["tree"], cfg["expansion"]
    rows, racks = tree["fanouts"][0], tree["fanouts"][1]
    osd = int(np.prod(tree["fanouts"]))
    out: list[tuple[str, dict]] = []
    for r in range(rows):
        rack = f"rack{r}-{racks}"
        out.append(("osd crush add-bucket", {"name": rack, "type": "rack"}))
        for h in range(int(grow["hosts_per_rack"])):
            host = f"host{r}-{racks}-{h}"
            out.append(("osd crush add-bucket",
                        {"name": host, "type": "host"}))
            out.append(("osd crush move",
                        {"name": host, "loc": {"rack": rack}}))
            for _ in range(int(grow["osds_per_host"])):
                out.append(("osd crush add", {
                    "name": f"osd.{osd}", "loc": {"host": host},
                    "weight": int(grow["initial_weight"]) / 0x10000}))
                osd += 1
        out.append(("osd crush move", {"name": rack,
                                       "loc": {"row": f"row{r}"}}))
    return out


def new_racks(cfg: dict) -> list[str]:
    rows, racks = cfg["tree"]["fanouts"][:2]
    return [f"rack{r}-{racks}" for r in range(rows)]


def step_weight(cfg: dict, step: int) -> int:
    grow = cfg["expansion"]
    return int(grow["target_weight"]) * step // int(grow["steps"])


def cycle_steps(cfg: dict) -> list[int]:
    """The weight step each epoch of a cycle brings: up, then down."""
    n = int(cfg["expansion"]["steps"])
    return list(range(1, n + 1)) + list(range(n - 1, -1, -1))


def reference_tree(cfg: dict) -> ref.WeightedTree:
    """The reference's map after the expansion epoch (new OSDs at
    weight 0), grown by its own edits in the commands' order."""
    tree_cfg = cfg["tree"]
    tree = ref.WeightedTree.uniform(tree_cfg["fanouts"],
                                    int(tree_cfg["osd_weight"]),
                                    tree_cfg["names"])
    for cmd, args in expansion_commands(cfg):
        if cmd == "osd crush add-bucket":
            tree.add_bucket(args["name"], int(tree_cfg["types"][args["type"]]))
        elif cmd == "osd crush move":
            bid = tree.by_name(args["name"])["id"]
            (parent,) = args["loc"].values()
            tree.insert(bid, tree.weight_of(bid), parent)
        else:
            (parent,) = args["loc"].values()
            tree.insert(int(args["name"][4:]),
                        int(round(args["weight"] * 0x10000)), parent)
    return tree


def osd_weights(cfg: dict, n_osds: int) -> np.ndarray:
    """The 16.16 in/out vector CRUSH is given: 0 for the OSDs drawn
    out from ``map_seed``, 0x10000 for every other."""
    state = cfg["osd_state"]
    old = int(np.prod(cfg["tree"]["fanouts"]))
    weights = np.full(n_osds, 0x10000, np.int64)
    weights[np.random.default_rng(int(state["map_seed"])).choice(
        old, int(state["out"]), replace=False)] = 0
    return weights


def build_program_map(cfg: dict):
    """The program's CrushMap of the cluster before the expansion,
    from the reference's description of it: buckets as data with their
    names and type names, both rules, the stated tunables."""
    from ceph_tpu.crush.builder import erasure_rule, replicated_rule
    from ceph_tpu.crush.types import Bucket, CrushMap

    tree_cfg = cfg["tree"]
    cm = CrushMap()
    cm.type_names = {int(t): n for n, t in tree_cfg["types"].items()}
    for key, val in cfg["tunables"].items():
        setattr(cm.tunables, key, val)
    base = ref.WeightedTree.uniform(tree_cfg["fanouts"],
                                    int(tree_cfg["osd_weight"]),
                                    tree_cfg["names"])
    for b in base.as_buckets():
        cm.add_bucket(Bucket(id=b["id"], type=b["type"], items=b["items"],
                             item_weights=b["item_weights"]), b["name"])
    host = int(tree_cfg["types"]["host"])
    for pool in cfg["pools"]:
        make = erasure_rule if pool["type"] == "erasure" else replicated_rule
        cm.add_rule(make(int(pool["rule"]["id"]), ROOT, choose_type=host,
                         leaf=True))
    return cm


def build_osdmap(cm, cfg: dict, weights: np.ndarray):
    """The OSDMap of the old cluster, made the way a consumer gets it:
    one incremental with the OSDs (all up, the drawn ones out), the two
    pools and the CRUSH map."""
    from ceph_tpu.mon.osdmap import (POOL_TYPE_ERASURE, POOL_TYPE_REPLICATED,
                                     Incremental, OSDMap, PoolSpec,
                                     crush_to_dict)

    old = int(np.prod(cfg["tree"]["fanouts"]))
    inc = Incremental(epoch=1, new_max_osd=old, new_crush=crush_to_dict(cm))
    for osd in range(old):
        inc.new_up[osd] = None
        if weights[osd] == 0:
            inc.new_out.append(osd)
    for pool in cfg["pools"]:
        erasure = pool["type"] == "erasure"
        inc.new_pools[int(pool["pool_id"])] = dataclasses.asdict(PoolSpec(
            pool_id=int(pool["pool_id"]), name=pool["name"],
            type=POOL_TYPE_ERASURE if erasure else POOL_TYPE_REPLICATED,
            size=int(pool["size"]),
            min_size=int(pool["k"]) + 1 if erasure else 2,
            pg_num=int(pool["pg_num"]), pgp_num=int(pool["pg_num"]),
            crush_rule=int(pool["rule"]["id"])))
    osdmap = OSDMap()
    osdmap.apply_incremental(inc)
    return osdmap


def table_arrays(pm, cfg: dict) -> dict[int, tuple[np.ndarray, np.ndarray]]:
    """A PGMapping's rows as (pg_num, size) arrays per pool, up and
    acting, a replicated row padded with the reference's ``HOLE``; an
    erasure row that is not ``size`` long differs everywhere."""
    out = {}
    for pool in cfg["pools"]:
        pid, size = int(pool["pool_id"]), int(pool["size"])
        both = np.full((2, int(pool["pg_num"]), size), ref.HOLE, np.int64)
        for pg in range(both.shape[1]):
            for rows, row in zip(both, pm.lookup(pid, pg)):
                if pool["type"] == "erasure" and len(row) != size:
                    rows[pg] = ref.ITEM_UNDEF
                else:
                    rows[pg, :len(row)] = row
        out[pid] = (both[0], both[1])
    return out


def timer_sums(dump: dict) -> dict[str, float]:
    """Seconds so far in each stage timer of a ``placement_cache`` dump."""
    return {k: float((dump.get(k) or {}).get("sum", 0.0)) for k in STAGES}


def run(cell, seed: int, seconds: float, traced: bool, meter) -> dict:
    require_program()
    from ceph_tpu.crush.builder import crush_command
    from ceph_tpu.mon.osdmap import Incremental, crush_to_dict

    cfg, mix = cell.config, cell.traffic
    pgs = sum(int(p["pg_num"]) for p in cfg["pools"])
    host_type = int(cfg["tree"]["types"]["host"])
    racks, steps = new_racks(cfg), cycle_steps(cfg)

    # ---- set-up: the old cluster, the expansion epoch, the cycle's maps -----
    t0 = time.perf_counter()
    cm = build_program_map(cfg)
    commands = expansion_commands(cfg)
    grown = sum(cmd == "osd crush add" for cmd, _ in commands)
    n_osds = int(np.prod(cfg["tree"]["fanouts"])) + grown
    weights = osd_weights(cfg, n_osds)
    osdmap = build_osdmap(cm, cfg, weights)
    perf = osdmap.placement_perf
    for cmd, args in commands:
        cm = crush_command(cm, cmd, args)
    inc = Incremental(epoch=osdmap.epoch + 1, new_max_osd=n_osds,
                      new_crush=crush_to_dict(cm))
    for osd in range(n_osds - grown, n_osds):
        inc.new_up[osd] = None
        inc.new_in.append(osd)
        inc.new_weights[osd] = 0x10000
    osdmap.apply_incremental(inc)
    step_maps = {}
    for step in sorted(set(steps)):
        stepped = cm
        for rack in racks:
            stepped = crush_command(
                stepped, "osd crush reweight-subtree",
                {"name": rack, "weight": step_weight(cfg, step) / 0x10000})
        step_maps[step] = crush_to_dict(stepped)
    say(f"{n_osds} OSDs ({grown} new in {len(racks)} racks at weight "
        f"{cfg['expansion']['initial_weight']}, {int((weights == 0).sum())} "
        f"out), {pgs} PGs in {len(cfg['pools'])} pools; {len(commands)} "
        f"osd crush commands and the cycle's {len(step_maps)} maps through "
        f"crush_command in {time.perf_counter() - t0:.1f}s")

    kept: list[dict] = []           # the epochs to check, lowest picks
    pick = np.random.default_rng([seed, 0xC0FFEE]).random(1 << 16)
    epoch_ms: list[float] = []
    moved_pgs: list[int] = []
    stale = failed = 0
    position = [0]                  # the next epoch's place in the cycle

    def one_epoch(keep: bool) -> None:
        """The next epoch of the cycle, as a map consumer runs it."""
        nonlocal stale
        place = position[0]
        step = steps[place]
        t1 = time.perf_counter()
        prev = osdmap.peek_placement_cache()
        osdmap.apply_incremental(Incremental(epoch=osdmap.epoch + 1,
                                             new_crush=step_maps[step]))
        cur = osdmap.placement_cache()
        moved = cur.delta(prev, perf=perf)
        dt = time.perf_counter() - t1
        position[0] = (place + 1) % len(steps)
        if not keep:
            return
        epoch_ms.append(1e3 * dt)
        moved_pgs.append(len(moved))
        stale += cur.epoch != osdmap.epoch or prev is None \
            or prev.epoch != osdmap.epoch - 1
        index = len(epoch_ms) - 1
        kept.append({"index": index, "step": step,
                     "before": steps[place - 1], "table": cur,
                     "moved": moved})
        kept.sort(key=lambda rec: pick[rec["index"] % len(pick)])
        del kept[int(mix["check_epochs"]):]

    t0 = time.perf_counter()
    osdmap.placement_cache()
    say(f"both programs compiled or loaded and the first table built in "
        f"{time.perf_counter() - t0:.1f}s ({meter.hits} cache hits, "
        f"{meter.misses} misses, {meter.programs} backend compiles so far)")
    t0, programs0 = time.perf_counter(), meter.programs
    start = int(np.random.default_rng([seed, 0x57A27]).integers(len(steps)))
    for _ in range(int(mix["warmup_cycles"]) * len(steps) + start):
        one_epoch(keep=False)
    say(f"warm-up: {int(mix['warmup_cycles'])} cycle(s) of {len(steps)} "
        f"epochs and {start} more to the starting step in "
        f"{time.perf_counter() - t0:.1f}s, {meter.programs - programs0} "
        f"backend compiles in them (a weight step must compile nothing)")

    # ---- the window ---------------------------------------------------------
    facts: dict = {}
    trace = Trace(cell.name) if traced else None
    t_open = time.perf_counter()
    setup_s = elapsed()
    cpu0, programs0, perf0 = time.process_time(), meter.programs, perf.dump()
    try:
        while (time.perf_counter() - t_open < seconds
               or len(epoch_ms) % len(steps)):
            if traced and len(epoch_ms) == 1:
                # the second epoch onwards: a slice of whole epochs
                t1 = time.perf_counter()
                trace.start()
                say(f"profiler started in {time.perf_counter() - t1:.2f}s")
                with trace.mark():
                    for _ in range(int(mix["trace_epochs"])):
                        one_epoch(keep=True)
                t1 = time.perf_counter()
                trace.stop()
                say(f"profiler stopped and trace written in "
                    f"{time.perf_counter() - t1:.2f}s")
                facts["slice.epochs"] = int(mix["trace_epochs"])
                # the table's sections against the device's idle gaps
                sl = layer_time.load(trace.file(), SECTIONS)
                if sl is not None:
                    span_time.report(sl, "placement.apply")
                continue
            one_epoch(keep=True)
    except Exception as e:              # the epoch raised: the map is unknown
        failed += 1
        say(f"epoch {len(epoch_ms)} of the window raised {e!r}: window closed")
    window_s = time.perf_counter() - t_open
    cpu_s = time.process_time() - cpu0
    compiles = meter.programs - programs0
    epochs = len(epoch_ms)
    perf1 = perf.dump()
    counter_delta("window.placement_cache", perf0, perf1, facts)
    stage_s = {k: v - timer_sums(perf0)[k]
               for k, v in timer_sums(perf1).items()}

    # ---- correct: outside the window ----------------------------------------
    t_check = time.perf_counter()
    tree = reference_tree(cfg)
    up = np.ones(n_osds, bool)
    tables: dict[int, dict] = {}

    def want_table(step: int) -> dict[int, np.ndarray]:
        if step not in tables:
            for rack in racks:
                tree.reweight_subtree(rack, step_weight(cfg, step))
            tables[step] = ref.table(tree, ROOT, cfg["pools"], host_type,
                                     weights, up)
        return tables[step]

    pgs_differing = delta_differing = checked = holes = 0
    for rec in kept:
        want = want_table(rec["step"])
        got = table_arrays(rec["table"], cfg)
        for pid, rows in want.items():
            got_up, got_acting = got[pid]
            pgs_differing += int(((got_up != rows).any(axis=1)
                                  | (got_acting != rows).any(axis=1)).sum())
            checked += rows.shape[0]
            holes += int((rows == ref.HOLE).sum())
        changed = ref.table_diff(want_table(rec["before"]), want)
        moved = [(int(pid), int(pg)) for pid, pg in rec["moved"]]
        delta_differing += len(set(moved) ^ changed) \
            + len(moved) - len(set(moved))
    w = {k.removeprefix("window.placement_cache."): v
         for k, v in facts.items() if k.startswith("window.placement_cache.")}
    launches = int(w.get("fused_launches", 0))
    correct = (epochs > 0 and failed == 0 and checked > 0
               and pgs_differing == 0 and delta_differing == 0
               and stale == 0 and int(w.get("scalar_pools", 0)) == 0)
    say(f"correct={correct}: pgs_differing {pgs_differing} (limit 0) of "
        f"{checked}, delta_differing {delta_differing} (limit 0), in epochs "
        f"{sorted(rec['index'] for rec in kept)} of the window's {epochs} "
        f"(steps {[rec['step'] for rec in kept]}) against the reference, in "
        f"{time.perf_counter() - t_check:.1f}s; stale_tables {stale} "
        f"(limit 0); scalar_pools {int(w.get('scalar_pools', 0))} (must be "
        f"0); holes the reference leaves {holes}")
    if epochs:
        rest_s = sum(epoch_ms) / 1e3 - sum(stage_s.values())
        say(f"epochs in window: {epochs} of {pgs} PGs, {min(epoch_ms):.1f} / "
            f"{statistics.median(epoch_ms):.1f} / "
            f"{statistics.fmean(epoch_ms):.1f} / "
            f"{percentile(epoch_ms, 95):.1f} / {max(epoch_ms):.1f} ms (min / "
            f"median / mean / p95 / max); mean by stage: " + ", ".join(
                f"{k} {1e3 * v / epochs:.2f}" for k, v in stage_s.items())
            + f", rest {1e3 * rest_s / epochs:.2f} ms; PGs moved an epoch "
            f"{min(moved_pgs)} to {max(moved_pgs)}; compiles_in_window "
            f"{compiles} (must be 0)")
        facts.update({f"window.{k}_s": v for k, v in stage_s.items()})
        facts.update({"window.rest_s": rest_s,
                      "window.fused_launches": launches,
                      "window.launches_reused":
                          launches - int(w.get("programs_built", 0))})
    say("placement_cache over the window: " + ", ".join(
        f"{k} {v}" for k, v in sorted(w.items())
        if not k.endswith("_per_s")))            # a gauge, not a count

    flatten("config", cfg, facts)
    facts.update({"check.pgs_differing": pgs_differing,
                  "check.delta_differing": delta_differing,
                  "check.stale_tables": stale,
                  "run.ops": epochs, "run.cpu_s": cpu_s,
                  "run.window_s": window_s,
                  "run.compiles_in_window": compiles,
                  "window.epochs": epochs})
    end_to_end = {"setup_s": setup_s}
    if epochs:
        end_to_end.update(op_p95_ms=percentile(epoch_ms, 95),
                          mappings_per_s=epochs * pgs / window_s)
    return {"correct": correct, "attempted": epochs + failed,
            "failed": failed, "end_to_end": end_to_end, "facts": facts,
            "trace_file": trace.file() if traced else None}
