"""The ``osd crush`` commands: add-bucket, move, add, reweight,
reweight-subtree.  Each edits a copy of the CRUSH map and keeps every
ancestor's weight the sum of its children
(``crush.builder.crush_command``); the monitor commits the result as
``Incremental.new_crush``; an expansion issued through them reaches the
OSDs' and the client's tables.
"""

from __future__ import annotations

import asyncio

import pytest

from ceph_tpu.crush.builder import (CRUSH_COMMANDS, build_hierarchy,
                                    crush_command)
from ceph_tpu.crush.mapper import crush_do_rule
from ceph_tpu.crush.types import CRUSH_BUCKET_STRAW2
from ceph_tpu.mon.osdmap import crush_from_dict, crush_to_dict

W = 0x10000


def named_tree():
    """root -> 2 racks -> 3 hosts -> 2 osds, every bucket named."""
    cm = build_hierarchy([2, 3, 2])
    cm.type_names = {0: "osd", 1: "host", 2: "rack", 3: "root"}
    cm.bucket_names = {-1: "default"}
    for r, rack in enumerate(cm.buckets[-1].items):
        cm.bucket_names[rack] = f"rack{r}"
        for h, host in enumerate(cm.buckets[rack].items):
            cm.bucket_names[host] = f"host{r}-{h}"
    return cm


def sums_hold(cm) -> bool:
    """Every bucket's weight in its parent is the sum of its items'."""
    return all(w == cm.buckets[item].weight
               for b in cm.buckets.values()
               for item, w in zip(b.items, b.item_weights) if item < 0)


def snapshot(cm) -> dict:
    return crush_to_dict(cm)


def test_the_five_commands_are_upstreams_names():
    assert sorted(CRUSH_COMMANDS) == [
        "osd crush add", "osd crush add-bucket", "osd crush move",
        "osd crush reweight", "osd crush reweight-subtree"]


def test_add_bucket_makes_an_empty_straw2_bucket_under_no_parent():
    cm = named_tree()
    before = snapshot(cm)
    new = crush_command(cm, "osd crush add-bucket",
                        {"name": "rack2", "type": "rack"})
    assert snapshot(cm) == before                 # the map handed in stands
    bid = new.name_to_id("rack2")
    assert bid == min(cm.buckets) - 1             # the next free id
    b = new.buckets[bid]
    assert (b.type, b.alg, b.items, b.item_weights) == (
        2, CRUSH_BUCKET_STRAW2, [], [])
    assert new.holders(bid) == [] and sums_hold(new)


@pytest.mark.parametrize("args,why", [
    ({"name": "rack2", "type": "pod"}, "no bucket type"),
    ({"name": "rack2", "type": "osd"}, "no bucket type"),
    ({"name": "rack0", "type": "rack"}, "exists"),
])
def test_add_bucket_refuses(args, why):
    with pytest.raises(ValueError, match=why):
        crush_command(named_tree(), "osd crush add-bucket", args)


def test_move_takes_a_bucket_with_its_weight_and_both_sides_add_up():
    cm = named_tree()
    host = cm.name_to_id("host0-1")
    new = crush_command(cm, "osd crush move",
                        {"name": "host0-1", "loc": {"rack": "rack1"}})
    rack0, rack1 = (new.buckets[new.name_to_id(n)]
                    for n in ("rack0", "rack1"))
    assert host not in rack0.items
    assert rack1.items[-1] == host and rack1.item_weights[-1] == 2 * W
    assert (rack0.weight, rack1.weight) == (4 * W, 8 * W)
    assert new.buckets[-1].item_weights == [4 * W, 8 * W]
    assert sums_hold(new) and new.buckets[-1].weight == 12 * W


def test_move_of_an_orphan_rack_attaches_it_at_its_own_weight():
    cm = named_tree()
    for cmd, args in [
            ("osd crush add-bucket", {"name": "rack2", "type": "rack"}),
            ("osd crush move", {"name": "host1-2",
                                "loc": {"rack": "rack2"}}),
            ("osd crush move", {"name": "rack2",
                                "loc": {"root": "default"}})]:
        cm = crush_command(cm, cmd, args)
    root = cm.buckets[-1]
    assert root.items[-1] == cm.name_to_id("rack2")
    assert root.item_weights == [6 * W, 4 * W, 2 * W]
    assert sums_hold(cm)


@pytest.mark.parametrize("args,why", [
    ({"name": "host9", "loc": {"rack": "rack1"}}, "no crush item"),
    ({"name": "host0-0", "loc": {"rack": "rack9"}}, "no rack named"),
    ({"name": "host0-0", "loc": {"host": "rack1"}}, "no host named"),
    ({"name": "rack0", "loc": {"rack": "rack1"}}, "no location above"),
    ({"name": "rack0", "loc": {}}, "no location above"),
])
def test_move_refuses_and_leaves_the_map_alone(args, why):
    cm = named_tree()
    before = snapshot(cm)
    with pytest.raises(ValueError, match=why):
        crush_command(cm, "osd crush move", args)
    assert snapshot(cm) == before


def test_add_puts_a_new_device_under_its_host_and_raises_max_devices():
    cm = named_tree()
    new = crush_command(cm, "osd crush add", {
        "name": "osd.12", "weight": 0.5, "loc": {"host": "host1-0",
                                                  "rack": "rack1"}})
    host = new.buckets[new.name_to_id("host1-0")]
    assert host.items[-1] == 12 and host.item_weights[-1] == W // 2
    assert new.max_devices == 13
    assert sums_hold(new)
    assert new.buckets[-1].weight == 12 * W + W // 2


@pytest.mark.parametrize("args,why", [
    ({"name": "osd.3", "weight": 1.0, "loc": {"host": "host1-0"}},
     "in the map already"),
    ({"name": "host0-0", "weight": 1.0, "loc": {"rack": "rack1"}},
     "not a device"),
    ({"name": "osd.12", "weight": -1.0, "loc": {"host": "host1-0"}},
     "negative"),
    ({"name": "osd.12", "weight": 1.0, "loc": {"host": "nohost"}},
     "no host named"),
])
def test_add_refuses(args, why):
    with pytest.raises(ValueError, match=why):
        crush_command(named_tree(), "osd crush add", args)


def test_reweight_sets_one_item_and_carries_the_sums_to_the_root():
    cm = named_tree()
    new = crush_command(cm, "osd crush reweight",
                        {"name": "osd.5", "weight": 0.25})
    host = new.buckets[new.holders(5)[0][0].id]
    assert host.item_weights[host.items.index(5)] == W // 4
    assert host.weight == W + W // 4
    assert new.buckets[-1].weight == 12 * W - 3 * W // 4
    assert sums_hold(new)
    # a bucket's own weight can be set too, as upstream allows
    again = crush_command(new, "osd crush reweight",
                          {"name": "host0-0", "weight": 3.0})
    rack = again.buckets[again.name_to_id("rack0")]
    assert rack.item_weights[0] == 3 * W
    assert again.buckets[-1].item_weights[0] == rack.weight


def test_reweight_subtree_sets_every_device_below_and_nothing_else():
    cm = named_tree()
    new = crush_command(cm, "osd crush reweight-subtree",
                        {"name": "rack1", "weight": 0.0})
    rack1 = new.name_to_id("rack1")
    assert new.buckets[-1].item_weights == [6 * W, 0]
    for host in new.buckets[rack1].items:
        assert new.buckets[host].item_weights == [0, 0]
    assert new.buckets[new.name_to_id("host0-0")].item_weights == [W, W]
    assert sums_hold(new)
    # weight 0 takes the subtree out of every mapping
    weights = [W] * 12
    under = set(new.devices_under(rack1))
    for x in range(200):
        assert not under & set(crush_do_rule(new, 0, x, 3, weights))


@pytest.mark.parametrize("cmd", ["osd crush reweight",
                                 "osd crush reweight-subtree"])
def test_reweight_refuses_an_unknown_name(cmd):
    with pytest.raises(ValueError, match="no crush item"):
        crush_command(named_tree(), cmd, {"name": "rack7", "weight": 1.0})


def test_the_edited_map_survives_the_incrementals_dict_form():
    """What ``Incremental.new_crush`` carries: names, type names, ids,
    items and weights come back as they went."""
    cm = named_tree()
    for cmd, args in [
            ("osd crush add-bucket", {"name": "row0", "type": "rack"}),
            ("osd crush reweight-subtree", {"name": "rack0",
                                            "weight": 0.0625})]:
        cm = crush_command(cm, cmd, args)
    back = crush_from_dict(crush_to_dict(cm))
    assert crush_to_dict(back) == crush_to_dict(cm)
    assert back.type_names == cm.type_names
    assert back.name_to_id("row0") == cm.name_to_id("row0")
    # and commands work on the map that came back
    crush_command(back, "osd crush move",
                  {"name": "host0-0", "loc": {"rack": "row0"}})


# -- against a running monitor ------------------------------------------------

def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def test_the_monitor_commits_each_command_as_an_epoch_and_shows_the_tree():
    from ceph_tpu.mon import Monitor
    from ceph_tpu.msg import Messenger
    from test_monitor import boot_osd, command

    async def main():
        mon = Monitor()
        addr = await mon.start()
        boots = [Messenger(f"osd.c{i}") for i in range(4)]
        for i, m in enumerate(boots):
            await boot_osd(addr, m, f"u{i}", f"host{i % 2}")
        cl = Messenger("client.crush")
        epoch = mon.osdmap.epoch
        steps = [
            ("osd crush add-bucket", {"name": "rack0", "type": "rack"}),
            ("osd crush move", {"name": "rack0",
                                "loc": {"root": "default"}}),
            ("osd crush move", {"name": "host1", "loc": {"rack": "rack0"}}),
            ("osd crush add-bucket", {"name": "host9", "type": "host"}),
            ("osd crush move", {"name": "host9", "loc": {"rack": "rack0"}}),
            ("osd crush add", {"name": "osd.9", "weight": 0.0,
                               "loc": {"host": "host9"}}),
            ("osd crush reweight", {"name": "osd.0", "weight": 0.5}),
            ("osd crush reweight-subtree", {"name": "rack0",
                                            "weight": 2.0}),
        ]
        for cmd, args in steps:
            got = await command(addr, cl, cmd, args)
            epoch += 1
            assert got == {"epoch": epoch} and mon.osdmap.epoch == epoch
        cm = mon.osdmap.crush
        assert sums_hold(cm)
        tree = await command(addr, cl, "osd tree")
        rows = {(r["type"], r.get("name", r["id"])): r for r in tree}
        assert rows[("root", "default")]["depth"] == 0
        assert rows[("rack", "rack0")]["crush_weight"] == 6 * W
        assert rows[("host", "host1")]["depth"] == 2
        assert rows[("host", "host0")]["crush_weight"] == W + W // 2
        assert rows[("osd", 0)]["crush_weight"] == W // 2
        assert rows[("osd", 0)]["up"] and rows[("osd", 0)]["in"]
        assert rows[("osd", 9)] == {
            "type": "osd", "id": 9, "up": False, "in": False, "weight": 0,
            "crush_weight": 2 * W, "depth": 3}
        order = [r.get("name", r["id"]) for r in tree]
        assert order.index("rack0") < order.index("host1") < order.index(1)
        # an unknown name is refused and commits nothing
        with pytest.raises(RuntimeError, match="no crush item"):
            await command(addr, cl, "osd crush reweight",
                          {"name": "nowhere", "weight": 1.0})
        assert mon.osdmap.epoch == epoch
        # an OSD that boots again where the map has it leaves the
        # operator's map alone
        await boot_osd(addr, boots[0], "u0", "host0", osd_id=0)
        assert crush_to_dict(mon.osdmap.crush) == crush_to_dict(cm)
        for m in boots + [cl]:
            await m.shutdown()
        await mon.stop()

    run(main())


def test_an_expansion_through_the_commands_reaches_osds_and_client():
    """A host is drained to weight 0, moved under a new rack and raised
    again in steps: after every epoch the client's and every OSD's
    table is the monitor's, and a write after the last step lands on
    the up set the monitor's map gives."""
    from ceph_tpu.client.rados import Rados
    from ceph_tpu.loadgen.cluster import SimCluster

    async def main():
        cluster = await SimCluster.create(6)
        rados = await Rados(cluster.addr, name="client.x").connect()
        await rados.pool_create("p", pg_num=16, size=2)
        io = await rados.open_ioctx("p")
        mon = cluster.mon
        pid = mon.osdmap.pool_names["p"]

        async def settled() -> None:
            want = mon.osdmap.placement_cache()
            for _ in range(200):
                maps = [o.osdmap for o in cluster.osds] + [
                    rados.objecter.osdmap]
                if all(m.epoch == mon.osdmap.epoch for m in maps):
                    break
                await asyncio.sleep(0.05)
            for m in maps:
                assert m.epoch == mon.osdmap.epoch
                got = m.placement_cache()
                assert list(got.iter_all()) == list(want.iter_all())

        def holders_of(osd: int) -> int:
            return sum(osd in up for _, _, up, _ in
                       mon.osdmap.placement_cache().iter_all())

        steps = [
            ("osd crush reweight-subtree", {"name": "host5",
                                            "weight": 0.0}),
            ("osd crush add-bucket", {"name": "rack1", "type": "rack"}),
            ("osd crush move", {"name": "rack1",
                                "loc": {"root": "default"}}),
            ("osd crush move", {"name": "host5", "loc": {"rack": "rack1"}}),
        ]
        for cmd, args in steps:
            await rados.mon_command(cmd, args)
            await settled()
        assert holders_of(5) == 0
        seen = []
        for w in (0.25, 0.5, 1.0):
            await rados.mon_command("osd crush reweight-subtree",
                                    {"name": "rack1", "weight": w})
            await settled()
            seen.append(holders_of(5))
        assert seen[-1] > 0 and seen == sorted(seen)
        # a PG the last step gave osd.5, and an object that hashes there
        oid = next(f"obj{i}" for i in range(2000)
                   if 5 in mon.osdmap.pg_to_up_acting(
                       *mon.osdmap.object_to_pg(pid, f"obj{i}"))[0])
        for _ in range(100):
            if all(not o.has_pending_recovery() for o in cluster.osds):
                break
            await asyncio.sleep(0.1)
        await io.write_full(oid, b"after the expansion" * 10)
        assert await io.read(oid) == b"after the expansion" * 10
        up, _ = mon.osdmap.pg_to_up_acting(
            *mon.osdmap.object_to_pg(pid, oid))
        pgid = mon.osdmap.pg_name(*mon.osdmap.object_to_pg(pid, oid))
        stored = [o.whoami for o in cluster.osds if pgid in o.pgs
                  and oid in o.store.list_objects(o.pgs[pgid].coll)]
        assert sorted(stored) == sorted(up) and 5 in stored
        await rados.shutdown()
        await cluster.stop()

    run(main())
