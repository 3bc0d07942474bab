"""CRUSH map model: buckets, rules, tunables.

Data-model rendering of src/crush/crush.h: bucket algorithms
(crush.h:141-191), rule steps (crush.h:54-74), rule types (crush.h:97-100),
tunables (crush.h:374-395).  Weights are 16.16 fixed point throughout.
"""

from __future__ import annotations

from copy import deepcopy
from dataclasses import dataclass, field

CRUSH_BUCKET_UNIFORM = 1
CRUSH_BUCKET_LIST = 2
CRUSH_BUCKET_TREE = 3
CRUSH_BUCKET_STRAW = 4
CRUSH_BUCKET_STRAW2 = 5

CRUSH_ITEM_UNDEF = 0x7FFFFFFE
CRUSH_ITEM_NONE = 0x7FFFFFFF

CRUSH_HASH_RJENKINS1 = 0

# rule step ops
CRUSH_RULE_NOOP = 0
CRUSH_RULE_TAKE = 1
CRUSH_RULE_CHOOSE_FIRSTN = 2
CRUSH_RULE_CHOOSE_INDEP = 3
CRUSH_RULE_EMIT = 4
CRUSH_RULE_CHOOSELEAF_FIRSTN = 6
CRUSH_RULE_CHOOSELEAF_INDEP = 7
CRUSH_RULE_SET_CHOOSE_TRIES = 8
CRUSH_RULE_SET_CHOOSELEAF_TRIES = 9
CRUSH_RULE_SET_CHOOSE_LOCAL_TRIES = 10
CRUSH_RULE_SET_CHOOSE_LOCAL_FALLBACK_TRIES = 11
CRUSH_RULE_SET_CHOOSELEAF_VARY_R = 12
CRUSH_RULE_SET_CHOOSELEAF_STABLE = 13

# rule types
CRUSH_RULE_TYPE_REPLICATED = 1
CRUSH_RULE_TYPE_ERASURE = 3


@dataclass
class Tunables:
    """Default == "jewel" profile (CrushWrapper.h set_tunables_jewel)."""

    choose_local_tries: int = 0
    choose_local_fallback_tries: int = 0
    choose_total_tries: int = 50
    chooseleaf_descend_once: int = 1
    chooseleaf_vary_r: int = 1
    chooseleaf_stable: int = 1
    straw_calc_version: int = 1


@dataclass
class Bucket:
    id: int                      # negative
    type: int                    # bucket type id (host=1, rack=2, ... by map)
    alg: int = CRUSH_BUCKET_STRAW2
    hash: int = CRUSH_HASH_RJENKINS1
    items: list[int] = field(default_factory=list)
    item_weights: list[int] = field(default_factory=list)  # 16.16 fixed
    # tree/list buckets carry derived node/sum weights, built lazily
    _tree_node_weights: list[int] | None = None
    _list_sum_weights: list[int] | None = None

    @property
    def size(self) -> int:
        return len(self.items)

    @property
    def weight(self) -> int:
        return sum(self.item_weights)


@dataclass
class RuleStep:
    op: int
    arg1: int = 0
    arg2: int = 0


@dataclass
class Rule:
    rule_id: int
    type: int = CRUSH_RULE_TYPE_REPLICATED
    steps: list[RuleStep] = field(default_factory=list)


class CrushMap:
    def __init__(self, tunables: Tunables | None = None) -> None:
        self.buckets: dict[int, Bucket] = {}    # id (negative) -> bucket
        self.rules: dict[int, Rule] = {}
        self.tunables = tunables or Tunables()
        self.max_devices = 0
        self.type_names: dict[int, str] = {0: "osd", 1: "host", 2: "rack",
                                           10: "root"}
        self.bucket_names: dict[int, str] = {}
        self.device_classes: dict[int, str] = {}
        # choose_args (CrushWrapper.h choose_args_map_t): bucket id ->
        # {"weight_set": [[w per item] per position], "ids": [...]}.
        # The balancer's crush-compat mode steers placement by writing
        # position-specific weight overrides here instead of touching
        # the real hierarchy weights (mapper.c:289-306).
        self.choose_args: dict[int, dict] = {}

    def add_bucket(self, bucket: Bucket, name: str | None = None) -> None:
        assert bucket.id < 0, "bucket ids are negative"
        self.buckets[bucket.id] = bucket
        if name:
            self.bucket_names[bucket.id] = name
        for item in bucket.items:
            if item >= 0:
                self.max_devices = max(self.max_devices, item + 1)

    def add_rule(self, rule: Rule) -> None:
        self.rules[rule.rule_id] = rule

    def bucket(self, item_id: int) -> Bucket | None:
        return self.buckets.get(item_id)

    def create_choose_args(self, positions: int) -> None:
        """Seed a weight-set for every straw2 bucket with its current
        weights at every position (CrushWrapper::create_choose_args) --
        the starting point the balancer then adjusts."""
        for bid, b in self.buckets.items():
            self.choose_args[bid] = {
                "weight_set": [list(b.item_weights)
                               for _ in range(positions)]}

    def choose_args_adjust_item_weight(self, item: int,
                                       weight: int | list[int]) -> None:
        """Set ``item``'s weight-set weight in every bucket that holds
        it, one value per position (CrushWrapper::
        choose_args_adjust_item_weight)."""
        for bid, b in self.buckets.items():
            if item not in b.items:
                continue
            arg = self.choose_args.get(bid)
            if arg is None:
                continue
            i = b.items.index(item)
            ws = arg["weight_set"]
            for pos, row in enumerate(ws):
                row[i] = (weight[min(pos, len(weight) - 1)]
                          if isinstance(weight, list) else weight)

    def name_to_id(self, name: str) -> int | None:
        for bid, n in self.bucket_names.items():
            if n == name:
                return bid
        return None

    # -- edits (CrushWrapper's: what the ``osd crush`` commands run) --------
    # A served map is replaced wholesale, never edited in place (its
    # compiled tables hang on the object): edit a ``copy()``.
    def copy(self) -> "CrushMap":
        """The map without what was derived from it (the compiled
        tables and the structural key other modules hang on it)."""
        m = CrushMap()
        m.__dict__ = {k: deepcopy(v) for k, v in self.__dict__.items()
                      if not k.startswith("_")}
        return m

    def item_id(self, name: str) -> int:
        """``osd.<n>`` or a bucket's name; an unknown one is refused."""
        if name.startswith("osd.") and name[4:].isdigit():
            return int(name[4:])
        bid = self.name_to_id(name)
        if bid is None:
            raise ValueError(f"no crush item {name!r}")
        return bid

    def holders(self, item: int) -> list[tuple[Bucket, int]]:
        """(bucket, index) of every place ``item`` is held."""
        return [(b, b.items.index(item)) for b in self.buckets.values()
                if item in b.items]

    def adjust_item_weight(self, item: int, weight: int) -> int:
        """Set ``item``'s 16.16 weight in every bucket that holds it and
        carry each such bucket's new sum up through its ancestors
        (CrushWrapper::adjust_item_weight).  Returns the entries
        changed."""
        changed = 0
        for b, i in self.holders(item):
            if b.item_weights[i] != weight:
                b.item_weights[i] = weight
                b._tree_node_weights = b._list_sum_weights = None
                changed += 1 + self.adjust_item_weight(b.id, b.weight)
        return changed

    def devices_under(self, item: int) -> list[int]:
        if item >= 0:
            return [item]
        return [d for child in self.buckets[item].items
                for d in self.devices_under(child)]

    def adjust_subtree_weight(self, item: int, weight: int) -> int:
        """Every device under ``item`` (or the device itself) weighs
        ``weight``, the sums carried up to the roots
        (CrushWrapper::adjust_subtree_weight)."""
        return sum(self.adjust_item_weight(dev, weight)
                   for dev in self.devices_under(item))

    def new_bucket(self, name: str, type_name: str) -> int:
        """An empty straw2 bucket under no parent, with the next free
        id (CrushWrapper::add_bucket as ``osd crush add-bucket`` calls
        it)."""
        types = {n: t for t, n in self.type_names.items()}
        if type_name not in types or types[type_name] == 0:
            raise ValueError(f"no bucket type {type_name!r}")
        if self.name_to_id(name) is not None:
            raise ValueError(f"bucket {name!r} exists")
        bid = min(self.buckets, default=0) - 1
        self.add_bucket(Bucket(id=bid, type=types[type_name]), name)
        return bid

    def _parent_from(self, item: int, loc: dict[str, str]) -> Bucket:
        """The bucket of ``loc`` ({type name: bucket name}) nearest
        above ``item``: every name must exist, be of its type, and be
        above the item's own type."""
        types = {n: t for t, n in self.type_names.items()}
        found = []
        for type_name, name in loc.items():
            bid = self.name_to_id(name)
            if bid is None or type_name not in types \
                    or self.buckets[bid].type != types[type_name]:
                raise ValueError(f"no {type_name} named {name!r}")
            found.append(self.buckets[bid])
        found = [b for b in found if b.type > self.item_type(item)]
        if not found:
            raise ValueError(f"no location above the item in {loc}")
        return min(found, key=lambda b: b.type)

    def detach(self, item: int) -> int:
        """Take ``item`` out of every bucket that holds it, the sums
        carried up; returns the weight it had."""
        weight = 0
        for b, i in self.holders(item):
            weight = b.item_weights[i]
            del b.items[i], b.item_weights[i]
            b._tree_node_weights = b._list_sum_weights = None
            self.adjust_item_weight(b.id, b.weight)
        return weight

    def insert_item(self, item: int, weight: int, loc: dict[str, str]) -> None:
        """``item`` under the nearest bucket of ``loc`` at ``weight``,
        the sums carried up (CrushWrapper::insert_item, with the
        location's buckets there already)."""
        parent = self._parent_from(item, loc)
        if item < 0 and parent.id in [item] + [
                b.id for b in self._buckets_under(item)]:
            raise ValueError("a bucket cannot move under itself")
        if item in parent.items:
            raise ValueError(f"item {item} is in {parent.id} already")
        parent.items.append(item)
        parent.item_weights.append(weight)
        parent._tree_node_weights = parent._list_sum_weights = None
        if item >= 0:
            self.max_devices = max(self.max_devices, item + 1)
        self.adjust_item_weight(parent.id, parent.weight)

    def _buckets_under(self, bid: int) -> list[Bucket]:
        out = []
        for child in self.buckets[bid].items:
            if child < 0:
                out += [self.buckets[child]] + self._buckets_under(child)
        return out

    def move_item(self, item: int, loc: dict[str, str]) -> None:
        """``osd crush move``: out of where it is, weight kept, into
        ``loc`` (CrushWrapper::move_bucket)."""
        self._parent_from(item, loc)              # refuse before detaching
        held = self.detach(item)
        self.insert_item(
            item, self.buckets[item].weight if item < 0 else held, loc)

    def is_device(self, item_id: int) -> bool:
        return item_id >= 0

    def item_type(self, item_id: int) -> int:
        if item_id >= 0:
            return 0
        b = self.buckets.get(item_id)
        return b.type if b else -1
