"""The plain reference for a store that commits later than it queues
(the configuration `radosbench_ec83_tpu_on_bluestore`): what an object
store owes its caller between `queue_transaction` and a kill.

It imports nothing of the program and starts no thread. A store is a
model here: collections of objects, an object its bytes, its attrs and
its omap. A transaction is a list of ops on one collection, applied
whole or not at all:

    ("mkcoll", c)                      the collection, empty
    ("touch", c, o)                    the object, empty, if it is not there
    ("write", c, o, offset, data)      a gap before `offset` reads as zeros
    ("truncate", c, o, size)           cut, or grown with zeros
    ("setattrs", c, o, {name: bytes})  set beside the attrs that are there
    ("omap_setkeys", c, o, {key: bytes})
    ("remove", c, o)                   the object, its attrs and its omap
    ("clone", c, src, dst)             dst becomes what src is, all three

Every op but `remove` and `clone` makes the object it names if it is
not there. The guarantees (upstream's ObjectStore contract, as
`ceph_tpu/objectstore/store.py` words it):

  * while the store lives, every read returns all that was QUEUED
    (`on_applied` is immediate): `live`;
  * after a kill and a fresh mount every transaction whose `on_commit`
    had fired is there whole, each later one is there whole or not at
    all, and the transactions of one collection that are there are a
    prefix of the order they were queued in: `states_after_kill`,
    `kill_verdict`.

The shards at rest stay `benchmarks.reference.expected_shards`'s to say.
"""
from __future__ import annotations

import copy
import random

INLINE_MAX = 64 * 1024      # BlueStore keeps an object up to this inline
#: object sizes on both sides of every edge a store has: nothing, one
#: allocation unit, the inline ceiling, a few units past it
SIZES = (0, 1, 100, 4095, 4096, 4097, INLINE_MAX - 1, INLINE_MAX,
         INLINE_MAX + 1, INLINE_MAX + 4096, 3 * INLINE_MAX + 17)
KINDS = ("touch", "write", "truncate", "setattrs", "omap_setkeys",
         "remove", "clone")


def apply(state: dict, txn: list[tuple]) -> dict:
    """`state` after `txn`, as a new model; `state` is not changed. A
    model is {collection: {object: {"data", "attrs", "omap"}}}. An op
    the contract refuses (no such collection, object or source) raises
    KeyError and the model stays as it was: all or nothing."""
    out = copy.deepcopy(state)
    for op in txn:
        kind, c = op[0], op[1]
        if kind == "mkcoll":
            if c in out:
                raise KeyError(f"collection {c} exists")
            out[c] = {}
            continue
        coll = out[c]
        o = op[2]
        if kind == "remove":
            del coll[o]
            continue
        if kind == "clone":
            coll[op[3]] = copy.deepcopy(coll[o])
            continue
        obj = coll.setdefault(o, {"data": b"", "attrs": {}, "omap": {}})
        if kind == "write":
            offset, data = op[3], op[4]
            cur = obj["data"].ljust(offset, b"\x00")
            obj["data"] = cur[:offset] + data + cur[offset + len(data):]
        elif kind == "truncate":
            obj["data"] = obj["data"][:op[3]].ljust(op[3], b"\x00")
        elif kind == "setattrs":
            obj["attrs"].update(op[3])
        elif kind == "omap_setkeys":
            obj["omap"].update(op[3])
        elif kind != "touch":
            raise ValueError(f"no such op {kind!r}")
    return out


def live(txns: list[list[tuple]]) -> list[dict]:
    """The model after each transaction: what every read must return
    once the i-th has been queued, committed or not."""
    out, state = [], {}
    for txn in txns:
        state = apply(state, txn)
        out.append(state)
    return out


def collection_of(txn: list[tuple]):
    """The one collection a transaction of this reference touches."""
    colls = {op[1] for op in txn}
    if len(colls) != 1:
        raise ValueError(f"a transaction on {len(colls)} collections")
    return colls.pop()


def states_after_kill(txns: list[list[tuple]], committed: set[int],
                      c) -> list[dict | None]:
    """Every state the collection `c` may be found in after a kill:
    what a prefix of its own transactions leaves, for each prefix that
    holds all of them in `committed` (indexes into `txns`). None is the
    collection not made yet."""
    mine = [i for i, txn in enumerate(txns) if collection_of(txn) == c]
    need = max((n + 1 for n, i in enumerate(mine) if i in committed),
               default=0)
    out, state = [], {}
    for n in range(len(mine) + 1):
        if n:
            state = apply(state, txns[mine[n - 1]])
        if n >= need:
            out.append(copy.deepcopy(state.get(c)))
    return out


def kill_verdict(txns: list[list[tuple]], committed: set[int],
                 found: dict) -> list[str]:
    """What is wrong with the model `found` on a fresh mount after a
    kill, one line a collection; nothing if the guarantees held."""
    wrong = []
    for c in sorted({collection_of(t) for t in txns} | set(found)):
        allowed = states_after_kill(txns, committed, c)
        if found.get(c) not in allowed:
            wrong.append(f"collection {c}: found a state that no prefix "
                         f"of its {len(allowed)} allowed ones leaves")
    return wrong


def make_transactions(seed: int, n: int = 24, collections: int = 3,
                      objects: int = 4) -> list[list[tuple]]:
    """`n` transactions drawn from `seed`, every one the contract
    accepts: the collections first, then one to three ops each on a few
    objects of one collection, sizes from `SIZES`."""
    rng = random.Random(seed)
    txns: list[list[tuple]] = [[("mkcoll", c)] for c in range(collections)]
    state: dict = {}
    for txn in txns:
        state = apply(state, txn)
    names = [f"obj{i}" for i in range(objects)]
    while len(txns) < n:
        c = rng.randrange(collections)
        txn: list[tuple] = []
        trial = state
        for _ in range(rng.randint(1, 3)):
            have = sorted(trial[c])
            kind = rng.choice(KINDS)
            o = rng.choice(names)
            if kind in ("remove", "clone") and not have:
                kind = "write"
            if kind == "touch":
                op = ("touch", c, o)
            elif kind == "write":
                size = rng.choice(SIZES)
                offset = rng.choice((0, 0, 0, 7, 4096, INLINE_MAX))
                op = ("write", c, o, offset, rng.randbytes(size))
            elif kind == "truncate":
                op = ("truncate", c, o, rng.choice(SIZES))
            elif kind == "setattrs":
                op = ("setattrs", c, o,
                      {f"a{rng.randrange(3)}": rng.randbytes(
                          rng.randrange(1, 40))})
            elif kind == "omap_setkeys":
                op = ("omap_setkeys", c, o,
                      {f"k{rng.randrange(5)}": rng.randbytes(
                          rng.randrange(0, 60)) for _ in range(2)})
            elif kind == "remove":
                op = ("remove", c, rng.choice(have))
            else:
                op = ("clone", c, rng.choice(have), o)
            trial = apply(trial, [op])
            txn.append(op)
        state = trial
        txns.append(txn)
    return txns
