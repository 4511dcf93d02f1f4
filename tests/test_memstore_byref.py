"""MemStore keeps the buffer it is given and hands out windows on what
it holds: a model test over random op sequences, and the named cases of
the contract (snapshots, clones, the quarter rule, the validation
overlay's atomicity)."""
from __future__ import annotations

import random
import time

import pytest

from ceph_tpu.msg import messages
from ceph_tpu.msg.frames import Frame, Tag
from ceph_tpu.objectstore import (CollectionId, FileStore, Ghobject,
                                  MemStore, StoreError, Transaction)
from ceph_tpu.utils import copytrack

CID = CollectionId.make_pg(1, 0x2A)
CID2 = CollectionId.make_pg(1, 0x2B)
KIB = 1024


def gh(name: str) -> Ghobject:
    return Ghobject(pool=1, name=name)


@pytest.fixture
def store():
    s = MemStore()
    s.USED_BYTES_TTL = 0.0          # used_bytes is compared every step
    s.mkfs()
    s.mount()
    s.queue_transaction(Transaction().create_collection(CID))
    yield s
    s.umount()


def commit(store, build) -> None:
    txn = Transaction()
    build(txn)
    store.queue_transaction(txn)


def rx_view(payload: bytes, slack: int = 1 * KIB) -> memoryview:
    """A payload as the messenger delivers it: a read-only window on a
    body a little larger than itself."""
    body = bytearray(slack) + bytearray(payload)
    return memoryview(body).toreadonly()[slack:]


def ledger(stage: str) -> dict:
    return dict(copytrack.snapshot()["stages"][stage])


def delta(before: dict, stage: str) -> tuple[int, int]:
    after = ledger(stage)
    return (after["referenced_bytes"] - before["referenced_bytes"],
            after["copied_bytes"] - before["copied_bytes"])


def is_private(store, name: str) -> bool:
    return isinstance(store._colls[CID][gh(name)].data, bytearray)


# -- the model ----------------------------------------------------------------

NAMES = ["a", "b", "c", "d", "e"]


def _payload(rng: random.Random, n: int):
    """`n` random bytes in one of the shapes a caller hands a
    transaction, and what they are as plain bytes."""
    raw = rng.randbytes(n)
    shape = rng.choice(["bytes", "bytearray", "rx_view", "sliver",
                        "writable_view"])
    if shape == "bytes":
        return raw, raw, None
    if shape == "bytearray":
        buf = bytearray(raw)
        return buf, raw, buf
    if shape == "rx_view":
        return rx_view(raw, rng.randrange(0, 64)), raw, None
    if shape == "sliver":
        return rx_view(raw, 5 * n + 64), raw, None
    buf = bytearray(raw)
    return memoryview(buf), raw, buf


def _model_write(model: dict, name: str, offset: int, raw: bytes) -> None:
    obj = model.setdefault(name, bytearray())
    if len(obj) < offset:
        obj.extend(bytes(offset - len(obj)))
    obj[offset:offset + len(raw)] = raw


@pytest.mark.parametrize("start", ["adopted", "private"])
@pytest.mark.parametrize("seed", range(10))
def test_random_ops_against_a_bytearray_model(store, seed, start):
    rng = random.Random(1000 * seed + len(start))
    model: dict[str, bytearray] = {}
    windows: list[tuple[object, bytes]] = []    # what a read returned, then

    for name in NAMES[:3]:
        raw = rng.randbytes(rng.randrange(1, 300))
        if start == "adopted":
            commit(store, lambda t: t.write(CID, gh(name), 0, rx_view(raw)))
            assert not is_private(store, name)
        else:
            commit(store, lambda t: t.write(CID, gh(name), 0, raw[:1])
                   .write(CID, gh(name), 1, raw[1:]))
            assert is_private(store, name) or len(raw) == 1
        model[name] = bytearray(raw)

    for _step in range(120):
        op = rng.choice(["touch", "write", "write", "write_full", "zero",
                         "truncate", "clone", "clone_range", "remove",
                         "read", "bad"])
        name = rng.choice(NAMES)
        have = sorted(model)
        if op == "touch":
            commit(store, lambda t: t.touch(CID, gh(name)))
            model.setdefault(name, bytearray())
        elif op == "write":
            size = len(model.get(name, b""))
            offset = rng.choice([0, 0, size, rng.randrange(0, size + 40)])
            data, raw, mutable = _payload(rng, rng.randrange(0, 200))
            commit(store, lambda t: t.write(CID, gh(name), offset, data))
            if mutable is not None:     # scribbled on once queued
                mutable[:] = bytes(len(mutable))
            _model_write(model, name, offset, raw)
        elif op == "write_full":
            data, raw, mutable = _payload(rng, rng.randrange(0, 300))
            commit(store, lambda t: t.touch(CID, gh(name))
                   .truncate(CID, gh(name), 0).write(CID, gh(name), 0, data))
            if mutable is not None:
                mutable[:] = bytes(len(mutable))
            model[name] = bytearray(raw)
        elif op == "zero":
            offset, length = rng.randrange(0, 300), rng.randrange(0, 100)
            commit(store, lambda t: t.zero(CID, gh(name), offset, length))
            _model_write(model, name, offset, bytes(length))
        elif op == "truncate":
            size = rng.choice([0, len(model.get(name, b"")),
                               rng.randrange(0, 400)])
            commit(store, lambda t: t.truncate(CID, gh(name), size))
            obj = model.setdefault(name, bytearray())
            if size < len(obj):
                del obj[size:]
            else:
                obj.extend(bytes(size - len(obj)))
        elif op == "clone" and have:
            src = rng.choice(have)
            commit(store, lambda t: t.clone(CID, gh(src), gh(name)))
            model[name] = bytearray(model[src])
        elif op == "clone_range" and have:
            src = rng.choice(have)
            s_off = rng.randrange(0, len(model[src]) + 10)
            length, d_off = rng.randrange(0, 200), rng.randrange(0, 100)
            commit(store, lambda t: t.clone_range(
                CID, gh(src), gh(name), s_off, length, d_off))
            _model_write(model, name, d_off,
                         bytes(model[src][s_off:s_off + length]))
        elif op == "remove" and name in model:
            commit(store, lambda t: t.remove(CID, gh(name)))
            del model[name]
        elif op == "read" and have:
            src = rng.choice(have)
            offset = rng.randrange(0, len(model[src]) + 5)
            length = rng.choice([None, rng.randrange(0, 300)])
            got = store.read(CID, gh(src), offset, length)
            end = None if length is None else offset + length
            assert got == bytes(model[src][offset:end])
            windows.append((got, bytes(got)))
        elif op == "bad":
            # a transaction whose LAST op cannot apply leaves no trace
            # of its first ones
            with pytest.raises(StoreError):
                commit(store, lambda t: t.write(CID, gh(name), 0, b"never")
                       .remove(CID, gh("never-there")))

        # the whole store against the model, after every step
        assert sorted(g.name for g in store.collection_list(CID)) == \
            sorted(model)
        for n, want in model.items():
            got = store.read(CID, gh(n))
            assert got == bytes(want), (n, op)
            assert store.stat(CID, gh(n))["size"] == len(want)
            # what leaves the store is read-only, and never a window on
            # a bytearray the store may yet resize
            if is_private(store, n):
                assert type(got) is bytes
            else:
                assert type(got) is memoryview and got.readonly
            windows.append((got, bytes(want)))
        assert store.used_bytes() == sum(len(v) for v in model.values())

    # every window ever handed out still shows the bytes it was read at
    for got, then in windows:
        assert got == then


# -- the named cases ----------------------------------------------------------

@pytest.mark.parametrize("shape", ["bytearray", "writable_view"])
def test_a_mutable_buffer_is_snapshotted(store, shape):
    buf = bytearray(b"as queued" * 100)
    data = buf if shape == "bytearray" else memoryview(buf)
    before = ledger("store_write")
    txn = Transaction().write(CID, gh("o"), 0, data)
    buf[:] = bytes(len(buf))        # between queueing and applying
    store.queue_transaction(txn)
    buf[:4] = b"late"               # and after
    assert store.read(CID, gh("o")) == b"as queued" * 100
    # one copy, at the snapshot; the store then keeps the bytes
    assert delta(before, "store_write") == (900, 900)
    assert not is_private(store, "o")


@pytest.mark.parametrize("then", ["overwrite", "write_full", "truncate",
                                  "truncate_0", "zero", "corrupt",
                                  "remove", "clone_range_into"])
def test_a_window_read_before_a_change_keeps_the_old_bytes(store, then):
    old = bytes(range(256)) * 8
    commit(store, lambda t: t.write(CID, gh("o"), 0, rx_view(old)))
    commit(store, lambda t: t.write(CID, gh("other"), 0, b"\xee" * 64))
    whole = store.read(CID, gh("o"))
    part = store.read(CID, gh("o"), 100, 1000)
    assert type(whole) is memoryview and whole.readonly
    {
        "overwrite": lambda: commit(
            store, lambda t: t.write(CID, gh("o"), 10, b"\xff" * 50)),
        "write_full": lambda: commit(
            store, lambda t: t.truncate(CID, gh("o"), 0)
            .write(CID, gh("o"), 0, b"\xff" * len(old))),
        "truncate": lambda: commit(
            store, lambda t: t.truncate(CID, gh("o"), 300)),
        "truncate_0": lambda: commit(
            store, lambda t: t.truncate(CID, gh("o"), 0)),
        "zero": lambda: commit(store, lambda t: t.zero(CID, gh("o"), 0, 64)),
        "corrupt": lambda: store.corrupt(CID, gh("o"), 150),
        "remove": lambda: commit(store, lambda t: t.remove(CID, gh("o"))),
        "clone_range_into": lambda: commit(
            store, lambda t: t.clone_range(CID, gh("other"), gh("o"),
                                           0, 64, 120)),
    }[then]()
    assert whole == old and part == old[100:1100]
    if then != "remove":
        assert store.read(CID, gh("o")) != old


@pytest.mark.parametrize("start", ["adopted", "private"])
def test_a_clone_and_its_source_never_see_each_others_writes(store, start):
    data = b"0123456789" * 50
    if start == "adopted":
        commit(store, lambda t: t.write(CID, gh("src"), 0, rx_view(data)))
    else:
        commit(store, lambda t: t.write(CID, gh("src"), 0, data[:7])
               .write(CID, gh("src"), 7, data[7:]))
    commit(store, lambda t: t.clone(CID, gh("src"), gh("dst")))
    src_obj, dst_obj = (store._colls[CID][gh(n)] for n in ("src", "dst"))
    # an immutable buffer is shared, a private one copied
    assert (src_obj.data is dst_obj.data) == (start == "adopted")
    commit(store, lambda t: t.write(CID, gh("src"), 0, b"SRC"))
    assert store.read(CID, gh("dst")) == data
    commit(store, lambda t: t.write(CID, gh("dst"), 5, b"DST"))
    assert store.read(CID, gh("src")) == b"SRC" + data[3:]
    assert store.read(CID, gh("dst")) == data[:5] + b"DST" + data[8:]


def test_no_window_on_a_private_bytearray_leaves_the_store(store):
    commit(store, lambda t: t.write(CID, gh("o"), 0, b"head")
           .write(CID, gh("o"), 4, b"tail"))
    assert is_private(store, "o")
    held = [store.read(CID, gh("o")), store.read(CID, gh("o"), 2, 4)]
    assert [type(h) for h in held] == [bytes, bytes]
    # with a window on it exported, each of these would raise BufferError
    commit(store, lambda t: t.write(CID, gh("o"), 8, b"x" * 4096))
    commit(store, lambda t: t.truncate(CID, gh("o"), 3))
    commit(store, lambda t: t.truncate(CID, gh("o"), 9000))
    assert held == [b"headtail", b"adta"]
    assert store.read(CID, gh("o")) == b"hea" + bytes(8997)


def test_growing_a_private_object_leaves_the_gap_zero(store):
    commit(store, lambda t: t.write(CID, gh("o"), 0, b"ab")
           .write(CID, gh("o"), 10, b"cd").write(CID, gh("o"), 11, b"XYZ"))
    assert store.read(CID, gh("o")) == b"ab" + bytes(8) + b"cXYZ"


@pytest.mark.parametrize("case,n,under,adopted", [
    ("chunk_in_an_envelope", 3 * KIB, 1024 * KIB, False),
    ("just_under_a_quarter", 256 * KIB - 1, 1024 * KIB, False),
    ("exactly_a_quarter", 256 * KIB, 1024 * KIB, True),
    ("sub_op_in_its_own_frame", 512 * KIB, 513 * KIB, True),
    ("whole_buffer", 4 * KIB, 4 * KIB, True),
])
def test_the_quarter_rule(store, case, n, under, adopted):
    payload = random.Random(n).randbytes(n)
    body = bytearray(under)
    body[under - n:] = payload
    view = memoryview(body).toreadonly()[under - n:]
    before = ledger("store_write")
    commit(store, lambda t: t.write(CID, gh("o"), 0, view))
    kept = store._colls[CID][gh("o")].data
    assert store.read(CID, gh("o")) == payload
    assert not is_private(store, "o")
    if adopted:
        assert kept is view and delta(before, "store_write") == (n, 0)
    else:
        # the ledger says the bytes were copied, and nothing pins the body
        assert type(kept) is bytes and delta(before, "store_write") == (0, n)


def test_each_half_of_a_two_write_envelope_is_adopted(store):
    """Two 512 KiB sub-op writes to one peer ride one batch envelope
    (`_coalesce`: 1 MiB): each is a frame's overhead UNDER half of the
    body it arrives in, and both must be kept by reference."""
    rng = random.Random(7)
    halves = [rng.randbytes(512 * KIB) for _ in range(2)]
    inner = []
    for i, half in enumerate(halves):
        m = messages.MOSDECSubOpWrite(
            {"pgid": [1, 0], "tid": i, "from": 0, "oid": f"o{i}",
             "shard": 3, "sub": "write_full", "entry": {"v": [1, i]}},
            half)
        m.seq = i + 1
        inner.append(m)
    blob = Frame(Tag.MESSAGE,
                 messages.pack_batch(inner).encode_segments()).encode()
    # as it arrives: a body the transport filled, read-only windows on it
    frame = Frame.decode(bytearray(blob))
    got = messages.unpack_batch(
        messages.Message.decode_segments(frame.segments))
    assert [len(m.data) for m in got] == [512 * KIB] * 2
    assert all(len(m.data) * 2 < len(m.data.obj) for m in got)
    before = ledger("store_write")
    for i, m in enumerate(got):
        commit(store, lambda t: t.touch(CID, gh(f"o{i}"))
               .write(CID, gh(f"o{i}"), 0, m.data))
    assert delta(before, "store_write") == (1024 * KIB, 0)
    for i, half in enumerate(halves):
        assert store._colls[CID][gh(f"o{i}")].data.obj is got[0].data.obj
        assert store.read(CID, gh(f"o{i}")) == half


def test_reads_are_entered_in_the_ledger(store):
    commit(store, lambda t: t.write(CID, gh("kept"), 0, b"k" * 1000)
           .write(CID, gh("own"), 0, b"o").write(CID, gh("own"), 1, b"w" * 99))
    before = ledger("store_read")
    store.read(CID, gh("kept"))
    store.read(CID, gh("kept"), 10, 100)
    assert delta(before, "store_read") == (1100, 0)
    store.read(CID, gh("own"), 50)
    assert delta(before, "store_read") == (1100, 50)


# -- validation reads the store through the transaction's own overlay --------

def _names(store, cid=CID):
    return sorted(g.name for g in store.collection_list(cid))


@pytest.mark.parametrize("case", [
    "remove_then_touch", "touch_then_remove_then_remove",
    "mkcoll_then_use", "mkcoll_twice", "rmcoll_then_use",
    "rmcoll_not_empty", "empty_it_then_rmcoll", "rmcoll_then_mkcoll",
    "rmcoll_of_a_coll_filled_here", "move_rename", "move_then_old_name",
    "move_to_absent_coll", "clone_of_removed", "clone_then_remove_src",
    "rmattr_of_absent", "failing_last_op"])
def test_validation_is_all_or_nothing_through_the_overlay(store, case):
    commit(store, lambda t: t.write(CID, gh("x"), 0, b"x-data")
           .setattrs(CID, gh("x"), {"k": b"v"}).touch(CID, gh("y")))
    before = (_names(store), store.list_collections(),
              bytes(store.read(CID, gh("x"))), store.getattrs(CID, gh("x")))
    x, y, z = gh("x"), gh("y"), gh("z")
    ok, build = {
        "remove_then_touch": (True, lambda t: t.remove(CID, x).touch(CID, x)),
        "touch_then_remove_then_remove": (
            False, lambda t: t.touch(CID, z).remove(CID, z).remove(CID, z)),
        "mkcoll_then_use": (True, lambda t: t.create_collection(CID2)
                            .write(CID2, z, 0, b"new")
                            .clone(CID2, z, x).remove(CID2, z)),
        "mkcoll_twice": (False, lambda t: t.create_collection(CID2)
                         .create_collection(CID2)),
        "rmcoll_then_use": (False, lambda t: t.remove(CID, x).remove(CID, y)
                            .remove_collection(CID).touch(CID, z)),
        "rmcoll_not_empty": (False, lambda t: t.remove(CID, x)
                             .remove_collection(CID)),
        "empty_it_then_rmcoll": (True, lambda t: t.remove(CID, x)
                                 .remove(CID, y).remove_collection(CID)),
        "rmcoll_then_mkcoll": (
            False, lambda t: t.remove(CID, x).remove(CID, y)
            .remove_collection(CID).create_collection(CID)
            .setattrs(CID, y, {"k": b"v"}).remove(CID, x)),
        "rmcoll_of_a_coll_filled_here": (
            False, lambda t: t.create_collection(CID2).touch(CID2, z)
            .remove_collection(CID2)),
        "move_rename": (True, lambda t: t.create_collection(CID2)
                        .collection_move_rename(CID, x, CID2, z)
                        .write(CID2, z, 6, b"+more").touch(CID, x)),
        "move_then_old_name": (
            False, lambda t: t.create_collection(CID2)
            .collection_move_rename(CID, x, CID2, z).rmattr(CID, x, "k")),
        "move_to_absent_coll": (
            False, lambda t: t.touch(CID, z)
            .collection_move_rename(CID, x, CID2, z)),
        "clone_of_removed": (False, lambda t: t.remove(CID, x)
                             .clone(CID, x, z)),
        "clone_then_remove_src": (True, lambda t: t.clone(CID, x, z)
                                  .remove(CID, x)),
        "rmattr_of_absent": (False, lambda t: t.write(CID, x, 0, b"no")
                             .rmattr(CID, z, "k")),
        "failing_last_op": (False, lambda t: t.write(CID, x, 0, b"NO")
                            .truncate(CID, y, 50).touch(CID, z)
                            .create_collection(CID2).remove(CID2, x)),
    }[case]
    if not ok:
        with pytest.raises(StoreError):
            commit(store, build)
        assert before == (_names(store), store.list_collections(),
                          bytes(store.read(CID, x)), store.getattrs(CID, x))
        assert store.stat(CID, y)["size"] == 0
        return
    commit(store, build)
    after = {
        "remove_then_touch": lambda: (
            _names(store) == ["x", "y"] and store.read(CID, x) == b""
            and store.getattrs(CID, x) == {}),
        "mkcoll_then_use": lambda: (
            _names(store, CID2) == ["x"]
            and store.read(CID2, x) == b"new"),
        "empty_it_then_rmcoll": lambda: store.list_collections() == [],
        "move_rename": lambda: (
            _names(store) == ["x", "y"] and _names(store, CID2) == ["z"]
            and store.read(CID2, z) == b"x-data+more"
            and store.getattrs(CID2, z) == {"k": b"v"}
            and store.read(CID, x) == b""),
        "clone_then_remove_src": lambda: (
            _names(store) == ["y", "z"]
            and store.read(CID, z) == b"x-data"),
    }[case]
    assert after()


def test_validation_copies_no_collection(store):
    """What `_validate` looks at is what the transaction names: a store
    of many objects validates a small transaction without walking them."""
    commit(store, lambda t: [t.touch(CID, gh(f"n{i}")) for i in range(500)])

    class Counting(dict):
        walked = 0

        def __iter__(self):
            Counting.walked += 1
            return super().__iter__()

    store._colls[CID] = Counting(store._colls[CID])
    commit(store, lambda t: t.touch(CID, gh("one")).remove(CID, gh("n7"))
           .write(CID, gh("n8"), 0, b"w"))
    assert Counting.walked == 0
    assert len(_names(store)) == 500


# -- what is given is what is kept, per shape of input -----------------------

@pytest.mark.parametrize("shape", ["bytes", "readonly_view", "writable_view",
                                   "bytearray"])
def test_what_is_read_back_is_what_was_given(store, shape):
    """An immutable input is the very buffer read back; a mutable one is
    snapshotted at `Transaction.write`, and what its giver writes into
    it later never shows. Size, `stat` and `used_bytes` are the
    payload's length either way."""
    raw = random.Random(len(shape)).randbytes(8 * KIB)
    under = bytearray(raw)
    data = {"bytes": raw, "bytearray": under,
            "readonly_view": memoryview(under).toreadonly(),
            "writable_view": memoryview(under)}[shape]
    before = ledger("store_write")
    commit(store, lambda t: t.write(CID, gh("o"), 0, data))
    got = store.read(CID, gh("o"))
    assert type(got) is memoryview and got.readonly and got == raw
    kept = store._colls[CID][gh("o")].data
    if shape == "bytes":
        assert kept is raw and got.obj is raw
        assert delta(before, "store_write") == (8 * KIB, 0)
    elif shape == "readonly_view":
        # the view itself is kept, and a window's owner is ITS owner:
        # the body the bytes arrived in
        assert kept is data and got.obj is under
        assert delta(before, "store_write") == (8 * KIB, 0)
    else:
        assert type(kept) is bytes and got.obj is kept
        assert delta(before, "store_write") == (8 * KIB, 8 * KIB)
        under[:] = bytes(len(under))        # the giver's buffer, reused
        assert got == raw and store.read(CID, gh("o")) == raw
    assert store.stat(CID, gh("o"))["size"] == len(raw)
    assert store.used_bytes() == len(raw)
    # a second, partial write makes it private with ONE copy and the
    # window read before still shows the payload
    commit(store, lambda t: t.write(CID, gh("o"), 4 * KIB, b"mid"))
    assert is_private(store, "o") and got == raw
    assert store.read(CID, gh("o")) == raw[:4 * KIB] + b"mid" \
        + raw[4 * KIB + 3:]
    assert store.stat(CID, gh("o"))["size"] == len(raw)


# -- every error of validation, for each op kind, on a store of many
# -- collections, in the middle of a transaction ------------------------------

MANY = [CollectionId.make_pg(2, seed) for seed in range(40)]
ABSENT = CollectionId.make_pg(3, 0)


@pytest.fixture
def many(store):
    """40 collections of 5 objects beside the fixture's own."""
    txn = Transaction()
    for i, cid in enumerate(MANY):
        txn.create_collection(cid)
        for j in range(5):
            txn.write(cid, gh(f"o{j}"), 0, bytes([i, j]) * 50)
            txn.setattrs(cid, gh(f"o{j}"), {"a": b"%d" % j})
            txn.omap_setkeys(cid, gh(f"o{j}"), {"k": b"v"})
    store.queue_transaction(txn)
    return store


def _everything(store) -> dict:
    return {cid: {g.name: (bytes(store.read(cid, g)), store.getattrs(cid, g),
                           store.omap_get(cid, g))
                  for g in store.collection_list(cid)}
            for cid in store.list_collections()}


# (op kind, code, what the failing op is given a collection `c` that
# holds o0..o4, an absent collection `n` and an absent object `z`)
BAD_OPS = [
    ("touch", "ENOENT", lambda t, c, n, z: t.touch(n, z)),
    ("write", "ENOENT", lambda t, c, n, z: t.write(n, z, 0, b"w")),
    ("zero", "ENOENT", lambda t, c, n, z: t.zero(n, z, 0, 4)),
    ("truncate", "ENOENT", lambda t, c, n, z: t.truncate(n, z, 9)),
    ("setattrs", "ENOENT", lambda t, c, n, z: t.setattrs(n, z, {"a": b"1"})),
    ("omap_setkeys", "ENOENT",
     lambda t, c, n, z: t.omap_setkeys(n, z, {"k": b"1"})),
    ("omap_rmkeys", "ENOENT", lambda t, c, n, z: t.omap_rmkeys(n, z, ["k"])),
    ("omap_clear", "ENOENT", lambda t, c, n, z: t.omap_clear(n, z)),
    ("remove_no_coll", "ENOENT", lambda t, c, n, z: t.remove(n, gh("o0"))),
    ("remove_no_obj", "ENOENT", lambda t, c, n, z: t.remove(c, z)),
    ("remove_twice", "ENOENT",
     lambda t, c, n, z: t.remove(c, gh("o1")).remove(c, gh("o1"))),
    ("rmattr_no_coll", "ENOENT",
     lambda t, c, n, z: t.rmattr(n, gh("o0"), "a")),
    ("rmattr_no_obj", "ENOENT", lambda t, c, n, z: t.rmattr(c, z, "a")),
    ("clone_no_coll", "ENOENT", lambda t, c, n, z: t.clone(n, gh("o0"), z)),
    ("clone_no_src", "ENOENT", lambda t, c, n, z: t.clone(c, z, gh("o0"))),
    ("clone_range_no_coll", "ENOENT",
     lambda t, c, n, z: t.clone_range(n, gh("o0"), z, 0, 4, 0)),
    ("clone_range_no_src", "ENOENT",
     lambda t, c, n, z: t.clone_range(c, z, gh("o0"), 0, 4, 0)),
    ("move_no_src_coll", "ENOENT",
     lambda t, c, n, z: t.collection_move_rename(n, gh("o0"), c, z)),
    ("move_no_src_obj", "ENOENT",
     lambda t, c, n, z: t.collection_move_rename(c, z, c, gh("o0"))),
    ("move_no_dst_coll", "ENOENT",
     lambda t, c, n, z: t.collection_move_rename(c, gh("o0"), n, z)),
    ("mkcoll_exists", "EEXIST", lambda t, c, n, z: t.create_collection(c)),
    ("mkcoll_made_here", "EEXIST",
     lambda t, c, n, z: t.create_collection(n).create_collection(n)),
    ("rmcoll_absent", "ENOENT", lambda t, c, n, z: t.remove_collection(n)),
    ("rmcoll_removed_here", "ENOENT",
     lambda t, c, n, z: t.create_collection(n).remove_collection(n)
     .remove_collection(n)),
    ("rmcoll_not_empty", "ENOTEMPTY",
     lambda t, c, n, z: t.remove_collection(c)),
    ("rmcoll_one_left", "ENOTEMPTY",
     lambda t, c, n, z: [t.remove(c, gh(f"o{j}")) for j in range(4)]
     and t.remove_collection(c)),
    ("rmcoll_refilled_here", "ENOTEMPTY",
     lambda t, c, n, z: [t.remove(c, gh(f"o{j}")) for j in range(5)]
     and t.touch(c, z).remove_collection(c)),
]


@pytest.mark.parametrize("kind,code,bad", BAD_OPS,
                         ids=[b[0] for b in BAD_OPS])
def test_each_validation_error_rejects_the_whole_transaction(many, kind,
                                                             code, bad):
    """The failing op stands in the MIDDLE: ops that would apply come
    before it, in other collections and its own, and after it. Nothing
    of them shows."""
    before = _everything(many)
    assert len(before) == 41
    c, other = MANY[17], MANY[3]
    txn = Transaction()
    txn.write(other, gh("new"), 0, b"never").remove(other, gh("o2"))
    txn.write(c, gh("o4"), 0, b"NEVER").setattrs(c, gh("o3"), {"a": b"no"})
    txn.create_collection(CollectionId.make_pg(3, 1))
    bad(txn, c, ABSENT, gh("zz"))
    txn.touch(MANY[30], gh("after")).truncate(c, gh("o0"), 0)
    fired = []
    txn.register_on_applied(lambda: fired.append("applied"))
    txn.register_on_commit(lambda: fired.append("commit"))
    with pytest.raises(StoreError) as err:
        many.queue_transaction(txn)
    assert err.value.code == code
    assert _everything(many) == before and not fired
    assert many.perf.dump()["txns"] == 2       # the two set-up commits


def test_validation_names_only_the_transactions_own_collections(many):
    """With 41 collections resident, validating a transaction that
    names two of them looks up those two and walks none: no collection
    is iterated, copied or measured but the one `rmcoll` must find
    empty."""
    class Spy(dict):
        walked: list = []

        def __iter__(self):
            Spy.walked.append("iter")
            return super().__iter__()

        def keys(self):
            Spy.walked.append("keys")
            return super().keys()

        def copy(self):
            Spy.walked.append("copy")
            return super().copy()

    for cid in list(many._colls):
        many._colls[cid] = Spy(many._colls[cid])
    seen = []
    real = many._colls.get
    many._colls = type("Colls", (dict,), {
        "get": lambda self, cid, d=None: (seen.append(cid), real(cid, d))[1],
        "items": lambda self: 1 / 0, "values": lambda self: 1 / 0,
    })(many._colls)
    a, b = MANY[5], MANY[6]
    commit(many, lambda t: t.touch(a, gh("one")).remove(a, gh("o1"))
           .write(b, gh("o2"), 0, b"w").clone(b, gh("o2"), gh("o9"))
           .collection_move_rename(a, gh("o3"), b, gh("moved")))
    assert Spy.walked == []
    assert set(seen) == {a, b}
    assert sorted(g.name for g in many.collection_list(b)) == \
        ["moved", "o0", "o1", "o2", "o3", "o4", "o9"]


def test_validation_does_not_grow_with_the_stores_population(store):
    """10 against 10,000 resident objects: a small transaction's
    validation does the same work (within a generous factor, on a
    median of many)."""
    def validate_us(n: int) -> float:
        s = MemStore()
        s.queue_transaction(Transaction().create_collection(CID))
        txn = Transaction()
        for i in range(n):
            txn.touch(CID, gh(f"r{i}"))
        s.queue_transaction(txn)
        small = Transaction().touch(CID, gh("x")).write(CID, gh("r1"), 0, b"w")
        times = []
        for _ in range(200):
            t0 = time.perf_counter()
            s._validate(small)
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2] * 1e6

    few, lots = validate_us(10), validate_us(10_000)
    assert lots < 5 * few + 20, (few, lots)


# -- FileStore: a subclass whose blobs must persist as before -----------------

@pytest.mark.parametrize("shape", ["readonly_view", "sliver", "bytes",
                                   "bytearray"])
def test_filestore_persists_and_reloads_what_it_was_handed(tmp_path, shape):
    raw = random.Random(3).randbytes(20 * KIB)
    data = {"bytes": raw, "bytearray": bytearray(raw),
            "readonly_view": rx_view(raw),
            "sliver": rx_view(raw, 200 * KIB)}[shape]
    fs = FileStore(str(tmp_path / "fs"))
    fs.mkfs()
    fs.mount()
    fs.queue_transaction(Transaction().create_collection(CID)
                         .write(CID, gh("o"), 0, data)
                         .setattrs(CID, gh("o"), {"a": b"1"}))
    got = fs.read(CID, gh("o"))
    assert type(got) is bytes and got == raw
    fs.queue_transaction(Transaction().write(CID, gh("o"), 10 * KIB, b"mid"))
    want = raw[:10 * KIB] + b"mid" + raw[10 * KIB + 3:]
    assert fs.read(CID, gh("o"), 10 * KIB - 1, 5) == want[10 * KIB - 1:][:5]
    fs.umount()
    del data
    again = FileStore(str(tmp_path / "fs"))
    again.mount()
    assert again.read(CID, gh("o")) == want
    assert type(again.read(CID, gh("o"))) is bytes
    assert again.stat(CID, gh("o"))["size"] == len(raw)
    assert again.getattrs(CID, gh("o")) == {"a": b"1"}
    again.umount()
