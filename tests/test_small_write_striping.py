"""A small whole-object write on an erasure pool, the program against
`benchmarks/reference_small.py`: how `write_full` stripes n bytes, what
each of the k+m OSDs then holds, and what `read` gives back, at the
sizes round a chunk (4,096), a stripe of 8+3 (32,768) and the 64 KiB
line the messenger and BlueStore draw (`msg/transport.SPILL_SIZE`;
`bluestore.INLINE_MAX`, under which a BlueStore write is deferred: KV
log first, block file after), one byte either side of each.

One live cluster a profile serves every size in a module fixture (2+1
on three OSDs; the north-star pool's own 8+3 on eleven), on the pool's
own plugin, through the offload service; each size is a case of its
own.
"""
from __future__ import annotations

import numpy as np
import pytest

from benchmarks import harness, reference_small
from ceph_tpu import offload
from ceph_tpu.tools.cluster_boot import ephemeral_cluster

from tests.test_cluster import run

CHUNK = 4096
SIZES = [1, 4095, 4096, 32768, 65535, 65536, 65537]
POOL = "small"


def _value(size: int) -> bytes:
    return np.random.default_rng([size, 49]).bytes(size)


async def _serve(k: int, m: int) -> dict:
    """Write every size as an object of its own, read it back and take
    the shards the OSDs hold; `used` is what the stores grew by."""
    out = {}
    async with ephemeral_cluster(k + m, prefix="small-") as (client, osds,
                                                              _mon):
        await client.command({"prefix": "osd erasure-code-profile set",
                              "name": "prof", "profile": {
                                  "plugin": "tpu", "k": str(k), "m": str(m),
                                  "technique": "reed_sol_van"}})
        await client.pool_create(POOL, pg_num=4, pool_type="erasure",
                                 erasure_code_profile="prof")
        io = client.ioctx(POOL)
        svc = offload.get_service()
        for osd in osds:
            osd.store.USED_BYTES_TTL = 0.0      # every reading is fresh
        for size in SIZES:
            name = f"obj{size}"
            used = sum(o.store.used_bytes() for o in osds)
            enc = svc.stats["enc_bytes"]
            await io.write_full(name, _value(size))
            out[size] = {
                "read": bytes(await io.read(name)),
                "blobs": harness._shard_blobs(osds, POOL, name),
                "used": sum(o.store.used_bytes() for o in osds) - used,
                "encoded": svc.stats["enc_bytes"] - enc}
        out["fallback_ops"] = svc.stats["fallback_ops"]
    return out


@pytest.fixture(scope="module")
def served_21():
    return run(_serve(2, 1), timeout=120)


@pytest.fixture(scope="module")
def served_83():
    return run(_serve(8, 3), timeout=300)


def _check(served, k, m, size):
    rec, value = served[size], _value(size)
    lay = reference_small.layout(size, k, m, CHUNK)
    want = reference_small.shards(value, k, m, CHUNK)
    assert rec["read"] == value
    assert sorted(rec["blobs"]) == list(range(k + m))
    for j, blob in rec["blobs"].items():
        assert len(blob) == lay["shard_bytes"]
        assert blob == want[j].tobytes(), f"shard {j}"
    least = reference_small.least_bytes(size, k, m, CHUNK)
    assert rec["used"] == least["at_rest"]
    # one job of the offload service, at the padded length and no more
    assert rec["encoded"] == least["link_up"] == lay["padded_bytes"]
    assert served["fallback_ops"] == 0


@pytest.mark.parametrize("size", SIZES)
def test_write_full_stripes_as_the_reference_at_2_1(served_21, size):
    _check(served_21, 2, 1, size)


@pytest.mark.parametrize("size", SIZES)
def test_write_full_stripes_as_the_reference_at_8_3(served_83, size):
    _check(served_83, 8, 3, size)
