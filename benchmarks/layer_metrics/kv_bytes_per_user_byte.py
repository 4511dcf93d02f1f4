"""Bytes the stores' KVs wrote for each byte the clients wrote: every
group's `kv_bytes` (the log's records, the deferred writes' among them,
and the runs that a flush or a compaction wrote since the group before:
`bstore_kv_sync` counts the KV's bytes from span to span) over the
window's user bytes. `benchmarks/reference_deferred.py` gives the
least, shards once on the logs and nothing else, (k+m)/k = 1.375 at
64 KiB on 8+3; the reader prints it beside the reading on a
`benchmark:` line of standard error. What lies between the two is
onodes, PG log entries, the freelist value, records carried into runs,
and compaction."""
import sys

from benchmarks import reference_deferred
from benchmarks.layer_metrics import deferred_spans

NAME = "kv_bytes_per_user_byte"
UNIT = "B/B"
LAYER = "objectstore"
MOVES = "ops_s"
AU = 4096       # the configuration's `assumed.min_alloc_size`


def least(config: dict) -> float:
    pool = config["pool"]
    line = config.get("osd_config", {}).get(
        "bluestore_prefer_deferred_size", 0)
    return reference_deferred.least_device_bytes(
        config["object_size"], pool["k"], pool["m"], pool["stripe_unit"],
        AU, line)["kv_bytes_per_user_byte"]


def read(ctx):
    groups = deferred_spans.groups(ctx)
    written = ctx.user_bytes.get("write", 0)
    if not groups or not written:
        return None
    value = sum(g["kv_bytes"] for g in groups) / written
    print(f"benchmark: {NAME} = {value} (reference_deferred least "
          f"{least(ctx.cell.config)})", file=sys.stderr)
    return value
