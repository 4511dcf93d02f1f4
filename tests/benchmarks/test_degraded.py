"""The degraded deployment (`radosbench_ec83_tpu_degraded`, cell
`rb4m_degraded_seqread`): the plain reference for reconstruction against
the program's decode for every pattern of one, two or three lost shards
at k=8 m=3, the new readers on hand-built spans and counters, and the
cell served tiny on the CPU backend."""
from __future__ import annotations

import asyncio
import itertools
import json
import os
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, reference, reference_decode  # noqa: E402
from tests.benchmarks.test_benchmarks import BENCH, _tiny  # noqa: E402

CELL = "rb4m_degraded_seqread"
CONFIG = "radosbench_ec83_tpu_degraded"
K, M, CHUNK, STRIPES = 8, 3, 1024, 4
PATTERNS = [lost for r in (1, 2, 3)
            for lost in itertools.combinations(range(K + M), r)]
NEW = ["degraded_read_pct", "ec_decode_ms", "decode_ops_per_batch",
       "decode_handoff_ms", "decode_device_call_ms",
       "decode_link_bytes_per_byte", "decode_bitmatrix_roofline"]
FROM_TRACE = {"device_idle_pct", "decode_bitmatrix_roofline"}
HOPS = {"sem_wait_us": 100.0, "pool_wait_us": 200.0, "resume_us": 700.0,
        "h2d_submit_us": 1000.0, "launch_us": 2000.0,
        "result_wait_us": 500.0}


def _reader(name):
    return harness._load_module(ROOT, "layer_metrics", name)


# -- the plain reference against the program, every pattern ---------------------

def test_reference_shares_nothing_with_the_program():
    src = open(os.path.join(ROOT, "benchmarks",
                            "reference_decode.py")).read()
    assert "ceph_tpu" not in src.split('"""', 2)[2]
    assert "benchmarks.reference import" in src


@pytest.fixture(scope="module")
def code():
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    return ErasureCodePluginRegistry.instance().factory(
        "tpu", {"plugin": "tpu", "k": str(K), "m": str(M),
                "technique": "reed_sol_van"})


def _stripes(lost):
    """A seeded random value, its k+m shard rows by the reference, and
    the rows that survive `lost`."""
    rng = np.random.default_rng([2 ** 31 + 26, *lost])
    value = rng.bytes(STRIPES * K * CHUNK)
    shards = reference.expected_shards(value, K, M, CHUNK)
    return value, shards, {j: shards[j] for j in range(K + M)
                           if j not in lost}


@pytest.mark.parametrize("lost", PATTERNS,
                         ids=["-".join(map(str, p)) for p in PATTERNS])
def test_program_reconstructs_as_the_reference_does(lost, code):
    """`reconstruct(expected_shards(v))` returns v; the plugin's batched
    decode rebuilds every lost shard, and `ec_util.decode_concat` the
    object, byte for byte as the reference gives them."""
    from ceph_tpu.osd import ec_util

    value, shards, alive = _stripes(lost)
    data = reference_decode.reconstruct(alive, K, M)
    assert np.array_equal(data, shards[:K])
    # rows back to the object's bytes: stripe s, shard j -> s*K + j
    assert data.reshape(K, STRIPES, CHUNK).transpose(1, 0, 2).tobytes() \
        == value
    avail = tuple(sorted(alive))[:K]
    stacked = np.stack([alive[j].reshape(STRIPES, CHUNK) for j in avail],
                       axis=1)
    rebuilt = code.decode_stripes(avail, lost, stacked)
    assert rebuilt.shape == (STRIPES, len(lost), CHUNK)
    for row, j in enumerate(lost):
        want = data[j] if j < K else shards[j]
        assert np.array_equal(rebuilt[:, row, :].reshape(-1), want), j
    sinfo = ec_util.StripeInfo(K, K * CHUNK)
    got = ec_util.decode_concat(
        sinfo, code, {j: row.tobytes() for j, row in alive.items()})
    assert got == value


def test_reference_refuses_what_cannot_be_solved():
    _value, shards, _alive = _stripes((0,))
    with pytest.raises(ValueError):
        reference_decode.reconstruct(
            {j: shards[j] for j in range(K - 1)}, K, M)
    with pytest.raises(ValueError):
        reference_decode.reconstruct({K + M: shards[0]}, 1, M)


def test_a_decode_runs_the_encode_program_of_its_shape(code):
    """No erasure pattern and no count of lost chunks compiles anything
    once stripes of the shape have been encoded: the recovery matrix is
    padded to m rows, and the bitmatrix is an argument of the program."""
    import jax

    compiles = []

    def on_compile(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    shape = (16, K, CHUNK)
    dev = jax.device_put(np.zeros(shape, dtype=np.uint8))
    np.asarray(code.encode_stripes(dev))
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        for lost in ((3,), (0, 9), (1, 2, 7), (8,), (5, 10)):
            avail = tuple(j for j in range(K + M) if j not in lost)[:K]
            out = code.decode_stripes(avail, lost, dev)
            assert out.shape == (16, M, CHUNK)       # device in: m rows
            assert not np.asarray(out)[:, len(lost):].any()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert compiles == []


# -- the configuration and its traffic ----------------------------------------

def entries_stand(bench, root=ROOT):
    """The configuration, the cell and the seven entries, each found by
    its name: they name this cell; since PR 41 the six that read a
    decode name the cell that reads fast too, and two accepted entries
    before them (the loop's share in the batcher, the median `ec_read`)
    name this one, which has had that work all along. A later cell may
    join any of the lists."""
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert len(entry["source"]) <= 200
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)
    entries = bench["per_layer"]
    names = [m["name"] for m in entries]
    at = names.index(NEW[0])
    assert names[at:at + 7] == NEW
    assert all(CELL in m["workloads"] for m in entries[at:at + 7])
    assert [m["name"] for m in entries[:at]
            if CELL in m.get("workloads", [])] == [
        "loop_offload_pct", "ec_read_ms"]
    loaded = {r.NAME for r in harness.load_cell(CELL, root=root).readers}
    assert set(NEW) | {"loop_offload_pct", "ec_read_ms"} <= loaded


def test_configuration_and_traffic_describe_one_failure():
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    config = json.load(open(os.path.join(ROOT, entry["file"])))
    traffic = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", CELL + ".json")))
    pool = config["pool"]
    assert config["osds_down"] == traffic["stop_osds"] == 1
    assert config["min_size"] == pool["k"] + 1
    assert config["osds"] - config["osds_down"] >= config["min_size"]
    assert "device_touch" not in traffic
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    sibling = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "radosbench_ec83_tpu.json")))
    for key in ("pool", "osds", "object_size", "concurrent_ops",
                "osd_config", "generator", "offload_service"):
        assert config[key] == sibling[key], key
    assert sorted(config["reduced"]) == sorted(sibling["reduced"])
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, CELL, 1)


def test_the_seven_entries_are_appended_for_this_cell_first():
    entries_stand(BENCH)
    by = {m["name"]: m for m in BENCH["per_layer"]}
    # the share of reads that reconstruct is this cell's alone: where
    # reads are fast, `fastread_decode_pct` is that share
    assert "rb4m_fastread_seqread" not in by["degraded_read_pct"][
        "workloads"]


def test_mon_keeps_a_down_osd_in_for_upstreams_ten_minutes():
    from ceph_tpu.mon.monitor import OSDMonitor

    assert OSDMonitor.DOWN_OUT_INTERVAL == 600.0


# -- the readers on hand-built spans and counters ------------------------------------

def _batch(kind=None, **tags):
    t = {**HOPS, "ops": 1, "bytes": 4 << 20, **tags}
    if kind is not None:
        t["kind"] = kind
    return {"name": "offload_batch", "duration_us": 5000.0, "tags": t}


def _span(name, us=1000.0, **tags):
    return {"name": name, "duration_us": us, "tags": tags}


def _ctx(spans=None, offload=(None, None), copy=(None, None), read=0,
         trace=None, peaks=None):
    groups = [{}, {}]
    for side in (0, 1):
        if offload[side] is not None:
            groups[side]["offload"] = offload[side]
        if copy[side] is not None:
            groups[side]["copy"] = {s: {"copied_bytes": b}
                                    for s, b in copy[side].items()}
    cell = types.SimpleNamespace(config={"pool": {"k": K, "m": M}})
    return types.SimpleNamespace(
        spans=spans or {}, open=groups[0], close=groups[1],
        user_bytes={"write": 0, "read": read}, trace=trace, peaks=peaks,
        cell=cell)


PEAKS = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
TRACE = {"programs": {"jit__apply_bitmatrix_batched_jit": 0.002},
         "busy_s": 0.002, "window_s": 1.0}
OLD_STATS = {"jobs": 9, "batches": 7}        # the parent's `svc.stats`


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("case", ["nothing", "untagged_batches",
                                  "parents_counters"])
def test_reader_finds_nothing_on_a_program_without_the_tags(name, case):
    """The driver runs the parent on this cell: its `offload_batch`
    spans carry no `kind`, its `svc.stats` no `dec_*`."""
    ctx = {
        "nothing": _ctx(),
        "untagged_batches": _ctx(
            spans={"offload_batch": [_batch(), _batch()]},
            trace=TRACE, peaks=PEAKS),
        "parents_counters": _ctx(offload=(OLD_STATS, OLD_STATS),
                                 trace=TRACE, peaks=PEAKS),
    }[case]
    assert _reader(name).read(ctx) is None


def test_readers_read_decode_batches_only():
    enc = _batch("enc", sem_wait_us=90000.0)
    dec = [_batch("dec", r=1, pattern="0,1,2,4,5,6,7,8>3"),
           _batch("dec", r=1, pattern="0,1,2,4,5,6,7,8>3",
                  resume_us=900.0, launch_us=2600.0),
           _batch("dec", r=2, pattern="1,2,4,5,6,7,8,9>0,3",
                  resume_us=1100.0, launch_us=3000.0)]
    ctx = _ctx(spans={"offload_batch": [enc] + dec})
    assert _reader("decode_handoff_ms").read(ctx) == pytest.approx(1.2)
    assert _reader("decode_device_call_ms").read(ctx) == pytest.approx(4.1)
    # the accepted readers go on reading every batch
    assert _reader("offload_handoff_ms").read(ctx) == pytest.approx(1.3)


def test_degraded_share_and_decode_median():
    spans = {"ec_read": [_span("ec_read")] * 11,
             "ec_decode": [_span("ec_decode", us)
                           for us in (4000.0, 9000.0, 5000.0, 7000.0,
                                      6000.0, 8000.0, 3000.0, 10000.0)]}
    ctx = _ctx(spans=spans)
    assert _reader("degraded_read_pct").read(ctx) == \
        pytest.approx(100 * 8 / 11)
    assert _reader("ec_decode_ms").read(ctx) == pytest.approx(6.5)
    # a healthy pool opens no `ec_decode`: nothing to read, not 0
    assert _reader("degraded_read_pct").read(
        _ctx(spans={"ec_read": spans["ec_read"]})) is None


def test_counters_are_deltas_over_the_window():
    before = dict(OLD_STATS, dec_jobs=10, dec_batches=8)
    after = dict(OLD_STATS, dec_jobs=40, dec_batches=28)
    ctx = _ctx(offload=(before, after), read=100 << 20,
               copy=({"h2d": 1 << 20, "d2h": 1 << 20},
                     {"h2d": (1 << 20) + (80 << 20),
                      "d2h": (1 << 20) + (30 << 20)}))
    assert _reader("decode_ops_per_batch").read(ctx) == pytest.approx(1.5)
    assert _reader("decode_link_bytes_per_byte").read(ctx) == \
        pytest.approx(1.1)
    still = _ctx(offload=(after, after), read=100 << 20,
                 copy=({"h2d": 5, "d2h": 5}, {"h2d": 5, "d2h": 5}))
    assert _reader("decode_ops_per_batch").read(still) is None
    assert _reader("decode_link_bytes_per_byte").read(still) is None


def test_roofline_reckons_each_batch_at_its_true_r():
    mod = _reader("decode_bitmatrix_roofline")
    dec = [_batch("dec", r=1), _batch("dec", r=1, bytes=8 << 20),
           _batch("dec", r=3)]
    ctx = _ctx(spans={"offload_batch": dec}, trace=TRACE, peaks=PEAKS)
    least = ((4 << 20) * 9 / 8 + (8 << 20) * 9 / 8
             + (4 << 20) * 11 / 8) / 819e9         # HBM binds
    assert mod.read(ctx) == pytest.approx(100 * least / 0.002)
    assert 0 < mod.read(ctx) < 100
    # an encode in the window ran the same program: no time of its own
    mixed = _ctx(spans={"offload_batch": dec + [_batch("enc")]},
                 trace=TRACE, peaks=PEAKS)
    assert mod.read(mixed) is None
    assert mod.read(_ctx(spans={"offload_batch": dec}, peaks=PEAKS)) is None


# -- the cell served, tiny, on the CPU backend ------------------------------------

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One traced run of the cell through `_tiny` (3 OSDs, k=2 m=1, one
    stopped). What the harness keeps to itself is caught on the way:
    the map epochs at the window's edges and the window's spans."""
    seen = {"epochs": [], "spans": None, "stopped": None}
    real_stop, real_snap, real_spans = (harness.stop_osds,
                                        harness._snapshot,
                                        harness.window_spans)

    async def stop_osds(n, seed, osds, client):
        seen["stopped"] = await real_stop(n, seed, osds, client)
        seen["maps"] = [client] + [o for o in osds
                                   if o.whoami not in seen["stopped"]]
        return seen["stopped"]

    def snapshot(svc):
        seen["epochs"].append([d.osdmap.epoch for d in seen["maps"]])
        return real_snap(svc)

    def window_spans(spans):
        seen["spans"] = real_spans(spans)
        return seen["spans"]
    harness.stop_osds, harness._snapshot, harness.window_spans = \
        stop_osds, snapshot, window_spans
    try:
        done, cell = _tiny(CELL, trace=True, seconds=1.0,
                           tmp=tmp_path_factory.mktemp("served"))
    finally:
        harness.stop_osds, harness._snapshot, harness.window_spans = \
            real_stop, real_snap, real_spans
    return done, cell, seen


def test_tiny_served_run_is_correct_and_reconstructs(served):
    done, cell, seen = served
    line = done["result"]
    assert line["correct"] is True and line["failed"] == 0
    assert all(value <= limit for _n, value, limit in done["checks"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(m) == {r.NAME for r in cell.readers} - FROM_TRACE
    assert set(NEW) - FROM_TRACE <= set(m)
    assert m["ec_read_ms"] > 0 and 0 < m["loop_offload_pct"] < 100
    assert 0 < m["degraded_read_pct"] <= 100
    assert m["compiles_in_window"] == 0
    assert done["info"]["compiles_in_window"] == 0
    assert m["decode_ops_per_batch"] >= 1
    assert m["ec_decode_ms"] > 0 and m["decode_device_call_ms"] > 0
    # k=2: two survivors up, m=1 row back, for each read that decodes
    assert m["decode_link_bytes_per_byte"] == pytest.approx(
        m["degraded_read_pct"] / 100 * 1.5, rel=0.25)


def test_tiny_served_run_sees_one_failure_and_a_still_map(served):
    done, _cell, seen = served
    assert len(seen["stopped"]) == 1
    checks = {n: v for n, v, _l in done["checks"]}
    assert checks["osd_markdowns_under_load"] == 0
    assert checks["fallback_ops"] == checks["breaker_trips"] == \
        checks["device_failovers"] == 0
    # no osdmap epoch between the window's edges, on any daemon
    opened, closed = seen["epochs"]
    assert opened == closed and len(set(opened)) == 1
    spans = seen["spans"]
    assert not spans.get("ec_recover")
    decodes = spans["ec_decode"]
    assert all(s["tags"]["offload"] and len(s["tags"]["missing"]) == 1
               and {"stack_us", "offload_us", "assemble_us"} <= set(s["tags"])
               for s in decodes)
    batches = [s["tags"] for s in spans["offload_batch"]]
    assert {t["kind"] for t in batches} == {"dec"}
    assert all(t["r"] == 1 and ">" in t["pattern"] for t in batches)
    # a batch may close inside the window and its read outside it
    assert abs(sum(t["ops"] for t in batches) - len(decodes)) <= 4


def test_controls_make_the_degraded_cell_incorrect(tmp_path):
    """Both controls in one run, each caught by a check of its own: one
    read of the window altered (`flip_read`), one byte of one surviving
    shard at rest rotted (`bitrot`). At `_tiny`'s k=2 m=1 a rotted
    survivor beside the lost shard is one loss more than m and the
    object is gone, so this run is cut to k=2 m=2 on four OSDs (and to
    the program's own heartbeat grace: the wait is not what is tested)."""
    cell = harness.load_cell(CELL)
    cell.config = dict(
        cell.config, osds=4, object_size=65536,
        pool=dict(cell.config["pool"], k=2, m=2, pg_num=8),
        osd_config={"osd_scrub_interval": 86400.0})
    cell.traffic = dict(cell.traffic, clients=4, warmup_ops=8,
                        payload_pool=4, preload_objects=8)
    done = asyncio.run(harness.run_cell(
        cell, 2 ** 31 + 5, 0.6, False, str(tmp_path), time.monotonic(),
        ("bitrot", "flip_read")))
    checks = {n: v for n, v, _l in done["checks"]}
    assert checks["read_mismatches"] == 1           # flip_read alone
    assert checks["shard_bytes_differing"] == 1     # bitrot alone
    assert checks["sample_read_mismatches"] == 0    # read round the rot
    assert done["result"]["correct"] is False
    assert done["result"]["failed"] == 1
