"""The benchmark's own tests: its parts run tiny on the CPU backend,
through functions and never through the entry that looks for the chip.
"""
from __future__ import annotations

import asyncio
import json
import math
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, reference, trace_reduce  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
# cells whose files are in the tree but which BENCHMARK.json does not
# list (PERF.md, Open questions): a later PR adds exactly these entries
YCSB_CONFIG = {
    "name": "ycsb_rados_ec83_tpu", "source": "github.com/brianfrankcooper/YCSB",
    "file": "benchmarks/configs/ycsb_rados_ec83_tpu.json",
    "reduced": ["hosts", "objectstore", "operationcount"], "why": "see PERF.md"}
SHELVED = ["ycsb_a", "ycsb_b"]
TESTDATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- BENCHMARK.json and the files it names ---------------------------------------

def contract_holds(bench, root=ROOT):
    """What BENCHMARK.json has to keep, of any copy of it: the file as
    it stands, and one with a later PR's entries appended
    (`test_appended_entries.py`)."""
    cells = [w["name"] for w in bench["workloads"]]
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks", "tests/benchmarks"]
    assert 1 <= bench["run_seconds"] <= 51
    end_to_end = [m["name"] for m in bench["end_to_end"]]
    names = end_to_end + [m["name"] for m in bench["per_layer"]]
    assert len(names) == len(set(names))
    assert end_to_end == ["ops_s", "op_p50_ms", "op_p95_ms", "setup_s"]
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in end_to_end
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len(cells) == len(set(cells))
    configs = [c["name"] for c in bench["configs"]]
    assert len(configs) == len(set(configs))
    for c in bench["configs"]:
        assert c["file"].startswith("benchmarks/")
        body = json.load(open(os.path.join(root, c["file"])))
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert all(key in body for key in c["reduced"])
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["chips"] == 1 and w["config"] in configs


def test_benchmark_json_keeps_the_contract():
    contract_holds(BENCH)


@pytest.mark.parametrize("cell", CELLS + SHELVED)
def test_cell_resolves_to_its_files(cell, with_ycsb):
    c = _load(cell, with_ycsb)
    assert callable(c.generator.make)
    assert c.config["generator"] in ("radosbench", "ycsb")
    assert {m["name"] for m in c.end_to_end} == \
        {"ops_s", "op_p50_ms", "op_p95_ms", "setup_s"}
    assert c.readers and all(callable(r.read) for r in c.readers)
    always = {"loop_busy_pct", "device_idle_pct", "compiles_in_window"}
    assert always <= {r.NAME for r in c.readers}


def appended_copy(tmp):
    """A copy of the benchmark under `tmp` with a later PR's entries
    **appended at the end** and its files beside the accepted ones,
    nothing that exists edited: a configuration on a persistent store
    with a `mon_config`, a cell of it whose traffic has `events`, and
    two per-layer entries (one that lists the new cell, one in every
    cell). Returns `(root, bench)`."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    config = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "radosbench_ec83_tpu.json")))
    config.update(name="radosbench_ec83_tpu_bluestore",
                  objectstore="bluestore",
                  mon_config={"osd_pool_default_ec_fast_read": False},
                  reduced={k: v for k, v in config["reduced"].items()
                           if k != "objectstore"})
    config["guarantees"]["durability"] = \
        "an acknowledged write is read back from a fresh mount of the store"
    (tmp / "benchmarks/configs/radosbench_ec83_tpu_bluestore.json"
     ).write_text(json.dumps(config))
    bench["configs"].append({
        "name": config["name"], "source": config["source"] + "; BlueStore",
        "file": "benchmarks/configs/radosbench_ec83_tpu_bluestore.json",
        "reduced": sorted(config["reduced"]), "why": "see README"})
    bench["workloads"].append({
        "name": "rb4m_restart_write", "config": config["name"],
        "traffic": "rb4m_restart_write", "chips": 1, "why": "see README"})
    (tmp / "benchmarks/traffic/rb4m_restart_write.json").write_text(
        json.dumps({"op": "write", "clients": 16, "preload_objects": 128,
                    "warmup_ops": 64, "payload_pool": 64, "events": [
                        {"at_s": 4, "do": "stop_osd", "osd": 0},
                        {"at_s": 24, "do": "start_osd", "osd": 0}]}))
    for name, only in (("restart_ops", ["rb4m_restart_write"]),
                       ("store_fsyncs_per_op", None)):
        entry = {"name": name, "unit": "count", "better": "lower",
                 "source": "program_counter", "layer": "objectstore",
                 "moves": "ops_s"}
        if only:
            entry["workloads"] = only
        bench["per_layer"].append(entry)
        (tmp / f"benchmarks/layer_metrics/{name}.py").write_text(
            f'NAME = "{name}"\nUNIT = "count"\nLAYER = "objectstore"\n'
            'MOVES = "ops_s"\n\n\ndef read(ctx):\n    return None\n')
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp), bench


def test_later_pr_adds_a_cell_with_new_files_only(tmp_path):
    """benchmarks/README.md's worked example: a configuration, a cell
    and two readers are files of their own and entries appended at the
    end; nothing that exists is edited, and every accepted cell loads
    what it loaded but for the reader that lists every cell."""
    root, bench = appended_copy(tmp_path)
    cell = harness.load_cell("rb4m_restart_write", root=root)
    assert cell.config["objectstore"] == "bluestore"
    assert cell.config["mon_config"] == {
        "osd_pool_default_ec_fast_read": False}
    assert [e["do"] for e in harness.schedule_of(cell.traffic)] == [
        "stop_osd", "start_osd"]
    assert callable(harness.store_factory(cell.config["objectstore"], []))
    names = {r.NAME for r in cell.readers}
    assert {"restart_ops", "store_fsyncs_per_op"} <= names
    assert "ec_encode_ms" not in names
    for name in CELLS:
        before = [r.NAME for r in harness.load_cell(name).readers]
        after = [r.NAME for r in harness.load_cell(name, root=root).readers]
        assert after == before + ["store_fsyncs_per_op"]
    with pytest.raises(SystemExit):
        harness.load_cell("no_such_cell", root=root)


# -- generators ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def with_ycsb(tmp_path_factory):
    """A copy of the benchmark with the shelved YCSB cells entered the
    way a later PR would enter them: entries only, no file edited."""
    root = tmp_path_factory.mktemp("with_ycsb")
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append(YCSB_CONFIG)
    for name in SHELVED:
        bench["workloads"].append({
            "name": name, "config": YCSB_CONFIG["name"], "traffic": name,
            "chips": 1, "why": "see PERF.md"})
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + ["ycsb_a", "ycsb_b"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def _load(cell, with_ycsb):
    return harness.load_cell(cell, root=with_ycsb if cell in SHELVED
                             else ROOT)


def _ops(cell, seed, with_ycsb, n=4096):     # one whole YCSB block
    c = _load(cell, with_ycsb)
    config = dict(c.config, object_size=4096)
    gen = c.generator.make(config, c.traffic, seed)
    return gen, gen.preload(), [gen.next_op() for _ in range(n)]


@pytest.mark.parametrize("cell", CELLS + SHELVED)
def test_generator_repeats_for_a_seed_and_keeps_names_across_seeds(
        cell, with_ycsb):
    big = 2 ** 31 + 11          # the driver's seeds exceed 32 signed bits
    gen_a, pre_a, ops_a = _ops(cell, big, with_ycsb)
    gen_b, pre_b, ops_b = _ops(cell, big, with_ycsb)
    gen_c, pre_c, ops_c = _ops(cell, 7, with_ycsb)
    assert (pre_a, ops_a) == (pre_b, ops_b)
    # every seed sends the same work: the same names as often, in
    # another order (YCSB) or the same order (rados bench)
    for part in (0, 1):         # op types, then names
        assert sorted(o[part] for o in ops_a) == \
            sorted(o[part] for o in ops_c)
    if cell in SHELVED:
        assert ops_a != ops_c
    assert sorted(o[1] for o in pre_a) == sorted(o[1] for o in pre_c)
    name, version = next((o[1], o[2]) for o in pre_a + ops_a
                         if o[0] == "write")
    assert gen_a.value_of(name, version) == gen_b.value_of(name, version)
    assert gen_a.value_of(name, version) != gen_c.value_of(name, version)
    assert len(gen_a.value_of(name, version)) == gen_a.object_bytes


def test_ycsb_shares_and_zipfian_constant(with_ycsb):
    c = harness.load_cell("ycsb_a", root=with_ycsb)
    from_b = harness.load_cell("ycsb_b", root=with_ycsb)
    n = 20_000
    for cell, want in ((c, 0.5), (from_b, 0.95)):
        gen = cell.generator.make(cell.config, cell.traffic, 3)
        ops = [gen.next_op() for _ in range(n)]
        assert abs(sum(o[0] == "read" for o in ops) / n - want) < 0.01
        assert all(o[1] in set(gen.names) for o in ops)
    gen = c.generator.make(c.config, c.traffic, 4)
    ranks = gen.draw_ranks(n) + 1
    weights = 1.0 / np.arange(1, gen.records + 1) ** 0.99
    assert abs(np.mean(ranks == 1) - weights[0] / weights.sum()) < 0.01
    # the constant, by maximum likelihood: E_s[log r] = mean(log r)
    target = float(np.mean(np.log(ranks)))
    logs = np.log(np.arange(1, gen.records + 1))

    def expected(s):
        w = np.exp(-s * logs)
        return float((w * logs).sum() / w.sum())
    lo, hi = 0.5, 1.5
    for _ in range(40):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if expected(mid) > target else (lo, mid)
    assert abs(lo - 0.99) / 0.99 < 0.01
    # hot ranks are scattered over the key space, as YCSB scrambles them
    hot = [gen._key_of_rank[r] for r in range(10)]
    assert hot != sorted(hot)
    assert gen.names[0] == "user6284781860667377211"     # YCSB's first key


# -- the plain reference ---------------------------------------------------------------

def test_reference_agrees_with_the_programs_codec():
    """The reference shares nothing with the program; here they meet."""
    from ceph_tpu.ec import gf256

    for k, m in ((8, 3), (2, 1), (4, 2)):
        assert np.array_equal(reference.reed_sol_van_matrix(k, m),
                              gf256.reed_sol_van_matrix(k, m))
    value = np.random.default_rng(0).bytes(70_000)
    shards = reference.expected_shards(value, 8, 3, 4096)
    assert shards.shape == (11, 3 * 4096)
    assert np.array_equal(
        shards[8:], gf256.mat_vec_apply(gf256.reed_sol_van_matrix(8, 3),
                                        shards[:8]))
    assert bytes(shards[1, :4096]) == value[4096:8192]
    assert bytes(shards[0, 4096:8192]) == value[32768:36864]


def test_object_model_orders_only_what_time_ordered():
    m = reference.ObjectModel()
    m.seed("a", 0)
    m.begin_write("a", 1)
    snap = m.begin_read("a")
    m.begin_write("a", 2)           # starts while the read runs
    assert m.end_read("a", snap) == {0, 1, 2}
    m.ack_write("a", 1)             # 0 ended before 1 began
    assert m.candidates("a") == {1, 2}
    m.ack_write("a", 2)             # 1 and 2 overlapped: either may stand
    assert m.candidates("a") == {1, 2}
    m.begin_write("a", 3)
    m.ack_write("a", 3)
    assert m.candidates("a") == {3}
    assert m.end_read("a", m.begin_read("a")) == {3}
    # two reads hold equal sets; the later one ends first; the earlier
    # one must still see a write that starts afterwards
    first, second = m.begin_read("a"), m.begin_read("a")
    m.end_read("a", second)
    m.begin_write("a", 4)
    assert m.end_read("a", first) == {3, 4}


def test_latency_arithmetic():
    assert harness.percentile([1.0, 2.0, 3.0, 4.0], 0.5) == 2.0
    assert harness.percentile(list(map(float, range(1, 101))), 0.95) == 95.0
    with pytest.raises(ValueError):
        harness.percentile([], 0.5)
    recs = [("read", 0.1, 0.4, True), ("read", 0.5, 1.5, True),
            ("read", -0.2, 0.3, True), ("read", 1.8, 2.4, True)]
    s = harness.series(recs, 0.0, 2.0)
    assert s["completions"] == [1, 1]
    assert s["p50_ms"][1] == pytest.approx(1000.0)


# -- a whole run, tiny ---------------------------------------------------------------------

def shrink(cell, seconds, run_seconds=BENCH["run_seconds"]):
    """`cell` cut, in place, to the smallest deployment its own files
    leave it: 2+1 on three OSDs, 64 KiB objects, four clients; where
    the mix stops OSDs under writes, as many parities more (a pool
    takes no write below `min_size`, k + 1), and an OSD to spare for
    each that an event marks out. A schedule keeps its place in the
    window (`at_s` by `seconds / run_seconds`); with one, a device
    batch is bounded by its bytes at the clients' four objects, as the
    chip's is at two, so that work beside the clients (recovery's
    decodes) meets no shape set-up did not warm."""
    events = cell.traffic.get("events", [])
    down = len(set(range(cell.traffic.get("stop_osds", 0)))
               | {e["osd"] for e in events if e["do"] == "stop_osd"})
    spares = len({e["osd"] for e in events if e["do"] == "osd_out"})
    m = 1 + (0 if cell.traffic.get("op") == "seq" else down)
    cell.config = dict(cell.config, osds=2 + m + spares, object_size=65536,
                       recordcount=40,
                       pool=dict(cell.config["pool"], k=2, m=m, pg_num=8))
    cell.traffic = dict(cell.traffic, clients=4, warmup_ops=8,
                        payload_pool=4)
    if cell.traffic.get("preload_objects"):
        cell.traffic["preload_objects"] = 8
    if events:
        cell.traffic["events"] = [
            dict(e, at_s=e["at_s"] * seconds / run_seconds) for e in events]
        cell.config["osd_config"] = dict(
            cell.config["osd_config"], ec_offload_max_batch_bytes=4 * 65536)
    return cell


def run_tiny(cell, trace=False, control=(), seconds=0.6, tmp="."):
    """One run of a cell that `shrink` has cut; the offload service's
    defaults, which a cell's `osd_config` may turn, are put back."""
    from ceph_tpu.offload import service

    kept = dict(service._DEFAULTS)
    try:
        return asyncio.run(harness.run_cell(
            cell, 2 ** 31 + 5, seconds, trace, str(tmp), time.monotonic(),
            control))
    finally:
        service._DEFAULTS.update(kept)


def _tiny(cell_name, trace=False, control=(), seconds=0.6, tmp=".",
          root=ROOT):
    cell = shrink(harness.load_cell(cell_name, root=root), seconds)
    return run_tiny(cell, trace, control, seconds, tmp), cell


def test_tiny_run_prints_the_contracts_keys(tmp_path, with_ycsb):
    done, cell = _tiny("ycsb_a", tmp=tmp_path, root=with_ycsb)
    line = json.loads(json.dumps(done["result"]))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"ops_s", "op_p50_ms", "op_p95_ms",
                                    "setup_s"}
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(value <= limit for _n, value, limit in done["checks"])
    assert done["info"]["compiles_in_window"] == 0
    series = json.load(open(tmp_path / "series.json"))
    assert sum(series["completions"]) == done["info"]["samples"]


def test_tiny_traced_run_reports_per_layer_metrics(tmp_path):
    done, cell = _tiny("rb4m_write", trace=True, tmp=tmp_path)
    line = done["result"]
    assert line["correct"] is True
    declared = {r.NAME for r in cell.readers}
    # the profiler's trace is read on the TPU only: its readers find
    # nothing here and are left out, not written as 0
    from_trace = {"device_idle_pct", "apply_bitmatrix_batched_roofline"}
    assert set(line["metrics"]) == declared - from_trace
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert line["metrics"]["store_bytes_per_user_byte"]["value"] == \
        pytest.approx(1.5)          # k=2 m=1
    assert line["metrics"]["link_bytes_per_byte"]["value"] == \
        pytest.approx(1.5, rel=0.1)
    assert 0 < line["metrics"]["loop_busy_pct"]["value"] <= 101


def test_altered_read_makes_the_run_incorrect(tmp_path, monkeypatch):
    """The timed path broken underneath: the client library returns one
    read with a flipped bit, and `correct` comes out false."""
    from ceph_tpu.rados import client as rados_client

    real = rados_client.IoCtx.read
    state = {"reads": 0}

    async def read(self, oid, *a, **kw):
        data = await real(self, oid, *a, **kw)
        state["reads"] += 1
        if state["reads"] == 30:
            data = bytes([data[0] ^ 0x10]) + data[1:]
        return data
    monkeypatch.setattr(rados_client.IoCtx, "read", read)
    done, _ = _tiny("rb4m_seqread", tmp=tmp_path)
    assert state["reads"] > 30
    assert done["result"]["correct"] is False
    assert done["result"]["failed"] == 1
    assert dict((n, v) for n, v, _l in done["checks"])["read_mismatches"] == 1


def test_controls_break_a_guarantee_and_fail(tmp_path):
    """The control kept as a test: one byte of one shard at rest rots
    (the program's own fault path) and one read-back is altered."""
    done, _ = _tiny("rb4m_write", control=("bitrot", "flip_read"),
                    tmp=tmp_path)
    checks = {n: v for n, v, _l in done["checks"]}
    assert checks["shard_bytes_differing"] == 1
    assert checks["sample_read_mismatches"] == 1
    assert done["result"]["correct"] is False


def test_entry_refuses_to_run_without_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONHASHSEED", None)
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not p.stdout.strip()


# -- the trace reduction -----------------------------------------------------------------

def _recorded():
    found = [f for f in os.listdir(TESTDATA) if f.endswith(".xplane.pb")]
    assert len(found) == 1
    return os.path.join(TESTDATA, found[0])


def test_trace_reduction_repeats_on_the_recorded_trace():
    a = trace_reduce.reduce_file(_recorded())
    b = trace_reduce.reduce_file(_recorded())
    assert a == b
    want = json.load(open(os.path.join(TESTDATA, "expected.json")))
    assert a["window_s"] == pytest.approx(want["window_s"])
    assert a["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < a["busy_s"] < a["window_s"]
    for name, secs in want["programs"].items():
        assert a["programs"][name] == pytest.approx(secs)
    assert a["breakdown"]["idle_gaps"] == [
        [n, pytest.approx(s)] for n, s in want["idle_gaps"]]
    assert [n for n, _ in a["breakdown"]["device_ops"]] == \
        [n for n, _ in want["device_ops"]]
    assert len(a["breakdown"]["device_ops"]) <= 10
    assert len(a["breakdown"]["idle_gaps"]) <= 10


def test_idle_gap_is_named_after_the_shortest_host_event_covering_half():
    host = [("bench_device_touch", 0.0, 100.0), ("Execute", 10.0, 30.0),
            ("TransferToDevice", 12.0, 15.0)]
    assert trace_reduce.name_gap(11.0, 29.0, host) == "Execute"
    assert trace_reduce.name_gap(12.0, 16.0, host) == "TransferToDevice"
    assert trace_reduce.name_gap(40.0, 90.0, host) == "bench_device_touch"
    assert trace_reduce.name_gap(95.0, 140.0, host) == \
        trace_reduce.NO_HOST_EVENT
    assert trace_reduce.op_name(
        "%convert_reduce_fusion = s32[3,128,4096]{2,0,1:T(4,128)S(1)} "
        "fusion(u8[128,8,4096]{2,1,0} %data.1), kind=kOutput") == \
        "convert_reduce_fusion_s32_3_128_4096"
    assert trace_reduce.op_name("%copy-start = (s8[24,64]{1,0}, u32[]) "
                                "copy-start(%p)") == "copy-start"


def test_peaks_are_published_and_an_unknown_device_is_an_error():
    v5e = trace_reduce.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["int8_ops_per_s"] == 393e12
    for kind in ("TPU v9 imaginary", "cpu", "source"):
        with pytest.raises(KeyError):
            trace_reduce.peaks_for(kind)


def test_roofline_is_reckoned_at_the_algorithms_minimum():
    mod = harness._load_module(ROOT, "layer_metrics",
                               "apply_bitmatrix_batched_roofline")
    peaks = trace_reduce.peaks_for("TPU v5 lite")
    least = mod.least_seconds(8 << 20, 8, 3, peaks)
    assert least["hbm"] == pytest.approx((8 << 20) * 11 / 8 / 819e9)
    assert least["int8"] == pytest.approx((8 << 20) * 3072 / 8 / 393e12)
    assert least["hbm"] > least["int8"]          # HBM binds at k=8 m=3
    assert math.isclose(max(least.values()), least["hbm"])
