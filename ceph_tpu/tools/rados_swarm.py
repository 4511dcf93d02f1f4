"""Swarm load generator: thousands of concurrent librados clients.

The missing half of the production-traffic story (ROADMAP "many-client
load harness"): every bench so far drives ONE client, but a store is
judged on how fairly it serves thousands of tenants — and the
per-client SLO observability (OpTracker ClientTable -> MgrReport ->
`ceph_client_*` exporter families) is ungradeable until something
generates attributable multi-tenant load. This is that something: a
fleet of independent `RadosClient` instances, each with its own
negotiated `client.<id>` identity and tenant label, optionally SHARDED
ACROSS WORKER PROCESSES (`procs=`) — one asyncio loop tops out around
a few hundred active clients, so the 1000+ storms the dmclock QoS
grader needs fan the fleet out over subprocesses that each drive an
index slice over TCP and ship their per-client tables back as JSON.

Workload shape (the knobs the SSD-array online-EC study, arXiv
1709.05365, says matter — system-level queueing under CONCURRENT load):

  * mixed op-size distribution: each client draws object sizes from a
    weighted set (4k metadata-ish writes through 256k bulk);
  * zipfian hot keys: object picks follow a Zipf(s) rank distribution
    over a shared namespace, so a handful of hot objects see most of
    the traffic (same-PG convoys, the contention a fair scheduler must
    arbitrate);
  * injected slow readers: a designated fraction of clients hammer
    full-object reads of the biggest objects with zero pacing (tenant
    "slowband") — the overload that must show up in OTHER clients'
    p99, in the SLO violation counters, and eventually in the mon's
    SLO_VIOLATIONS check;
  * adversarial tenants (the QoS storm cast, all unpaced):
      - `bullies`  (tenant "bully"): hot-key hammering — small writes
        pinned to the hottest ranks, the same-PG convoy from hell;
      - `streamers` (tenant "streamer"): full-size bulk writes/reads
        back-to-back — byte-heavy load that must not hide behind op
        counts (the scheduler's byte-cost normalization exists for
        exactly this);
      - `spammers` (tenant "spammer"): zero-byte stat storms — pure
        IOPS pressure with no payload;
      - `victims`  (tenant "victim"): PACED small ops at a gentle
        rate — the well-behaved slow-band tenant whose p99-vs-SLO is
        the isolation grade.

Fairness figures: `p99_fairness` = max(client p99) / median(client
p99) over the whole fleet (the legacy figure); `tenant_fairness` =
the same ratio over per-tenant merged-histogram p99s EXCLUDING the
adversarial tenants (an arbiter that throttles a bully makes the
bully's own p99 terrible — that is the point, not unfairness);
`goodput_mb_s` = bytes moved by non-adversarial tenants only.

Usage (standalone, boots its own EC cluster):
    python -m ceph_tpu.tools.rados_swarm [--clients 200] [--seconds 5]
        [--procs 4] [--bullies 8] [--streamers 8] [--spammers 8]
Programmatic: `await run_swarm(mon_addrs, pool, ...)` against a live
cluster (what the tests call).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import random
import sys
import time


def raise_fd_limit(want: int = 8192) -> None:
    """Hundreds of clients * (messenger + mon + OSD sessions) blow the
    default 1024-fd rlimit; raise it as far as the hard cap allows."""
    try:
        import resource
        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        if soft < want:
            resource.setrlimit(resource.RLIMIT_NOFILE,
                               (min(want, hard), hard))
    except (ImportError, ValueError, OSError):
        pass


class _ZipfPicker:
    """Incremental zipf draws (pre-drawing count for a timed window is
    impossible); cumulative-weight bisect per draw."""

    def __init__(self, n: int, s: float):
        import bisect
        self._bisect = bisect
        self.cum = []
        total = 0.0
        for r in range(n):
            total += 1.0 / (r + 1) ** s
            self.cum.append(total)
        self.total = total

    def pick(self, rng: random.Random) -> int:
        return self._bisect.bisect_left(self.cum,
                                        rng.random() * self.total)


#: (size_bytes, weight) mixed op-size distribution defaults: mostly
#: small ops with a bulk tail — the shape that exposes per-op overhead
#: AND byte-bandwidth contention at once
DEFAULT_SIZES = ((4096, 8), (16384, 4), (65536, 2), (262144, 1))

#: tenants whose latency/throughput is EXCLUDED from the fairness and
#: goodput figures — they are the attack, not the workload
ADVERSARY_TENANTS = frozenset(("bully", "streamer", "spammer"))


def _role_of(i: int, clients: int, n_slow: int, n_bully: int,
             n_stream: int, n_spam: int, n_victim: int,
             tenants: int) -> tuple[str, str]:
    """(role, tenant) of global fleet index `i`. Special roles occupy
    the top of the index space (slowband highest, then bullies,
    streamers, spammers, victims) so the legacy slow_readers layout is
    unchanged when the adversary counts are zero."""
    top = clients
    if i >= top - n_slow:
        return "slow", "slowband"
    top -= n_slow
    if i >= top - n_bully:
        return "bully", "bully"
    top -= n_bully
    if i >= top - n_stream:
        return "streamer", "streamer"
    top -= n_stream
    if i >= top - n_spam:
        return "spammer", "spammer"
    top -= n_spam
    if i >= top - n_victim:
        return "victim", "victim"
    return "normal", f"tenant{i % max(1, tenants)}"


def _n_vic_objs(objects: int) -> int:
    """Size of the victim band's dedicated key space. Victims get
    their own objects: sharing the bully's hot keys would serialize
    victim ops behind bully convoys on the OBJECT WINDOW — correctness
    ordering no op scheduler can arbitrate away — and the victim band
    exists to grade the scheduler, not the locking."""
    return max(1, min(32, objects // 4))


def _bucket_of_us(us: float) -> int:
    """Quarter-octave µs latency bucket index (bucket i covers
    (2^(i/4), 2^((i+1)/4)] µs): finer than the mgr's power-of-two rule
    because the tenant p99 grades a 4x-SLO criterion — a 2x bucket
    edge would eat the whole margin."""
    import math
    return max(0, int(math.log2(us) * 4)) if us >= 1.0 else 0


def _bucket_p99_ms(buckets: dict, q: float = 0.99) -> float:
    """Quantile from merged quarter-octave µs buckets, quoting the
    bucket's 2^((i+1)/4) µs upper edge (~19% worst-case overquote)."""
    total = sum(buckets.values())
    if not total:
        return 0.0
    need = q * total
    seen = 0
    for b in sorted(int(k) for k in buckets):
        seen += buckets[b] if b in buckets else buckets[str(b)]
        if seen >= need:
            return round(2.0 ** ((b + 1) / 4.0) / 1e3, 3)
    return 0.0


async def _run_slice(mon_addrs, pool: str, lo: int, hi: int, *,
                     clients: int, seconds: float, objects: int,
                     sizes, zipf_s: float, read_fraction: float,
                     slow_readers: int, bullies: int, streamers: int,
                     spammers: int, victims: int, victim_iops: float,
                     normal_iops: float,
                     tenants: int, seed: int, connect_batch: int,
                     auth_key: bytes | None,
                     client_prefix: str,
                     op_timeout: float | None = None,
                     adversary_depth: int = 1,
                     settle_s: float = 0.0) -> dict:
    """Connect and drive fleet indices [lo, hi) for the timed window;
    returns {client_name: stats}. The namespace must already be seeded
    (run_swarm does it once, before any slice starts)."""
    from ceph_tpu.rados.client import RadosClient

    raise_fd_limit()
    size_vals = [s for s, _w in sizes]
    size_weights = [w for _s, w in sizes]
    picker = _ZipfPicker(objects, zipf_s)
    obj_size = {r: size_vals[r % len(size_vals)] for r in range(objects)}
    big = max(size_vals)
    big_objs = [r for r in range(objects) if obj_size[r] == big] or [0]
    hot_objs = list(range(min(4, objects)))
    n_slow = min(slow_readers, clients)
    vic_picker = _ZipfPicker(_n_vic_objs(objects), zipf_s)

    def role_of(i):
        return _role_of(i, clients, n_slow, bullies, streamers,
                        spammers, victims, tenants)

    # -- connect the slice (batched: each connect waits for an osdmap) --
    fleet: list[RadosClient] = []

    async def _connect(i: int) -> RadosClient:
        role, tenant = role_of(i)
        c = RadosClient(mon_addrs, auth_key=auth_key,
                        name=f"{client_prefix}{i:04d}", tenant=tenant)
        if op_timeout:
            # storm fleets queue THOUSANDS deep: the default 15 s op
            # deadline would turn honest queue wait into error noise,
            # and 5 s attempt-level resends churn non-idempotent
            # retries into dup-superseded EIOs on the hot objects
            c.OP_TIMEOUT = float(op_timeout)
            c.ATTEMPT_TIMEOUT = float(op_timeout)
        await c.connect()
        return c

    t_connect = time.monotonic()
    for base in range(lo, hi, connect_batch):
        batch = await asyncio.gather(
            *[_connect(i) for i in range(base,
                                         min(hi, base + connect_batch))])
        fleet.extend(batch)
    connect_s = time.monotonic() - t_connect

    # Each slice's window opens as soon as ITS connect finishes — while
    # sibling worker procs may still be mid-connect-storm. Without a
    # settle, early ops eat auth/osdmap churn from hundreds of foreign
    # connects and the tail quotes the ramp, not the steady state.
    if settle_s > 0:
        await asyncio.sleep(settle_s)

    # -- timed window ---------------------------------------------------
    per_client: dict[str, dict] = {}
    stop_at = time.monotonic() + seconds

    async def worker(idx: int, c: RadosClient) -> None:
        io = c.ioctx(pool)
        crng = random.Random((seed << 16) ^ idx)
        role, _tenant = role_of(idx)
        lats: list[float] = []
        buckets: dict[int, int] = {}
        stats = {"ops": 0, "read_bytes": 0, "written_bytes": 0,
                 "errors": 0, "tenant": c.tenant, "role": role}
        per_client[c.name] = stats
        # pacing: victims always pace (their SLO band is defined by a
        # demanded rate); normals pace when normal_iops is set — paced
        # well-behaved tenants vs unconstrained adversaries is the
        # dmclock evaluation shape, and demand-attainment fairness
        # needs a defined demand
        if role == "victim" and victim_iops > 0:
            pace = 1.0 / victim_iops
        elif role == "normal" and normal_iops > 0:
            pace = 1.0 / normal_iops
        else:
            pace = 0.0

        async def op_loop():
            if pace > 0:
                # random phase start: a paced fleet must not arrive as
                # one thundering herd at t=0
                await asyncio.sleep(crng.random() * pace)
            while time.monotonic() < stop_at:
                t_op = time.monotonic()
                try:
                    if role == "slow":
                        # slowband: unpaced full reads of the biggest
                        # objects — the overload injection
                        r = crng.choice(big_objs)
                        data = await io.read(f"sw-{r:04d}")
                        stats["read_bytes"] += len(data)
                    elif role == "bully":
                        # hot-keyed bully: small writes pinned to the
                        # hottest ranks — a same-PG convoy
                        r = crng.choice(hot_objs)
                        await io.write_full(f"sw-{r:04d}", bytes(4096))
                        obj_size[r] = 4096
                        stats["written_bytes"] += 4096
                    elif role == "streamer":
                        # byte-heavy streamer: full-size bulk ops
                        # back-to-back
                        r = crng.choice(big_objs)
                        if crng.random() < 0.5:
                            await io.write_full(f"sw-{r:04d}",
                                                bytes(big))
                            stats["written_bytes"] += big
                        else:
                            data = await io.read(f"sw-{r:04d}")
                            stats["read_bytes"] += len(data)
                    elif role == "spammer":
                        # metadata-spammer: zero-byte stat storm
                        r = picker.pick(crng)
                        await io.stat(f"sw-{r:04d}")
                    elif role == "victim":
                        # the well-behaved slow-band tenant: paced
                        # small ops over its OWN key space (see
                        # _n_vic_objs); its p99-vs-SLO is the
                        # isolation grade
                        r = vic_picker.pick(crng)
                        if crng.random() < read_fraction:
                            data = await io.read(f"vic-{r:04d}")
                            stats["read_bytes"] += len(data)
                        else:
                            await io.write_full(f"vic-{r:04d}",
                                                bytes(4096))
                            stats["written_bytes"] += 4096
                    elif crng.random() < read_fraction:
                        r = picker.pick(crng)
                        data = await io.read(f"sw-{r:04d}")
                        stats["read_bytes"] += len(data)
                    else:
                        r = picker.pick(crng)
                        # draw the size fresh from the distribution:
                        # sizes fluctuate around the mix instead of
                        # ratcheting down, so the big objects the
                        # slowband readers hammer keep existing for
                        # the whole window
                        size = crng.choices(size_vals, size_weights)[0]
                        if r in big_objs:
                            size = big
                        await io.write_full(f"sw-{r:04d}", bytes(size))
                        obj_size[r] = size
                        stats["written_bytes"] += size
                    stats["ops"] += 1
                    lat_ms = (time.monotonic() - t_op) * 1e3
                    lats.append(lat_ms)
                    b = _bucket_of_us(lat_ms * 1e3)
                    buckets[b] = buckets.get(b, 0) + 1
                except Exception as e:
                    stats["errors"] += 1
                    stats["last_error"] = \
                        f"{type(e).__name__}: {e}"[:120]
                if pace > 0:
                    now = time.monotonic()
                    wait = min(pace - (now - t_op), stop_at - now)
                    if wait > 0:
                        await asyncio.sleep(wait)

        # adversaries pipeline `adversary_depth` concurrent ops per
        # connection (real hogs use async queue depth, and a 1-deep
        # client in a big fleet is DILUTED into fairness by FIFO
        # itself — depth is what gives the scheduler something to
        # arbitrate); everyone else stays 1-deep
        depth = adversary_depth \
            if role in ("bully", "streamer", "spammer") else 1
        await asyncio.gather(*[op_loop()
                               for _ in range(max(1, int(depth)))])
        lats.sort()
        n = len(lats)
        stats["p50_ms"] = round(lats[n // 2], 2) if n else 0.0
        stats["p99_ms"] = round(lats[min(n - 1, int(n * 0.99))], 2) \
            if n else 0.0
        stats["lat_buckets"] = buckets
        stats["throttled"] = c.throttled_ops

    t0 = time.monotonic()
    await asyncio.gather(*[worker(lo + j, c)
                           for j, c in enumerate(fleet)])
    elapsed = time.monotonic() - t0

    # -- teardown -------------------------------------------------------
    for base in range(0, len(fleet), connect_batch):
        await asyncio.gather(
            *[c.shutdown() for c in fleet[base:base + connect_batch]])
    return {"per_client": per_client,
            "connect_s": round(connect_s, 2),
            "elapsed": round(elapsed, 3)}


async def _worker_main(spec: dict) -> dict:
    """Subprocess entry (`--worker`): drive one fleet slice and print
    the result JSON on stdout."""
    spec = dict(spec)
    auth_hex = spec.pop("auth_key_hex", None)
    spec["auth_key"] = bytes.fromhex(auth_hex) if auth_hex else None
    spec["mon_addrs"] = [tuple(a) for a in spec["mon_addrs"]]
    spec["sizes"] = tuple(tuple(x) for x in spec["sizes"])
    mon_addrs = spec.pop("mon_addrs")
    pool = spec.pop("pool")
    lo, hi = spec.pop("lo"), spec.pop("hi")
    return await _run_slice(mon_addrs, pool, lo, hi, **spec)


async def run_swarm(mon_addrs, pool: str, *,
                    clients: int = 200,
                    seconds: float = 5.0,
                    objects: int = 128,
                    sizes=DEFAULT_SIZES,
                    zipf_s: float = 1.1,
                    read_fraction: float = 0.5,
                    slow_readers: int = 0,
                    bullies: int = 0,
                    streamers: int = 0,
                    spammers: int = 0,
                    victims: int = 0,
                    victim_iops: float = 20.0,
                    normal_iops: float = 0.0,
                    tenants: int = 4,
                    seed: int = 1234,
                    connect_batch: int = 32,
                    auth_key: bytes | None = None,
                    client_prefix: str = "sw",
                    op_timeout: float | None = None,
                    adversary_depth: int = 1,
                    settle_s: float = 0.0,
                    procs: int = 1) -> dict:
    """Drive `clients` concurrent librados clients against `pool` for
    `seconds`; returns aggregate MB/s, per-client and per-tenant p99,
    and the fairness ratios. The cluster must already exist; the
    namespace is seeded before the timed window so reads never miss.
    `procs` > 1 shards the fleet across that many worker subprocesses
    (each its own event loop over TCP) — the only way past one loop's
    few-hundred-client ceiling."""
    from ceph_tpu.rados.client import RadosClient

    raise_fd_limit()
    size_vals = [s for s, _w in sizes]
    obj_size = {r: size_vals[r % len(size_vals)] for r in range(objects)}

    # -- seed the namespace (once, before any slice connects) -----------
    seeder = RadosClient(mon_addrs, auth_key=auth_key,
                         name=f"{client_prefix}-seed", tenant="seed")
    await seeder.connect()
    io = seeder.ioctx(pool)
    await asyncio.gather(*[
        io.write_full(f"sw-{r:04d}", bytes(obj_size[r]))
        for r in range(objects)])
    if victims > 0:
        await asyncio.gather(*[
            io.write_full(f"vic-{r:04d}", bytes(4096))
            for r in range(_n_vic_objs(objects))])
    await seeder.shutdown()

    slice_kw = dict(
        clients=clients, seconds=seconds, objects=objects,
        sizes=[list(x) for x in sizes], zipf_s=zipf_s,
        read_fraction=read_fraction, slow_readers=slow_readers,
        bullies=bullies, streamers=streamers, spammers=spammers,
        victims=victims, victim_iops=victim_iops,
        normal_iops=normal_iops, tenants=tenants,
        seed=seed, connect_batch=connect_batch,
        client_prefix=client_prefix, op_timeout=op_timeout,
        adversary_depth=adversary_depth, settle_s=settle_s)

    procs = max(1, int(procs))
    slices = []
    if procs <= 1:
        slices.append((0, clients))
    else:
        per = (clients + procs - 1) // procs
        slices = [(lo, min(clients, lo + per))
                  for lo in range(0, clients, per)]

    t0 = time.monotonic()
    if procs <= 1:
        kw = dict(slice_kw, sizes=tuple(tuple(x) for x in slice_kw
                                        ["sizes"]), auth_key=auth_key)
        results = [await _run_slice(mon_addrs, pool, 0, clients, **kw)]
    else:
        # fan out worker subprocesses; each prints one JSON result
        async def spawn(lo, hi):
            spec = dict(slice_kw, mon_addrs=[list(a) for a in mon_addrs],
                        pool=pool, lo=lo, hi=hi,
                        auth_key_hex=auth_key.hex() if auth_key else None)
            p = await asyncio.create_subprocess_exec(
                sys.executable, "-m", "ceph_tpu.tools.rados_swarm",
                "--worker", json.dumps(spec),
                stdout=asyncio.subprocess.PIPE,
                stderr=asyncio.subprocess.PIPE)
            out, err = await p.communicate()
            if p.returncode != 0:
                raise RuntimeError(
                    f"swarm worker [{lo},{hi}) rc={p.returncode}: "
                    f"{err.decode(errors='replace')[-500:]}")
            return json.loads(out.decode().strip().splitlines()[-1])
        results = list(await asyncio.gather(
            *[spawn(lo, hi) for lo, hi in slices]))
    elapsed = time.monotonic() - t0

    # -- aggregate ------------------------------------------------------
    per_client: dict[str, dict] = {}
    for res in results:
        per_client.update(res["per_client"])
    connect_s = max(res["connect_s"] for res in results)
    # rates and demand are computed over the REQUESTED window: every
    # op is issued within it, but stragglers draining a limit-blocked
    # backlog can stretch the measured elapsed far past it, and a
    # drain-diluted MB/s would claim backpressure destroyed
    # throughput it merely delayed. The measured drain is reported
    # separately.
    window = max(seconds, 0.001)
    drain = max(res["elapsed"] for res in results)

    total_ops = sum(s["ops"] for s in per_client.values())
    rd = sum(s["read_bytes"] for s in per_client.values())
    wr = sum(s["written_bytes"] for s in per_client.values())
    errors = sum(s["errors"] for s in per_client.values())
    throttled = sum(s.get("throttled", 0) for s in per_client.values())
    p99s = sorted(s["p99_ms"] for s in per_client.values() if s["ops"])
    fair = {"median_p99_ms": 0.0, "max_p99_ms": 0.0,
            "p99_fairness": 0.0}
    if p99s:
        med = p99s[len(p99s) // 2]
        fair = {"median_p99_ms": med, "max_p99_ms": p99s[-1],
                "p99_fairness": round(p99s[-1] / med, 3) if med else 0.0}

    # per-tenant merge: sum the ledgers, merge the power-of-two µs
    # histograms so the tenant p99 is an honest pooled percentile
    per_tenant: dict[str, dict] = {}
    for s in per_client.values():
        t = per_tenant.setdefault(s["tenant"], {
            "clients": 0, "ops": 0, "errors": 0, "read_bytes": 0,
            "written_bytes": 0, "throttled": 0, "_buckets": {}})
        t["clients"] += 1
        t["ops"] += s["ops"]
        t["errors"] += s["errors"]
        t["read_bytes"] += s["read_bytes"]
        t["written_bytes"] += s["written_bytes"]
        t["throttled"] += s.get("throttled", 0)
        if s.get("last_error") and "error_sample" not in t:
            t["error_sample"] = s["last_error"]
        for b, n in (s.get("lat_buckets") or {}).items():
            b = int(b)
            t["_buckets"][b] = t["_buckets"].get(b, 0) + n
    for t in per_tenant.values():
        b = t.pop("_buckets")
        t["p50_ms"] = _bucket_p99_ms(b, q=0.5)
        t["p99_ms"] = _bucket_p99_ms(b)

    # isolation figures over the NON-adversarial population only
    well = {name: t for name, t in per_tenant.items()
            if name not in ADVERSARY_TENANTS and name != "slowband"}
    tp99 = sorted(t["p99_ms"] for t in well.values() if t["ops"])
    tenant_fairness = 0.0
    if tp99:
        tmed = tp99[len(tp99) // 2]
        tenant_fairness = round(tp99[-1] / tmed, 3) if tmed else 0.0
    # client-level spread WITHIN the equal-peer population: the figure
    # an arbiter actually moves (per-entity round-robin vs FIFO's
    # hot-key convoy tail); max/median p99 over normal-tenant clients.
    # The victim band is excluded here too — its reservation makes it
    # deliberately faster, which is isolation, not unfairness (it is
    # graded separately against its SLO).
    gp99 = sorted(s["p99_ms"] for s in per_client.values()
                  if s["ops"] and s["tenant"] in well
                  and s["tenant"] != "victim")
    good_fairness = 0.0
    if gp99:
        gmed = gp99[len(gp99) // 2]
        good_fairness = round(gp99[-1] / gmed, 3) if gmed else 0.0
    good_bytes = sum(t["read_bytes"] + t["written_bytes"]
                     for t in well.values())
    victim_p99 = per_tenant.get("victim", {}).get("p99_ms", 0.0)
    # victim isolation ratio: the paced band's pooled p99 over the
    # saturated equal-weight majority's median pooled p99. 1.0 means
    # the adversaries dragged the protected band into the same
    # collapse despite its tiny demand; an arbiter holds it well
    # below (its reservation serves it ahead of the backlog)
    norm99 = sorted(t["p99_ms"] for name, t in per_tenant.items()
                    if name.startswith("tenant") and t["ops"])
    victim_isolation = 0.0
    if norm99 and victim_p99:
        nmed = norm99[len(norm99) // 2]
        victim_isolation = round(victim_p99 / nmed, 3) if nmed else 0.0
    # demand-attainment fairness: every PACED well-behaved tenant has
    # a defined demand (clients x iops x window); the ratio is the
    # worst tenant's demanded/attained ops — dmclock's actual promise
    # is that no entitled tenant is denied its rate while hogs are
    # active. 1.0 = everyone attains demand; adversaries stealing
    # service drive it up. Unpaced tenants have no demand baseline
    # and are skipped.
    demand_fairness = 0.0
    for name, t in per_tenant.items():
        iops_t = victim_iops if name == "victim" else \
            normal_iops if name.startswith("tenant") else 0.0
        if iops_t <= 0:
            continue
        demanded = t["clients"] * iops_t * window
        t["attainment"] = round(t["ops"] / demanded, 3) \
            if demanded else 0.0
        ratio = demanded / t["ops"] if t["ops"] else 999.0
        demand_fairness = max(demand_fairness, round(ratio, 3))

    return {
        "clients": clients, "procs": procs,
        "slow_readers": min(slow_readers, clients),
        "bullies": bullies, "streamers": streamers,
        "spammers": spammers, "victims": victims,
        "adversary_depth": adversary_depth,
        "seconds": round(window, 3),
        "drain_s": round(drain, 3),
        "wall_s": round(elapsed, 3),
        "connect_s": connect_s,
        "objects": objects, "zipf_s": zipf_s,
        "ops": total_ops,
        "iops": round(total_ops / window, 1) if window else 0.0,
        "mb_s": round((rd + wr) / window / 1e6, 2) if window else 0.0,
        "read_mb_s": round(rd / window / 1e6, 2) if window else 0.0,
        "write_mb_s": round(wr / window / 1e6, 2) if window else 0.0,
        "goodput_mb_s": round(good_bytes / window / 1e6, 2)
        if window else 0.0,
        "errors": errors,
        "throttled_ops": throttled,
        **fair,
        "tenant_fairness": tenant_fairness,
        "good_fairness": good_fairness,
        "victim_isolation": victim_isolation,
        "demand_fairness": demand_fairness,
        "victim_p99_ms": victim_p99,
        "per_tenant": per_tenant,
        "per_client": per_client,
    }


async def _main(args) -> dict:
    from ceph_tpu.tools.cluster_boot import ephemeral_cluster

    raise_fd_limit()
    async with ephemeral_cluster(args.osds, prefix="rados-swarm-") \
            as (client, _osds, mon):
        await client.command({
            "prefix": "osd erasure-code-profile set",
            "name": "swarmprof",
            "profile": {"plugin": "jerasure", "k": str(args.k),
                        "m": str(args.m)}})
        await client.pool_create("swarm", pg_num=8,
                                 pool_type="erasure",
                                 erasure_code_profile="swarmprof")
        out = await run_swarm(
            list(mon.monmap.mons.values()), "swarm",
            clients=args.clients, seconds=args.seconds,
            objects=args.objects, slow_readers=args.slow_readers,
            bullies=args.bullies, streamers=args.streamers,
            spammers=args.spammers, victims=args.victims,
            adversary_depth=args.adversary_depth,
            normal_iops=args.normal_iops, settle_s=args.settle,
            zipf_s=args.zipf, procs=args.procs)
        if not args.per_client:
            out.pop("per_client", None)
        return out


def main() -> None:
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        # subprocess slice driver: spec JSON in argv, result JSON out
        spec = json.loads(sys.argv[2])
        print(json.dumps(asyncio.run(_worker_main(spec))))
        return
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=200)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--objects", type=int, default=128)
    ap.add_argument("--osds", type=int, default=4)
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--m", type=int, default=1)
    ap.add_argument("--slow-readers", type=int, default=8)
    ap.add_argument("--bullies", type=int, default=0)
    ap.add_argument("--streamers", type=int, default=0)
    ap.add_argument("--spammers", type=int, default=0)
    ap.add_argument("--victims", type=int, default=0)
    ap.add_argument("--adversary-depth", type=int, default=1,
                    help="concurrent ops each adversary pipelines")
    ap.add_argument("--normal-iops", type=float, default=0.0,
                    help="pace normal tenants (0 = unpaced)")
    ap.add_argument("--settle", type=float, default=0.0,
                    help="post-connect settle before the timed window")
    ap.add_argument("--procs", type=int, default=1)
    ap.add_argument("--zipf", type=float, default=1.1)
    ap.add_argument("--per-client", action="store_true",
                    help="include the full per-client table in the JSON")
    args = ap.parse_args()
    print(json.dumps(asyncio.run(_main(args))))


if __name__ == "__main__":
    main()
