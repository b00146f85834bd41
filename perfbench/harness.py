"""One run of one benchmark cell: set-up, the measured window, the check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``<file>`` of the configuration entry: the deployment (table shape,
  tenants, records, guarantees, and ``shards``, the chips its one table is
  split over: 1 when absent, and then the cell's ``chips``);
* ``perfbench/traffic/<traffic>.json``: the mix (YCSB workloads per tenant,
  key distribution, clients, requests per client, warm-up ticks);
* ``perfbench/metrics/<name>.py``: a reader with ``read(run)`` that returns
  the metric's value from a :class:`RunRecord`, or None when it finds
  nothing to read.  On the CPU the metric is then left out of the result;
  on the chip a metric the cell declares that reads nothing fails the run
  (:class:`SilentMetric`).

Traffic is a closed loop, YCSB's client model: each client holds one
request of ``ops_per_request`` ops and submits its next one the moment the
last completes, with no think time.  Streams are drawn from the seed before
the window (``ycsb.py``); a client that reaches the end of its list starts
it again.  A request's latency runs from its submission to the end of the
tick that answers its last op.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import inspect
import json
import math
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

from perfbench import devtrace, roofline
from perfbench.reference import HostReference, Keyspace, seed_words, values_of
from perfbench.ycsb import OpStream

BENCH_DIR = "perfbench"
CACHE_DIR = ".jax_cache"
# sampled engine telemetry is off: it runs eager ops at the tick's unpadded
# probe count, which compile anew inside the window (PERF.md, open questions)
NO_SAMPLING = 1 << 62
# record ids a shard build hashes at a time: the buffers of one chunk stay
# small beside the shard's table
BUILD_CHUNK = 1 << 24


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, with its
    configuration, its traffic and the metrics it reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / entry["file"]).read_text())
    if w["chips"] != shards(config):
        raise ValueError(
            f"cell {workload!r} asks for {w['chips']} chip(s), but its "
            f"configuration {w['config']!r} splits its table over "
            f"{shards(config)} shard(s); they must agree")
    traffic = json.loads(
        (root / BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    if traffic["loop"] != "closed":
        raise ValueError(f"traffic {w['traffic']!r}: only closed loops run")

    def applies(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if applies(m) and m["moves"] in names]
    return Cell(workload, w["chips"], config, traffic, e2e, layer)


def metric_reader(root: Path, name: str):
    """``read`` of ``<root>/perfbench/metrics/<name>.py``."""
    path = root / BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_cache(root: Path):
    """JAX's persistent compilation cache at ``<root>/.jax_cache``, every
    program kept: a fixed path inside the checkout, whatever the
    environment says, so no two checkouts share one."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def keyspace(config: dict) -> Keyspace:
    bits = config["tenant_bits"]
    return Keyspace(config["tenants"], config["records_per_tenant"],
                    32 - bits if bits else 32)


def shards(config: dict) -> int:
    """The chips the configuration's one table is split over."""
    return config.get("shards", 1)


def table_config(config: dict):
    """The table's shape; over several shards, each shard's."""
    from repro.configs.base import HashMemConfig
    return HashMemConfig(**config["table"])


def router() -> str:
    """The router that the engine, built with its default arguments as
    :func:`make_engine` builds it, sends each key to its shard with; shard
    builds place keys by it, so set-up and serving cannot disagree."""
    from repro.serving import ServingEngine
    return inspect.signature(ServingEngine).parameters["shard_by"].default


def serving_mesh(config: dict):
    """The mesh of one shard per chip that the table is split over; None
    for a table on one chip."""
    if shards(config) == 1:
        return None
    from repro.launch.mesh import make_serving_mesh
    return make_serving_mesh(shards(config))


def folded_keys(space: Keyspace, i):
    """The folded key of each record id ``i`` (tenant-major)."""
    if space.key_bits == 32:
        return i
    return ((i // space.records) << space.key_bits) | (i % space.records)


def build_table(config: dict, words: tuple):
    """The whole table in one jitted program: every record's folded key and
    its value from the seed's words, then ``hashmap.build``.  Returns as
    soon as the program is dispatched."""
    import jax
    import jax.numpy as jnp
    from repro.core import hashmap
    space = keyspace(config)
    cfg = table_config(config)

    def make(w):
        keys = folded_keys(space, jnp.arange(space.size, dtype=jnp.uint32))
        return hashmap.build(cfg, keys, values_of(keys, (w[0], w[1]), jnp))

    return jax.jit(make)(jnp.asarray(words, jnp.uint32))


def shard_capacity(config: dict) -> int:
    """Records a shard build makes room for: an even share and eight
    standard deviations of a binomial share, plus a little."""
    even = -(-keyspace(config).size // shards(config))
    return even + 8 * math.isqrt(even) + 64


def shard_program(config: dict, n: int):
    """The jitted build of one of ``n`` shards, from (the seed's two words,
    the shard's index): it walks the record ids in chunks, keeps the keys
    that the engine's router (:func:`router`, through
    ``rlu.owner_and_local_bucket``) gives the shard, and builds them with
    their values into the local buckets that router gives them.  Returns
    (the shard's table, its count of records)."""
    import jax
    import jax.numpy as jnp
    from repro.core import hashmap, rlu
    from repro.core.hashing import EMPTY_KEY
    space = keyspace(config)
    cfg = table_config(config)
    by, cap = router(), shard_capacity(config)
    chunk = min(BUILD_CHUNK, space.size)
    chunks = -(-space.size // chunk)
    u32 = jnp.uint32

    def make(w):
        def take(c, carry):
            out, count = carry
            i = c.astype(u32) * u32(chunk) + jnp.arange(chunk, dtype=u32)
            keys = folded_keys(space, i)
            owner, _ = rlu.owner_and_local_bucket(keys, cfg, n, by)
            mine = (owner == w[2].astype(jnp.int32)) & (i < u32(space.size))
            at = count + jnp.cumsum(mine, dtype=jnp.int32) - 1
            out = out.at[jnp.where(mine, at, cap)].set(keys, mode="drop")
            return out, count + jnp.sum(mine, dtype=jnp.int32)

        keys, count = jax.lax.fori_loop(
            0, chunks, take, (jnp.full(cap, EMPTY_KEY, u32), jnp.int32(0)))
        _, local = rlu.owner_and_local_bucket(keys, cfg, n, by)
        # the room past the shard's records: EMPTY_KEY in no bucket, which
        # the bulk loader drops
        local = jnp.where(jnp.arange(cap) < count, local, cfg.num_buckets)
        vals = values_of(keys, (w[0], w[1]), jnp)
        return hashmap.build_with_buckets(cfg, keys, vals, local), count

    return jax.jit(make)


def build_shards(config: dict, words: tuple, devices: list) -> tuple:
    """Shard ``s`` of the table on ``devices[s]``, for every shard, each
    built by :func:`shard_program` on its own device, so no device holds
    the whole key set.  The programs are dispatched from one thread per
    device, so they compile and run side by side; returns (the shard
    tables, each shard's count of records) as soon as all are
    dispatched."""
    import jax
    import jax.numpy as jnp
    program = shard_program(config, len(devices))

    def dispatch(s):
        return program(jax.device_put(
            jnp.asarray((*words, s), jnp.uint32), devices[s]))

    with ThreadPoolExecutor(len(devices)) as pool:
        built = list(pool.map(dispatch, range(len(devices))))
    return [t for t, _ in built], [c for _, c in built]


def check_owned(config: dict, owned: list):
    """Every record in exactly one shard build, and within its room."""
    got = [int(c) for c in owned]
    cap = shard_capacity(config)
    if sum(got) != keyspace(config).size or max(got) > cap:
        raise RuntimeError(f"shard builds hold {got} records (room {cap} "
                           f"each), not the {keyspace(config).size} records "
                           "split over them")


class OpLog:
    """The engine's log of executed ops (``record_schedule``), which the
    check replays, kept off the cyclic collector.

    The engine appends ``(tick, kind, keys, val, res)`` with ``res`` a dict
    that its writeback fills; a tuple holding a dict stays tracked by the
    collector, so the log would grow the collector's oldest generation by
    one object per op, and each full pass, which walks all of it, would
    stall the window for longer the more ops it has served: the benchmark's
    own bookkeeping, not the system under test.  :meth:`seal` turns entries
    whose tick has been answered into tuples of plain values, which the
    collector stops tracking; iterating gives every entry back with ``res``
    as a dict (lists of a scan's answers as tuples)."""

    def __init__(self):
        self.sealed: list = []
        self.open: list = []

    def append(self, entry: tuple):
        self.open.append(entry)

    def seal(self, before_tick: int):
        """Seal the entries of ticks before ``before_tick``."""
        i = 0
        for i, (tick, kind, keys, val, res) in enumerate(self.open):
            if tick >= before_tick:
                break
            flat = []      # flat: the fewer levels, the sooner untracked
            for k, v in res.items():
                flat += (k, tuple(v) if isinstance(v, list) else v)
            self.sealed.append((tick, kind, keys, val, tuple(flat)))
        else:
            i = len(self.open)
        del self.open[:i]

    def __iter__(self):
        for tick, kind, keys, val, res in self.sealed:
            yield tick, kind, keys, val, dict(zip(res[::2], res[1::2]))
        yield from self.open

    def __len__(self):
        return len(self.sealed) + len(self.open)


def make_engine(config: dict, traffic: dict, table, tracer=None,
                mesh=None):
    """The engine over ``table``, with the tenants registered in order;
    over a ``mesh``, ``table`` is the list of shard tables in the mesh's
    device order.  Returns (engine, [tenant or None per tenant index])."""
    from repro.serving import ServingEngine, TenantRegistry
    from repro.serving.metrics import MetricsCollector
    space = keyspace(config)
    reg, tenants = None, [None] * config["tenants"]
    if config["tenant_bits"]:
        reg = TenantRegistry(config["tenant_bits"])
        wls = workloads(config, traffic)
        tenants = [reg.register(f"tenant{i}-{wl}") for i, wl in enumerate(wls)]
        for i, t in enumerate(tenants):      # the build folded keys so too
            want = [(i << space.key_bits) | k for k in (0, space.records - 1)]
            if t.tid != i or reg.fold(t.tid, [0, space.records - 1]).tolist() \
                    != want:
                raise RuntimeError("tenant folding differs from the build's")
    tables = [table] if mesh is None else list(table)
    eng = ServingEngine(table_config(config), tables=tables, mesh=mesh,
                        max_slots=traffic["clients"], tenants=reg,
                        record_schedule=True, trace=tracer,
                        metrics=MetricsCollector(chain_sample_every=NO_SAMPLING))
    eng.schedule = OpLog()
    return eng, tenants


def set_up(config: dict, traffic: dict, seed: int, words: tuple,
           tracer=None) -> tuple:
    """The table built from the seed on its chips, the engine over it and
    the clients' streams, drawn while the build runs.  Returns (engine,
    clients, the table's devices in shard order) once the table is on
    them."""
    import jax
    mesh = serving_mesh(config)
    t = time.perf_counter()
    if mesh is None:
        devices = jax.devices()[:1]
        table, owned = build_table(config, words), None
    else:
        devices = list(mesh.devices.flat)
        table, owned = build_shards(config, words, devices)
    t_build = time.perf_counter() - t
    eng, tenants = make_engine(config, traffic, table, tracer, mesh)
    del table
    clients = make_clients(config, traffic, tenants, seed)
    t_streams = time.perf_counter() - t
    if owned is not None:
        def ready_s(count):
            count.block_until_ready()
            return time.perf_counter() - t
        with ThreadPoolExecutor(len(owned)) as pool:
            ready = list(pool.map(ready_s, owned))
        check_owned(config, owned)
        log(f"shard records={[int(c) for c in owned]} dispatch_s={t_build} "
            f"built_s={ready}")
    jax.block_until_ready(eng.shards)
    log(f"build_s={time.perf_counter() - t} streams_s={t_streams}")
    return eng, clients, devices


def workloads(config: dict, traffic: dict) -> list:
    """The YCSB workload of each tenant: the traffic's list, repeated."""
    wl = traffic["workloads"]
    return [wl[i % len(wl)] for i in range(config["tenants"])]


@dataclasses.dataclass
class Client:
    tenant: object
    reqs: list                 # op lists, replayed in a loop
    i: int = 0
    req: object = None
    t_submit: float = 0.0
    tick_submit: int = 0


def make_clients(config: dict, traffic: dict, tenants: list, seed: int) -> list:
    """``traffic["clients"]`` clients spread evenly over the tenants
    (``make_engine``'s list); each tenant's YCSB stream is drawn from (seed,
    tenant index) and dealt round robin to its clients."""
    n_t = config["tenants"]
    per_tenant = traffic["clients"] // n_t
    if per_tenant * n_t != traffic["clients"]:
        raise ValueError("clients must split evenly over the tenants")
    cdfs: dict = {}
    clients = []
    for i, wl in enumerate(workloads(config, traffic)):
        stream = OpStream(wl, config["records_per_tenant"],
                          ops_per_request=traffic["ops_per_request"],
                          distribution=traffic.get("distribution", ""),
                          theta=traffic.get("theta", 0.99),
                          seed=np.random.SeedSequence([seed, i]), cdfs=cdfs)
        reqs = stream.requests(per_tenant * traffic["requests_per_client"])
        clients += [Client(tenants[i], reqs[j::per_tenant])
                    for j in range(per_tenant)]
    return clients


# ---------------------------------------------------------------------------
# the closed loop
# ---------------------------------------------------------------------------

class ClosedLoop:
    """Drives the engine's ``tick`` with the clients' requests."""

    def __init__(self, eng, clients: list):
        from repro.serving import Request
        self.Request = Request
        self.eng = eng
        self.clients = clients
        self.latencies: list = []
        self.lat_ticks: list = []      # engine ticks from submission to answer
        self.tick_ends: list = []      # harness clock at each recorded tick
        self.ops = 0
        self.ticks = 0
        self.submitting = True
        self.waves: list = []
        self.annotate = None       # TraceAnnotation class while profiling

    def submit(self, c: Client):
        c.req = self.Request(ops=c.reqs[c.i % len(c.reqs)], tenant=c.tenant)
        c.i += 1
        c.t_submit = time.perf_counter()
        c.tick_submit = self.eng.ticks
        self.eng.submit(c.req)

    def start(self):
        """Submit the first requests in ``ops_per_request`` waves, one a
        tick, so that clients complete on different ticks as independent
        YCSB threads do, and not all on every fourth."""
        k = len(self.clients[0].reqs[0])
        self.waves = [self.clients[w::k] for w in range(k)]
        for c in self.waves.pop(0):
            self.submit(c)

    def tick(self, record: bool) -> int:
        ann = self.annotate
        if ann is None:
            n = self.eng.tick()
        else:
            with ann("tick"):
                n = self.eng.tick()
        now = time.perf_counter()
        eng = self.eng
        eng.schedule.seal(eng.ticks - eng.pipeline_depth + 1)
        done = [c for c in self.clients
                if c.req is not None and c.req.cursor >= len(c.req.ops)]
        if record:
            self.latencies.extend(now - c.t_submit for c in done)
            self.lat_ticks.extend(eng.ticks - c.tick_submit for c in done)
            self.tick_ends.append(now)
            self.ops += n
            self.ticks += 1
        for c in done:
            c.req = None
        if self.waves:
            done += self.waves.pop(0)
        if self.submitting and done:
            if ann is None:
                for c in done:
                    self.submit(c)
            else:
                with ann("submit"):
                    for c in done:
                        self.submit(c)
        return n

    def drain(self, max_ticks: int = 10_000) -> int:
        """Stop submitting and tick until every request has completed;
        returns the ops of submitted requests that got no answer."""
        self.submitting = False
        for _ in range(max_ticks):
            if self.eng.pool.idle():
                break
            self.tick(record=False)
        self.eng.flush()
        return sum(len(c.req.ops) - c.req.cursor for c in self.clients
                   if c.req is not None)


# ---------------------------------------------------------------------------
# what the per-layer readers read
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TraceView:
    events: list               # devtrace.Event rows
    lo_ns: float               # the traced window, on the profiler clock
    hi_ns: float
    ticks: int                 # engine ticks inside it
    first_tick: int            # engine tick index of the first of them
    engine_spans: list         # (name, start_ns, end_ns), profiler clock
    device_ids: list | None = None   # the cell's chips; None: every plane

    @property
    def window_s(self) -> float:
        return (self.hi_ns - self.lo_ns) * 1e-9

    @property
    def planes(self) -> list | None:
        """The trace planes of the cell's chips."""
        if self.device_ids is None:
            return None
        return [devtrace.plane(i) for i in self.device_ids]


@dataclasses.dataclass
class RunRecord:
    cell: Cell
    device_kind: str
    device_ids: list           # the cell's chips, in shard order
    window_s: float
    window_ticks: int
    window_ops: int
    latencies_s: list
    engine: object
    tables_end: list           # the engine's tables as the window closed
    trace: TraceView | None = None

    def probe_keys(self, first: int, stop: int) -> list:
        """Per engine tick in [first, stop): the folded keys its probe
        call was given (reads, rmw reads, scans)."""
        out = [[] for _ in range(stop - first)]
        for tick, kind, keys, _, _ in self.engine.schedule:
            if first <= tick < stop and kind in ("read", "rmw", "scan"):
                out[tick - first].extend(keys)
        return out


class Profile:
    """The ``--trace 1`` window: ``jax.profiler`` from ``start_s`` into the
    measured window for ``length_s``, with the harness's tick and submit
    spans and the engine's own Tracer spans on."""

    def __init__(self, loop: ClosedLoop, tracer, start_s: float,
                 length_s: float, device_ids: list):
        self.loop, self.tracer = loop, tracer
        self.device_ids = device_ids
        self.start_s, self.stop_s = start_s, start_s + length_s
        self.dir = None
        self.state = "before"

    def step(self, elapsed: float):
        import jax
        if self.state == "before" and elapsed >= self.start_s:
            self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.window = jax.profiler.TraceAnnotation("perfbench_window")
            self.tracer.enabled = True
            self.window.__enter__()
            self.us0 = self.tracer.now_us()
            self.tick0 = self.loop.eng.ticks
            self.loop.annotate = jax.profiler.TraceAnnotation
            self.state = "on"
        elif self.state == "on" and elapsed >= self.stop_s:
            self.stop()

    def stop(self):
        import jax
        if self.state != "on":
            return
        self.loop.annotate = None
        self.window.__exit__(None, None, None)
        self.tracer.enabled = False
        self.ticks = self.loop.eng.ticks - self.tick0
        jax.profiler.stop_trace()
        self.state = "done"

    def view(self) -> TraceView | None:
        if self.state != "done":
            return None
        planes = [devtrace.plane(i) for i in self.device_ids]
        try:
            events = devtrace.on_planes(devtrace.load(self.dir), planes)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        win = devtrace.host_spans(events, "perfbench_window")
        if not win:
            raise RuntimeError("the traced window's span is not in the trace")
        lo, hi = win[0].start_ns, win[0].end_ns
        # the engine's Tracer runs on perf_counter microseconds; its clock
        # met the profiler's where the window span began
        spans = [(n, lo + (s - self.us0) * 1e3, lo + (e - self.us0) * 1e3)
                 for n, s, e in engine_spans(self.tracer)]
        return TraceView(events, lo, hi, self.ticks, self.tick0, spans,
                         self.device_ids)


def engine_spans(tracer) -> list:
    """(name, start_us, end_us) of the Tracer's duration spans."""
    out, open_ = [], {}
    for ev in tracer.to_events():
        if ev["ph"] == "B":
            open_.setdefault(ev["tid"], []).append((ev["name"], ev["ts"]))
        elif ev["ph"] == "E" and open_.get(ev["tid"]):
            name, ts = open_[ev["tid"]].pop()
            out.append((name, ts, ev["ts"]))
    return out


def breakdown(view: TraceView) -> dict:
    """The device operations that took most time, summed over the cell's
    chips, and the time in which all of them were idle by what the host
    was doing meanwhile (the innermost engine phase or harness span around
    each gap), ten of each."""
    lo, hi = view.lo_ns, view.hi_ns
    by_op = devtrace.time_by_name(view.events, lo, hi)
    spans = [("engine:" + n, s, e) for n, s, e in view.engine_spans]
    spans += [("harness:" + name, e.start_ns, e.end_ns)
              for name in ("tick", "submit")
              for e in devtrace.host_spans(view.events, name)]
    idle: dict = {}
    for g in devtrace.gaps(view.events, lo, hi, view.planes):
        name = devtrace.name_gap(g, spans, "harness:loop")
        idle[name] = idle.get(name, 0.0) + (g[1] - g[0])

    def top(d):
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(by_op), "idle_gaps": top(idle)}


class SilentMetric(RuntimeError):
    """A per-layer metric that the cell declares read nothing on the chip:
    what its reader looks for (a kernel, a program, a span) is gone, and the
    yardstick with it.  Only the CPU, which has no device plane, may leave
    a declared metric out."""

    def __init__(self, root: Path, names: list):
        what = "; ".join(
            f"{n}: {reader_doc(root, n)}" for n in names)
        super().__init__(f"declared per-layer metrics read nothing: {what}")


def on_chip(devices) -> bool:
    return devices[0].platform != "cpu"


def reader_doc(root: Path, name: str) -> str:
    """The docstring of a metric's reader: what it reads, on one line."""
    doc = metric_reader(root, name).__globals__.get("__doc__") or ""
    return " ".join(doc.split())


def timing_summary(loop: ClosedLoop, t0: float) -> str:
    """The window's tick durations and request latencies, for the log:
    what sets the tail (slow ticks, or requests that took more ticks)."""
    if not loop.tick_ends:
        return "no ticks"
    ends = np.asarray(loop.tick_ends)
    dur = np.diff(ends, prepend=t0) * 1e3
    med = float(np.median(dur))
    slow = np.flatnonzero(dur > 2 * med)
    worst = sorted(slow, key=lambda i: -dur[i])[:8]
    lat = np.asarray(loop.latencies) * 1e3
    lt = np.asarray(loop.lat_ticks)
    p99 = float(np.percentile(lat, 99)) if lat.size else 0.0
    tail = lt[lat >= p99] if lat.size else lt
    hist = dict(zip(*(x.tolist() for x in np.unique(lt, return_counts=True))))
    thist = dict(zip(*(x.tolist() for x in
                       np.unique(tail, return_counts=True))))
    q = [float(x) for x in np.percentile(lat, [50, 90, 95, 99, 99.9])] \
        if lat.size else []
    return (f"tick_ms median={med} p99={float(np.percentile(dur, 99))} "
            f"max={float(dur.max())} over_2x_median={slow.size} "
            f"({float(dur[slow].sum())} ms); slowest (s into window, ms): "
            f"{[(float(ends[i] - t0), float(dur[i])) for i in worst]}; "
            f"request_ms p50/p90/p95/p99/p99.9={q}; request ticks: {hist}; "
            f"ticks of requests at or over p99: {thist}")


class GcPauses:
    """Count and time the cyclic collector's passes until ``close``."""

    def __init__(self):
        self.n, self.total_s, self.max_s, self.t = [0, 0, 0], 0.0, 0.0, 0.0
        self.long: list = []       # (harness clock, generation, s) over 5 ms
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self.t = time.perf_counter()
        else:
            d = time.perf_counter() - self.t
            self.n[info["generation"]] += 1
            self.total_s += d
            self.max_s = max(self.max_s, d)
            if d > 5e-3:
                self.long.append((self.t, info["generation"], d))

    def close(self):
        gc.callbacks.remove(self._cb)

    def __str__(self):
        return (f"passes by generation {self.n} total_s={self.total_s} "
                f"max_s={self.max_s}")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def log(msg: str):
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float) -> tuple:
    """Set up, measure for ``seconds``, check.  Returns (result dict for
    the last line, [(check name, value, limit), ...])."""
    import jax
    from repro.serving.tracing import COMPILES, Tracer
    cell = load_cell(root, workload)
    enable_cache(root)
    config, traffic = cell.config, cell.traffic
    words = seed_words(seed)
    tracer = Tracer(capacity=1 << 21, enabled=False) if trace else None
    eng, clients, devices = set_up(config, traffic, seed, words, tracer)
    # set-up's objects (the streams above all) live for the whole run: out
    # of the collector's generations, as a server's start-up state would be
    gc.collect()
    gc.freeze()
    loop = ClosedLoop(eng, clients)
    loop.start()
    t = time.perf_counter()
    for _ in range(traffic["warmup_ticks"]):
        loop.tick(record=False)
    log(f"warmup_s={time.perf_counter() - t} "
        f"ticks={traffic['warmup_ticks']}")
    # and so do the programs and caches that warming up made
    gc.collect()
    gc.freeze()

    device_ids = [d.id for d in devices]
    prof = Profile(loop, tracer, seconds / 4, min(3.0, seconds / 2),
                   device_ids) if trace else None
    pauses = GcPauses()
    compiles0 = COMPILES.counts["compiles"]
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    t_end = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if prof is not None:
            prof.step(now - t0)
        loop.tick(record=True)
    t1 = time.perf_counter()
    pauses.close()
    log(f"gc in the window: {pauses}; over 5 ms (s into window, gen, s): "
        f"{[(t - t0, g, d) for t, g, d in pauses.long]}")
    log(timing_summary(loop, t0))
    window_end_tick = eng.ticks - 1
    if prof is not None:
        prof.stop()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    peak = max(peaks)
    log(f"peak_bytes_in_use per chip={peaks}")
    tables_end = list(eng.shards)
    table_bytes = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
        tables_end))
    log(f"window_s={t1 - t0} ticks={loop.ticks} ops={loop.ops} "
        f"requests={len(loop.latencies)} "
        f"compiles={COMPILES.counts['compiles'] - compiles0}")

    unanswered = loop.drain()
    t = time.perf_counter()
    ref = HostReference(keyspace(config), words)
    res = ref.check(eng.schedule, window_end_tick)
    log(f"check_s={time.perf_counter() - t} ops_checked={res['ops']}")
    for f in res["first"]:
        log(f"wrong answer: {f}")

    rec = RunRecord(cell, devices[0].device_kind, device_ids, t1 - t0,
                    loop.ticks, loop.ops, loop.latencies, eng, tables_end)
    metrics: dict = {}
    result_breakdown = None
    if not trace:
        values = {
            "ops_per_s": loop.ops / (t1 - t0),
            "table_bytes_per_user_byte":
                table_bytes / (res["live_at_end"] * roofline.PAGE_ENTRY_BYTES),
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        rec.trace = prof.view()
        for m in cell.per_layer:
            v = metric_reader(root, m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        silent = [m["name"] for m in cell.per_layer
                  if m["name"] not in metrics]
        if silent and on_chip(devices):
            raise SilentMetric(root, silent)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": peak}
    if trace and rec.trace is not None:
        v = rec.trace
        device["busy_s"] = devtrace.busy_ns(v.events, v.lo_ns, v.hi_ns,
                                            v.planes) * 1e-9
        device["window_s"] = v.window_s
        result_breakdown = breakdown(v)

    checks = [("wrong_answers", res["wrong"], 0),
              ("unanswered_ops", unanswered, 0)]
    correct = all(v <= lim for _, v, lim in checks)
    result = {"correct": correct, "attempted": res["ops"] + unanswered,
              "failed": res["wrong"] + unanswered, "metrics": metrics,
              "device": device}
    if result_breakdown is not None:
        result["breakdown"] = result_breakdown
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result, checks
