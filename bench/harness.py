"""One run of one cell: set-up, the measured window, the check, the result.

Set-up builds the cell's deployment from the seed with numpy, ingests it
through the façade (``figaro.Session().ingest(tables).join(edges, root=)``),
opens ``ds.serve(kind=..., max_batch=..., queue_depth=...)``, builds the
payload pool and runs one batch of every live batch size the traffic can
form, so every program the window uses is compiled (or loaded from the
persistent compilation cache) before it opens. The window then drives
``AsyncFigaroServer.submit()`` (`load`), and once it has closed, device
memory has been read and the server is closed, a sample of payloads drawn
from the seed is recomputed by the plain reference (`reference`) and every
answer given for them is compared (`checks/<kind>.py`).
"""

from __future__ import annotations

import gc
import json
import os
import shutil
import threading
import time

import numpy as np

from bench import load, reference, registry, tracing

PERTURB = 0.1  # a tenant's values: the base values times (1 + 0.1·N(0, 1))
CACHE_DIR = registry.REPO / ".jax_cache"
TRACE_DIR = registry.REPO / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_S = 10.0  # the traced part of a --trace 1 window: about 60 MB of trace


class NoChip(RuntimeError):
    """No TPU of a known kind, or fewer chips than the cell asks for."""


class Compiles:
    """Counts executables built (compiled, or loaded from the persistent
    cache) and the seconds spent on them, from ``jax.monitoring``."""

    def __init__(self, monitoring):
        self._monitoring = monitoring
        self._lock = threading.Lock()
        self.count = 0
        monitoring.register_event_duration_secs_listener(self)

    def __call__(self, name, secs, **_):
        if name == COMPILE_EVENT:
            with self._lock:
                self.count += 1

    def read(self) -> int:
        with self._lock:
            return self.count

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self)


def start_jax():
    """Import JAX with 64-bit types."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    import jax

    jax.config.update("jax_enable_x64", True)
    return jax


def use_cache(jax) -> None:
    """Keep compiled programs in the checkout's cache directory (set once the
    chip is found, so a refused run leaves the process's JAX as it was)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # No eviction: an evicting cache reads every entry's access-time file
    # under a lock that does not exclude the server's own threads, and a
    # concurrent write then fails and leaves the program uncached.
    jax.config.update("jax_compilation_cache_max_size", -1)


def device_info(jax, chips: int) -> dict:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {dev.platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    if dev.device_kind not in registry.peaks():
        raise NoChip(f"device kind {dev.device_kind!r} is not in peaks.json")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def device_memory(jax) -> dict:
    """The fullest chip's peak: buffers in use plus the memory reserved for
    the programs' temporaries, which ``peak_bytes_in_use`` leaves out."""
    def peak(stats: dict) -> int:
        return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)

    fullest = max((d.memory_stats() for d in jax.local_devices()), key=peak)
    return {"peak_bytes": peak(fullest),
            "peak_bytes_in_use": fullest["peak_bytes_in_use"],
            "peak_bytes_reserved": fullest.get("peak_bytes_reserved"),
            "bytes_limit": fullest.get("bytes_limit")}


def check_layout(ds, tables: dict, order: list[str]) -> None:
    """The payloads are built in the benchmark's own row and column order;
    refuse to run if the plan orders either differently, or reduced rows."""
    want = tuple(f"{r}.{c}" for r in order for c in tables[r][2])
    if tuple(ds.columns) != want:
        raise RuntimeError(f"plan columns {ds.columns} differ from {want}")
    for name, data in zip(order, ds.plan.data):
        values = tables[name][1]
        if not np.array_equal(np.asarray(data)[:len(values)], values):
            raise RuntimeError(f"{name}: the plan's rows are not in the "
                               f"benchmark's order")
        live = ds.stats()["nodes"][name]["live_rows"]
        if live != len(values):
            raise RuntimeError(f"{name}: {live} live rows, generated "
                               f"{len(values)}")


def payload_pool(tables: dict, order: list[str], size: int, dtype, rng):
    return [tuple((tables[r][1] * (1.0 + PERTURB * rng.standard_normal(
        tables[r][1].shape))).astype(dtype) for r in order)
        for _ in range(size)]


def warm_up(server, pool, cycle, batches) -> None:
    """One coalesced batch of each live size in ``batches``."""
    for b in batches:
        server.pause()
        futures = [server.submit(pool[cycle[j % len(cycle)]])
                   for j in range(b)]
        server.resume()
        for f in futures:
            f.result()


class Tracer:
    """The profiler around the traced part of the window, and its
    ``bench.window`` annotation."""

    def __init__(self, jax, clock):
        self.jax, self.clock = jax, clock
        self.span = None
        self._window = None

    def start(self) -> None:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # Host annotations and device operations only: the Python tracer
        # would record every call of every thread, and the HLO protos add
        # size without adding a number.
        options = self.jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        self.jax.profiler.start_trace(str(TRACE_DIR),
                                      profiler_options=options)
        self._window = self.jax.profiler.TraceAnnotation(tracing.WINDOW)
        self._window.__enter__()
        self.span = [self.clock(), None]

    def stop(self) -> None:
        self.span[1] = self.clock()
        self._window.__exit__(None, None, None)
        self.jax.profiler.stop_trace()

    def reduce(self) -> dict:
        found = sorted(TRACE_DIR.glob("**/*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{TRACE_DIR}")
        profile = self.jax.profiler.ProfileData.from_file(str(found[-1]))
        return tracing.reduce(profile)


def compare(kind: str, done, tables, order, cfg, pool, sample) -> dict:
    """The largest error per check over every answer given for a payload in
    ``sample``."""
    keys = {r: tables[r][0] for r in order}
    join = reference.JoinReference(keys, cfg.ROOT, cfg.EDGES)
    moments = join.moments([dict(zip(order, pool[p])) for p in sample])
    by_payload = dict(zip((int(p) for p in sample), moments))
    numbers: dict[str, float] = {}
    check = registry.check(kind)
    import jax

    for req in done:
        if req.payload not in by_payload:
            continue
        answer = jax.tree.map(np.asarray, req.result)
        for name, err in check(answer, by_payload[req.payload]).items():
            numbers[name] = max(numbers.get(name, 0.0), err)
    return numbers


def run(cell: str, seed: int, seconds: float, traced: bool, *,
        started: float, rehearse: bool = False, control: bool = False,
        say=print) -> dict:
    """One run of ``cell``. ``rehearse`` runs the configuration's tiny size
    on whatever backend JAX has and reports no metric; ``control`` serves in
    the precision below the traffic's (the comparison's control)."""
    clock = time.perf_counter
    bench = registry.benchmark()
    cell_entry = registry.workload(bench, cell)
    cfg = registry.config(cell_entry["config"])
    mix = registry.traffic(cell_entry["traffic"])
    limits = registry.limits(cell)
    entries = registry.metrics_for(bench, cell, traced)

    jax = start_jax()
    import jax.numpy as jnp
    from jax import monitoring

    device = None if rehearse else device_info(jax, cell_entry["chips"])
    if not rehearse:
        use_cache(jax)
    from repro import figaro

    annotate = jax.profiler.TraceAnnotation
    rng = np.random.default_rng(seed)
    sizes = cfg.TINY if rehearse else cfg.SIZES
    order = reference.preorder(cfg.ROOT, cfg.EDGES)
    phases = {"start": clock() - started}
    with annotate("bench.payload"):
        tables = cfg.relations(rng, sizes)
    phases["generate"] = clock() - started
    ds = figaro.Session().ingest(tables).join(list(cfg.EDGES), root=cfg.ROOT)
    check_layout(ds, tables, order)
    phases["plan"] = clock() - started
    served = mix["control_dtype"] if control else mix["dtype"]
    server = ds.serve(kind=mix["kind"], max_batch=mix["max_batch"],
                      queue_depth=mix["queue_depth"], dtype=jnp.dtype(served))
    with annotate("bench.payload"):
        pool = payload_pool(tables, order, mix["pool"],
                            np.dtype(mix["dtype"]), rng)
    # Each request's payload is drawn from the pool on its own, so every
    # payload takes every position in a coalesced batch.
    cycle = rng.integers(0, mix["pool"], 1 << 16)
    phases["pool"] = clock() - started
    compiles = Compiles(monitoring)
    try:
        warm_up(server, pool, cycle, mix["warm_batches"])
        setup_s = phases["warm_up"] = clock() - started
        built = compiles.read()
        tracer = Tracer(jax, clock) if traced else None
        if mix["arrival"] == "closed":
            result = load.closed_loop(
                server, pool, cycle, outstanding=mix["outstanding"],
                batch=mix["max_batch"], seconds=seconds, annotate=annotate,
                clock=clock, on_first=tracer and tracer.start,
                on_stop=tracer and tracer.stop, trace_for=TRACE_S)
        else:
            gaps = load.poisson_gaps(mix["rate_per_s"], seconds, rng)
            result = load.open_loop(server, pool, cycle, gaps,
                                    annotate=annotate, clock=clock,
                                    on_start=tracer and tracer.start,
                                    on_stop=tracer and tracer.stop,
                                    trace_for=TRACE_S)
        window_compiles = compiles.read() - built
    finally:
        compiles.close()
    isolated = server.stats()["isolated_redispatches"]
    memory = None if rehearse else device_memory(jax)
    server.close()
    del server, ds
    gc.collect()

    reqs = result["requests"]
    done = [r for r in reqs if r.done is not None and r.error is None]
    t_check = clock()
    ids = sorted({r.payload for r in done})
    sample = np.random.default_rng([seed, 1]).choice(
        ids, size=min(mix["check_payloads"], len(ids)), replace=False)
    numbers = compare(mix["kind"], done, tables, order, cfg, pool, sample)
    check_s = clock() - t_check
    correct = (bool(done) and len(done) == len(reqs)
               and set(numbers) == set(limits)
               and all(numbers[k] <= limits[k]["limit"] for k in limits))

    late = [r.submitted - r.due for r in reqs if r.submitted is not None] \
        if result["arrival"] == "open" else [0.0]
    t_reduce = clock()
    run_record = {
        "setup_s": setup_s, "result": result, "seconds": seconds,
        "compiles_in_window": window_compiles,
        "trace": tracer.reduce() if traced else None,
        "trace_span": tracer.span if traced else None,
    }
    reduce_s = clock() - t_reduce
    info = {
        "cell": cell, "seed": seed, "control": control, "served_dtype": served,
        "config": {"name": cell_entry["config"], "source": cfg.SOURCE,
                   "reduced": list(cfg.REDUCED), "assumed": cfg.ASSUMED,
                   "sizes": sizes},
        "traffic": dict(mix, name=cell_entry["traffic"]),
        "setup_s": setup_s, "setup_phases_s": phases,
        "window": result.get("window"), "trace_reduce_s": reduce_s,
        "attempted": len(reqs), "completed": len(done),
        "compiles_in_window": window_compiles,
        "isolated_redispatches": isolated,
        "memory": memory, "check_s": check_s,
        "checked_payloads": [int(p) for p in sample],
        "generator_late_max_s": max(late),
    }
    say("bench info " + json.dumps(info))
    out = {"correct": correct, "attempted": len(reqs),
           "failed": len(reqs) - len(done)}
    if not rehearse:
        out["metrics"] = {}
        for m in entries:
            value = registry.reader(m["name"])(run_record)
            if value is not None:
                out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        out["device"] = dict(device, memory_peak_bytes=memory["peak_bytes"])
        if traced:
            tr = run_record["trace"]
            out["device"].update(busy_s=tr["busy_s"], window_s=tr["window_s"])
            out["breakdown"] = {"device_ops": tr["device_ops"],
                                "idle_gaps": tr["idle_gaps"]}
    out["checks"] = {k: {"value": numbers.get(k), "limit": v["limit"]}
                     for k, v in limits.items()}
    return out
