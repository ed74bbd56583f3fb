"""The benchmark's one command: run one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run, in order:

1. set-up: generate the configuration's tables from ``--seed``
   (``bench/datagen``), load them with ``create_table``, and run a
   warm-up round (every template of the mix once), which compiles every
   step (or loads it from the compile cache) and fills the block cache
   where the budget retains blocks;
2. the window: a closed loop of ``streams`` clients, each sending the mix's
   SQL through ``Database.sql`` -> ``Query.execute(distributed=True)`` ->
   ``to_pydict()``, its next query only after the last one returned.  The
   query in flight when ``--seconds`` elapse is finished.  With
   ``--trace 1`` the window runs under the JAX profiler;
3. the check: the device's peak memory is read, the database is shut
   down, and every answer of the window is compared with the template's
   numpy reference over the generated arrays.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and ``checks``: each number compared with its limit).  The
numbers compared are also the last lines of standard error.  Without an
accelerator, or with fewer chips than the cell asks for, the run exits 2
and prints no result.  A run that cannot measure the device exits 3 and
prints no result: when a warm-up query fails as a window query would
(it raised, ran on the host, or fell back to it), or when a traced run
on the chip finds no device operation inside its window.

Everything that belongs to one configuration, traffic mix, query template
or per-layer metric is found by name: ``bench/configs/<config>.json``
(through ``BENCHMARK.json``), ``bench/traffic/<traffic>.json``,
``bench/queries/<template>.py`` and ``bench/metrics/<metric>.py``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                "/jax/compilation_cache/cache_misses": "cache_misses"}


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


class OffDevice(RuntimeError):
    """The run cannot measure the device: a warm-up query left the device
    tier, or a traced chip run saw no device operation in its window."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = json.loads(spec_path.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])


def template(name: str):
    return importlib.import_module(f"bench.queries.{name}")


def jax_for_benchmark():
    """JAX as the engine configures it (x64, the persistent compile cache
    where ``jax_runtime`` places it), with every compile kept, however
    short, so later runs compile nothing."""
    from repro.core.device_cache import jax_runtime
    jax = jax_runtime()
    # JAX writes into the cache directory but never makes it
    Path(jax.config.jax_compilation_cache_dir).mkdir(parents=True,
                                                      exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def devices_for(jax, chips: int, require_tpu: bool = True) -> list:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX reports "
                     f"{len(devs)}")
    return devs[:chips]


# ---- load ----------------------------------------------------------------

def load(db, data: dict) -> None:
    """Hand the generated columns to the engine as they are: codes over
    their sorted heaps, scaled integers, day numbers."""
    import numpy as np
    from repro.core.column import Column, StringHeap
    from repro.core.table import Table
    from repro.core.types import ColumnSchema, DBType, TableSchema
    kinds = {"int64": DBType.INT64, "float64": DBType.FLOAT64,
             "date": DBType.DATE, "decimal": DBType.DECIMAL,
             "varchar": DBType.VARCHAR}
    for name, cols in data.items():
        columns, schema = {}, []
        for cname, col in cols.items():
            t = kinds[col.kind]
            col.data.flags.writeable = False     # the references read these
            heap = None if col.heap is None else \
                StringHeap(np.asarray(col.heap, dtype=object))
            columns[cname] = Column(t, col.data, heap=heap, scale=col.scale)
            schema.append(ColumnSchema(cname, t, scale=col.scale))
        db.create_table(name, Table(TableSchema(name, tuple(schema)),
                                    columns))


# ---- the closed loop -----------------------------------------------------

@dataclass
class Record:
    template: str
    start: float
    end: float
    answer: dict | None
    stats: dict
    error: str = ""


STAT_FIELDS = ("device_tier", "device_fallback", "device_bytes_h2d",
               "device_cache_hits", "device_prefetch_hits",
               "device_evictions")


def run_query(jax, db, name: str, sql: str, mesh) -> Record:
    span = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    answer, stats, error = None, {}, ""
    try:
        with span("sql"):
            q = db.sql(sql)
        with span(f"execute:{name}"):
            table = q.execute(distributed=True, mesh=mesh)
        st = db.last_stats
        stats = {f: getattr(st, f) for f in STAT_FIELDS}
        with span("fetch"):
            answer = table.to_pydict()
    except Exception as e:     # a failed query is counted, not fatal
        error = f"{type(e).__name__}: {e}"
    return Record(name, t0, time.perf_counter(), answer, stats, error)


def failed(r: Record) -> bool:
    """Raised, ran on the host (no device tier), or fell back to it."""
    return bool(r.error) or not r.stats.get("device_tier") \
        or bool(r.stats.get("device_fallback"))


@dataclass
class Window:
    start: float
    records: list = field(default_factory=list)
    compiles: int = 0
    setup: dict = field(default_factory=dict)   # set-up phases and compiles


def closed_loop(jax, db, traffic: dict, seconds: float, mesh) -> Window:
    """``streams`` clients, stream k starting at mix offset k; each sends
    its next query when the last one returned, until ``seconds`` elapse."""
    mix = traffic["mix"]
    sqls = {t: template(t).SQL for t in mix}
    lock = threading.Lock()
    win = Window(time.perf_counter())
    deadline = win.start + seconds

    def client(k: int) -> None:
        i = k
        while time.perf_counter() < deadline:
            t = mix[i % len(mix)]
            rec = run_query(jax, db, t, sqls[t], mesh)
            with lock:
                win.records.append(rec)
            i += 1

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(int(traffic["streams"]))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return win


# ---- the run ---------------------------------------------------------------

@dataclass
class Context:
    """What a per-layer metric reader sees."""
    trace: object            # bench.trace.Summary, or None
    queries: list            # [{template, stats, logical_bytes, latency_s}]
    peaks: dict


def _compile_counter(jax) -> dict:
    """Counts, since it was registered, of programs made for the device
    (``programs``: compiled or taken from the persistent cache), and of
    persistent-cache hits and misses."""
    count = {"programs": 0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, duration, **kw):
        if event == COMPILE_EVENT:
            count["programs"] += 1

    def on_event(event, **kw):
        if event in CACHE_EVENTS:
            count[CACHE_EVENTS[event]] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return count


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             scale: float | None = None, require_tpu: bool = True,
             t_start: float = T_PROCESS) -> dict:
    """One run; returns the result object.  ``scale`` (a fraction of the
    configuration's scale factor and device budget) and ``require_tpu``
    exist for the checks under ``bench/checks``, which run a cell small on
    the CPU."""
    import numpy as np

    from bench import trace as btrace
    from bench.datagen import tpch as gen

    jax = jax_for_benchmark()
    counts = _compile_counter(jax)
    devices = devices_for(jax, cell.chips, require_tpu)
    from jax.sharding import Mesh
    from repro.core import startup
    mesh = Mesh(np.array(devices), ("data",))

    conf, traffic = cell.config, cell.traffic
    if traffic.get("loop") != "closed" or traffic.get("entry") != "sql":
        raise SystemExit(f"traffic {traffic} is not a closed SQL loop")
    sf, budget = conf["scale_factor"], conf["device_budget"]
    if scale is not None:            # the checks' tiny runs keep the ratio
        sf, budget = sf * scale, int(budget * scale)   # of data to budget
    phases = {"start_s": time.perf_counter() - t_start}
    t0 = time.perf_counter()
    data = gen.generate(sf, seed % 2**63, conf["tables"])
    phases["generate_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    db = startup(device_budget=budget)
    load(db, data)
    phases["load_s"] = time.perf_counter() - t0
    for t in dict.fromkeys(traffic["mix"]):
        r = run_query(jax, db, t, template(t).SQL, mesh)
        if failed(r):           # the window's rule, before the window
            db.shutdown()
            raise OffDevice(
                f"warm-up {t} left the device tier: tier "
                f"{r.stats.get('device_tier', '')!r}, fallback "
                f"{r.stats.get('device_fallback', '')!r}, error {r.error!r}")
        phases[f"warmup_{t}_s"] = r.end - r.start
    setup_s = time.perf_counter() - t_start
    phases.update(counts)
    in_setup = dict(counts)

    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(btrace.WINDOW_SPAN):
            win = closed_loop(jax, db, traffic, seconds, mesh)
    finally:
        if trace:
            jax.profiler.stop_trace()
    win.compiles = counts["programs"] - in_setup["programs"]
    win.setup = phases

    summary = None
    if trace:
        try:
            summary = btrace.summarize(
                btrace.read(btrace.find_xplane(log_dir)))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)

    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    db.shutdown()
    del db
    if trace:
        require_device_ops(summary, require_tpu)

    checks = check_answers(win.records, traffic["mix"], data)
    return result(cell, win, summary, checks, setup_s, data, devices, peak,
                  trace)


def require_device_ops(summary, require_tpu: bool) -> None:
    """A traced run on the chip whose trace has no window span, or no
    device operation inside it (``summary`` None), measured nothing of the
    device.  The CPU's trace has no device plane, so the checks' runs
    (``require_tpu`` false) pass."""
    if require_tpu and summary is None:
        raise OffDevice("the traced window holds no device operation")


def check_answers(records: list, mix: list, data: dict) -> dict:
    """``{name: (value, limit)}`` over every answer of the window: each
    template's numbers at their worst, and the queries left unanswered."""
    out = {"unanswered": (sum(1 for r in records if r.answer is None), 0)}
    for t in dict.fromkeys(mix):
        mod = template(t)
        want = mod.reference(data)
        worst = {k: None for k in mod.LIMITS}
        for r in records:
            if r.template != t or r.answer is None:
                continue
            for k, v in mod.compare(r.answer, want).items():
                worst[k] = v if worst[k] is None else max(worst[k], v)
        for k, lim in mod.LIMITS.items():
            # a template that no answer reached has shown nothing correct
            out[k] = (worst[k], lim)
    return out


def _ok(value, limit) -> bool:
    return value is not None and value <= limit


def result(cell: Cell, win: Window, summary, checks: dict, setup_s: float,
           data: dict, devices: list, peak: int, trace: bool) -> dict:
    import numpy as np
    done = [r for r in win.records if r.answer is not None]
    lat_ms = [(r.end - r.start) * 1e3 for r in win.records]
    metrics = {}
    if not trace:
        values = {}
        if done:
            values["qps"] = len(done) / (max(r.end for r in done)
                                         - win.start)
        if lat_ms:
            values["latency_p95_ms"] = float(np.percentile(lat_ms, 95))
        values["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        kind = devices[0].device_kind
        peaks = json.loads((BENCH / "peaks.json").read_text())
        if devices[0].platform == "tpu" and kind not in peaks:
            raise RuntimeError(f"no peaks for device kind {kind!r} in "
                               "bench/peaks.json")
        bytes_of = {t: template(t).logical_bytes(data)
                    for t in dict.fromkeys(r.template for r in done)}
        ctx = Context(summary, [
            {"template": r.template, "stats": r.stats,
             "logical_bytes": bytes_of[r.template],
             "latency_s": r.end - r.start} for r in done],
            peaks.get(kind, {}))
        for m in cell.per_layer:
            v = importlib.import_module(f"bench.metrics.{m['name']}") \
                .read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": all(_ok(v, lim) for v, lim in checks.values()),
           "attempted": len(win.records),
           "failed": sum(1 for r in win.records if failed(r)),
           "metrics": metrics, "device": device}
    if trace and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    out["diagnostics"] = diagnostics(win, lat_ms)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def diagnostics(win: Window, lat_ms: list) -> dict:
    per = {}
    for r in win.records:
        per.setdefault(r.template, []).append(r)
    return {"compiles_in_window": win.compiles,
            "setup": win.setup,
            "errors": sorted({r.error for r in win.records if r.error})[:3],
            "templates": {
                t: {"n": len(rs),
                    "median_ms": statistics.median(
                        (r.end - r.start) * 1e3 for r in rs),
                    "tiers": sorted({r.stats.get("device_tier", "")
                                     for r in rs}),
                    "fallbacks": sorted({r.stats.get("device_fallback", "")
                                         for r in rs} - {""})[:2],
                    "h2d_bytes": sum(r.stats.get("device_bytes_h2d", 0)
                                     for r in rs)}
                for t, rs in per.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the compile cache is this checkout's, at a fixed path: the engine
    # takes its directory from this variable, and one set for the whole
    # machine would be shared with every other checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    cell = load_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except OffDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out.pop("diagnostics")), file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"{k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
