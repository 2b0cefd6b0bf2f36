"""One run of one cell: set-up, the measured window, the check.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name ``BENCHMARK.json`` gives:

    bench/configs/<file named by the configuration entry>
    bench/generators/<config["generator"]>.py     generate(n, seed)
    bench/traffic/<traffic>.json                  read by ``load.py``
    bench/metrics/<metric>.py, or the file of its  read(ctx) -> float | None
        base name (without its last dotted suffix)

so a new cell, mix or metric adds files and entries and edits nothing.

Every seed does the same work. The data is one point set, drawn from
the configuration's ``data_seed`` as the source is one file. The
warm-up is drawn from the mix's ``set_seed`` alone, so set-up repeats
from run to run. The window's queries and arrival gaps are one set
drawn from ``set_seed``, in an order drawn from ``--seed``; the sample
of replies that is checked is drawn from ``--seed``. Each stream has
its own ``default_rng([stream, seed])``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import threading
import time

import numpy as np

from . import compare, load

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
RUN_DIR = ".bench_run"          # runtime outputs inside the checkout
WAIT_AFTER_CLOSE_S = 60.0       # how long a late reply is waited for
STREAM_WARM, STREAM_WINDOW, STREAM_SAMPLE = 1, 2, 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# a traced window: a few seconds of it. Writing and reducing the trace
# of a 4-s window took about 90 s on a v5e host, and a traced run has
# to end within 360 s, set-up included.
TRACE_SECONDS = 2.0


# -- finding things by name --------------------------------------------------


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _module(path: str, label: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{label}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list    # metric entries this cell reports with --trace 0
    per_layer: list     # metric entries this cell reports with --trace 1
    root: str

    def generator(self):
        name = self.config["generator"]
        return _module(os.path.join(self.root, "bench", "generators", name + ".py"),
                       f"bench_generator_{name}").generate

    def reader(self, metric: str):
        path = reader_path(metric, self.root)
        return _module(path, "bench_metric_" + os.path.basename(path)[:-3]).read


def reader_path(metric: str, root: str = ROOT) -> str:
    """The reader of a per-layer metric: ``metrics/<name>.py``, or else
    the reader of its base name, the name without its last dotted
    suffix (``engine.dispatch_ms.open`` reads with
    ``metrics/engine.dispatch_ms.py``), so that one reader serves the
    metric in cells that report different end-to-end metrics."""
    d = os.path.join(root, "bench", "metrics")
    own = os.path.join(d, metric + ".py")
    if os.path.isfile(own) or "." not in metric:
        return own
    return os.path.join(d, metric.rsplit(".", 1)[0] + ".py")


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    key lists, or else every cell (end-to-end) or every cell that
    reports the end-to-end metric it moves (per-layer)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` with its configuration, mix and metrics."""
    spec = load_benchmark(root)
    entry = _named(spec["workloads"], name, "workload")
    cfg_entry = _named(spec["configs"], entry["config"], "config")
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic", entry["traffic"] + ".json")) as f:
        traffic = load.check(json.load(f))
    e2e = [m for m in spec["end_to_end"] if _reports(m, name, ())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(entry["chips"]), config, traffic, e2e, per_layer, root)


# -- compile counting -------------------------------------------------------


class CompileCounter:
    """Counts backend compiles (a persistent-cache hit counts too: it
    still builds an executable) and their seconds, and the persistent
    cache's hits, through ``jax.monitoring`` listeners, from outside the
    program."""

    _instance = None

    def __init__(self) -> None:
        import jax

        self._lock = threading.Lock()
        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            with self._lock:
                self.n += 1
                self.seconds += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = CompileCounter()
        return cls._instance


# -- set-up -----------------------------------------------------------------


def rng(seed: int, stream: int):
    return np.random.default_rng([stream, int(seed)])


def fixed_rng(c: Cell, stream: int):
    """A stream of the mix's fixed set of work: the same in every run."""
    return rng(int(c.traffic["set_seed"]), stream)


def window_work(s: "Served", seed: int, seconds: float):
    """The window's queries and arrival offsets: the mix's fixed set of
    ``round(rate_qps * seconds)`` queries and arrival gaps, in the order
    that ``seed`` draws."""
    fixed = fixed_rng(s.cell, STREAM_WINDOW)
    offsets = load.open_schedule(s.cell.traffic, seconds, fixed)
    pool = load.queries(s.points, len(offsets), fixed)
    order = rng(seed, STREAM_WINDOW)
    return pool[order.permutation(len(pool))], load.reorder(offsets, order)


@dataclasses.dataclass
class Served:
    """The system under test, set up for one cell and seed."""

    cell: Cell
    seed: int
    points: np.ndarray      # (n, d) float32, as the index holds them
    r: float
    index: object
    fe: object
    warm_dispatches: int = 0
    warm_compiles: int = 0
    width_classes: dict = dataclasses.field(default_factory=dict)
    setup_parts: dict = dataclasses.field(default_factory=dict)


def serve(c: Cell, seed: int) -> Served:
    """Generate the data, bulk-load the index, start the frontend and
    warm every shape the cell's traffic uses."""
    from repro.core.types import TreeSpec
    from repro.index import StreamingConfig, StreamingIndex
    from repro.serve.frontend import FrontendConfig, SearchFrontend

    cfg = c.config
    parts = {}
    phase = _Phases(parts, CompileCounter.get())
    pts = c.generator()(int(cfg["n_points"]), int(cfg["data_seed"])).astype(np.float32)
    if pts.shape != (cfg["n_points"], cfg["dim"]):
        raise ValueError(f"generator gave {pts.shape}")
    r = load.radius(pts, c.traffic)
    ix = cfg["index"]
    if ix["tree"] != "ballstar" or ix.get("segments", 1) != 1:
        raise ValueError(f"unsupported index settings {ix}")
    index = StreamingIndex(StreamingConfig(
        dim=int(cfg["dim"]),
        spec=TreeSpec.ballstar(leaf_size=int(ix["leaf_size"])),
        storage_dtype=ix["storage_dtype"],
    ))
    phase.end("data")
    index.bulk_load(pts)
    if index.stats()["n_segments"] != 1:
        raise RuntimeError("bulk load did not give one segment")
    phase.end("bulk_load")
    fe = SearchFrontend(index, FrontendConfig(k=int(c.traffic["k"]), radius=r))
    fe.start()
    phase.end("frontend_start")
    s = Served(c, seed, pts, r, index, fe, setup_parts=parts)
    try:
        _warm(s)
    except BaseException:
        fe.stop()
        raise
    return s


WARM_QUERIES = 2048     # warm queries whose frontier widths are measured
PAIR_P_MIN = 1e-6       # warm a (batch, width) pair at least this likely
CLASSIC_SWITCH = "REPRO_FUSED_TRAVERSAL"   # "0": the engine's classic path


def width_class(widths) -> np.ndarray:
    """The pow2 class of each frontier width, as phase 2 pads it."""
    return np.array([1 << max(0, int(w) - 1).bit_length() for w in widths])


def warm_pairs(wcls, batch_classes, p_min: float = PAIR_P_MIN) -> list:
    """The (batch class, width class) pairs that one dispatch meets with
    a chance of at least ``p_min``, if its rows' width classes are drawn
    from ``wcls``. A batch of class b holds between the class below
    plus one and b real rows (its pad rows copy a real one), and its
    width class is the largest of theirs."""
    wcls = np.asarray(wcls)
    pairs, below = [], 0
    for b in batch_classes:
        rows = np.arange(below + 1, b + 1)
        for w in np.unique(wcls):
            at_most, under = np.mean(wcls <= w), np.mean(wcls < w)
            if np.max(at_most ** rows - under ** rows) >= p_min:
                pairs.append((int(b), int(w)))
        below = b
    return pairs


def _warm(s: Served) -> None:
    """Compile what the window will run. The phase-2 programs are keyed
    by the batch's pow2 class and by the pow2 class of its widest leaf
    frontier, which the frontend's class warm-up (one repeated query)
    does not reach. So: measure the frontier widths of ``WARM_QUERIES``
    warm queries (leaves scanned, from the engine's per-query trace);
    dispatch one batch for every pair that ``warm_pairs`` finds likely,
    its widest query of that width class, again while a compile still
    fires (several pairs at once: compiling releases the interpreter);
    warm the classic path that a frontier over the cap falls back to;
    then pass the cell's own traffic until a pass compiles nothing. A
    pair left cold costs the window a compile, or a load from the
    cache, of a second or more, which is the whole tail. Everything is
    drawn from the warm stream; each phase's seconds and compiles go
    into the set-up note. Nothing here depends on the run's seed."""
    from concurrent.futures import ThreadPoolExecutor

    from repro import obs

    k, r = int(s.cell.traffic["k"]), s.r
    counter = CompileCounter.get()
    c0 = counter.n
    g = fixed_rng(s.cell, STREAM_WARM)
    phase = _Phases(s.setup_parts, counter)
    pool = load.queries(s.points, WARM_QUERIES, g)
    widths = []
    for i in range(0, len(pool), 64):
        with obs.QueryTrace() as qt:
            s.index.constrained_knn(pool[i:i + 64], k, r)
        widths.append(np.asarray(qt.metrics["leaves_scanned"]))
    wcls = width_class(np.concatenate(widths))
    s.width_classes = {int(w): int(n) for w, n in zip(*np.unique(wcls, return_counts=True))}
    phase.end("warm_widths")
    pairs = warm_pairs(wcls, s.fe.config.batch_classes)
    jobs = []
    for b, w in pairs:
        wide = np.flatnonzero(wcls == w)
        narrow = np.flatnonzero(wcls <= w)
        jobs.append([np.concatenate([g.choice(wide, 1), g.choice(narrow, b - 1)])
                     for _ in range(4)])

    def one(batches) -> int:
        sent = 0
        for attempt, rows in enumerate(batches):
            c1 = counter.n
            s.index.constrained_knn(pool[rows], k, r)
            sent += len(rows)
            if attempt and counter.n == c1:
                break
        return sent

    with ThreadPoolExecutor(max_workers=8, thread_name_prefix="bench-warm") as ex:
        n = sum(ex.map(one, jobs))
    phase.end(f"warm_pairs({len(pairs)})")
    # A frontier wider than the engine's cap falls back to the classic
    # traversal, one program per batch class. On road3d about one query
    # in 8,000 does: the window meets a few, the warm sample seldom. The
    # program's own switch to that path warms it, while nothing else
    # dispatches; the window runs with the switch as it was.
    was = os.environ.get(CLASSIC_SWITCH)
    os.environ[CLASSIC_SWITCH] = "0"
    try:
        for b in s.fe.config.batch_classes:
            s.index.constrained_knn(pool[:b], k, r)
            n += b
    finally:
        if was is None:
            del os.environ[CLASSIC_SWITCH]
        else:
            os.environ[CLASSIC_SWITCH] = was
    phase.end("warm_classic")
    for _ in range(4):
        c1 = counter.n
        n += _traffic_pass(s, g)
        if counter.n == c1:
            break
    phase.end("warm_traffic")
    s.warm_dispatches = n
    s.warm_compiles = counter.n - c0


class _Phases:
    """Seconds and backend compiles of each set-up phase, into ``parts``
    as ``name: (seconds, compiles)``."""

    def __init__(self, parts: dict, counter) -> None:
        self.parts, self.counter = parts, counter
        self.t, self.n = time.perf_counter(), counter.n

    def end(self, name: str) -> None:
        t, n = time.perf_counter(), self.counter.n
        self.parts[name] = (t - self.t, n - self.n)
        self.t, self.n = t, n


def _traffic_pass(s: Served, g, seconds: float = 2.0) -> int:
    offsets = load.open_schedule(s.cell.traffic, seconds, g)
    qpool = load.queries(s.points, len(offsets), g)
    win = load.run(s.fe, s.cell.traffic, qpool, offsets, seconds)
    win.wait(WAIT_AFTER_CLOSE_S)
    return win.count


# -- the window and its check -------------------------------------------------


@dataclasses.dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    metrics: dict
    device: dict
    checks: dict
    notes: list
    breakdown: dict | None = None
    sample: tuple = ()      # (queries, points, r) of the checked replies


def _quantile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def measure(s: Served, seconds: float, trace: bool, t_start: float) -> Outcome:
    """Run the window, read the metrics, stop the system, check a
    sample of its replies against the reference."""
    import jax

    from repro import obs

    c = s.cell
    counter = CompileCounter.get()
    marks = {}

    def on_start():
        marks["obs0"] = obs.REGISTRY.snapshot()
        marks["compiles0"] = counter.n
        marks["set-up compiles"] = (counter.n, counter.seconds, counter.cache_hits)
        marks["setup_s"] = time.perf_counter() - t_start

    tracer = _Tracer(c.name) if trace else None
    if tracer is not None:
        seconds = min(seconds, TRACE_SECONDS)
    qpool, offsets = window_work(s, s.seed, seconds)
    if tracer is not None:
        tracer.start()
    pauses = _GcPauses()
    try:
        if tracer is not None:
            with jax.profiler.TraceAnnotation("bench.window"):
                win = load.run(s.fe, c.traffic, qpool, offsets, seconds,
                               on_start)
        else:
            win = load.run(s.fe, c.traffic, qpool, offsets, seconds, on_start)
        obs1 = obs.REGISTRY.snapshot()
        compiles = counter.n - marks["compiles0"]
        gc_max_ms = pauses.max_ms
        win.wait(WAIT_AFTER_CLOSE_S)
    finally:
        pauses.close()
        if tracer is not None:
            tracer.stop()
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }
    s.fe.stop()

    ok = win.answered()
    attempted = win.count
    failed = int(attempted - ok.sum())
    notes = [
        f"window: {attempted} requests sent, {int(ok.sum())} answered, "
        f"{failed} unanswered; {compiles} backend compiles inside the window "
        f"(set-up: {s.warm_compiles} in warm-up over {s.warm_dispatches} "
        f"warm requests)",
        "set-up phases (s, compiles): " + ", ".join(
            f"{k} {v[0]:.3f} {v[1]}" for k, v in s.setup_parts.items()),
        "set-up compiles: %d, %.3f s in backend compile, %d persistent-cache hits"
        % marks["set-up compiles"],
        f"warm queries by frontier width class: {s.width_classes}",
    ]
    late = (win.sent[:attempted] - win.due[:attempted]) * 1e3
    notes.append(
        f"generator lateness ms: p50 {_quantile(late, 50):.3f} "
        f"p95 {_quantile(late, 95):.3f} max {float(np.max(late)):.3f}")
    wait = (win.done[:attempted] - win.due[:attempted]) * 1e3
    first = win.due[:attempted] < win.t0 + seconds / 2
    if ok[first].any() and ok[~first].any():
        notes.append(
            "answered p95 ms by half of the window: "
            f"{_quantile(wait[ok & first], 95):.3f} / "
            f"{_quantile(wait[ok & ~first], 95):.3f}")
    notes.append(f"longest garbage-collector pause in the window: {gc_max_ms:.3f} ms")
    lat = _latency_ms(win, ok)
    notes.append("latency ms from the due time, every request: " + " ".join(
        f"p{q} {_quantile(lat, q):.3f}" for q in (50, 90, 95, 99))
        + f" mean {float(np.mean(lat)):.3f}")

    metrics, breakdown = {}, None
    if not trace:
        metrics = _end_to_end(c, win, ok, marks["setup_s"])
    else:
        ctx = _Context(c, marks["obs0"], obs1, tracer.reduce(), device)
        for m in c.per_layer:
            v = c.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        breakdown = {"device_ops": ctx.trace["device_ops"],
                     "idle_gaps": ctx.trace["idle_gaps"]}

    # the check: a seeded sample of the answered requests
    answered = np.flatnonzero(ok)
    n_sample = min(len(answered), int(c.config["check_sample"]))
    pick = np.sort(rng(s.seed, STREAM_SAMPLE).choice(answered, n_sample,
                                                     replace=False))
    ids, dists = win.gids[pick], win.dists[pick]
    sample_q = qpool[win.qrow[pick]]
    points, r = s.points, s.r
    s.index = s.fe = None      # free the program's state first
    gc.collect()
    dist_gap, id_gap = compare.gaps(ids, dists, sample_q, points,
                                    int(c.traffic["k"]), r)
    correct, checks = compare.judge(dist_gap, id_gap, failed, c.config["limits"])
    return Outcome(correct, attempted, failed, metrics, device, checks,
                   notes, breakdown, (sample_q, points, r))


def _latency_ms(win, ok) -> np.ndarray:
    """Each request sent in the window, from when it was due; one never
    answered counts as waiting until it was given up."""
    n = win.count
    done = np.where(ok, win.done[:n], win.t_end + WAIT_AFTER_CLOSE_S)
    return (done - win.due[:n]) * 1e3


def _end_to_end(c: Cell, win, ok, setup_s: float) -> dict:
    out = {}
    for m in c.end_to_end:
        name = m["name"]
        if name == "setup_s":
            v = setup_s
        elif name.startswith("read_p") and name.endswith("_ms"):
            # read_p<q>_ms: the q-th percentile of every request's latency
            v = _quantile(_latency_ms(win, ok), float(name[6:-3]))
        else:
            raise KeyError(f"no end-to-end metric {name!r} in the harness")
        out[name] = {"value": float(v), "unit": m["unit"]}
    return out


class _GcPauses:
    """The longest pause of Python's garbage collector while open."""

    def __init__(self) -> None:
        self.max_ms = 0.0
        self._t0 = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.max_ms = max(self.max_ms, (time.perf_counter() - self._t0) * 1e3)

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


class _Context:
    """What a per-layer reader reads: window deltas of the program's
    counters and histograms, the reduced device trace, the cell."""

    def __init__(self, c: Cell, obs0: dict, obs1: dict, trace: dict,
                 device: dict) -> None:
        self.cell, self.trace, self.device = c, trace, device
        self.obs0, self.obs1 = obs0, obs1

    def counter(self, key: str) -> float:
        return (self.obs1["counters"].get(key, 0)
                - self.obs0["counters"].get(key, 0))

    def histogram(self, key: str) -> tuple:
        """(count, sum) over the window."""
        h0 = self.obs0["histograms"].get(key, {"count": 0, "sum": 0.0})
        h1 = self.obs1["histograms"].get(key, {"count": 0, "sum": 0.0})
        return h1["count"] - h0["count"], h1["sum"] - h0["sum"]


class _Tracer:
    """The profiler around the window, written inside the checkout."""

    def __init__(self, cell_name: str) -> None:
        self.dir = os.path.join(ROOT, RUN_DIR, "trace", cell_name)

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # host spans only, no per-call tracing
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()

    def reduce(self) -> dict:
        from . import trace_reduce

        return trace_reduce.reduce_file(trace_reduce.find_xplane(self.dir),
                                        window="bench.window")


# -- the command --------------------------------------------------------------


def start(c: Cell, root: str = ROOT) -> str | None:
    """Check that JAX sees the TPU chips the cell needs, then put the
    program on the path and configure JAX. Returns why not, or None."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"no TPU: JAX sees platform {devs[0].platform!r}"
    if len(devs) < c.chips:
        return f"the cell needs {c.chips} chips, JAX sees {len(devs)}"
    src = os.path.join(root, "src")
    if os.path.isdir(src) and src not in sys.path:
        sys.path.insert(0, src)
    configure_jax(root)
    return None


def result_line(o: Outcome) -> str:
    line = {"correct": o.correct, "attempted": o.attempted,
            "failed": o.failed, "metrics": o.metrics, "device": o.device}
    if o.breakdown is not None:
        line["breakdown"] = o.breakdown
    line["checks"] = o.checks
    return json.dumps(line)


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, root: str = ROOT) -> int:
    """One run of one cell on the chip; prints the notes and checks on
    stderr and the result as the last line of stdout. Returns the exit
    code: 2, with no result, where the chips the cell needs are not
    there."""
    c = cell(workload, root)
    why = start(c, root)
    if why is not None:
        print(f"bench: {why}", file=sys.stderr)
        return 2
    out = run_cell(c, seed, seconds, trace, t_start)
    for note in out.notes:
        print(note, file=sys.stderr)
    for name, chk in out.checks.items():
        rel = ">=" if name == "compared" else "<="
        print(f"check {name} = {chk['value']!r} (limit {rel} {chk['limit']!r})",
              file=sys.stderr)
    print(f"correct = {out.correct}", file=sys.stderr)
    sys.stderr.flush()
    print(result_line(out), flush=True)
    return 0


def run_cell(c: Cell, seed: int, seconds: float, trace: bool,
             t_start: float) -> Outcome:
    """Set up, measure and check one cell on whatever device JAX has."""
    served = serve(c, seed)
    try:
        return measure(served, seconds, trace, t_start)
    finally:
        if served.fe is not None:
            served.fe.stop()


def configure_jax(root: str = ROOT) -> str:
    """JAX's persistent compile cache at a fixed path inside the
    checkout (the program's own default), whatever the environment
    says, keeping every program and never evicting: so a second run of
    a cell compiles nothing, and no cache lock is taken per program."""
    import jax

    from repro import compile_cache

    path = os.path.join(root, ".jax_cache")
    os.environ[compile_cache.ENV] = path
    compile_cache.configure()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    CompileCounter.get()
    return path
