"""One run of one cell: build the engine, warm it, ramp, measure a window,
check what it served against the reference, and say so in one JSON line.

Everything a cell is made of is found by name from ``BENCHMARK.json``:
``bench/configs/<config>.json`` (model, cut and engine settings),
``bench/traffic/<mix>.json`` (read by ``bench/traffic.py``),
``bench/checks/<workload>.json`` (the correctness limit and the readings it
was set from) and ``bench/metrics/<metric>.py`` (one reader per metric).

The loop drives ``ServingEngine`` through the calls its ``run()`` makes
(admission, ``add_request``, ``step``) but enqueues each request at its
due time (open loop) or when its client's previous request completes
(closed loop), and stamps every output token when the step that made it
returns (the step ends in the engine's one device-to-host sync).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import peaks, traffic as traffic_lib

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
clock = time.perf_counter
SETUP_RID = 1 << 30   # set-up requests' ids (the engine wants rid >= 0)
TRACE_S = 5.0          # traced sub-window of a --trace 1 run, seconds
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class NoChip(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# the cell, found by name


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    check: dict
    end_to_end: list
    per_layer: list

    @property
    def weights(self):
        """The configuration's weights module (its ``weights`` key)."""
        return module_at(self.config["weights"])

    @property
    def reference(self):
        """The configuration's plain reference (its ``reference`` key)."""
        return module_at(self.config["reference"])

    @property
    def dims(self) -> dict:
        return self.weights.model_dims(self.config)


def module_at(path: str):
    """The module of a file under the benchmark's directory, by its path
    from the checkout's root (``bench/weights.py`` -> ``bench.weights``)."""
    if not (path.startswith("bench/") and path.endswith(".py")):
        raise ValueError(f"not a module of the benchmark: {path!r}")
    return importlib.import_module(path[:-3].replace("/", "."))


def load_cell(workload: str, bench_json: pathlib.Path = ROOT / "BENCHMARK.json",
              bench_dir: pathlib.Path = BENCH) -> Cell:
    spec = json.loads(pathlib.Path(bench_json).read_text())
    (w,) = [w for w in spec["workloads"] if w["name"] == workload]

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads((bench_dir / "configs" / f"{w['config']}.json")
                          .read_text()),
        mix=traffic_lib.load(bench_dir / "traffic" / f"{w['traffic']}.json"),
        check=json.loads((bench_dir / "checks" / f"{workload}.json")
                         .read_text()),
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)])


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH):
    """``value(run)`` of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.value


# ---------------------------------------------------------------------------
# the engine


def _layout(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, [(a.shape, a.dtype) for a in leaves]


def build_engine(cell: Cell, seed: int):
    from repro.core.famous import FamousConfig
    from repro.launch.serve import platform_defaults
    from repro.models import module, transformer
    from repro.serve.engine import ServingEngine

    W = cell.weights
    cfg = W.program_config(cell.config)
    impl, dtype = platform_defaults()
    params = W.serving_params(cell.dims, dtype)(jnp.asarray(W.seed_words(seed)))
    want = module.param_shapes(transformer.model_spec(cfg), dtype)
    if _layout(params) != _layout(want):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter tree")
    e = cell.config["engine"]
    return ServingEngine(params, cfg, FamousConfig(impl=impl), dtype=dtype,
                         n_slots=e["n_slots"], max_seq=e["max_seq"],
                         cache_kind="paged", page_size=e["page_size"],
                         n_pages=e["n_pages"], chunk=e["chunk"],
                         token_budget=e.get("token_budget", 0),
                         prefix_cache=e["prefix_cache"])


# ---------------------------------------------------------------------------
# the loop


@dataclasses.dataclass
class Record:
    """One request as the client sees it."""
    rid: int
    prompt_len: int
    due: float
    client: int = -1
    first: float | None = None
    stamps: list = dataclasses.field(default_factory=list)
    end: float | None = None
    failed: bool = False
    slots: list = dataclasses.field(default_factory=list)  # slot per admission


@dataclasses.dataclass
class StepLog:
    """One ``engine.step()``: when it returned, the scheduler's plan, and
    the cache entries each decoding slot attended over."""
    t_end: float
    chunks: list          # (start, n) of each prefill chunk
    decode_lens: list     # per slot that emitted a token: entries attended


class Runner:
    """Drives one engine with one traffic source."""

    def __init__(self, engine, traffic: traffic_lib.Traffic):
        self.engine = engine
        self.traffic = traffic
        self.recs: dict = {}
        self.reqs: dict = {}
        self.steps: list = []
        self.lags: list = []          # open loop: enqueue time - due time
        self.admissions: list = []    # (time, prompt tokens, cached tokens)
        self._rid = 0
        self._next = None             # open loop: next (due, item)
        self._plan = None
        orig = engine.sched.plan

        def plan():
            self._plan = orig()
            return self._plan

        engine.sched.plan = plan      # observe each step's plan

    # -- requests -----------------------------------------------------------
    def _submit(self, item, due: float, client: int = -1):
        from repro.serve.engine import Request
        rid = self._rid
        self._rid += 1
        req = Request(rid=rid, tokens=item.tokens, max_new=item.max_new)
        req.t_submit = due
        self.reqs[rid] = req
        self.recs[rid] = Record(rid=rid, prompt_len=len(item.tokens),
                                due=due, client=client)
        self.engine.sched.enqueue(req)

    def start(self, t_origin: float):
        """Open the traffic at ``t_origin``: the ramp starts then."""
        self.t_origin = t_origin
        if self.traffic.loop == "open":
            item = self.traffic.next()
            self._next = (t_origin + item.offset, item)
        else:
            for c in range(self.traffic.clients):
                self._submit(self.traffic.next(), t_origin, client=c)

    def _release(self, now: float):
        while self._next is not None and self._next[0] <= now:
            due, item = self._next
            self._submit(item, due)
            self.lags.append(now - due)
            nxt = self.traffic.next()
            self._next = (self.t_origin + nxt.offset, nxt)

    def _finish(self, req, now: float):
        rec = self.recs[req.rid]
        rec.end = now
        rec.failed = req.error is not None
        if rec.client >= 0:
            self._submit(self.traffic.next(), now, client=rec.client)

    def _admit(self):
        from repro.serve.paged import PagePoolExhausted
        eng, s = self.engine, self.engine.sched
        while s.has_queued and s.free_slot() is not None:
            try:
                if not eng._admissible(s.next_queued()):
                    break
            except PagePoolExhausted as e:
                req = s.pop_queued()
                req.error, req.done = str(e), True
                self._finish(req, clock())
                continue
            req = s.pop_queued()
            hits = eng.prefix_hit_tokens
            self.recs[req.rid].slots.append(eng.add_request(req))
            self.admissions.append((clock(), len(req.tokens) + len(req.out),
                                    eng.prefix_hit_tokens - hits))

    def _step(self):
        eng = self.engine
        live = [(s.req, len(s.req.out)) for s in eng.sched.slots
                if s.req is not None]
        with jax.profiler.TraceAnnotation("bench.step"):
            finished = eng.step()
        now = clock()
        with jax.profiler.TraceAnnotation("bench.stamp"):
            lens = []
            for req, n0 in live:
                k = len(req.out) - n0
                if k > 0:
                    rec = self.recs[req.rid]
                    rec.stamps.extend([now] * k)
                    if rec.first is None:
                        rec.first = now
                    lens.append(len(req.tokens) + len(req.out) - 1)
            plan = self._plan
            self.steps.append(StepLog(
                now, [(c.start, c.n) for c in plan.chunks], lens))
            for req in finished:
                self._finish(req, now)

    def drive(self, until: float):
        """Serve until the first step that ends at or after ``until``."""
        s = self.engine.sched
        while True:
            now = clock()
            if now >= until:
                return
            with jax.profiler.TraceAnnotation("bench.admit"):
                self._release(now)
                self._admit()
            if s.busy:
                self._step()
            else:
                nxt = self._next[0] if self._next is not None else until
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(nxt, until) - clock()))

    def serve_now(self, items):
        """Set-up: serve ``items`` to completion, untimed."""
        from repro.serve.engine import Request
        reqs = []
        for item in items:
            req = Request(rid=SETUP_RID + len(reqs), tokens=item.tokens,
                          max_new=item.max_new)
            reqs.append(req)
            self.engine.sched.enqueue(req)
        while self.engine.sched.has_queued or self.engine.sched.busy:
            self._admit_setup()
            self.engine.step()
        self.engine.sched.stats.clear()
        return reqs

    def _admit_setup(self):
        eng, s = self.engine, self.engine.sched
        while (s.has_queued and s.free_slot() is not None
               and eng._admissible(s.next_queued())):
            eng.add_request(s.pop_queued())


class CompileWatch:
    """Counts compilations and persistent-cache loads while open."""

    def __init__(self):
        self.events = []

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.events.append((event, duration))

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


# ---------------------------------------------------------------------------
# what the window saw


@dataclasses.dataclass
class RunData:
    """Everything a metric reader may read."""
    cell: Cell
    t0: float
    t1: float
    recs: list
    steps: list
    dims: dict
    peak: dict
    setup_s: float
    lags: list
    admissions: list
    trace: object = None          # bench.trace.Summary of the traced window

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def steps_in(self, t0: float, t1: float) -> list:
        return [s for s in self.steps if t0 <= s.t_end <= t1]

    def window_steps(self) -> list:
        """Steps of the window, or of the traced sub-window when traced."""
        if self.trace is not None:
            return self.steps_in(self.trace.host_t0, self.trace.host_t1)
        return self.steps_in(self.t0, self.t1)


# ---------------------------------------------------------------------------
# correctness


def sample_items(recs: list, reqs: dict, seed: int, b_pad: int, s_pad: int,
                 n_slots: int) -> list:
    """Up to ``b_pad`` finished requests to judge, drawn from the seed:
    the longest one first, then others in a seeded order.  The slots fall
    into ``b_pad`` equal groups, and a request that decoded in a group no
    earlier pick did comes first, so a fault in any part of the decode
    batch that served a finished request reaches the sample."""
    done = [r for r in recs if r.end is not None and not r.failed
            and reqs[r.rid].out
            and r.prompt_len + len(reqs[r.rid].out) - 1 <= s_pad]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(reqs[r.rid].out),
                                       r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(
        np.random.SeedSequence([int(seed) & (2 ** 64 - 1), 7])).permutation(
            len(rest))

    def groups(r):
        return {s * b_pad // n_slots for s in r.slots}

    pool = [rest[i] for i in order]
    picked, seen = [longest], groups(longest)
    while pool and len(picked) < b_pad:
        r = next((r for r in pool if groups(r) - seen), pool[0])
        pool.remove(r)
        picked.append(r)
        seen |= groups(r)
    return [(list(reqs[r.rid].tokens), list(reqs[r.rid].out)) for r in picked]


def verdict(gap: np.ndarray, limit: float) -> tuple:
    """The widest gap of the judged tokens, and whether the run is
    correct: some token was judged and no gap exceeds the limit."""
    widest = float(gap.max()) if gap.size else float("inf")
    return widest, bool(gap.size > 0 and widest <= limit)


def judge(cell: Cell, seed: int, items: list, served, controls=()) -> dict:
    """Reference gaps of the sampled served tokens (and of the controls)."""
    c = cell.check
    return cell.reference.score(cell.dims, seed, items, s_pad=c["s_pad"],
                                b_pad=c["b_pad"], n_score=c["n_score"],
                                dtype=served, controls=controls)


# ---------------------------------------------------------------------------
# one run


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def require_chip(cell: Cell):
    info = device_info()
    if info["platform"] != "tpu" or info["count"] < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"sees {info['count']} {info['platform']} device(s)")


def memory_peak() -> int:
    st = jax.devices()[0].memory_stats() or {}
    return int(st.get("peak_bytes_in_use", 0))


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        engine_hook=None, kind: str | None = None, controls=(),
        inspect=None) -> dict:
    """One run; returns the result object (the contract's last line).

    ``engine_hook(engine)`` runs on the built engine before set-up (the
    fault tests plant their faults there); ``kind`` stands in for the
    device kind when looking up peaks (CPU tests); ``controls`` (names
    of ``reference`` controls, ``"fp8"``, ``"int8"``) also scores each
    control on the same sampled tokens and reports under ``checks`` its
    widest gap and the verdict the same decision gives it (the
    calibration of the limit);
    ``inspect({"data": RunData, "reqs": {rid: Request}})`` sees what the
    window served (the loop's tests)."""
    from repro.launch.serve import platform_defaults
    dev = device_info()
    peak = peaks.peaks(kind or dev["kind"])
    engine = build_engine(cell, seed)
    if engine_hook is not None:
        engine_hook(engine)
    tr = traffic_lib.Traffic(cell.mix, seed, cell.dims["vocab"])
    runner = Runner(engine, tr)
    runner.serve_now([tr.warmup(engine.chunk)]
                     + tr.priming(engine.pcfg.page_size))
    jax.block_until_ready(engine.caches)
    t_origin = clock()
    runner.start(t_origin)
    t0 = t_origin + tr.ramp_s
    t1 = t0 + seconds
    runner.drive(t0)
    setup_s = t0 - t_start
    from repro.analysis.retrace_guard import retrace_guard
    from bench import trace as trace_lib
    tsum = None
    with retrace_guard(engine, label="measured window"), CompileWatch() as cw:
        if trace:
            span = min(TRACE_S, seconds)
            ts = t0 + (seconds - span) / 2
            runner.drive(ts)
            tdir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            h0 = clock()
            with jax.profiler.TraceAnnotation(trace_lib.MARK):
                pass
            runner.drive(ts + span)
            h1 = clock()
            jax.profiler.stop_trace()
        runner.drive(t1)
    if cw.events:
        raise RuntimeError(f"compiled inside the measured window: {cw.events}")
    mem_peak = memory_peak()
    if trace:
        tsum = trace_lib.summarize(trace_lib.latest_xspace(tdir), h0, h1,
                                   mark_host=h0)
        trace_lib.remove(tdir)
    recs = list(runner.recs.values())
    data = RunData(cell=cell, t0=t0, t1=t1, recs=recs, steps=runner.steps,
                   dims=cell.dims, peak=peak, setup_s=setup_s,
                   lags=runner.lags, admissions=runner.admissions,
                   trace=tsum)
    specs = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for spec in specs:
        v = metric_reader(spec["name"])(data)
        if v is not None:
            metrics[spec["name"]] = {"value": float(v), "unit": spec["unit"]}
    if inspect is not None:
        inspect({"data": data, "reqs": dict(runner.reqs)})
    in_window = [r for r in recs if t0 <= r.due < t1]
    attempted = len(in_window)
    failed = sum(r.failed for r in in_window)
    # free the program's state before the reference runs
    c = cell.check
    items = sample_items(recs, runner.reqs, seed, c["b_pad"], c["s_pad"],
                         engine.n_slots)
    runner.engine = None
    del engine, runner
    gc.collect()
    served = platform_defaults()[1]
    gaps = (judge(cell, seed, items, served, controls) if items
            else {"gap": np.array([]), "n": 0, "control_gap": {}})
    widest, correct = verdict(gaps["gap"], c["limit"])
    device = dict(dev, memory_peak_bytes=mem_peak)
    if tsum is not None:
        device.update(busy_s=tsum.busy_s, window_s=tsum.window_s)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if tsum is not None:
        result["breakdown"] = tsum.breakdown()
    result["generator_lag_p99_ms"] = (
        float(np.percentile(data.lags, 99) * 1e3) if data.lags else 0.0)
    result["checks"] = {"logit_gap": {"value": widest, "limit": c["limit"],
                                      "tokens": int(gaps["n"]),
                                      "requests": len(items)}}
    for name, g in gaps["control_gap"].items():
        value, ok = verdict(g, c["limit"])
        result["checks"][f"control_{name}_logit_gap"] = {
            "value": value, "limit": c["limit"], "tokens": int(gaps["n"]),
            "requests": len(items), "correct": ok}
    return result


def print_result(result: dict, out=sys.stdout, err=sys.stderr):
    for name, chk in result["checks"].items():
        said = (f", correct {str(chk['correct']).lower()}"
                if "correct" in chk else "")
        print(f"check {name}: {chk['value']!r} limit {chk['limit']!r} "
              f"({chk['tokens']} tokens of {chk['requests']} "
              f"requests{said})", file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
