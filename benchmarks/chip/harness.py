"""The chip benchmark of the continuous-learning loop, driven by data.

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix. Each is a file found by its name:

* ``configs/<config>.json``: the student and teacher sizes, the precision
  policy as the configuration states it, and the limits of the check;
* ``traffic/<traffic>.json``: lanes (cameras), the drift scenario, the
  frame rate that is scored, the session's hyper-parameters and dispatch,
  and the short pretraining that makes the starting state;
* ``metrics/<metric>.py``: one metric, read by its ``read(ctx)`` from the
  context :func:`run_cell` gathers (``None`` where it finds nothing).

A run builds the session through the front door
(``FleetSpec(..., reduced=False).build()``), hands it weights made on the
device from the seed, steps the run through its first phases as set-up
(which compiles the cell's shapes), then measures the same run one phase at
a time until ``seconds`` have passed, closing at that phase's barrier.
Afterwards the plain reference (``reference.py``) checks what the window
produced (``check.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import resource
import time
from collections import defaultdict
from contextlib import nullcontext
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import check
import reference
import trace_reduce
from repro.configs.dacapo_pairs import VisionConfig
from repro.core import CLHyperParams
from repro.core.fleet import FleetSpec
from repro.core.mx import PrecisionPolicy
from repro.data.pipeline import FramePipeline
from repro.data.stream import DriftStream, scenario

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]
BENCHMARK_JSON = ROOT / "BENCHMARK.json"
CACHE_DIR = ROOT / ".jax_cache"

# How many rows of each serving and labeling call the check may draw, and
# how many of those it compares once the window has closed.
ROWS_PER_CALL = 16
SAMPLE = 256

# A traced run traces the end of its window: from the first phase barrier at
# which at most this many seconds of the window are left, to its close. The
# whole window's trace holds more host memory than a one-chip machine has.
TRACE_S = 12.0


# ------------------------------------------------------------------ cells
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench: Optional[Dict[str, Any]] = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files."""
    bench = bench if bench is not None else load_json(BENCHMARK_JSON)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    return Cell(name, int(w["chips"]), config, traffic,
                bench["end_to_end"], bench["per_layer"])


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ---------------------------------------------------------- instruments
class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's events."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Spans:
    """The benchmark's own host spans, written into the profiler's trace
    while it runs (``bench.<name>``); free when tracing is off."""

    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return nullcontext()
        return jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name)


class TimedPipeline(FramePipeline):
    """The session's data plane, timing the engine's calls for frames."""

    def __init__(self, stream: DriftStream, speculative: bool, spans: Spans):
        super().__init__(stream, speculative=speculative)
        self.spans = spans
        self.fetch_s = 0.0
        self.label_frames = 0

    def frames(self, t0, t1, max_frames=0, tag=None):
        start = time.perf_counter()
        with self.spans("frames"):
            x, y = super().frames(t0, t1, max_frames=max_frames, tag=tag)
        self.fetch_s += time.perf_counter() - start
        if tag == "label":
            self.label_frames += len(x)
        return x, y


class Recorder:
    """Watches the kernels' calls: every SGD batch (for the reference to
    replay), the first three steps' outputs, and a seeded sample of the rows
    each serving and labeling call in the window answered. A fleet serving
    call (one program for several lanes) counts as one call per lane; one
    with a single lane goes through ``predict_async`` and counts there."""

    def __init__(self, session, run, seed: int, spans: Spans):
        self.run = run
        self.spans = spans
        self.rng = np.random.default_rng([seed, 1])
        self.in_window = False
        self.steps: Dict[int, List[tuple]] = defaultdict(list)
        self.first: Dict[str, list] = {"loss": []}
        self.served: List[tuple] = []  # (lane, version, x rows, preds, rows)
        self.labeled: List[tuple] = []  # (x rows, labels, rows)
        self.rows_served = 0
        self.rows_labeled = 0
        self._lane = None
        inf, lab, ret = session.inference, session.labeling, session.retrain
        fit, step = ret.fit, ret._step
        predict, label = inf.predict_async, lab.label_async
        predict_fleet = inf.predict_fleet_async

        def fit_wrapped(params, opt, *args, **kwargs):
            self._lane = self._lane_of(params, "params")
            with self.spans("fit"):
                return fit(params, opt, *args, **kwargs)

        def step_wrapped(params, opt, x, y):
            out = step(params, opt, x, y)
            steps = self.steps[self._lane]
            steps.append((x, y))
            if self._lane == 0 and len(steps) <= 3:
                self.first["loss"].append(out[2])
                if len(steps) == 1:
                    self.first["mom1"] = out[1]
                if len(steps) == 3:
                    self.first["params3"] = out[0]
            return out

        def predict_wrapped(params, x):
            with self.spans("serve"):
                out = predict(params, x)
            if self.in_window:
                self._serve(params, x, out)
            return out

        def predict_fleet_wrapped(params_list, windows):
            with self.spans("serve"):
                out = predict_fleet(params_list, windows)
            if self.in_window and len(windows) > 1:
                for params, x, preds in zip(params_list, windows, out):
                    self._serve(params, x, preds)
            return out

        def label_wrapped(params, x, precision, microbatch=None):
            with self.spans("label"):
                out = label(params, x, precision, microbatch)
            if self.in_window:
                rows = self._rows(len(x))
                self.labeled.append((np.asarray(x[rows]), out, rows))
                self.rows_labeled += len(x)
            return out

        ret.fit, ret._step = fit_wrapped, step_wrapped
        inf.predict_async, lab.label_async = predict_wrapped, label_wrapped
        inf.predict_fleet_async = predict_fleet_wrapped

    def _serve(self, params, x, preds) -> None:
        """Keep a seeded sample of the rows one lane's serving answered,
        with the lane's version (its SGD steps so far)."""
        lane = self._lane_of(params, "serving")
        rows = self._rows(len(x))
        self.served.append((lane, len(self.steps[lane]),
                            np.asarray(x[rows]), preds, rows))
        self.rows_served += len(x)

    def _lane_of(self, tree, attr: str) -> int:
        for lane in self.run.lanes:
            if getattr(lane, attr) is tree:
                return lane.index
        raise RuntimeError(f"a kernel was called on a tree that is no "
                           f"lane's current {attr}")

    def _rows(self, n: int) -> np.ndarray:
        return np.sort(self.rng.choice(n, min(n, ROWS_PER_CALL),
                                       replace=False))

    def to_host(self) -> Dict[str, Any]:
        """What the check needs, on the host: a seeded sample of SAMPLE
        served and SAMPLE labeled frames, every SGD batch, and the first
        three steps' outputs. Drops the device arrays."""
        take = np.random.default_rng(self.rng.integers(2 ** 32))

        def sample(calls):
            rows = [(c, j) for c, call in enumerate(calls)
                    for j in range(len(call[-1]))]
            pick = (take.choice(len(rows), min(len(rows), SAMPLE),
                                replace=False) if rows else [])
            chosen = defaultdict(list)
            for r in sorted(pick):
                chosen[rows[r][0]].append(rows[r][1])
            return chosen

        served = []
        for c, js in sample(self.served).items():
            lane, version, x, preds, rows = self.served[c]
            served.append((lane, version, x[js], np.asarray(preds)[rows[js]]))
        labeled = []
        for c, js in sample(self.labeled).items():
            x, labels, rows = self.labeled[c]
            labeled.append((x[js], np.asarray(labels)[rows[js]]))
        first = {"loss": [float(v) for v in self.first["loss"]],
                 "mom1": jax.device_get(self.first.get("mom1")),
                 "params3": jax.device_get(self.first.get("params3"))}
        steps = {lane: [(np.asarray(x), np.asarray(y)) for x, y in s]
                 for lane, s in self.steps.items()}
        self.served, self.labeled, self.first = [], [], {"loss": []}
        self.run = None
        return {"served": served, "labeled": labeled, "first": first,
                "steps": steps}


def sync() -> None:
    """Wait for every program issued so far: one device runs its programs
    in issue order, so a fresh one finishes last."""
    (jnp.zeros(()) + 1).block_until_ready()


# -------------------------------------------------------------- weights
def make_weights(config: Dict[str, Any], traffic: Dict[str, Any], seed: int,
                 stream: DriftStream):
    """Teacher and student weights from the seed, made on the device by one
    jitted call per model, then pretrained briefly (teacher on the whole
    scenario, student on its first segment), so that labels and drift
    verdicts follow a teacher that knows the scene."""
    pre = traffic["pretrain"]
    rng = np.random.default_rng([seed, 2])
    out = []
    for n, (role, steps, segments) in enumerate((
            ("teacher", pre["teacher_steps"], None),
            ("student", pre["student_steps"], stream.segments[:1]))):
        cfg = config[role]
        key = np.random.SeedSequence([seed, n]).generate_state(2)
        params = jax.jit(lambda k, c=cfg: reference.init_params(c, k))(key)
        mom = jax.tree_util.tree_map(jnp.zeros_like, params)
        ref = reference.Reference(cfg, precision="default")
        for _ in range(steps):
            x, y = stream.sample_dataset(pre["batch"], rng,
                                         segments=segments)
            params, mom, _, _ = ref.step(pre["lr"], params, mom, x, y)
        out.append(params)
    jax.block_until_ready(out)
    return out[0], out[1]


def build_spec(config: Dict[str, Any], traffic: Dict[str, Any], seed: int
               ) -> FleetSpec:
    return FleetSpec(
        student=VisionConfig(**config["student"]),
        teacher=VisionConfig(**config["teacher"]),
        policy=PrecisionPolicy(**config["precision_policy"]),
        apply_mx=True,
        hp=CLHyperParams(**traffic["hp"]),
        allocator=traffic["allocator"],
        eval_fps=traffic["eval_fps"],
        dispatch=traffic["dispatch"],
        serve_batched=traffic.get("serve_batched", False),
        seed=seed,
        reduced=False,
    )


def streams(config, traffic, seed: int) -> List[DriftStream]:
    segs = scenario(traffic["scenario"], traffic["segments"])
    return [DriftStream(segs, fps=traffic["camera_fps"], seed=seed + lane,
                        img=config["student"]["img_size"])
            for lane in range(traffic["lanes"])]


# ------------------------------------------------------------------ run
def warm_up(run, warmup: Dict[str, int]) -> None:
    """Step the run until it has executed both kinds of phase that recur
    after the first two: a steady phase and a drift phase that retrains
    (each has serving batches of its own size, so its programs compile
    here and not in the window). At least ``min_phases``, at most
    ``max_phases``."""
    kinds = set()
    while len(run.lanes[0].records) < warmup["max_phases"] and run.step():
        kinds.update(bool(r.decision.reset_buffer)
                     for ln in run.lanes for r in ln.records[2:])
        if (len(run.lanes[0].records) >= warmup["min_phases"]
                and kinds == {False, True}):
            return


class Profiler:
    """A profiler session kept in memory from its start to :meth:`stop`: no
    file is written, no program's HLO is kept, and of the host only the
    user-level spans (the benchmark's own among them) are recorded."""

    def __init__(self):
        from jax._src.lib import _profiler

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        self.session = _profiler.ProfilerSession(opts)

    def stop(self) -> trace_reduce.Trace:
        return trace_reduce.load(self.session.stop_and_get_profile_data())


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, log: Callable[[str], None] = print,
             device: Optional[Any] = None) -> Dict[str, Any]:
    """One run of ``cell``: set-up, the measured window, the check.
    Returns the result object that ``run.py`` prints."""
    config, traffic = cell.config, cell.traffic
    device = device or jax.devices()[0]
    meter = CompileMeter()
    spans = Spans(trace)

    lanes = streams(config, traffic, seed)
    t_params, s_params = make_weights(config, traffic, seed, lanes[0])
    session = build_spec(config, traffic, seed).build()
    session.set_pretrained(t_params, s_params)
    pipes = [TimedPipeline(s, session.speculative_frames, spans)
             for s in lanes]
    run = session.open_run(pipes, duration=min(s.duration for s in lanes))
    rec = Recorder(session, run, seed, spans)
    try:
        warm_up(run, traffic["warmup"])
        jax.block_until_ready([ln.params for ln in run.lanes])
        sync()
        setup_s = time.perf_counter() - t_start
        setup_phases = len(run.lanes[0].records)
        mem_setup = device.memory_stats() or {}

        base = dict(
            clock=run.clock, compiles=meter.compiles,
            calls={k.name: k.n_apply_calls for k in session.kernels},
            h2d_bytes=sum(k.h2d_bytes for k in session.kernels),
            fetch_s=sum(p.fetch_s for p in pipes),
            label_frames=sum(p.label_frames for p in pipes))
        rec.in_window = True
        prof = tr = None
        t0 = time.perf_counter()
        try:
            while True:
                if (trace and prof is None
                        and time.perf_counter() - t0 >= seconds - TRACE_S):
                    prof, span = Profiler(), spans("window")
                    span.__enter__()
                    at_trace = dict(
                        rows_served=rec.rows_served,
                        rows_labeled=rec.rows_labeled,
                        calls={k.name: k.n_apply_calls
                               for k in session.kernels})
                with spans("phase"):
                    alive = run.step()
                if not alive or time.perf_counter() - t0 >= seconds:
                    break
            # The window closes at this barrier: no tail is scored.
            run.duration = run.clock
            result = run.finalize()
            jax.block_until_ready([ln.params for ln in run.lanes])
            sync()
            window_s = time.perf_counter() - t0
        finally:
            if prof is not None:
                span.__exit__(None, None, None)
                tr = prof.stop()
        rec.in_window = False
        compiles = meter.compiles - base["compiles"]
        records = [r for ln in run.lanes for r in ln.records[setup_phases:]]
        calls = {k.name: k.n_apply_calls - base["calls"][k.name]
                 for k in session.kernels}
        ctx = dict(
            cell=cell, chips=cell.chips, seed=seed, setup_s=setup_s,
            window_s=window_s,
            lanes=len(run.lanes),
            camera_s=len(run.lanes) * (run.clock - base["clock"]),
            phases=len(records) // max(1, len(run.lanes)),
            drift_phases=sum(r.drift for r in records),
            calls=calls, sgd_steps=calls["retraining"],
            h2d_bytes=sum(k.h2d_bytes for k in session.kernels)
            - base["h2d_bytes"],
            rows_served=rec.rows_served, rows_labeled=rec.rows_labeled,
            label_frames=sum(p.label_frames for p in pipes)
            - base["label_frames"],
            fetch_s=sum(p.fetch_s for p in pipes) - base["fetch_s"],
            spec_hits=sum(r.spec_hits for r in records),
            spec_misses=sum(r.spec_misses for r in records),
            compiles_in_window=compiles,
            sgd_batch=session.hp.sgd_batch,
            student=config["student"], teacher=config["teacher"],
            device_kind=device.device_kind,
            trace=tr,
            traced=None if tr is None else dict(
                rows_served=rec.rows_served - at_trace["rows_served"],
                rows_labeled=rec.rows_labeled - at_trace["rows_labeled"],
                calls={k.name: k.n_apply_calls - at_trace["calls"][k.name]
                       for k in session.kernels}),
            avg_accuracy=result.fleet_avg_accuracy)
        stats = device.memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
        log(f"memory: after set-up in use "
            f"{mem_setup.get('bytes_in_use')} peak "
            f"{mem_setup.get('peak_bytes_in_use')}; after the window in use "
            f"{stats.get('bytes_in_use')} peak {memory_peak}; host peak "
            f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}")
        recorded = rec.to_host()
    finally:
        run.close()
        for p in pipes:
            p.close()
    lr = session.hp.lr
    del run, session, rec, result
    gc.collect()

    log(f"work in the window: phases={ctx['phases']} "
        f"drift_phases={ctx['drift_phases']} "
        f"camera_s={ctx['camera_s']!r} window_s={window_s!r} "
        f"frames_served={ctx['rows_served']} "
        f"frames_labeled={ctx['rows_labeled']} "
        f"sgd_steps={ctx['sgd_steps']} programs={calls} "
        f"compiles_in_window={compiles} "
        f"avg_accuracy={ctx['avg_accuracy']!r}")
    log(f"set-up: setup_s={setup_s!r} phases={setup_phases} "
        f"backend_compiles={meter.compiles} compile_s={meter.compile_s!r} "
        f"cache_hits={meter.cache_hits}")

    t_check = time.perf_counter()
    readings = check.readings(config, lr, s_params, t_params, recorded)
    limits = config["limits"]
    correct = check.correct(readings, limits)
    log(f"check: {time.perf_counter() - t_check!r} s, "
        f"{len(recorded['served'])} serving and {len(recorded['labeled'])} "
        f"labeling calls sampled; lanes served "
        f"{sorted({lane for lane, *_ in recorded['served']})}")

    return dict(ctx=ctx, correct=correct, readings=readings, limits=limits,
                memory_peak=memory_peak, device=device,
                attempted=ctx["rows_served"] + ctx["rows_labeled"],
                recorded=recorded, weights=(s_params, t_params), lr=lr)


def metrics_of(cell: Cell, ctx: Dict[str, Any], trace: bool,
               bench_dir: Path = BENCH_DIR) -> Dict[str, Dict[str, Any]]:
    """The cell's end-to-end metrics (untraced) or per-layer metrics
    (traced), each read by its own reader; a metric whose reader finds
    nothing is left out."""
    wanted = cell.per_layer if trace else cell.end_to_end
    out = {}
    for m in wanted:
        value = metric_reader(m["name"], bench_dir)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def trace_summary(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``busy_s``/``window_s`` and the breakdown of a traced run."""
    tr = ctx["trace"]
    win = trace_reduce.window(tr) if tr else None
    chips = trace_reduce.chip_ops(tr, ctx["chips"]) if tr else []
    if win is None or not any(chips):
        return None
    lo, hi = win
    busy = np.mean([trace_reduce.busy_ns(iv, lo, hi) for iv in chips]) / 1e9
    progs: Dict[str, float] = defaultdict(float)
    for mods in tr.modules.values():
        for name, (_, ns) in trace_reduce.program_times(mods, lo, hi).items():
            progs[name] += ns / 1e9
    top = sorted(progs.items(), key=lambda kv: -kv[1])[:10]
    idle = []
    for iv in chips:
        for gap in trace_reduce.gaps(iv, lo, hi):
            idle.append((gap[1] - gap[0], gap))
    idle = sorted(idle, key=lambda g: -g[0])[:10]
    return dict(busy_s=float(busy), window_s=(hi - lo) / 1e9,
                device_ops=[[n, s] for n, s in top],
                idle_gaps=[[trace_reduce.host_activity(g, tr), d / 1e9]
                           for d, g in idle])


def env_setup() -> str:
    """JAX's persistent compile cache at a fixed path inside the checkout
    (whatever ``JAX_COMPILATION_CACHE_DIR`` says), every program kept in it
    and none evicted, so that only a cell's first run there compiles."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)

