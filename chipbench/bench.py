"""One run of one benchmark cell, found by name in ``BENCHMARK.json``.

A cell names a configuration (``configs/<config>.json``, with its plain
reference ``reference/<reference>.py`` and the bridge to the program,
``adapters/<reference>.py``) and a traffic mix (``traffic/<traffic>.json``);
its correctness limits are ``limits/<cell>.json``; every metric is read
by ``metrics/<metric>.py``, or a metric ``<family>.<part>`` by its
family's ``metrics/<family>.py``.  Adding a cell, a configuration or a metric
adds files and entries; nothing here names one.

A run: weights from the seed on the device, the program's
``ServeEngine`` with the traffic's settings, a warm-up that compiles
every shape the traffic uses, a measured window of ``--seconds`` driven
through ``ServeEngine.submit`` / ``step`` under
``ops.strict_fallbacks()``, then the reference check of what the window
served.  With ``--trace 1`` the same window runs under the JAX profiler
and the per-layer metrics are read from the trace, the program's spans
and its counters.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
TRACE_DIR = HERE / "traces"          # profiler output, deleted once read


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


# ----------------------------------------------------------------------
# the benchmark's data, by name
# ----------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list        # metric entries of BENCHMARK.json
    per_layer: list

    @property
    def open_loop(self) -> bool:
        return self.traffic["loop"] == "open"


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, bench_path: Path = REPO / "BENCHMARK.json") -> Cell:
    bench = _json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(m):
        return name in m.get("workloads", [name])
    return Cell(name=name, chips=int(w["chips"]), config_name=conf["name"],
                config=_json(REPO / conf["file"]),
                traffic=_json(HERE / "traffic" / f"{w['traffic']}.json"),
                limits=_json(HERE / "limits" / f"{name}.json"),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """``read(run) -> float | None`` of ``metrics/<metric>.py``, or, for
    a metric split by the cells it moves (``<family>.<part>``), of the
    family's one reader ``metrics/<family>.py``."""
    name = metric
    while not (HERE / "metrics" / f"{name}.py").is_file():
        if "." not in name:
            raise FileNotFoundError(f"no reader for metric {metric!r} "
                                    f"under {HERE / 'metrics'}")
        name = name.rsplit(".", 1)[0]
    return _module(HERE / "metrics" / f"{name}.py", "chipbench_metric_"
                   + name.replace(".", "_").replace("-", "_")).read


def reference_module(cell: Cell):
    return importlib.import_module(
        f"chipbench.reference.{cell.config['reference']}")


def adapter_module(cell: Cell):
    return importlib.import_module(
        f"chipbench.adapters.{cell.config['reference']}")


def peaks(device_kind: str) -> dict:
    table = _json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


# ----------------------------------------------------------------------
# what a metric reader sees
# ----------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """Everything one run measured; metric readers take what they need."""

    cell: Cell
    dims: object
    seed: int
    seconds: float
    t0: float                 # window opens (host clock, s)
    t_close: float            # the window's nominal close (t0 + seconds)
    t_end: float              # the window's end: the last step it ran
    logs: dict                # rid -> loops.Log
    stats: object             # the program's EngineStats over the window
    num_slots: int
    steps_per_dispatch: int
    setup_s: float
    peak: dict
    chips: int
    spans: list | None = None         # the program's obs records
    trace: object | None = None       # chipbench.trace.Trace
    trace_window: tuple | None = None  # (w0, w1) ns, the traced window
    matmul_records: tuple = ()        # (M, N, K, bm, bn, bk)
    notes: list = dataclasses.field(default_factory=list)

    def note(self, line: str) -> None:
        self.notes.append(line)


# ----------------------------------------------------------------------
def _device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory_peak(jax) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


class _CompileCounter:
    """Counts traces and compiles while ``on`` (none belong in the
    measured window).  One per process: JAX keeps its listeners."""

    _one = None

    @classmethod
    def get(cls, jax) -> "_CompileCounter":
        if cls._one is None:
            cls._one = cls()
            jax.monitoring.register_event_duration_secs_listener(cls._one)
        cls._one.on, cls._one.n = False, 0
        return cls._one

    def __call__(self, name, *args, **kwargs):
        if self.on and name in ("/jax/core/compile/jaxpr_trace_duration",
                                "/jax/core/compile/backend_compile_duration"):
            self.n += 1


class Session:
    """The program under test, set up for one cell and seed: weights on
    the device, ``ServeEngine`` with the traffic's settings, every
    shape compiled by a warm-up.  :meth:`window` then drives measured
    windows; :meth:`close` frees the program's state and reads the
    device memory peak."""

    def __init__(self, cell: Cell, seed: int, *, trace: bool = False,
                 require_tpu: bool = True, backend: str = "pallas",
                 t_start: float | None = None):
        t_start = time.perf_counter() if t_start is None else t_start
        import jax
        import jax.numpy as jnp
        if require_tpu:
            self.device = _device_info(jax, cell.chips)
            self.peak = peaks(self.device["kind"])
        else:
            d = jax.devices()[0]
            self.device = {"platform": d.platform, "kind": d.device_kind,
                           "count": len(jax.devices())}
            self.peak = peaks("TPU v5 lite")
        from repro import obs, tune
        from repro.launch.compile_cache import use_compile_cache
        from repro.models import Ctx, build_model
        from repro.serve import ServeEngine

        from chipbench.gen import Traffic

        self.jax, self.obs, self.tune = jax, obs, tune
        self.cell, self.seed, self.trace = cell, seed, trace
        self.ref = reference_module(cell)
        self.dims = self.ref.Dims.from_config(cell.config)
        adapter = adapter_module(cell)
        use_compile_cache()
        self.counter = _CompileCounter.get(jax)
        self.sink = obs.ListSink() if trace else None
        # tile picks come from this run's (analytic, deterministic)
        # tuner, never from a cache another run left behind
        self._tmp = tempfile.TemporaryDirectory()
        self._prev_tune = tune.get_cache()
        tune.set_cache(tune.TuneCache(Path(self._tmp.name) / "tune.json"))
        if trace:
            obs.reset_records()
            obs.enable(sink=self.sink)
        self.weights = self.ref.init_weights(self.dims, seed)
        jax.block_until_ready(self.weights)
        model = build_model(adapter.model_config(cell.config_name,
                                                 self.dims))
        self.engine = ServeEngine(
            model, adapter.program_params(self.weights),
            Ctx(plan=backend, dtype=jnp.bfloat16), cache_dtype=jnp.bfloat16,
            plan="trace", **cell.traffic["engine"])
        self.traffic = Traffic(cell.traffic, seed, self.dims.vocab)
        self.engine.run([_request(r) for r in self.traffic.warmup()])
        self.engine.pop_results()
        self.n_plan = len(self.engine.plan)
        self.setup_s = time.perf_counter() - t_start

    def window(self, seconds: float, *, profile_dir: Path | None = None,
               drain_s: float = 60.0, traffic: dict | None = None):
        """One measured window; returns ``(logs, t0, t_close, t_end,
        engine stats)``.  ``traffic`` replaces the open loop's traffic
        parameters (a rate sweep)."""
        from repro.serve.stats import EngineStats

        from chipbench import loops
        from chipbench.gen import Traffic
        jax, eng, cell = self.jax, self.engine, self.cell
        eng.stats = EngineStats(num_slots=eng.num_slots)
        if cell.open_loop:
            src = self.traffic if traffic is None else \
                Traffic(traffic, self.seed, self.dims.vocab)
            schedule = src.open_schedule(seconds)
        else:
            stream = self.traffic.closed_stream()
        if self.sink is not None:
            self.sink.records.clear()
        gc.collect()
        gc.freeze()
        if profile_dir is not None:
            shutil.rmtree(profile_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # annotations only, no calls
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(profile_dir),
                                     profiler_options=opts)
        self.counter.on = True
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            t_close = t0 + seconds
            if cell.open_loop:
                logs = loops.open_loop(eng, schedule, t0, t_close + drain_s)
            else:
                logs = loops.closed_loop(eng, stream,
                                         int(cell.traffic["outstanding"]),
                                         t_close)
            t_end = time.perf_counter()
        self.counter.on = False
        if profile_dir is not None:
            jax.profiler.stop_trace()
        gc.unfreeze()
        return logs, t0, t_close, t_end, eng.stats

    def matmul_records(self) -> tuple:
        """The matmul problems the program traced: (M, N, K, bm, bn, bk)."""
        return tuple((r.M, r.N, r.K, r.config.bm, r.config.bn, r.config.bk)
                     for r in self.obs.recorded_ops()
                     if r.op == "matmul" and r.config is not None)

    def close(self) -> int:
        """Free the program's state; return the device memory peak."""
        if self.trace:
            self.obs.disable()
        peak = _memory_peak(self.jax)
        del self.engine, self.weights
        gc.collect()
        self.tune.set_cache(self._prev_tune)
        self._tmp.cleanup()
        return peak


def _request(r):
    from repro.serve import Request
    return Request(rid=r.rid, prompt=r.prompt.tolist(),
                   max_new_tokens=r.max_new_tokens)


def run(workload: str, seed: int, seconds: float, trace: bool,
        *, log=print, cell: Cell | None = None,
        require_tpu: bool = True, backend: str = "pallas",
        inspect: dict | None = None) -> dict:
    """One run; returns the result line's object (see ``run.py``).

    ``cell``, ``require_tpu=False`` and ``backend="interpret"`` let a
    test drive the same run at a tiny size on the CPU; ``inspect``, if
    given, receives the run, the verdict and the reference."""
    t_start = time.perf_counter()
    cell = cell or load_cell(workload)
    from repro.kernels import ops

    from chipbench import check
    from chipbench.stats import percentile
    with ops.strict_fallbacks():
        ses = Session(cell, seed, trace=trace, require_tpu=require_tpu,
                      backend=backend, t_start=t_start)
        profile_dir = TRACE_DIR / f"{workload}.{seed}" if trace else None
        logs, t0, t_close, t_end, stats = ses.window(
            seconds, profile_dir=profile_dir,
            drain_s=float(cell.limits.get("drain_s", 60)))
        n_plan = len(ses.engine.plan)
        slots = ses.engine.num_slots
        k = ses.engine.steps_per_dispatch
        records = ses.matmul_records()
        device = dict(ses.device, memory_peak_bytes=ses.close())

    result_run = Run(cell=cell, dims=ses.dims, seed=seed, seconds=seconds,
                     t0=t0, t_close=t_close, t_end=t_end, logs=logs,
                     stats=stats, num_slots=slots, steps_per_dispatch=k,
                     setup_s=ses.setup_s, peak=ses.peak, chips=cell.chips,
                     spans=ses.sink.records if trace else None,
                     matmul_records=records)
    if n_plan != ses.n_plan:
        result_run.note(f"warning: the plan grew in the window "
                        f"({ses.n_plan} -> {n_plan} entries)")
    lates = [(g.submit - g.due) * 1e3 for g in logs.values()]
    ttft = [(g.times[0] - g.submit) * 1e3 for g in logs.values() if g.times]
    if lates and ttft:
        result_run.note(
            f"client: {len(logs)} requests; submitted late by p50 "
            f"{percentile(lates, 50):.3f} ms, max {max(lates):.3f} ms; "
            f"submit to first token p50 {percentile(ttft, 50):.3f} ms, "
            f"p90 {percentile(ttft, 90):.3f} ms")
    if ses.counter.n:
        result_run.note(f"warning: {ses.counter.n} traces or compiles "
                        f"inside the measured window")
    out = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
           "device": device}
    if trace:
        from chipbench import trace as tr
        t = tr.load(str(profile_dir))
        result_run.trace = t
        result_run.trace_window = w0, w1 = tr.window(t)
        off = w0 - t0 * 1e9     # host clock -> trace clock, ns
        spans = [(s["name"], s["t"] * 1e9 + off,
                  (s["t"] + s["dur_s"]) * 1e9 + off)
                 for s in result_run.spans if s.get("type") == "span"]
        device["busy_s"] = tr.busy_s(t, w0, w1)
        device["window_s"] = (w1 - w0) * 1e-9
        out["breakdown"] = {"device_ops": tr.device_ops(t, w0, w1),
                            "idle_gaps": tr.idle_gaps(t, w0, w1, spans)}
        shutil.rmtree(profile_dir, ignore_errors=True)
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"])(result_run)
        if v is not None:
            out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}

    verdict = check.check(result_run, ses.ref, ses.dims, seed)
    out["correct"] = verdict.correct
    out["attempted"] = verdict.attempted
    out["failed"] = verdict.failed
    for line in result_run.notes + verdict.lines:
        log(line)
    out["compared"] = verdict.compared
    if inspect is not None:
        inspect.update(run=result_run, verdict=verdict, ref=ses.ref,
                       dims=ses.dims)
    return out
