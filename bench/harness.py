"""One run of one cell: set-up, a measured window, the output check.

Set-up makes the corpus on the device from the seed, copies it to the host
block by block, builds the configuration's index through the program's own
entry points (``build_index`` → ``save_index`` →
``RetrievalService.register(artifact=)``) and warms every micro-batch shape
of the cell's traffic.  The window then drives ``RetrievalService.query``
for ``seconds``.  After it, the device's peak memory is read, the program's
state is freed, and the plain reference checks a sample of what the window
served.  Each piece of set-up is printed on a line of its own.

With ``trace=True`` the last part of the window is recorded by the profiler
and reduced to device busy time; the run then reports the cell's per-layer
metrics instead of its end-to-end ones.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Optional

import numpy as np

from bench import catalog, check, loadgen, traces, work
from bench.corpus import Corpus, CorpusSpec

INDEX_NAME = "bench"
TRACE_SECONDS = 2.0            # longest traced part of a window
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec",
                  "/jax/core/compile/jaxpr_trace_duration")


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


class CompileCounter:
    """Counts JAX's trace/compile/cache-load events (process-wide)."""

    _installed: Optional["CompileCounter"] = None

    def __init__(self):
        self.count = 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._installed is None:
            import jax.monitoring as mon
            counter = cls()

            def on_event(event: str, duration: float, **kw) -> None:
                if event in COMPILE_EVENTS:
                    counter.count += 1
            mon.register_event_duration_secs_listener(on_event)
            cls._installed = counter
        return cls._installed


class Run:
    """What one run measured; the metric readers read its attributes.

    ``setup_s`` seconds from process start to the window; ``setup`` its
    pieces; ``requests`` the window's :class:`bench.loadgen.Request`\\ s;
    ``window_s`` the window's length; ``counters`` the engine's counters at
    the window's start and end; ``batch_latency_s`` the engine's per-batch
    search times recorded in the window; ``trace`` the reduced trace of the
    traced part (or ``None``); ``work`` what the least-work functions need;
    ``peaks`` the chip's peaks; ``relevant`` the supporting passages of the
    pool's queries; ``max_batch`` the served engine's micro-batch cap.
    """

    def __init__(self, cell: catalog.Cell):
        self.cell = cell
        self.traffic = cell.traffic
        self.setup: dict[str, float] = {}
        self.setup_s = 0.0
        self.requests: list[loadgen.Request] = []
        self.window_s = 0.0
        self.counters: dict[str, dict] = {}
        self.batch_latency_s: list[float] = []
        self.trace: Optional[traces.Reduced] = None
        self.traced: dict = {}
        self.work: dict = {}
        self.peaks: dict = {}
        self.relevant: Optional[np.ndarray] = None
        self.max_batch = 0
        self.compiles_in_window = 0


def require_chips(chips: int):
    """The devices, or :class:`NoChip`: a run never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoChip(f"JAX finds no accelerator (platform "
                     f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def _engine(service):
    return service._registry.get(INDEX_NAME).live_version().engine


def _counters(engine) -> dict:
    s = engine.stats()
    return {"queries_served": s["queries_served"],
            "batches_served": s["batches_served"],
            "latency_recorded": engine.latency.total_recorded,
            "t": time.perf_counter()}


class _Tracer:
    """Records the profiler over ``[start, stop)`` seconds of the window
    from a timer thread, with the engine's counters at both ends."""

    def __init__(self, engine, log_dir: Path, start: float, stop: float):
        self.engine, self.log_dir = engine, log_dir
        self.start_s, self.stop_s = start, stop
        self.at: dict[str, dict] = {}
        self.error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._t0 = 0.0

    def begin(self, t0: float) -> None:
        self._t0 = t0
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _sleep_until(self, rel: float) -> None:
        lag = self._t0 + rel - time.perf_counter()
        if lag > 0:
            time.sleep(lag)

    def _run(self) -> None:
        import jax
        try:
            self._sleep_until(self.start_s)
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 2
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
            self.at["start"] = _counters(self.engine)
            self._sleep_until(self.stop_s)
            self.at["stop"] = _counters(self.engine)
            jax.profiler.stop_trace()
        except BaseException as e:   # reported by end(); never lost
            self.error = e

    def end(self) -> None:
        self._thread.join(timeout=120.0)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop")
        if self.error is not None:
            raise RuntimeError("tracing failed") from self.error


def _mark(enabled: bool):
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax
    return lambda name: jax.profiler.TraceAnnotation(name)


def _work_model(index, cell: catalog.Cell, pool: np.ndarray,
                trace: bool) -> dict:
    """What the least-work functions need, read from the served index
    before it is freed; IVF probes come from plain routing of the pool's
    queries on the index's centroids."""
    import jax
    import jax.numpy as jnp
    storage = index.storage
    model = {"n_docs": int(storage.shape[0]),
             "code_bytes": int(storage.shape[1] * storage.dtype.itemsize),
             "dim": int(getattr(index, "_dim", 0) or storage.shape[1]),
             "in_dim": int(pool.shape[1]), "k": cell.traffic.k,
             "ivf": hasattr(index, "centroids")}
    if model["ivf"] and trace:
        lists = np.asarray(index.lists)
        model["list_lens"] = (lists >= 0).sum(axis=1)
        zq = jnp.asarray(index.encode_queries(jnp.asarray(pool)), jnp.float32)
        cs = jnp.matmul(zq, jnp.asarray(index.centroids).T,
                        precision=jax.lax.Precision.HIGHEST)
        model["pool_probes"] = np.asarray(
            jax.lax.top_k(cs, int(index.nprobe))[1])
    return model


class Session:
    """One cell's set-up in one process: the corpus of a seed, the index
    built, saved and registered, every shape of the traffic warm.  A run is
    one :meth:`window`; the knee sweep and the readings of the output
    check drive several windows of one session."""

    def __init__(self, cell: catalog.Cell, seed: int, *, t_start: float,
                 root: Path = catalog.ROOT, require_chip: bool = True):
        import jax
        from repro.retrieval import IndexSpec, build_index, save_index
        from repro.serve import RetrievalService

        self.cell, self.seed, self.root = cell, int(seed), root
        self.run = run = Run(cell)
        cfg, traffic = cell.config, cell.traffic
        self.rng = np.random.default_rng(self.seed)

        self.devices = (require_chips(cell.chips) if require_chip
                        else jax.devices())
        self.device = dev = self.devices[0]
        self.peaks = work.peaks(dev.device_kind) if require_chip else {}
        CompileCounter.get()
        run.setup["jax_init"] = time.perf_counter() - t_start
        log(f"device: {len(self.devices)} x {dev.platform} {dev.device_kind}")
        log(f"setup jax_init {run.setup['jax_init']:.3f} s")

        t = time.perf_counter()
        spec = CorpusSpec(**cfg["corpus"])
        self.corpus = Corpus(spec, self.seed)
        n_sample = int(cfg["fit_sample"])
        self.fit_queries, _ = self.corpus.queries(0, n_sample)
        self.pool, self.relevant = self.corpus.queries(n_sample, traffic.pool)
        run.setup["corpus"] = time.perf_counter() - t
        log(f"setup corpus {run.setup['corpus']:.3f} s ({spec.n_docs} x "
            f"{spec.d}, seed {self.seed}; {n_sample} fit queries, pool "
            f"{traffic.pool})")

        t = time.perf_counter()
        docs = self.corpus.host_docs()
        run.setup["host_copy"] = time.perf_counter() - t
        log(f"setup host_copy {run.setup['host_copy']:.3f} s "
            f"({docs.nbytes} B)")

        t = time.perf_counter()
        index = build_index(IndexSpec.from_dict(cfg["index"]), docs,
                            self.fit_queries)
        del docs
        gc.collect()
        run.setup["build"] = time.perf_counter() - t
        log(f"setup build {run.setup['build']:.3f} s "
            f"({type(index).__name__}, {index.nbytes} B of codes)")
        if hasattr(index, "lists"):
            lens = (np.asarray(index.lists) >= 0).sum(axis=1)
            log(f"ivf lists {lens.size}: max_len {index.lists.shape[1]}, "
                f"length min / median / max {lens.min()} / "
                f"{int(np.median(lens))} / {lens.max()}")

        t = time.perf_counter()
        # one file per process and seed: sessions may run side by side
        self.work_dir = root / "artifacts" / "bench"
        self.work_dir.mkdir(parents=True, exist_ok=True)
        self.tag = f"{cell.name}-{os.getpid()}-{self.seed}"
        self.artifact = self.work_dir / f"{self.tag}.npz"
        save_index(index, str(self.artifact))
        del index
        gc.collect()
        run.setup["save"] = time.perf_counter() - t
        log(f"setup save {run.setup['save']:.3f} s "
            f"({self.artifact.stat().st_size} B)")

        t = time.perf_counter()
        self.service = RetrievalService(default_k=traffic.k)
        try:
            self.service.register(INDEX_NAME, artifact=str(self.artifact))
            self.engine = _engine(self.service)
            run.max_batch = int(self.engine.batcher.max_batch)
            run.setup["register"] = time.perf_counter() - t
            log(f"setup register {run.setup['register']:.3f} s")
            self.warm(traffic)
        except BaseException:
            self.close()
            raise

    def warm(self, traffic: loadgen.Traffic) -> None:
        t = time.perf_counter()
        rows_warmed = traffic.warm_rows(self.run.max_batch)
        for rows in rows_warmed:
            block = self.pool[np.arange(rows) % len(self.pool)]
            self.service.query(block, index=INDEX_NAME,
                               k=traffic.k).result(timeout=1200.0)
        self.run.setup["warmup"] = time.perf_counter() - t
        log(f"setup warmup {self.run.setup['warmup']:.3f} s (rows "
            f"{rows_warmed}, k {traffic.k})")

    def window(self, seconds: float, trace: bool, *, t_start: float,
               traffic: Optional[loadgen.Traffic] = None) -> Run:
        """Drive the service for ``seconds``; fills and returns a
        :class:`Run` (the session's own for the first window)."""
        traffic = traffic or self.cell.traffic
        run, engine = self.run, self.engine
        run.traffic = traffic
        tracer = None
        trace_dir = self.work_dir / f"trace-{self.tag}"
        submit = (lambda block: self.service.query(block, index=INDEX_NAME,
                                                   k=traffic.k))
        loop = (loadgen.open_loop if traffic.loop == "open"
                else loadgen.closed_loop)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            traced = min(TRACE_SECONDS, seconds / 2)
            tracer = _Tracer(engine, trace_dir, seconds - traced, seconds)
        compiles = CompileCounter.get()
        before = compiles.count
        run.counters["start"] = _counters(engine)
        run.setup_s = run.counters["start"]["t"] - t_start
        log(f"setup_s {run.setup_s:.3f} s")
        if tracer is not None:
            tracer.begin(run.counters["start"]["t"])
        run.requests, run.window_s = loop(
            submit, lambda idx: self.pool[idx], traffic, self.rng, seconds,
            mark=_mark(trace))
        run.counters["end"] = _counters(engine)
        if tracer is not None:
            tracer.end()
        run.compiles_in_window = compiles.count - before
        n_new = (run.counters["end"]["latency_recorded"]
                 - run.counters["start"]["latency_recorded"])
        samples = engine.latency.samples
        run.batch_latency_s = list(samples[-n_new:]) if n_new else []
        run.relevant, run.peaks = self.relevant, self.peaks
        log(f"window {run.window_s:.3f} s: {len(run.requests)} requests, "
            f"{sum(r.error is not None for r in run.requests)} failed, "
            f"{run.compiles_in_window} compiles or cache loads in the window")
        run.work = _work_model(engine.index, self.cell, self.pool, trace)
        if tracer is not None:
            run.traced = {"start": tracer.at["start"],
                          "stop": tracer.at["stop"]}
            run.trace = traces.reduce_dir(
                trace_dir, run.traced["stop"]["t"] - run.traced["start"]["t"])
            shutil.rmtree(trace_dir, ignore_errors=True)
            n_b = (run.traced["stop"]["batches_served"]
                   - run.traced["start"]["batches_served"])
            log(f"trace: window {run.trace.window_s:.6f} s, device busy "
                f"{run.trace.busy_s:.6f} s, {n_b} batches")
        return run

    def memory_peak(self) -> int:
        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in self.devices))

    def close(self) -> None:
        """Free the program's state: the service, its index, the artifact."""
        if self.service is not None:
            self.service.close()
        self.service = self.engine = None
        gc.collect()
        self.artifact.unlink(missing_ok=True)

    def checked_rows(self, run: Run) -> tuple:
        """(queries, served ids, served scores) of the requests the output
        check compares, drawn from the seed."""
        picked = check.sample_requests(run.requests,
                                       run.traffic.sample_rows, self.rng)
        q = self.pool[np.concatenate([r.rows for r in picked])]
        return (q, np.concatenate([r.ids for r in picked]),
                np.concatenate([r.scores for r in picked]))

    def reference(self, levels: Optional[int] = None):
        from bench.reference import Reference
        ref = self.cell.config["reference"]
        return Reference(self.corpus, self.fit_queries, dim=ref["dim"],
                         levels=levels or ref["levels"])


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = catalog.ROOT,
             require_chip: bool = True, cell: Optional[catalog.Cell] = None,
             fault=None) -> dict:
    """One run; returns the result object.  ``fault`` (tests only) is
    called with the served index after warm-up to break the timed path."""
    cell = cell or catalog.load_cell(cell_name, root)
    session = Session(cell, seed, t_start=t_start, root=root,
                      require_chip=require_chip)
    try:
        if fault is not None:
            fault(session.engine)
        run = session.window(seconds, trace, t_start=t_start)
        peak = session.memory_peak()
    finally:
        session.close()

    if run.traffic.loop == "open":
        from bench import readers
        lat = readers.latencies_ms(run)
        log(f"latency over {lat.size} requests: p50 "
            f"{readers.latency_percentile(run, 50)} ms, p95 "
            f"{readers.latency_percentile(run, 95)} ms, p99 "
            f"{readers.latency_percentile(run, 99)} ms")

    # --- the output check, after the program's state is gone ---------------
    t = time.perf_counter()
    q, ids, scores = session.checked_rows(run)
    limits = cell.config["check"]
    values = check.compare(q, ids, scores, session.reference(),
                           run.traffic.k, limits)
    values["lost"] = float(sum(r.error is not None and "QueueFull" not in r.error
                               for r in run.requests))
    correct = check.verdict(values, limits)
    log(f"reference {time.perf_counter() - t:.3f} s over {q.shape[0]} rows "
        f"(not in setup_s)")

    metrics = catalog.read_metrics(cell.per_layer if trace
                                   else cell.end_to_end, run, root)
    dev = session.device
    result = {
        "correct": bool(correct),
        "attempted": len(run.requests),
        "failed": sum(r.error is not None for r in run.requests),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "device_kind": dev.device_kind,
                   "count": len(session.devices), "memory_peak_bytes": peak},
    }
    if run.trace is not None:
        result["device"]["busy_s"] = run.trace.busy_s
        result["device"]["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["check"] = check.report(values, limits)
    for name, v in result["check"].items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    return result


def dump(result: dict) -> str:
    return json.dumps(result, allow_nan=False)
