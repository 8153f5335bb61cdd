"""One run of one cell: set-up, the measured window, the check, the metrics.

The program is driven only through public entries: ``ServeEngine(cfg,
EngineConfig(...), params, admission=, on_chunk=, on_prefill=)`` and
``ServeEngine.run``.  Set-up builds the weights on the device from the seed,
builds the engine and warms it with one ``run`` of a warm-up trace; the
window is a second ``run`` on the same engine, fed open-loop on the wall
clock by the admission hook.  Arrivals stop at the window's end and the run
drains what is in flight.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time

import numpy as np

from harness import check, records, spec, trace as tracing, traffic


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events, with the time each happened."""

    def __init__(self, clock=time.perf_counter):
        import jax
        self.clock = clock
        self.compiles: list[tuple[float, float]] = []   # (when, seconds)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((self.clock(), duration))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def between(self, a: float, b: float) -> int:
        return sum(1 for t, _ in self.compiles if a <= t < b)


@dataclasses.dataclass
class Run:
    """What a metric reader gets."""
    sizes: dict              # the reference's sizes of the configuration
    engine: dict             # EngineConfig settings of the configuration
    window: records.Window
    setup_s: float
    peaks: dict | None
    trace: tracing.Summary | None


def key_from_seed(seed: int):
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _bucket(L: int, lo: int) -> int:
    b = lo
    while b < L:
        b *= 2
    return b


def _check_layout(weights, program_tree) -> None:
    import jax
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), weights)
    want = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)),
                        program_tree)
    if got != want:
        raise RuntimeError(f"seeded weights do not match the program's "
                           f"parameter layout:\n{got}\nvs\n{want}")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Session:
    """The weights and the engine of one seed, and its windows."""

    def __init__(self, root, cell: spec.Cell, seed: int, *,
                 allow_cpu: bool = False):
        import jax
        self.cell, self.seed = cell, seed
        devices = jax.devices()
        self.dev = devices[0]
        self.n_devices = len(devices)
        if not allow_cpu and (self.dev.platform != "tpu"
                              or len(devices) < cell.chips):
            raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); "
                         f"JAX found {len(devices)} {self.dev.platform} "
                         f"device(s)")
        self.peaks = None if allow_cpu else spec.peaks(self.dev.device_kind)
        self.meter = CompileMeter()

        from repro.configs import get_arch
        from repro.models import transformer as tfm
        from repro.serving import EngineConfig, Request, ServeEngine
        self.Request = Request

        conf = cell.config
        self.bench = conf["bench"]
        self.ref = spec.reference(self.bench["reference"])
        self.sizes = self.ref.sizes(conf)
        pcfg = dataclasses.replace(
            get_arch(self.bench["program"]["arch"]).config,
            **self.bench["program"]["overrides"])
        self.ref.check_program(self.sizes, pcfg)
        self.key = key_from_seed(seed)
        sizes = self.sizes
        self.params = jax.jit(lambda k: self.ref.init_weights(k, sizes))(
            self.key)
        _check_layout(self.params, jax.eval_shape(
            lambda: tfm.init_lm(jax.random.PRNGKey(0), pcfg)[0]))
        jax.block_until_ready(self.params)

        self.ecfg = EngineConfig(**self.bench["engine"])
        self.hook = records.WallClockAdmission()
        self.rec = records.Recorder(chunk_steps=self.ecfg.decode_chunk)
        self.engine = ServeEngine(pcfg, self.ecfg, self.params,
                                  admission=self.hook,
                                  on_chunk=self.rec.on_chunk,
                                  on_prefill=self.rec.on_prefill)

    def requests(self, planned, rid_offset: int = 0):
        return [self.Request(rid=p.rid + rid_offset, prompt=p.prompt,
                             max_new_tokens=p.max_new_tokens, arrival_step=0)
                for p in planned]

    def warm(self, planned, warm) -> None:
        """Every program the window can run, then the warm-up trace.

        One cold prompt per prefill bucket the window's prompts can take;
        where the mix shares prefixes, one prompt that matches a probe
        through a page and a half (chunked suffix prefill and the page
        copy); then the warm-up trace (the decode loop, and the cache a
        running server holds)."""
        rng = np.random.default_rng([self.seed & 0xFFFFFFFF, 7])
        vocab, ps = self.sizes["vocab"], self.ecfg.page_size
        buckets = sorted({_bucket(len(p.prompt), self.ecfg.min_prefill_bucket)
                          for p in planned})
        probes = [traffic.Planned(i, 0.0, rng.integers(
            0, vocab, b - 1, dtype=np.int32), 1, {})
            for i, b in enumerate(buckets)]
        runs = [probes]
        if any(s["kind"] == "pooled" for s in self.cell.traffic["segments"]):
            head = probes[0].prompt[:ps + ps // 2]
            runs.append([traffic.Planned(0, 0.0, np.concatenate(
                [head, rng.integers(0, vocab, ps, dtype=np.int32)]), 1, {})])
        runs.append(warm)
        self.hook.due = None
        for k, batch in enumerate(runs):
            rep = self.engine.run(self.requests(batch, (k + 1) * 10**8))
            bad = [r.rid for r in rep.results
                   if r.n_tokens != r.max_new_tokens]
            if bad:
                raise RuntimeError(f"warm-up requests {bad[:5]} did not "
                                   "finish")

    def window(self, planned, seconds: float, tracer=None) -> records.Window:
        """Serve ``planned`` open-loop; returns the window's records."""
        rec, hook = self.rec, self.hook
        rec.prefills.clear()
        rec.chunks.clear()
        rec.chunk_listeners = [tracer.on_chunk] if tracer else []
        hook.admitted.clear()
        hook.late.clear()
        hook.spans.clear()
        reqs = self.requests(planned)
        t0 = time.perf_counter()
        hook.due = {p.rid: t0 + p.arrival_s for p in planned}
        if tracer is not None:
            tracer.begin(t0)
        report = self.engine.run(reqs)
        t_end = time.perf_counter()
        if tracer is not None:
            tracer.finish()
        hook.due = None
        log(f"window {seconds} s, drained {t_end - t0 - seconds:.3f} s "
            f"later; compiles inside the window: "
            f"{self.meter.between(t0, t0 + seconds)}")

        by_rid = {r.rid: r for r in report.results}
        zero = records.engine_clock_zero(rec.prefills,
                                         [r.admit_t for r in report.results])
        reqs_rec = []
        for p in planned:
            res = by_rid[p.rid]
            reqs_rec.append(records.Req(
                rid=p.rid, due=t0 + p.arrival_s, prompt_len=len(p.prompt),
                max_new_tokens=p.max_new_tokens,
                admitted=hook.admitted.get(p.rid, -1.0),
                first=zero + res.admit_t, finish=zero + res.finish_t,
                n_tokens=res.n_tokens, saved=res.prefill_tokens_saved,
                finish_reason=res.finish_reason, tokens=list(res.tokens),
                prompt=p.prompt))
        spans = list(hook.spans)
        spans += [records.Span("join", r.admitted, r.first)
                  for r in reqs_rec]
        spans += [records.Span("chunk", c.end - c.wall_s, c.end)
                  for c in rec.chunks]
        late = records.pct(hook.late, 90)
        log(f"{len(reqs_rec)} requests; admissions on an empty engine ran "
            f"late by p90 {late if late is not None else 0.0:.6f} s over "
            f"{len(hook.late)} sleeps")
        return records.Window(t0, seconds, reqs_rec, list(rec.chunks), spans,
                              list(hook.late), report)

    def device(self) -> dict:
        stats = self.dev.memory_stats() or {}
        return {"platform": self.dev.platform, "kind": self.dev.device_kind,
                "count": self.n_devices,
                "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    def free(self) -> None:
        """Drop the program's state, so the reference runs without it."""
        self.engine = None
        self.params = None
        gc.collect()


def run(root, cell_name: str, *, seed: int, seconds: float, trace: bool,
        t_process: float, allow_cpu: bool = False, benchmark_file=None,
        traffic_dir=None, engine_hook=None) -> dict:
    """One run; returns the result line as a dict.  ``engine_hook(engine)``
    lets a test break the engine underneath before the window."""
    cell = spec.load_cell(root, cell_name, benchmark_file=benchmark_file,
                          traffic_dir=traffic_dir)
    s = Session(root, cell, seed, allow_cpu=allow_cpu)
    planned, warm = traffic.make_trace(cell.traffic, seed=seed,
                                       seconds=seconds,
                                       vocab=s.sizes["vocab"])
    s.warm(planned, warm)
    if engine_hook is not None:
        engine_hook(s.engine)
    n_setup = len(s.meter.compiles)
    tracer = tracing.WindowTracer(root, seconds) if trace else None
    t_setup_end = time.perf_counter()
    window = s.window(planned, seconds, tracer)
    setup_s = window.start - t_process
    log(f"set-up {setup_s:.3f} s ({n_setup} compiles, {s.meter.cache_hits} "
        f"persistent-cache hits; {window.start - t_setup_end:.6f} s of it "
        f"between set-up and the window)")
    device = s.device()
    summary = None
    if tracer is not None:
        summary = tracer.reduce(window)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    s.free()

    failed = sum(1 for r in window.reqs if r.n_tokens != r.max_new_tokens)
    verdict = check.compare(s.ref, cell.config, s.sizes, s.key, window.reqs,
                            seed=seed, failed=failed)
    run_ = Run(s.sizes, s.bench["engine"], window, setup_s, s.peaks, summary)
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        value = spec.metric_reader(m["name"])(run_)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": verdict.correct, "attempted": len(window.reqs),
           "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = summary.breakdown
    out["checks"] = verdict.checks
    return out
