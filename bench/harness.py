"""One run of one cell: set-up, the measured window, readers, `correct`.

Set-up builds the system under test from the cell's files (weights made
on the device from the seed, engine geometry from ``cells/<cell>.json``)
and warms exactly the shapes the cell's traffic can produce.  The window
then drives ``ContinuousBatchingEngine.run`` with the cell's traffic for
``seconds``; load continues past the window until every request due in
it has completed.  The readers named in ``BENCHMARK.json`` turn the
recorded run into metrics, and the reference comparison decides
``correct``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import json
import pathlib
import shutil
import sys
import time
from typing import Optional

import jax
import numpy as np

from bench import check, common, weights
from bench import trace as trace_lib
from bench.recorder import CompileClock, Recorder
from bench.traffic import Traffic

PEAKS = common.BENCH_DIR / "peaks.json"
CACHE_DIR = ".jax_cache"  # inside the checkout, at a fixed path
TRACE_DIR = ".bench_trace"
DRAIN_LIMIT_S = 120.0  # a request due in the window never completing


class NoChip(RuntimeError):
    """The cell needs accelerator chips JAX does not see."""


class WindowDone(Exception):
    """Raised from the recorder's flush — where the engine has finished
    a readback or an admission group — once every request due in the
    window has completed."""


@dataclasses.dataclass
class Run:
    """What the metric readers see of one run."""
    cell: object
    seconds: float
    rec: Recorder
    t0: float
    t_end: float
    setup_s: float
    setup_compile_s: float
    counters: dict
    model: dict  # the plain description of the model served
    reference: object  # the configuration's reference module
    value_bytes: int  # bytes of an activation and a cached K/V value
    peak: dict
    trace: Optional[trace_lib.Trace] = None
    tlo: int = 0  # traced window in trace time (ns)
    thi: int = 0

    @property
    def trace_window_s(self):
        return (self.thi - self.tlo) / 1e9

    def in_trace(self, t):
        """Is monotonic time ``t`` inside the traced window?"""
        return self.t0 <= t <= self.t_end


def _program():
    """The system under test (imported only once a chip is confirmed)."""
    from repro.configs.base import get_config
    from repro.models import get_family
    from repro.serve import ContinuousBatchingEngine, Request
    return dict(get_config=get_config, get_family=get_family,
                Engine=ContinuousBatchingEngine, Request=Request)


def _same_widths(cfg, widths):
    """Raise unless the program's configuration holds ``widths`` (the
    reference's ``program_widths``): {field: (program's, file's)}."""
    bad = {k: (getattr(cfg, k), v) for k, v in widths.items()
           if getattr(cfg, k) != v}
    if bad:
        raise ValueError(f"the program's model is not the configuration "
                         f"file's: {bad}")


class Session:
    """The system under test for one cell, built from its files."""

    def __init__(self, cell, seed, seconds):
        self.P = P = _program()
        self.cell = cell
        prog = cell.config["program"]
        self.cfg = P["get_config"](prog["arch"]).replace(
            decode_kernel=prog["decode_kernel"])
        self.ref = cell.reference
        self.spec = self.ref.describe(cell.config)
        _same_widths(self.cfg, self.ref.program_widths(self.spec))
        g = cell.serve
        self.geom = dict(capacity=g["capacity"], max_len=g["max_len"],
                         prefill_bucket=g["prefill_bucket"], k=g["k"],
                         pool=g["pool"])
        self.seed = seed
        self.traffic = Traffic(cell.traffic, seed, seconds,
                               self.cfg.vocab_size)
        fam = P["get_family"](self.cfg)
        shapes = jax.eval_shape(
            lambda: fam.init(jax.random.PRNGKey(0), self.cfg))
        self.params = weights.make_params(shapes, weights.seed_key(seed))
        jax.block_until_ready(self.params)

    def engine(self, journal=None):
        return self.P["Engine"](self.cfg, self.params, journal=journal,
                                **self.geom)

    # --------------------------------------------------------- warm-up
    def buckets(self):
        """Prefill buckets the cell's prompt lengths can produce."""
        spec, b = self.cell.traffic["prompt"], self.geom["prefill_bucket"]
        return sorted({min(-(-n // b) * b, self.geom["max_len"])
                       for n in range(spec["min"], spec["max"] + 1)})

    def groups(self):
        cap = self.geom["capacity"]
        return sorted({min(1 << i, cap) for i in range(cap.bit_length())})

    def warm(self, eng):
        """Run every (admission group, prefill bucket) shape once on
        ``eng`` (one-token requests: admission alone), then the decode
        loop once."""
        Request = self.P["Request"]
        limit = self.geom["max_len"] - 2
        # distinct prompts: shared ones would take the prefix-hit path
        rng = np.random.default_rng(0)
        uid = -1
        shapes = [(n, b, 1) for n in self.groups() for b in self.buckets()]
        for n, b, new in shapes + [(1, self.buckets()[0], 2)]:
            eng.run([Request(uid=uid - i, prompt=rng.integers(
                         0, self.cfg.vocab_size, min(b, limit),
                         dtype=np.int32), max_new_tokens=new)
                     for i in range(n)])
            uid -= n

    def setup(self):
        """Compile and run every shape the window will use."""
        eng = self.engine()
        self.warm(eng)
        del eng
        gc.collect()


# ------------------------------------------------------------ the window
class Load:
    """Submits the cell's traffic through the recorder's hooks and
    closes the run once every request due in the window is done."""

    def __init__(self, sess, eng, rec, seconds):
        self.sess, self.eng, self.rec = sess, eng, rec
        self.tr = sess.traffic
        self.seconds = seconds
        self.pending = set()  # measured uids not yet done
        self.t0 = None
        self._fillers = None
        self._next_filler = None
        self._uid = 0  # closed loop: the next request's uid
        self.closing = False
        rec.on_tokens = self.on_tokens
        rec.on_finish = self.on_finish
        rec.on_flush = self.on_flush

    def submit(self, r):
        self.rec.expect(r.uid, r.arrival, r.prompt_len, r.measured)
        if r.measured:
            self.pending.add(r.uid)
        self.eng.submit(self.sess.P["Request"](
            uid=r.uid, prompt=self.tr.tokens(r.uid, r.prompt_len),
            max_new_tokens=r.out_len, arrival=r.arrival))

    def open(self):
        """Submit what is due at the start; returns the window's t0."""
        if self.tr.loop == "open":
            reqs = self.tr.window_requests()
            for r in reqs:
                self.submit(r)
            self._fillers = self.tr.filler_requests(len(reqs))
            self._next_filler = next(self._fillers)
        else:
            for _ in range(self.tr.clients(self.sess.geom["capacity"])):
                self.submit(self.tr.closed_request(self._uid, 0.0, True))
                self._uid += 1
        self.t0 = time.monotonic()
        return self.t0

    def done(self):
        """Every request due in the window has completed."""
        return not self.pending

    def on_tokens(self, now):
        rel = now - self.t0
        if self._fillers is not None and not self.done():
            # the same arrival process continues past the window
            while self._next_filler.arrival <= rel + 1.0:
                self.submit(self._next_filler)
                self._next_filler = next(self._fillers)
        self._close_if_done(now)

    def on_finish(self, tr):
        self.pending.discard(tr.uid)
        if self.tr.loop == "closed" and not self.done():
            now = tr.t_done
            rel = now - self.t0
            self.submit(self.tr.closed_request(self._uid, rel,
                                               rel < self.seconds))
            self._uid += 1
        self._close_if_done(tr.t_done)

    def _close_if_done(self, now):
        if now - self.t0 >= self.seconds and (
                self.done() or now - self.t0 > self.seconds + DRAIN_LIMIT_S):
            # (past the drain limit, whatever is still pending has failed)
            self.closing = True

    def on_flush(self):
        if self.closing:
            raise WindowDone()


def _annotate(obj, attr, label):
    f = getattr(obj, attr, None)
    if f is None:
        return

    @functools.wraps(f)
    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(label):
            return f(*a, **kw)

    setattr(obj, attr, wrapped)


def run_window(sess, seconds, traced, root):
    """The measured window.  Returns (Run fields, trace or None)."""
    rec = Recorder()
    eng = sess.engine(journal=rec)
    programs = None
    if traced:
        for attr, label in (("_admit_batch", "bench.admit"),
                            ("_dispatch", "bench.dispatch"),
                            ("_process", "bench.readback")):
            _annotate(eng, attr, label)
        programs = trace_lib.Programs()
        programs.watch(eng)
    load = Load(sess, eng, rec, seconds)
    found = None
    gc_spans = []  # (start, end) of each garbage collection

    def on_gc(phase, _info):
        if phase == "start":
            gc_spans.append([time.monotonic(), None])
        elif gc_spans:
            gc_spans[-1][1] = time.monotonic()

    gc.callbacks.append(on_gc)
    try:
        with contextlib.ExitStack() as stack:
            clock = stack.enter_context(CompileClock())
            if traced:
                found = stack.enter_context(
                    trace_lib.capture(str(pathlib.Path(root) / TRACE_DIR)))
            t0 = load.open()
            with jax.profiler.TraceAnnotation("bench.window"):
                try:
                    eng.run(realtime=True)
                except WindowDone:
                    pass
            t_end = time.monotonic()
    finally:
        gc.callbacks.remove(on_gc)
    xplane = found() if found is not None else None
    counters = eng.lifetime_totals()
    rec.on_tokens = rec.on_finish = rec.on_flush = None
    del eng, load
    gc.collect()  # the engine's pools go before the reference runs
    # where the host stalled: the longest time inside the window in which
    # no token reached it, and the seconds the garbage collector ran
    times = sorted(t for t in rec.token_times() if t0 <= t <= t0 + seconds)
    out = dict(rec=rec, t0=t0, t_end=t_end, counters=counters,
               programs=programs,
               window_programs=clock.programs,
               window_compile_s=clock.seconds,
               longest_token_gap_s=max(
                   (b - a for a, b in zip([t0] + times, times)),
                   default=None),
               gc_s=sum(e - s for s, e in gc_spans if e is not None))
    return out, xplane


def scoped_programs(root, metrics):
    """The programs whose ops the readers of ``metrics`` read by scope:
    the union of their ``SCOPE_MAP``s."""
    return set().union(*(getattr(common.load_reader(root, m["name"]),
                                 "SCOPE_MAP", ()) for m in metrics))


def place_ops(trace, programs, wanted, lo, hi):
    """Fill the trace's scope map from the compiled text of each program
    in ``wanted`` that the window ran; returns what it took and the ops
    of those programs that it could not place."""
    t = time.monotonic()
    n = 0
    for text in programs.texts(trace.program_names() & set(wanted)):
        trace.add_program_text(text)
        n += 1
    return {"scope_map_s": time.monotonic() - t, "scope_map_programs": n,
            "unplaced_ops": {p: v for p, v in trace.unplaced_ops(lo, hi)
                             .items() if p in wanted}}


# -------------------------------------------------------------- metrics
def read_metrics(root, metrics, run):
    out = {}
    for m in metrics:
        v = common.load_reader(root, m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------- correctness
def compare(sess, rec, limits, control=False):
    """The numbers compared (each beside its limit) and whether the run
    recorded in ``rec`` is correct.  ``control=True`` reads the control
    instead: the reference one precision step below the configuration's,
    in the program's place, on the same sample."""
    ctl = (check.LOWER[sess.spec["dtype"]], sess.params) if control else None
    ok = all(t.outcome == "finished" for t in rec.measured())
    # the sample may hold requests finished past the window too
    picked = check.sample(rec.trails.values(), sess.seed)
    gs = [check.token_gaps(sess.ref, sess.spec, sess.params,
                           sess.traffic.tokens(t.uid, t.prompt_len),
                           t.tokens, 0, len(t.tokens), ctl)
          for t in picked]
    numbers = check.readings(gs, limits)
    ok = ok and bool(gs)
    for v, lim in numbers.values():
        if lim is not None and not v <= lim:
            ok = False
    return ok, {k: {"value": v, "limit": lim}
                for k, (v, lim) in numbers.items()}


# ------------------------------------------------------------- one run
def enable_cache(root):
    """JAX's persistent compilation cache at the fixed ``.jax_cache/`` of
    the checkout, for every program however small."""
    jax.config.update("jax_compilation_cache_dir",
                      str(pathlib.Path(root) / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run_cell(root, workload, seed, seconds, traced, *, require_chip=True,
             process_start=None):
    """Everything one invocation does; returns the result dict (the
    ``check`` key last).  Raises ``NoChip`` before any work if JAX sees
    no accelerator or fewer chips than the cell asks for."""
    process_start = process_start or time.monotonic()
    cell = common.Cell(root, workload)
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell.chips):
        raise NoChip(f"{workload} needs {cell.chips} TPU chip(s); JAX "
                     f"sees {len(devs)} {devs[0].platform} device(s)")
    peaks = common.load_json(PEAKS)
    kind = devs[0].device_kind
    if require_chip and kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}")
    peak = peaks.get(kind, {})
    if require_chip:
        enable_cache(root)

    with CompileClock() as setup_clock:
        sess = Session(cell, seed, seconds)
        sess.setup()
    w, xplane = run_window(sess, seconds, traced, root)
    setup_s = w["t0"] - process_start
    peaks_in_use = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                    for d in devs[:cell.chips]]
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs),
              "memory_peak_bytes": max((p for p in peaks_in_use if p),
                                       default=None)}
    run = Run(cell=cell, seconds=seconds, rec=w["rec"], t0=w["t0"],
              t_end=w["t_end"], setup_s=setup_s,
              setup_compile_s=setup_clock.seconds, counters=w["counters"],
              model=sess.spec, reference=sess.ref,
              value_bytes=np.dtype(sess.cfg.compute_dtype).itemsize,
              peak=peak)
    print(json.dumps({"programs_compiled_in_window": w["window_programs"],
                      "compile_s_in_window": w["window_compile_s"],
                      "setup_compile_s": setup_clock.seconds,
                      "setup_s": setup_s,
                      "longest_token_gap_s": w["longest_token_gap_s"],
                      "gc_s": w["gc_s"]}), file=sys.stderr, flush=True)
    result = {"correct": None, "attempted": len(run.rec.measured()),
              "failed": sum(t.outcome != "finished"
                            for t in run.rec.measured())}
    breakdown = None
    if traced:
        t = time.monotonic()
        run.trace = trace_lib.load(xplane)
        shutil.rmtree(pathlib.Path(root) / TRACE_DIR, ignore_errors=True)
        run.tlo, run.thi = run.trace.window()
        print(json.dumps({"trace_load_s": time.monotonic() - t,
                          **place_ops(run.trace, w["programs"],
                                      scoped_programs(root, cell.per_layer),
                                      run.tlo, run.thi)}),
              file=sys.stderr, flush=True)
        device["busy_s"] = run.trace.busy_ns(run.tlo, run.thi) / 1e9
        device["window_s"] = run.trace_window_s
        breakdown = {"device_ops": run.trace.top_ops(run.tlo, run.thi),
                     "idle_gaps": run.trace.idle_by_host(run.tlo, run.thi)}
        metrics = read_metrics(root, cell.per_layer, run)
    else:
        metrics = read_metrics(root, cell.end_to_end, run)
    t = time.monotonic()
    ok, numbers = compare(sess, run.rec, cell.serve["limits"])
    print(json.dumps({"check_s": time.monotonic() - t}), file=sys.stderr,
          flush=True)
    result["correct"] = bool(ok)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    return result
