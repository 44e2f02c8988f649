"""One run of one cell: set-up, load, window, drain, readings, check.

The path under test is the served one: clients -> ``ServeDriver.submit``
-> ``DiffusionServeEngine`` as ``launch/serve.build_diffusion_engine``
builds it from the serving CLI's options (``max_group`` 8, compaction and
join on, the traffic's seq_len buckets) -> AOT step executors -> decode.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import queue
import shutil
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import check, cost, model, stall, tracing, traffic

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
TRACE_CAP_S = 10.0      # the traced part of a --trace 1 window, at most
TAIL_S = 10.0           # open-loop load scheduled past the window's close
HOST_SPANS = ("admit", "dispatch", "step_wait", "compile")   # the engine's
POLL_S = 0.002          # the open-loop collector's poll


@dataclasses.dataclass
class Record:
    """One request as its client saw it (host perf_counter seconds)."""
    uid: int
    send: traffic.Send
    t_due: float                 # scheduled (open loop) or sent (closed)
    t_sub: float = math.nan
    t_done: float | None = None  # when its handle resolved
    result: object = None
    error: str | None = None
    events: list = dataclasses.field(default_factory=list)

    @property
    def ok(self) -> bool:
        return self.result is not None


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: object
    open_loop: bool
    seconds: float
    t0: float
    t1: float
    setup_s: float
    records: list
    group_steps: float           # engine group steps inside the window
    model: dict
    peaks: dict
    trace: object = None         # TraceView of a --trace 1 run
    drain_end: float = math.nan
    # (perf_counter, true lengths of the request rows) per engine group
    # step, noted as the step completes
    row_steps: list = dataclasses.field(default_factory=list)

    def in_window(self, r: Record) -> bool:
        return self.t0 <= r.t_due < self.t1


@dataclasses.dataclass
class TraceView:
    trace: tracing.Trace
    lo: float                    # traced window on the trace's clock (ns)
    hi: float
    t_lo: float                  # the same window on perf_counter (s)
    t_hi: float

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9


class WindowCounters:
    """Counts JAX's compile events (every program compiled or loaded from
    the persistent cache) and times the garbage collector's passes while
    ``on``."""

    def __init__(self):
        self.on = False
        self.programs = 0
        self.cache_hits = 0
        self.names: dict[str, int] = {}
        self.gc_s: list[float] = []
        self._gc_t0 = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self.on:
            self.gc_s.append(time.perf_counter() - self._gc_t0)

    def close(self):
        gc.callbacks.remove(self._gc)

    def _dur(self, event, duration, **kw):
        if self.on and event == COMPILE_EVENT:
            self.programs += 1
            name = str(kw.get("fun_name", "?"))
            self.names[name] = self.names.get(name, 0) + 1

    def _event(self, event, **kw):
        if self.on and event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_cache(root) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    for every program, however small or fast to compile."""
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _bucket(length: int, edges) -> int:
    for e in edges:
        if length <= e:
            return e
    return length


def _warm(eng, cfg, tr: dict, lens: list[int], params) -> int:
    """Compile every program the traffic can reach before the window:
    the step executor of every (bucket, grid, group size 1..max_group),
    the prior draw of every length the generator can send, the plan
    padding of every NFE mix, and the decode of every finished-row count.
    Each group is admitted and stepped once, then dropped."""
    from repro.diffusion import lm as DLM
    from repro.serving.engine import Request
    nfes = sorted(tr["nfe"])
    by_bucket: dict[int, list[int]] = {}
    for n in lens:
        by_bucket.setdefault(_bucket(n, tr["buckets"]), []).append(n)
    uid, steps = -1, 0
    for s_len, group_lens in sorted(by_bucket.items()):
        todo = list(group_lens)
        pos = 0
        for gi, grid in enumerate(nfes):
            below = [f for f in nfes if f <= grid]
            for r in range(1, eng.max_group + 1):
                reqs = []
                for j in range(r):
                    n = todo[pos % len(todo)]
                    pos += 1
                    nfe = grid if j == 0 else below[(j + gi) % len(below)]
                    reqs.append(Request(uid=uid, seq_len=n, nfe=nfe,
                                        solver=tr["solver"], seed=j))
                    uid -= 1
                for q in reqs:
                    eng.submit(q)
                eng.tick()
                eng.reset()
                steps += 1
        if s_len in todo:
            # a group whose rows all sit at the bucket's length draws its
            # priors in one batched program of its own
            for r in range(1, eng.max_group + 1):
                for j in range(r):
                    eng.submit(Request(uid=uid, seq_len=s_len, nfe=nfes[0],
                                       solver=tr["solver"], seed=j))
                    uid -= 1
                eng.tick()
                eng.reset()
                steps += 1
        while pos < len(todo):                 # lengths not drawn yet
            for j in range(eng.max_group):
                eng.submit(Request(uid=uid, seq_len=todo[pos % len(todo)],
                                   nfe=nfes[0], solver=tr["solver"], seed=j))
                uid -= 1
                pos += 1
            eng.tick()
            eng.reset()
            steps += 1
        # the decode of the rows that finish at one step: every row of a
        # uniform group, or any count of a ragged one
        # uncommitted, as the executors' outputs are: the eager programs'
        # cache keys tell the two apart
        x = jnp.zeros((eng.max_group, s_len, cfg.d_model), jnp.float32)
        for r in range(1, eng.max_group + 1):
            xr = x[:r]
            for n in (range(1, r + 1) if len(nfes) > 1 else (r,)):
                np.asarray(DLM.decode_tokens(params, cfg,
                                             xr[jnp.asarray(list(range(n)))]))
    return steps


def percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank percentile, None of an empty list."""
    if not values:
        return None
    v = sorted(values)
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def latencies(run: Run) -> list[float]:
    """Client latency of every request due in the window: scheduled send to
    handle resolved. A request that failed or never resolved counts as
    slow as the slowest that finished, or as its wait to the drain's end
    if that is longer."""
    due = [r for r in run.records if run.in_window(r)]
    done = [r.t_done - r.t_due for r in due if r.ok and r.t_done is not None]
    worst = max(done, default=0.0)
    return done + [max(worst, run.drain_end - r.t_due) for r in due
                   if not (r.ok and r.t_done is not None)]


@dataclasses.dataclass
class Setup:
    """A cell made ready to serve: weights, engine, every program warm."""
    cell: object
    m: dict                      # model sizes as run
    cfg: object                  # the program's ModelConfig
    params: object               # the benchmark's weights, on the device
    eng: object                  # DiffusionServeEngine
    ref_mod: object              # the configuration's plain reference
    t_seed: int                  # traffic seed
    c_seed: int                  # seed of the check's sample


def prepare(cell, *, seed: int, horizon: float, trace: bool, t_start: float,
            root, rehearsal: dict | None = None) -> Setup:
    """Weights from the seed, the engine the serving CLI builds, and a
    warm-up of every program the traffic of ``horizon`` seconds can reach."""
    from repro.launch import serve
    from repro.obs.trace import Tracer

    tr, conf = cell.traffic, cell.config
    if rehearsal is None:
        log(f"compile_cache {enable_cache(root)}")
    rng = np.random.default_rng(seed)
    w_seed, t_seed, c_seed = (int(x) for x in rng.integers(0, 2 ** 31 - 1,
                                                           size=3))
    ref_mod = cell.reference()
    m = dict(conf["model"], **(rehearsal or {}).get("model", {}))
    cfg = model.program_config(conf, (rehearsal or {}).get("model"))
    params = model.make_weights(cfg, m, ref_mod.init_std, w_seed)
    jax.block_until_ready(params)
    log(f"weights {sum(x.nbytes for x in jax.tree.leaves(params))} bytes "
        f"at {time.perf_counter() - t_start:.3f} s")
    argv = ["--arch", conf["arch"], "--seq-len-buckets",
            ",".join(str(b) for b in tr["buckets"])]
    eng = serve.build_diffusion_engine(serve.make_parser().parse_args(argv),
                                       cfg, params)
    if trace:
        eng.tracer = Tracer(eng.metrics, annotate=True)
    lens = traffic.lengths(tr, traffic.n_requests(tr, horizon))
    n_warm = _warm(eng, cfg, tr, lens, params)
    # what set-up made lives as long as the process: collect it once and
    # keep the collector's later passes off it
    gc.collect()
    gc.freeze()
    log(f"warm-up {n_warm} groups, {len(lens)} lengths, "
        f"{eng.num_executors} executors at "
        f"{time.perf_counter() - t_start:.3f} s")
    return Setup(cell, m, cfg, params, eng, ref_mod, t_seed, c_seed)


@dataclasses.dataclass
class Load:
    """What one stretch of load left: the requests and the window."""
    records: list
    t0: float
    t1: float
    group_steps: float
    programs: int                # compiled or loaded inside the window
    cache_hits: int
    view: object
    drain_end: float
    memory_peak_bytes: object
    stalls: list
    row_steps: list


def _sleep_until(t: float) -> None:
    while True:
        dt = t - time.perf_counter()
        if dt <= 0:
            return
        time.sleep(min(dt, 0.05))


def serve_load(su: Setup, sends: list, *, lead: float, seconds: float,
               trace_dir=None) -> Load:
    """Drive the cell's traffic through a ServeDriver: ``lead`` seconds of
    load, the window of ``seconds``, then the drain of the window's
    requests. Open loop: ``sends`` go out at their offsets, load going on
    through the drain. Closed loop: the clients draw ``sends`` in order
    (cycled) and stop sending when the window closes. With ``trace_dir``
    the first TRACE_CAP_S seconds of the window are traced."""
    from repro.serving.driver import ServeDriver
    from repro.serving.engine import Request

    tr, eng = su.cell.traffic, su.eng
    open_loop = tr["loop"] == "open"
    counter = WindowCounters()
    records: list[Record] = []
    opened: queue.SimpleQueue = queue.SimpleQueue()
    stop_sending, stop_collecting = threading.Event(), threading.Event()
    steps_hist = eng.metrics.get("serve_step_seconds")

    def wait(rec: Record, handle) -> None:
        """A closed-loop client's wait: every step event, then the end."""
        try:
            for _ev in handle:
                rec.events.append(time.perf_counter())
            rec.t_done = time.perf_counter()
            rec.result = handle.result(timeout=0)
        except Exception as e:   # noqa: BLE001 - a failed request is counted
            rec.error = f"{type(e).__name__}: {e}"

    def collect():
        """The open loop's one collector: it polls the handles in flight
        and stamps each when it resolves."""
        live = []
        while True:
            while True:
                try:
                    live.append(opened.get_nowait())
                except queue.Empty:
                    break
            now, still = time.perf_counter(), []
            for rec, h in live:
                if not h.done():
                    still.append((rec, h))
                    continue
                rec.t_done = now
                try:
                    rec.result = h.result(timeout=0)
                except Exception as e:   # noqa: BLE001 - counted as failed
                    rec.error = f"{type(e).__name__}: {e}"
                    rec.t_done = None
            live = still
            if stop_collecting.is_set():
                return
            time.sleep(POLL_S)

    def submit(drv, i: int, s: traffic.Send, t_due: float | None):
        """Send one request; returns its record and handle. ``t_due``
        None: a closed-loop send, due when it is sent."""
        req = Request(uid=i, seq_len=s.seq_len, nfe=s.nfe,
                      solver=tr["solver"], seed=s.seed)
        t_sub = time.perf_counter()
        rec = Record(uid=i, send=s, t_due=t_sub if t_due is None else t_due,
                     t_sub=t_sub)
        records.append(rec)
        return rec, drv.submit(req)

    row_steps: list = []
    tick = eng.tick

    def noted_tick(*, on_step=None, **kw):
        """The engine's tick, noting each group step's request rows through
        the engine's own per-step callback (a retired row still riding an
        uncompacted group is not one)."""
        def note(ev):
            n = ev.row_steps or (ev.n_steps,) * len(ev.uids)
            k = ev.row_k or (ev.k,) * len(ev.uids)
            row_steps.append((time.perf_counter(), tuple(
                ln for ln, a, b in zip(ev.row_seq_lens, k, n) if a <= b)))
            if on_step is not None:
                on_step(ev)
        return tick(on_step=note, **kw)

    eng.tick = noted_tick
    watch = stall.StallWatch().start()
    drv = ServeDriver(eng)
    drv.start()
    t_load = time.perf_counter()
    t0, t1 = t_load + lead, t_load + lead + seconds

    def open_sender():
        for i, s in enumerate(sends):
            due = t_load + s.at_s
            while not stop_sending.is_set():
                dt = due - time.perf_counter()
                if dt <= 0:
                    break
                stop_sending.wait(min(dt, 0.05))
            if stop_sending.is_set():
                return
            opened.put(submit(drv, i, s, due))

    pool_lock = threading.Lock()
    pool = iter(range(10 ** 9))

    def closed_client(c: int, n_clients: int):
        start = t_load + lead * c / n_clients
        while time.perf_counter() < start and not stop_sending.is_set():
            time.sleep(0.01)
        while not stop_sending.is_set():
            with pool_lock:
                i = next(pool)
            wait(*submit(drv, i, sends[i % len(sends)], None))

    if open_loop:
        senders = [threading.Thread(target=open_sender, daemon=True),
                   threading.Thread(target=collect, daemon=True)]
    else:
        n_cl = int(tr["clients"])
        senders = [threading.Thread(target=closed_client, args=(c, n_cl),
                                    daemon=True) for c in range(n_cl)]
    for th in senders:
        th.start()

    _sleep_until(t0)
    steps0 = steps_hist.count
    counter.on = True
    view = None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host spans are TraceMe events
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
        ann.__enter__()
        t_lo = time.perf_counter()
        _sleep_until(min(t1, t_lo + TRACE_CAP_S))
        t_hi = time.perf_counter()
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
    _sleep_until(t1)
    counter.on = False
    counter.close()
    steps1 = steps_hist.count
    if not open_loop:
        stop_sending.set()
    log(f"window {seconds} s: {counter.programs} programs compiled or "
        f"loaded inside it ({counter.cache_hits} from the persistent cache)"
        + (f": {sorted(counter.names.items(), key=lambda kv: -kv[1])[:8]}"
           if counter.names else "")
        + f"; {len(counter.gc_s)} collector passes, longest "
        f"{max(counter.gc_s, default=0.0):.3f} s")

    # drain: every request due before the window closed resolves, or the
    # limit passes
    t_drain = t1 + float(tr["drain_s"])
    while time.perf_counter() < t_drain:
        due = [r for r in records if r.t_due < t1]
        if all(r.t_done is not None or r.error is not None for r in due):
            break
        time.sleep(0.02)
    drain_end = time.perf_counter()
    stop_sending.set()
    for r in list(records):
        if r.t_done is None and r.error is None:
            drv.cancel(r.uid)
    drv.stop()
    del eng.tick
    time.sleep(2 * POLL_S)
    stop_collecting.set()
    for th in senders:
        th.join()
    watch.stop()
    stalls = watch.within(t0, t1)
    log(f"stalls in the window: {watch.summary(stalls)}")
    gc.unfreeze()
    stats = jax.devices()[0].memory_stats() or {}
    if trace_dir is not None:
        tr_data = tracing.read(str(trace_dir))
        lo, hi = tracing.window(tr_data)
        view = TraceView(tr_data, lo, hi, t_lo, t_hi)
    return Load(records, t0, t1, float(steps1 - steps0), counter.programs,
                counter.cache_hits, view, drain_end,
                stats.get("peak_bytes_in_use"), stalls, row_steps)


def check_sample(su: Setup, records: list, control: bool = False) -> dict:
    """Run the reference (and the control) over a sample of the finished
    requests drawn from the seed; the engine must be freed first."""
    tr = su.cell.traffic
    finished = [(r.uid, r.send, r.result) for r in records if r.ok]
    picked = check.sample(finished, int(tr["check_requests"]),
                          np.random.default_rng(su.c_seed))
    pad_to = max(list(tr["buckets"]) + [r.send.seq_len for r in records])
    diff = su.cell.config["diffusion"]
    ref = check.Reference(su.ref_mod, su.params, su.m, diff, pad_to)
    ctl = (check.Reference(su.ref_mod, su.params, su.m, diff, pad_to, "fp8")
           if control else None)
    t_check = time.perf_counter()
    got = check.compare(ref, picked, tr["solver"], ctl)
    log(f"reference check of {got['requests_compared']} requests "
        f"({got['tokens_compared']} tokens) took "
        f"{time.perf_counter() - t_check:.3f} s")
    return got


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, root, rehearsal: dict | None = None,
             control: bool = False) -> dict:
    """One run; returns the result line's object (and prints the numbers
    it compared on standard error). With ``control`` the tokens compared
    are the control's, put in the program's place, through the same
    comparison and limit: such a run has to come out not correct."""
    tr = cell.traffic
    dev = jax.devices()[0]
    lead = float(tr["lead_s"])
    su = prepare(cell, seed=seed, horizon=lead + seconds + TAIL_S,
                 trace=trace, t_start=t_start, root=root, rehearsal=rehearsal)
    sends = traffic.schedule(tr, su.t_seed, lead + seconds + TAIL_S)
    trace_dir = root / ".bench_trace" if trace else None
    ld = serve_load(su, sends, lead=lead, seconds=seconds, trace_dir=trace_dir)
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    open_loop = tr["loop"] == "open"
    run = Run(cell=cell, open_loop=open_loop, seconds=seconds, t0=ld.t0,
              t1=ld.t1, setup_s=ld.t0 - t_start, records=ld.records,
              group_steps=ld.group_steps, model=su.m,
              peaks=cost.peaks(dev.device_kind) if rehearsal is None else {},
              trace=ld.view, drain_end=ld.drain_end, row_steps=ld.row_steps)
    in_win = [r for r in ld.records if run.in_window(r)]
    late = [r.t_sub - r.t_due for r in in_win] if open_loop else [0.0]
    log(f"generator lateness p99 {percentile(late, 99) or 0.0:.6f} s over "
        f"{len(in_win)} requests")
    failed = [r for r in in_win if not r.ok]
    errors = [r.error for r in in_win if r.error]
    if errors:
        log(f"request errors: {errors[:3]}")

    metrics = {}
    for ent in (cell.per_layer if trace else cell.end_to_end):
        val = cell.reader(ent["name"])(run)
        if val is not None:
            metrics[ent["name"]] = {"value": val, "unit": ent["unit"]}

    # the check: the program's state goes first, then the reference runs
    su.eng = None
    gc.collect()
    got = check_sample(su, ld.records, control=control)
    limit = cell.limits["max_logit_gap"]
    if control:
        log(f"control run: the program's own max_logit_gap "
            f"{got['max_logit_gap']!r} is not compared")
    gap = got["control_max_logit_gap" if control else "max_logit_gap"]
    compared = {"max_logit_gap": {"value": gap, "limit": limit}}
    ok = (got["requests_compared"] > 0 and gap <= limit
          and not errors and all(r.t_done is not None for r in in_win))
    for name, v in compared.items():
        log(f"compared {name} {v['value']!r} limit {v['limit']!r}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": ld.memory_peak_bytes}
    out = {"correct": ok, "attempted": len(in_win), "failed": len(failed),
           "metrics": metrics, "device": device}
    view = ld.view
    if view is not None:
        ops = [e for evs in view.trace.device_ops.values() for e in evs]
        n_dev = max(1, len(view.trace.device_ops))
        busy = sum(tracing.busy_ns(evs, view.lo, view.hi)
                   for evs in view.trace.device_ops.values()) / n_dev
        device["busy_s"] = busy * 1e-9
        device["window_s"] = view.window_s
        out["breakdown"] = breakdown(view, ops, ld.records)
    out["compared"] = compared
    return out


def breakdown(view: TraceView, ops, records) -> dict:
    """The device operations that took most time (leaf operations: a
    loop's own event spans its body's), and the longest idle gaps of the
    first device, each named by the engine span the host was in, else
    ``no_request`` when no request was in flight, else ``unattributed``."""
    top = tracing.top_ops([e for e in ops if not tracing.is_container(e)],
                          view.lo, view.hi, 10)
    gaps = []
    if view.trace.device_ops:
        first = sorted(view.trace.device_ops)[0]
        g = tracing.gaps(view.trace.device_ops[first], view.lo, view.hi)
        g = sorted(g, key=lambda ab: ab[0] - ab[1])[:10]
        for a, b in g:
            name = tracing.attribute((a, b), view.trace.host_spans,
                                     HOST_SPANS)
            if name == "unattributed":
                t = view.t_lo + ((a + b) / 2 - view.lo) * 1e-9
                if not any(r.t_sub <= t and (r.t_done is None or r.t_done > t)
                           for r in records):
                    name = "no_request"
            gaps.append([name, (b - a) * 1e-9])
    return {"device_ops": [[n, s * 1e-9] for n, s in top],
            "idle_gaps": gaps}
