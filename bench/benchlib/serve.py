"""Drive the program under test: build it, warm it up, and serve a mix.

This is the only module that imports the program (``src/repro``).  It takes
from it the model configuration registry, the engine and its public driving
calls (``submit``, ``scheduler.plan``, ``step``, ``drain``, ``run``), the
request objects it returns, its ``EngineStats`` counters and the TP layout of
the parameters; everything else is the benchmark's own.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from benchlib import trace as trace_lib

TRACE_S = 3.0            # length of the traced part of a --trace 1 window
TRACE_AFTER_S = 1.0      # it starts this long after the window opens


def model_config(conf: dict, ref):
    """The program's config for the published one: its registered entry
    (for the layer pattern), with every field that the configuration's
    reference module maps from the published ``config.json``."""
    from repro.configs import get_config
    return dataclasses.replace(get_config(conf["program_config"]),
                               **ref.program_config(conf))


def make_params(conf: dict, mc, ref, key: jax.Array, tp: int):
    """The served weights, made on the device(s) in one jitted call, sharded
    as the engine's TP step takes them when ``tp > 1``."""
    from repro.models import model
    shardings = None
    if tp > 1:
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.distributed import tp as tp_lib
        from repro.launch.mesh import make_tp_mesh
        mesh = make_tp_mesh(tp)
        shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), tp_lib.param_pspecs_tp(mc),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
    fn = jax.jit(lambda k: ref.program_params(conf, k),
                 out_shardings=shardings)
    want = jax.eval_shape(lambda: model.init(mc, jax.random.PRNGKey(0)))
    got = jax.eval_shape(fn, key)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise SystemExit("the reference's weights do not fit the program's "
                         "parameter tree")
    return fn(key)


def build_engine(conf: dict, mc, params, seed: int):
    from repro.serving.config import EngineConfig
    from repro.serving.engine import ServeEngine
    kw = {k: tuple(v) if isinstance(v, list) else v
          for k, v in conf["engine"].items()}
    return ServeEngine(mc, params, EngineConfig(seed=seed & 0x7FFFFFFF, **kw))


def warm(eng, conf: dict) -> int:
    """Compile every (launch length, KV bucket) program the mix can use,
    through the engine's public calls: one request at a time whose last
    prefill chunk has each launch length at each KV bucket, and whose one
    decode step runs at that bucket.  Returns the requests served."""
    from repro.serving.request import Request
    e = conf["engine"]
    buckets = sorted(e["kv_buckets"])
    n = 0
    for lo, hi in zip([0] + buckets[:-1], buckets):
        for t in sorted(e["discrete_sizes"]):
            plen = lo + t
            if plen + 2 > min(hi, e["max_len"]):
                continue
            eng.submit(Request(rid=-1 - n, prompt=[1 + n % 7] * plen,
                               max_new_tokens=2))
            eng.run()
            n += 1
    return n


def iteration_tokens(plan) -> list:
    """(request, position, sampled) for every real token of a plan."""
    toks = [(r.rid, r.prompt_len + len(r.output) + r.inflight - 1, True)
            for r in plan.decode]
    for c in plan.prefill:
        last = c.req.prompt_len - 1
        toks += [(c.req.rid, p, p == last)
                 for p in range(c.offset, c.offset + c.length)]
    return toks


class Driver:
    """One engine, stepped by the harness, with host spans around each call
    and the tokens of every iteration launched while ``record`` is set."""

    def __init__(self, eng):
        self.eng = eng
        self.record = None

    def once(self):
        """Plan and launch one iteration (or retire one in flight).
        Returns the requests finished, or None when there is nothing to do."""
        eng = self.eng
        with jax.profiler.TraceAnnotation("scheduler.plan"):
            plan = eng.scheduler.plan()
        if plan is None:
            if not eng.in_flight:
                return None
            with jax.profiler.TraceAnnotation("engine.drain"):
                return eng.drain(max_retire=1)
        if self.record is not None:
            self.record.append(iteration_tokens(plan))
        with jax.profiler.TraceAnnotation("engine.step"):
            return eng.step(plan)

    def drain(self):
        with jax.profiler.TraceAnnotation("engine.drain"):
            return self.eng.drain()


class Tracer:
    """Profiles ``TRACE_S`` seconds of the window, starting and ending on an
    empty pipeline so that every traced iteration ran wholly inside it."""

    def __init__(self, drv: Driver, out_dir):
        self.drv, self.out_dir = drv, out_dir
        self.state = "waiting"
        self.iterations: list = []
        self.finished: list = []

    def tick(self, now: float, w0: float) -> None:
        if self.state == "waiting" and now >= w0 + TRACE_AFTER_S:
            self.finished += self.drv.drain()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.out_dir),
                                     profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation(trace_lib.WINDOW)
            self.span.__enter__()
            self.t0 = time.perf_counter()
            self.drv.record = self.iterations
            self.state = "tracing"
        elif self.state == "tracing" and now >= self.t0 + TRACE_S:
            self.finished += self.drv.drain()
            self.span.__exit__(None, None, None)
            self.drv.record = None
            jax.profiler.stop_trace()
            self.state = "done"


def progress(reqs) -> int:
    """Prompt tokens prefilled plus output tokens committed so far."""
    return sum(r.prefill_done + len(r.output) for r in reqs)


def stats(eng) -> dict:
    return {k: v for k, v in eng.stats.snapshot().items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def serve_closed(drv: Driver, items, mix: dict, seconds: float,
                 tracer) -> dict:
    """The offline batch: everything queued at once; the window opens once
    ``ramp_finished`` requests have finished, and lasts ``seconds``."""
    from repro.serving.request import Request
    eng = drv.eng
    reqs = [Request(rid=i, prompt=it.prompt.tolist(), max_new_tokens=it.max_new)
            for i, it in enumerate(items)]
    for r in reqs:
        eng.submit(r)
    finished = []
    while len(finished) < mix["ramp_finished"]:
        got = drv.once()
        if got is None:
            raise SystemExit("the batch ran dry during the ramp")
        finished += got
    s0, p0 = stats(eng), progress(reqs)
    t0 = now = time.perf_counter()
    while now < t0 + seconds:
        got = drv.once()
        if got is None:
            raise SystemExit("the batch ran dry inside the window: queue "
                             "more requests")
        finished += got
        now = time.perf_counter()
        if tracer is not None:
            tracer.tick(now, t0)
    t1 = time.perf_counter()
    p1, s1 = progress(reqs), stats(eng)
    finished += drv.drain()
    if tracer is not None:
        finished += tracer.finished
    return {"t0": t0, "window_s": t1 - t0, "committed": p1 - p0,
            "stats": {k: s1[k] - s0.get(k, 0) for k in s1},
            "finished": finished,
            "attempted": sum(1 for r in reqs if r.prefill_done > 0),
            "failed": 0, "window_requests": []}


def serve_open(drv: Driver, items, mix: dict, seconds: float,
               tracer, rid0: int = 0) -> dict:
    """The open loop: each request is submitted when due and timed from
    then.  The window is the requests due in [ramp_s, ramp_s + seconds);
    arrivals go on until each of them has finished, or ``drain_s`` after the
    window."""
    from repro.serving.request import Request
    eng = drv.eng
    reqs = [Request(rid=rid0 + i, prompt=it.prompt.tolist(),
                    max_new_tokens=it.max_new) for i, it in enumerate(items)]
    origin = time.perf_counter()
    due = np.asarray([origin + it.due for it in items])
    w0, w1 = origin + mix["ramp_s"], origin + mix["ramp_s"] + seconds
    window = [r for r, d in zip(reqs, due) if w0 <= d < w1]
    submitted = np.zeros(len(reqs))
    finished, i, s0, t0, queue = [], 0, None, None, []
    while True:
        now = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.submit"):
            while i < len(reqs) and due[i] <= now:
                reqs[i].arrival = float(due[i])
                submitted[i] = now
                eng.submit(reqs[i])
                i += 1
        if s0 is None and now >= w0:
            s0, t0 = stats(eng), now
        if len(queue) < 2 and now >= (w1 if queue else w0):
            queue.append(len(eng.scheduler.waiting))
        if now >= w1 and all(r.finished_at is not None for r in window):
            break
        if now >= w1 + mix["drain_s"] or (i == len(reqs)
                                          and not eng.in_flight
                                          and not eng.scheduler.active
                                          and not eng.scheduler.waiting):
            break
        if tracer is not None and s0 is not None:
            tracer.tick(now, t0)
        got = drv.once()
        if got is None:
            if i < len(reqs):
                time.sleep(max(0.0, min(due[i] - time.perf_counter(),
                                        0.002)))
            continue
        finished += got
    s1 = stats(eng)
    finished += drv.drain()
    if tracer is not None:
        finished += tracer.finished
    late = [(submitted[r.rid - rid0] - r.arrival) for r in window
            if submitted[r.rid - rid0] > 0]
    return {"t0": t0 if t0 is not None else w0, "window_s": seconds,
            "stats": {k: s1[k] - s0.get(k, 0) for k in s1} if s0 else {},
            "finished": finished, "window_requests": window,
            "drain_end": time.perf_counter(),
            "lateness_p99_ms": float(np.percentile(late, 99) * 1e3)
            if late else 0.0,
            "queue": queue,
            "attempted": len(window),
            "failed": sum(1 for r in window if r.finished_at is None),
            "committed": None}
