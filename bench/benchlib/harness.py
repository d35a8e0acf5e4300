"""One run of one cell: set up, measure the window, check, report.

``run`` does everything but look for the chip, so the tests can drive it on
the CPU at a small size.
"""
from __future__ import annotations

import gc
import pathlib
import shutil
import sys
import time

import jax

from benchlib import check, serve, spec, traffic, work
from benchlib import trace as trace_lib

TRACE_DIR = spec.ROOT / ".bench_out" / "trace"


class Compiles:
    """Counts programs compiled, and persistent-cache hits and misses."""

    def __init__(self):
        self.n = {"compiles": 0, "cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n["compiles"] += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.n["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.n["cache_misses"] += 1


_COMPILES = None


def compiles() -> Compiles:
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = Compiles()
    return _COMPILES


def setup(conf: dict, ref, seed: int, tp: int, t_start: float):
    """Weights, engine and warm-up, each timed on standard error."""
    t = time.perf_counter()
    _say(f"imports {t - t_start:.3f} s")
    mc = serve.model_config(conf, ref)
    params = serve.make_params(conf, mc, ref, ref.base_key(seed), tp)
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    eng = serve.build_engine(conf, mc, params, seed)
    jax.block_until_ready(eng.cache)
    t2 = time.perf_counter()
    n_warm = serve.warm(eng, conf)
    _say(f"weights {t1 - t:.3f} s, engine {t2 - t1:.3f} s, warm-up "
         f"{time.perf_counter() - t2:.3f} s")
    return eng, n_warm


class Run:
    """What a metric reader sees."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float, control: bool = False,
        keep_trace: str | None = None) -> dict:
    conf, mix = cell.config, cell.traffic
    ref = spec.load_module(spec.BENCH / "configs" / conf["reference"])
    d = ref.dims(conf)
    cc = compiles()
    tp = conf["engine"].get("tp", 1)

    from repro.kernels import ops
    eng, n_warm = setup(conf, ref, seed, tp, t_start)
    items = traffic.make(mix, seed, d["V"], seconds)
    drv = serve.Driver(eng)
    tracer = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer = serve.Tracer(drv, TRACE_DIR)
    c_setup = dict(cc.n)
    serve_fn = {"closed": serve.serve_closed,
                "open": serve.serve_open}[mix["kind"]]
    w = serve_fn(drv, items, mix, seconds, tracer)
    setup_s = w["t0"] - t_start
    c_window = {k: cc.n[k] - c_setup[k] for k in cc.n}
    _say(f"set-up {setup_s:.3f} s: {n_warm} warm-up requests, compiles "
         f"{c_setup['compiles']}, persistent-cache hits "
         f"{c_setup['cache_hits']} misses {c_setup['cache_misses']}; "
         f"kernels {ops.default_impl()}")
    _say(f"window {w['window_s']:.3f} s: compiles {c_window['compiles']}, "
         f"cache hits {c_window['cache_hits']} misses "
         f"{c_window['cache_misses']} (should all be 0); requests "
         f"attempted {w['attempted']}, failed {w['failed']}, finished "
         f"{len(w['finished'])}, in the window {len(w['window_requests'])}")
    if "lateness_p99_ms" in w:
        _say(f"generator lateness p99 {w['lateness_p99_ms']:.3f} ms")
    memory_peak = _memory_peak(devices)

    reduced = None
    if trace:
        files = sorted(TRACE_DIR.glob("**/*.xplane.pb"))
        if not files:
            raise SystemExit("the profiler wrote no trace")
        if keep_trace:
            dst = pathlib.Path(keep_trace)
            dst.mkdir(parents=True, exist_ok=True)
            shutil.copy(files[-1], dst / f"{cell.name}.{seed}.xplane.pb")
        reduced = trace_lib.reduce(str(files[-1]), conf["kernel"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        _say(f"traced {len(tracer.iterations)} iterations over "
             f"{reduced.window_s:.6f} s, device busy {reduced.busy_s:.6f} s")

    # the program's state goes before the reference runs
    finished = w["finished"]
    traced = tracer.iterations if tracer is not None else []
    del eng, drv, tracer
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()
    t_ref = time.perf_counter()
    picked = check.sample(finished, seed, conf["correct"]["min_tokens"],
                          conf["correct"]["max_requests"])
    g = check.gaps(ref, conf, seed, picked, control) if picked else None
    _say(f"reference: {g['requests'] if g else 0} requests, "
         f"{g['tokens'] if g else 0} served tokens, "
         f"{time.perf_counter() - t_ref:.3f} s")
    if g is not None:
        _say(f"widest logit gap (not compared): served "
             f"{g['served']['widest']}"
             + (f", control {g['control']['widest']}" if control else ""))

    # a control run judges the lower precision's picks, put in the
    # program's place, by the same comparison
    judged = g["control" if control else "served"]["mean"] if g else None
    limit = conf["correct"]["mean_logit_gap"]
    checks = {"mean_logit_gap": {"value": judged, "limit": limit}}
    # a configuration whose limit has not been measured yet is never correct
    correct = judged is not None and limit is not None and judged <= limit
    if control and g is not None:
        _say(f"control run: the lower precision's picks are judged; the "
             f"program's own read {g['served']['mean']} (not judged)")

    r = Run(cell=cell, dims=d, chips=cell.chips, setup_s=setup_s,
            window=w, trace=reduced,
            traced_iterations=traced,
            peaks=work.peaks(devices[0].device_kind)
            if devices[0].platform != "cpu" else None,
            itemsize=d["dtype"].itemsize)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(r)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": int(w["attempted"]),
           "failed": int(w["failed"]), "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown()
    if control and g is not None:
        out["program_mean_logit_gap"] = g["served"]["mean"]
    out["checks"] = checks
    return out

