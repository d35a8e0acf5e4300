"""Chip smoke test: the served path once on TPU, at published model widths.

    python chip_smoke.py              # one chip: Qwen3-4B end to end
    python chip_smoke.py --chips 4    # four chips: tensor parallelism only

One chip.  Qwen3-4B at its published widths (36 layers, d_model 2560,
32/8 heads, vocab 151936, bf16) with random weights from ``--seed`` is
served by ``ServeEngine`` on the single-dispatch packed step
(``async_depth=1``): requests with prompts of 16 to 1500 tokens, so that
prefill is chunked and several KV-length buckets run.  Checks: every
request finishes with the length it asked for, the step holds the Pallas
packed-attention kernel, a teacher-forced ``forward_packed`` with the
Pallas kernel matches the XLA reference (f32 attention) within a bf16
tolerance (with a bf16 slot cache and with an int8 block-table cache),
and a second engine with int8 KV and prefix caching (the int8 and
block-table kernel variants) serves too.

Four chips (``--chips 4``; nothing else runs).  Qwen3-8B, which does not
fit one chip, initialized already sharded and served at tp=4; each
parameter and cache leaf must have shards on all four devices and no
device may hold close to the whole model.  Then Qwen3-4B's first packed
step at tp=4 (wrapped as the engine wraps its TP step) is compared with
tp=1, within the same tolerance.

Every logit comparison also runs once with a planted fault (a mask one
row short, the wrong kv head's int8 scales, the attention all-reduce
dropped), which must exceed the tolerance: a check that cannot fail
proves nothing.

Figures printed are smoke figures, not benchmark results.  The last line
of stdout, printed only when every check passed, is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# one-chip sizes: tests/test_tpu_compile.py compiles this step for v5e and
# checks it fits (8 x 2048 does, 16 x 2048 does not: the step holds a
# second copy of the KV cache)
SLOTS, MAX_LEN = 8, 2048
ENGINE_KW = dict(max_slots=SLOTS, max_len=MAX_LEN, discrete_sizes=(256,),
                 kv_buckets=(512, 1024, 2048), async_depth=1)
# teacher-forced check: two prefill segments packed into one 256-token
# step; block-table mode stores them in 16-row blocks of a shuffled table
REF_SEGMENTS, REF_KV_BUCKET, REF_BLOCK = (192, 64), 512, 16
# bf16 tolerance on logits, relative to the largest reference |logit|:
# eight units of bf16's 2^-8 relative precision.  On v5e the sound
# comparisons read 0.019-0.022 and the planted faults 0.61-1.51, so the
# limit sits between them
LOGIT_RTOL = 2.0 ** -5

_compile_s = 0.0


def _count_compile(event: str, duration: float, **_) -> None:
    global _compile_s
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s += duration


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAIL: {msg}")


def smoke(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def hbm(dev, key: str = "peak_bytes_in_use") -> int:
    return dev.memory_stats()[key]


def gib(n: float) -> str:
    return f"{n / 2**30:.3f} GiB"


def make_requests(vocab: int, seed: int, n: int, hi: int, prefix=()):
    """``n`` requests from ``seed``: prompt lengths log-uniform in
    [16, hi] with both ends present, 16-64 new tokens each."""
    from repro.serving.request import Request
    rng = np.random.default_rng(seed)
    lens = np.exp(rng.uniform(np.log(16), np.log(hi), n)).astype(int)
    lens[:2] = (16, hi)
    return [Request(rid=i, prompt=list(prefix) + [
        int(x) for x in rng.integers(0, vocab, int(p))],
        max_new_tokens=int(rng.integers(16, 65)))
        for i, p in enumerate(lens)]


def serve(cfg, params, ecfg, waves, label):
    """Serve each wave of requests to completion in turn and check every
    request finished with the length it asked for.  Returns (engine,
    lowered text of the first packed step at tp=1)."""
    from repro.serving.engine import ServeEngine
    eng = ServeEngine(cfg, params, ecfg)
    lowered = []
    if eng.tp == 1:
        step = eng._packed_step

        def observed(*args):
            # lowering only (no compile): the program the step runs
            if not lowered:
                lowered.append(step.lower(*args).as_text())
            return step(*args)
        eng._packed_step = observed
    reqs = [r for wave in waves for r in wave]
    want = {r.rid: r.max_new_tokens for r in reqs}
    t0, c0 = time.perf_counter(), _compile_s
    done = []
    for wave in waves:
        for r in wave:
            eng.submit(r)
        done += eng.run()
    wall = time.perf_counter() - t0
    check(len(done) == len(reqs),
          f"{label}: {len(done)}/{len(reqs)} requests finished")
    short = {r.rid: (len(r.output), want[r.rid]) for r in done
             if len(r.output) != want[r.rid]}
    check(not short, f"{label}: output lengths differ {short}")
    st = eng.stats
    check(st.dispatches_per_iter == 1.0 and st.syncs_per_iter == 1.0,
          f"{label}: {st.dispatches_per_iter} dispatches and "
          f"{st.syncs_per_iter} syncs per iteration")
    peak = [hbm(d) for d in jax.devices()]
    smoke(f"{label}: {len(done)}/{len(reqs)} requests, {st.total_tokens} "
          f"tokens ({st.prefill_tokens} prefill + {st.decode_tokens} "
          f"decode) in {st.iterations} iterations; wall {wall:.3f} s, of "
          f"which compile {_compile_s - c0:.3f} s; kv buckets "
          f"{dict(sorted(st.kv_bucket_hist.items()))}; process peak HBM "
          f"per device {[gib(p) for p in peak]}")
    return eng, (lowered[0] if lowered else None)


def release(eng) -> None:
    """Free the engine's device state now (its KV cache is GBs)."""
    for a in jax.tree.leaves((eng.cache, eng._slot_init, eng.cache_len,
                              eng.last_token, eng.accept_len)):
        a.delete()
    gc.collect()


def ref_inputs(cfg, seed, blocks=False):
    """Operands of one packed step that prefills ``REF_SEGMENTS`` (one
    slot each) from position 0: tokens, slots, positions, active mask and
    — with ``blocks`` — the flat write rows and a shuffled block table
    (else None, None)."""
    rng = np.random.default_rng(seed + 1)
    t = sum(REF_SEGMENTS)
    slot = np.repeat(np.arange(len(REF_SEGMENTS)), REF_SEGMENTS)
    pos = np.concatenate([np.arange(n) for n in REF_SEGMENTS])
    tok = rng.integers(0, cfg.vocab_size, (1, t))
    dst = bt = None
    if blocks:
        cols = REF_KV_BUCKET // REF_BLOCK
        bt = rng.permutation(len(REF_SEGMENTS) * cols).reshape(-1, cols)
        dst = bt[slot, pos // REF_BLOCK] * REF_BLOCK + pos % REF_BLOCK
    i32 = (lambda a: None if a is None else jnp.asarray(a, jnp.int32))
    return (i32(tok), i32(slot), i32(pos), jnp.ones((t,), bool), i32(dst),
            i32(bt))


def packed_logits(cfg, impl, kv_dtype=None, mesh=None):
    """Jitted teacher-forced ``forward_packed`` -> f32 logits (T, vocab),
    with kernel implementation ``impl`` over a cache of ``kv_dtype``; with
    ``mesh``, wrapped by ``tp.shard_map_packed`` as the engine wraps its
    TP step."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed import tp as tp_lib
    from repro.kernels import ops
    from repro.models import model
    from repro.serving.config import EngineConfig

    def body(params, cache, tok, slot, pos, act, dst, bt):
        ops.set_default_impl(impl)
        try:
            logits, _ = model.forward_packed(
                cfg, params, tok, cache, slot, pos, pos, act,
                kv_bucket=REF_KV_BUCKET, token_dst=dst, block_tables=bt)
        finally:
            ops.set_default_impl(None)
        return logits[0].astype(jnp.float32)
    if mesh is None:
        return jax.jit(body)
    return jax.jit(tp_lib.shard_map_packed(
        body, mesh, EngineConfig().nano,
        (tp_lib.param_pspecs_tp(cfg), tp_lib.cache_pspecs_tp(cfg, kv_dtype))
        + (P(),) * 6, P()))


# planted faults: each must push its logit comparison over the tolerance
def skip_own_key(real, q, k, v, slot, lengths, **kw):
    """a mask one row short"""
    return real(q, k, v, slot, lengths - 1, **kw)


def wrong_head_scales(real, *args, k_scale, v_scale, **kw):
    """the next kv head's int8 scales"""
    return real(*args, k_scale=jnp.roll(k_scale, 1, axis=-1),
                v_scale=jnp.roll(v_scale, 1, axis=-1), **kw)


def no_allreduce(real, out, wo):
    """attention output projection without its all-reduce"""
    return jnp.einsum("thk,hkd->td", out, wo)


@contextlib.contextmanager
def planted(module, name, fault):
    """Trace with ``module.name`` replaced by ``fault`` (which gets the
    real function first)."""
    real = getattr(module, name)
    setattr(module, name, functools.partial(fault, real))
    try:
        yield
    finally:
        setattr(module, name, real)


def logit_gap(got, ref, label) -> float:
    """Print and return the max |logit| gap relative to the largest
    reference |logit|."""
    got, ref = np.asarray(got), np.asarray(ref)
    check(np.isfinite(got).all() and np.isfinite(ref).all(),
          f"{label}: non-finite logits")
    gap = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    smoke(f"{label}: max |logit gap| {gap:.6f} over max |logit| "
          f"{scale:.6f} = {gap / scale:.6f} (tolerance {LOGIT_RTOL}); "
          f"argmax agreement {agree:.4f} over {got.shape[0]} tokens")
    return gap / scale


def check_gaps(sound: dict, faulty: dict) -> None:
    """Sound comparisons within the tolerance, planted faults over it
    (checked only after every gap is printed)."""
    for label, rel in sound.items():
        check(rel <= LOGIT_RTOL, f"{label}: logit gap {rel} of max |logit| "
              f"over {LOGIT_RTOL}")
    for label, rel in faulty.items():
        check(rel > LOGIT_RTOL, f"{label}: a planted fault stays within "
              f"the tolerance ({rel} <= {LOGIT_RTOL}), so the check has no "
              f"power")


def pallas_vs_ref(cfg, params, seed: int) -> None:
    """Teacher-forced Pallas kernel against the XLA reference (f32
    attention), over a bf16 slot cache and an int8 block-table cache, each
    also with its planted fault."""
    from repro.kernels import ops
    from repro.models import model
    sound, faulty = {}, {}
    for layout, kv_dtype, fault in (("bf16 slot", None, skip_own_key),
                                    ("int8 block-table", "int8",
                                     wrong_head_scales)):
        inputs = ref_inputs(cfg, seed, blocks=kv_dtype is not None)
        cache = model.init_cache(cfg, 1, len(REF_SEGMENTS), REF_KV_BUCKET,
                                 kv_dtype)

        def run(impl):
            return packed_logits(cfg, impl, kv_dtype)(params, cache, *inputs)
        ref = run("ref")
        label = f"pallas vs ref, {layout} cache"
        sound[label] = logit_gap(run("pallas"), ref, label)
        label = f"planted fault ({fault.__doc__}) vs ref, {layout} cache"
        with planted(ops, "packed_attention", fault):
            faulty[label] = logit_gap(run("pallas"), ref, label)
        del ref, cache
    check_gaps(sound, faulty)


def one_chip(seed: int) -> None:
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import model
    from repro.serving.config import EngineConfig

    cfg = get_config("qwen3-4b")
    check((cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.vocab_size, cfg.dtype) == (36, 2560, 32, 8, 151936,
                                          "bfloat16"),
          f"qwen3-4b is not at its published widths: {cfg}")
    # the packed path must never enter these: some of their shapes route
    # to the XLA reference without saying so
    for name in ("flash_attention", "decode_attention"):
        def forbidden(*a, _name=name, **k):
            raise AssertionError(f"served path entered ops.{_name}")
        setattr(ops, name, forbidden)

    params = jax.jit(lambda k: model.init(cfg, k))(jax.random.PRNGKey(seed))
    n_params = sum(a.size for a in jax.tree.leaves(params))
    base = hbm(jax.devices()[0], "bytes_in_use")
    smoke(f"qwen3-4b: {n_params} parameters, {gib(base)} on the device")

    pallas_vs_ref(cfg, params, seed)

    reqs = make_requests(cfg.vocab_size, seed, n=12, hi=1500)
    eng, text = serve(cfg, params, EngineConfig(seed=seed, **ENGINE_KW),
                      [reqs], "bf16 engine")
    check(text is not None and "tpu_custom_call" in text
          and "packed_attention" in text,
          "the packed step holds no Pallas packed-attention kernel")
    smoke(f"packed step: {text.count('tpu_custom_call')} tpu_custom_call "
          f"(Pallas) in its lowered program")
    release(eng)
    del eng
    left = hbm(jax.devices()[0], "bytes_in_use")
    check(left <= base + 2**28, f"first engine not released: {gib(left)} "
          f"in use, {gib(base)} before it")

    # int8 KV + prefix caching: int8 and block-table kernel variants.  A
    # first request publishes the shared 512-token prefix, the rest reuse it
    prefix = [int(x) for x in np.random.default_rng(seed + 2).integers(
        0, cfg.vocab_size, 512)]
    reqs = make_requests(cfg.vocab_size, seed + 3, n=8, hi=900,
                         prefix=prefix)
    eng, text = serve(cfg, params, EngineConfig(
        seed=seed, kv_dtype="int8", prefix_caching=True, **ENGINE_KW),
        [reqs[:1], reqs[1:]], "int8 + prefix-caching engine")
    check(text is not None and "tpu_custom_call" in text,
          "the int8 packed step holds no Pallas kernel")
    check(jax.tree.leaves(eng.cache)[0].dtype == jnp.int8,
          "int8 engine cache is not int8")
    hits = eng.kv.stats.prefix_hit_tokens
    check(hits > 0, "prefix caching served no prompt tokens from shared "
          "blocks")
    smoke(f"int8 + prefix caching: {hits} prompt tokens from shared "
          f"blocks, {eng.kv.stats.cow_copies} CoW copies")
    release(eng)


def four_chips(seed: int) -> None:
    from repro.configs import get_config
    from repro.distributed import tp as tp_lib
    from repro.launch.mesh import make_tp_mesh
    from repro.models import model
    from repro.serving.config import EngineConfig

    mesh = make_tp_mesh(4)
    devs = list(mesh.devices.flat)

    # Qwen3-8B at tp=4, weights sharded from init
    cfg = get_config("qwen3-8b")
    params = tp_lib.init_params_tp(cfg, jax.random.PRNGKey(seed), mesh)
    eng, _ = serve(cfg, params, EngineConfig(seed=seed, tp=4, **ENGINE_KW),
                   [make_requests(cfg.vocab_size, seed, n=12, hi=1500)],
                   "qwen3-8b tp=4 engine")
    check(eng.params is params, "the engine re-placed sharded params")
    full = 0
    per_dev = dict.fromkeys(devs, 0)
    for what, tree in (("param", eng.params), ("cache", eng.cache)):
        for a in jax.tree.leaves(tree):
            full += a.nbytes
            on = {s.device for s in a.addressable_shards}
            check(on == set(devs), f"a {what} leaf {a.shape} has shards on "
                  f"{len(on)} of 4 devices")
            for s in a.addressable_shards:
                per_dev[s.device] += s.data.nbytes
    peak = {d: hbm(d) for d in devs}
    for d in devs:
        smoke(f"device {d.id}: params + cache shards {gib(per_dev[d])} = "
              f"{per_dev[d] / full:.4f} of the whole {gib(full)}; peak "
              f"{gib(peak[d])} = {peak[d] / full:.4f} of it (a quarter "
              f"would be 0.25; embedding and head are replicated)")
        check(peak[d] < 0.5 * full, f"device {d.id} peak {gib(peak[d])} "
              f"is not a share of the model ({gib(full)})")
    release(eng)
    del eng, params
    gc.collect()

    # Qwen3-4B first packed step: tp=4 against tp=1
    cfg = get_config("qwen3-4b")
    key = jax.random.PRNGKey(seed)
    inputs = ref_inputs(cfg, seed)
    params = tp_lib.init_params_tp(cfg, key, mesh)
    cache = tp_lib.init_cache_tp(cfg, len(REF_SEGMENTS), REF_KV_BUCKET, mesh)

    def tp4():
        return packed_logits(cfg, "pallas", mesh=mesh)(params, cache, *inputs)
    got = tp4()
    with planted(tp_lib, "out_project", no_allreduce):
        bad = tp4()
    del params, cache
    params = jax.jit(lambda k: model.init(cfg, k))(key)
    cache = model.init_cache(cfg, 1, len(REF_SEGMENTS), REF_KV_BUCKET)
    ref = packed_logits(cfg, "pallas")(params, cache, *inputs)
    label = f"planted fault ({no_allreduce.__doc__}), tp=4 vs tp=1"
    check_gaps({"qwen3-4b tp=4 vs tp=1": logit_gap(got, ref, "qwen3-4b "
                                                   "tp=4 vs tp=1")},
               {label: logit_gap(bad, ref, label)})


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX reports platform {devs[0].platform!r}")
    check(len(devs) >= args.chips,
          f"--chips {args.chips} but {len(devs)} devices")
    smoke(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")

    from repro.kernels import ops
    from repro.launch.runtime import enable_compile_cache
    check(ops.default_impl() == "pallas",
          f"kernel implementation resolves to {ops.default_impl()!r} "
          f"(REPRO_KERNEL_IMPL={os.environ.get('REPRO_KERNEL_IMPL')!r})")
    smoke(f"compile cache: {enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_count_compile)

    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    smoke(f"all phases passed in {time.perf_counter() - t0:.3f} s, of which "
          f"compile {_compile_s:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
