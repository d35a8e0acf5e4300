"""Distribution-layer tests.

Multi-device semantics (shard_map collectives, GSPMD lowering) run in
subprocesses so the XLA fake-device flag never leaks into this process
(smoke tests must keep seeing 1 device)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed import checkpoint as ckpt
from repro.distributed.elastic import (ClusterState, ElasticManager,
                                       StragglerMitigator, per_replica_batch)
from repro.models import model

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_collective_matmuls_multi_device():
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collective_matmul import (
            allgather_matmul, matmul_reduce_scatter, matmul_allreduce)
        mesh = jax.make_mesh((8,), ("model",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
        f = jax.jit(jax.shard_map(
            lambda a, b: allgather_matmul(a, b, "model"), mesh=mesh,
            in_specs=(P(None, "model"), P(None, "model")),
            out_specs=P(None, "model")))
        assert float(jnp.abs(f(x, w) - x @ w).max()) < 1e-4
        g = jax.jit(jax.shard_map(
            lambda a, b: matmul_reduce_scatter(a, b, "model"), mesh=mesh,
            in_specs=(P(None, "model"), P("model", None)),
            out_specs=P(None, "model")))
        assert float(jnp.abs(g(x, w) - x @ w).max()) < 1e-4
        h = jax.jit(jax.shard_map(
            lambda a, b: matmul_allreduce(a, b, "model"), mesh=mesh,
            in_specs=(P(None, "model"), P("model", None)),
            out_specs=P(None, None), check_vma=False))
        assert float(jnp.abs(h(x, w) - x @ w).max()) < 1e-4
        print("OK")
    """)
    assert "OK" in out


def test_tp_engine_token_equivalence_subprocess():
    """DESIGN.md §11 smoke under tier-1's single-device run: the shard_map
    TP packed step must be f32 token-exact against tp=1 (the full
    per-family suite lives in tests/test_tp_engine.py and runs in CI's
    tp-host-devices job)."""
    out = run_subprocess("""
        import dataclasses
        import jax, numpy as np
        from repro.configs import get_config
        from repro.models import model
        from repro.serving.engine import ServeEngine
        from repro.serving.request import Request

        cfg = dataclasses.replace(get_config("tiny-toy"), dtype="float32")
        params = model.init(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = [list(map(int, rng.integers(0, cfg.vocab_size,
                                              size=int(n))))
                   for n in rng.integers(3, 12, size=4)]
        outs = {}
        for tp in (1, 2):
            eng = ServeEngine(cfg, params, max_slots=2, max_len=32,
                              discrete_sizes=(16, 8), avg_decode_len=4,
                              tp=tp)
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=i, prompt=list(p), max_new_tokens=3))
            done = eng.run()
            outs[tp] = {r.rid: tuple(r.output) for r in done}
            assert eng.stats.model_dispatches == eng.stats.iterations
            assert eng.stats.host_syncs == eng.stats.iterations
        assert outs[1] == outs[2], (outs[1], outs[2])
        print("OK")
    """, devices=2)
    assert "OK" in out


def _wire_bytes(jaxpr, p: int) -> int:
    """Bytes one shard receives through the collectives of a traced
    program: a ``ppermute`` its operand, an ``all_gather`` the (p-1)/p of
    its result that other shards hold, a ``psum`` a ring all-reduce;
    loop bodies count once per trip."""
    from jax.extend import core as jcore
    total = 0
    for eqn in jaxpr.eqns:
        trips = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jcore.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jcore.Jaxpr):
                    total += trips * _wire_bytes(sub, p)
        ins = sum(a.aval.size * a.aval.dtype.itemsize for a in eqn.invars
                  if hasattr(a.aval, "size"))
        if eqn.primitive.name == "ppermute":
            total += ins
        elif eqn.primitive.name == "all_gather":
            total += sum(a.aval.size * a.aval.dtype.itemsize
                         for a in eqn.outvars) * (p - 1) // p
        elif eqn.primitive.name == "psum":
            total += 2 * ins * (p - 1) // p
    return total


# (arch, layers kept): whole periods of each layer pattern, every mixer
# family's reduction points (GQA, MLA+MoE, Mamba+attention+MoE, mLSTM+sLSTM)
WIRE_CASES = [("qwen3-8b", 2), ("deepseek-v2-236b", 2),
              ("jamba-1.5-large-398b", 8), ("xlstm-1.3b", 2)]


@pytest.mark.parametrize("arch,layers", WIRE_CASES,
                         ids=[a for a, _ in WIRE_CASES])
def test_collective_bytes_model_matches_the_traced_step(arch, layers):
    """``tp.collective_bytes_per_iter`` (``EngineStats.tp_collective_bytes``)
    equals the collectives of the TP packed forward as traced at published
    widths, over an abstract four-way mesh (no devices): at Qwen3-8B's
    T=256 each layer's two row-parallel reductions, in two nano groups, are
    three float32 (128, 1024) ring hops and one bf16 all-gather each."""
    import dataclasses

    from jax.sharding import AbstractMesh, PartitionSpec as P

    from repro.distributed import tp

    cfg = dataclasses.replace(get_config(arch), n_layers=layers)
    t, p = 256, 4

    def body(params, cache, tok, slot, pos, act):
        return model.forward_packed(cfg, params, tok, cache, slot, pos, pos,
                                    act, kv_bucket=512)[0][0]
    fn = tp.shard_map_packed(
        body, AbstractMesh((p,), ("model",)), 2,
        (tp.param_pspecs_tp(cfg), tp.cache_pspecs_tp(cfg)) + (P(),) * 4,
        P())
    vec = jax.ShapeDtypeStruct((t,), jnp.int32)
    traced = jax.make_jaxpr(fn)(
        jax.eval_shape(lambda: model.init(cfg, jax.random.PRNGKey(0))),
        jax.eval_shape(lambda: model.init_cache(cfg, 1, 8, 512)),
        jax.ShapeDtypeStruct((1, t), jnp.int32), vec, vec,
        jax.ShapeDtypeStruct((t,), bool))
    itemsize = jnp.dtype(cfg.dtype).itemsize
    assert _wire_bytes(traced.jaxpr, p) == \
        tp.collective_bytes_per_iter(cfg, t, p, itemsize)
    if arch == "qwen3-8b":
        full = dataclasses.replace(cfg, n_layers=36)
        assert tp.collective_bytes_per_iter(full, t, p, itemsize) == \
            36 * 2 * 2 * 3 * 128 * 1024 * (4 + 2)


@pytest.mark.parametrize("arch", ["tiny-toy", "xlstm-1.3b",
                                  "jamba-1.5-large-398b"])
def test_init_params_tp_matches_host_sharding(arch):
    """Params made directly in the TP layout (no device ever holds the
    whole model) equal the host init placed by ``shard_params_tp``,
    fused-column re-interleave included, and the engine takes them as they
    are: same tokens as from the host-layout params."""
    out = run_subprocess(f"""
        import jax, numpy as np
        from repro.configs import get_config, scale_down
        from repro.distributed import tp
        from repro.launch.mesh import make_tp_mesh
        from repro.models import model
        from repro.serving.engine import ServeEngine
        from repro.serving.request import Request

        cfg = get_config("{arch}")
        cfg = cfg if cfg.name == "tiny-toy" else scale_down(cfg)
        mesh = make_tp_mesh(2)
        key = jax.random.PRNGKey(3)
        # jitted like init_params_tp: eager init differs by f32 ulps in
        # the mamba a_log / dt_bias transcendentals
        host = jax.jit(lambda k: model.init(cfg, k))(key)
        direct = tp.init_params_tp(cfg, key, mesh)
        placed = tp.shard_params_tp(cfg, host, mesh)
        for a, b in zip(jax.tree.leaves(direct), jax.tree.leaves(placed)):
            assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
            assert len(a.sharding.device_set) == 2
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert tp.place_params_tp(cfg, direct, mesh) is direct
        outs = []
        for params in (host, direct):
            eng = ServeEngine(cfg, params, max_slots=2, max_len=32,
                              discrete_sizes=(16, 8), avg_decode_len=4,
                              tp=2)
            eng.submit(Request(rid=0, prompt=[5, 6, 7, 8], max_new_tokens=3))
            outs.append(tuple(eng.run()[0].output))
        assert outs[0] == outs[1], outs
        print("OK")
    """, devices=2)
    assert "OK" in out


def test_compressed_psum_error_feedback():
    out = run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import (
            compressed_psum, compress_state_init, plain_psum)
        mesh = jax.make_mesh((4,), ("pod",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)  # per-pod rows

        def exchange(gs, rs):
            return compressed_psum({"w": gs}, {"w": rs}, "pod")

        f = jax.jit(jax.shard_map(
            exchange, mesh=mesh, in_specs=(P("pod"), P("pod")),
            out_specs=(P("pod"), P("pod")), check_vma=False))
        # accumulated compressed means track the true mean (error feedback)
        true_mean = np.asarray(g).mean(axis=0)
        res = jnp.zeros_like(g)
        acc_c, acc_t = 0.0, 0.0
        for step in range(8):
            out_, new_res = f(g, res)
            res = new_res["w"]
            acc_c += np.asarray(out_["w"])[0]
            acc_t += true_mean
        err = np.abs(acc_c - acc_t).max() / (np.abs(acc_t).max() + 1e-9)
        assert err < 0.05, err       # error feedback keeps drift bounded
        print("OK", err)
    """)
    assert "OK" in out


def test_checkpoint_roundtrip(tmp_path):
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    tree = {"params": params, "step": jnp.asarray(7)}
    ckpt.save(tree, str(tmp_path), 7)
    back, step = ckpt.restore(str(tmp_path), tree)
    assert step == 7
    flat_a = jax.tree.leaves(tree)
    flat_b = jax.tree.leaves(back)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_retention_and_latest(tmp_path):
    tree = {"x": jnp.ones((4,))}
    for s in (10, 20, 30, 40):
        ckpt.save(tree, str(tmp_path), s)
    ckpt.retain(str(tmp_path), keep=2)
    assert ckpt.latest_step(str(tmp_path)) == 40
    with pytest.raises(FileNotFoundError):
        ckpt.restore(str(tmp_path), tree, step=10)


def test_trainer_crash_restart_bitexact(tmp_path):
    """Injected failure + restart == uninterrupted run (deliverable:
    fault-tolerant checkpoint/restart)."""
    from repro.training.data import DataConfig, synthetic_stream
    from repro.training.optimizer import AdamWConfig
    from repro.training.trainer import DriverConfig, TrainConfig, Trainer

    cfg = get_config("tiny-toy")
    tc = TrainConfig(opt=AdamWConfig(lr=1e-3, total_steps=12, warmup_steps=2))
    dconf = DataConfig(batch=2, seq_len=16, vocab_size=cfg.vocab_size, seed=3)

    # uninterrupted reference
    dc_ref = DriverConfig(steps=12, ckpt_dir=str(tmp_path / "ref"),
                          ckpt_every=4)
    ref = Trainer(cfg, tc, dc_ref, seed=1)
    ref.fit(synthetic_stream(dconf))

    # crash at step 7, then restart
    dc = DriverConfig(steps=12, ckpt_dir=str(tmp_path / "ft"), ckpt_every=4,
                      inject_failure_at=7)
    tr = Trainer(cfg, tc, dc, seed=1)
    with pytest.raises(RuntimeError, match="injected failure"):
        tr.fit(synthetic_stream(dconf))
    tr2 = Trainer(cfg, tc, dc, seed=1)           # restores from step 4
    assert tr2.start_step == 4
    stream = synthetic_stream(dconf)
    for _ in range(tr2.start_step):              # deterministic data order
        next(stream)
    tr2.dc.inject_failure_at = None
    tr2.fit(stream)

    a = jax.tree.leaves(ref.params)
    b = jax.tree.leaves(tr2.params)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_elastic_rescale_policy():
    em = ElasticManager(ClusterState(data=16, model=16, pods=2), min_data=4)
    d = em.on_failure("data")
    assert d.action == "rescale" and d.new_state.data == 15
    d = em.on_failure("model")
    assert d.action == "rescale" and d.new_state.pods == 1
    d = em.on_failure("model")
    assert d.action == "halt"
    assert per_replica_batch(256, ClusterState(data=15, model=16)) == 18


def test_elastic_checkpoint_restore_to_new_topology(tmp_path):
    """Save params, restore under different sharding — elastic path."""
    cfg = get_config("tiny-toy")
    params = model.init(cfg, jax.random.PRNGKey(0))
    ckpt.save({"params": params}, str(tmp_path), 1)
    # restore with explicit (single-device) shardings
    dev = jax.devices()[0]
    shardings = jax.tree.map(
        lambda _: jax.sharding.SingleDeviceSharding(dev), {"params": params})
    back, _ = ckpt.restore(str(tmp_path), {"params": params},
                           shardings=shardings)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_straggler_mitigator_shifts_load():
    sm = StragglerMitigator(4, alpha=1.0, max_skew=0.25)
    sm.observe([1.0, 1.0, 1.0, 2.0])     # host 3 is 2× slower
    shares = sm.shares()
    assert shares[3] == min(shares)
    split = sm.split_batch(256, multiple_of=8)
    assert sum(split) == 256
    assert split[3] <= min(split[:3])
    assert all(s % 8 == 0 or i == int(np.argmax(shares))
               for i, s in enumerate(split))
