"""Fault-tolerant multi-replica serving (DESIGN.md §14).

Covers the tentpole guarantees:
  * chaos exactness — a seeded ``FaultPlan`` killing one of two replicas
    mid-stream loses no request, and every request's generated stream is
    f32 token-exact vs the same workload on an unperturbed pool (committed
    tokens replayed as forced prefix), across GQA/MLA and async depth 0/1,
    under greedy and (rid,pos)-keyed stochastic sampling;
  * graceful degradation — under over-saturation with admission control on,
    shed requests carry explicit ``REJECTED`` + reason, nothing deadlocks
    (bounded ticks), and every submitted request lands in exactly one of
    results/shed;
  * timeout/retry — a stalled replica's queued requests time out, back off,
    and retry elsewhere; with nowhere to go they are shed at
    ``retry_limit``, never parked forever;
  * elastic join/leave — zero dropped requests across a mid-stream rescale;
  * ``ElasticManager`` decision coverage (data/model axes, ``min_data``
    halt floor, capacity adds) and the pool snapshot counter schema.
"""
import dataclasses

import jax
import pytest

from repro.configs import get_config, scale_down
from repro.distributed.elastic import ClusterState, ElasticManager
from repro.models import model
from repro.serving.config import EngineConfig, PoolConfig
from repro.serving.engine import ServeEngine
from repro.serving.faults import FaultEvent, FaultPlan
from repro.serving.pool import ReplicaPool
from repro.serving.request import Request, State

SIZES = (16, 8)
ENGINE_FAMILIES = ["tiny-toy", "deepseek-v2-236b"]   # GQA and (absorbed) MLA


@pytest.fixture(scope="module", params=ENGINE_FAMILIES)
def family(request):
    cfg = get_config(request.param) if request.param == "tiny-toy" \
        else scale_down(get_config(request.param))
    if cfg.moe is not None:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=16.0))
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = model.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def toy():
    cfg = dataclasses.replace(get_config("tiny-toy"), dtype="float32")
    return cfg, model.init(cfg, jax.random.PRNGKey(0))


def _ecfg(depth, **kw):
    return EngineConfig(max_slots=4, max_len=64, kv_block_size=8,
                        discrete_sizes=SIZES, async_depth=depth,
                        avg_decode_len=4.0, **kw)


def _arrivals(n, stagger=2):
    return [(i // stagger, Request(rid=i,
                                   prompt=list(range(5 + i, 15 + i)),
                                   max_new_tokens=8))
            for i in range(n)]


def _run_pool(cfg, params, ecfg, plan, n=8, pcfg=None, max_ticks=500):
    def mk():
        return ServeEngine(cfg, params, ecfg)
    pool = ReplicaPool([mk(), mk()], pcfg or PoolConfig(replicas=2),
                       fault_plan=plan, virtual_dt=0.01, engine_factory=mk)
    results = pool.run_ticked(_arrivals(n), max_ticks=max_ticks)
    return pool, {rid: tuple(r.generated) for rid, r in results.items()}


# ---------------------------------------------------------------------------
# chaos exactness (tentpole acceptance)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("depth", [0, 1])
def test_chaos_kill_exactness(family, depth):
    """Kill replica 1-of-2 mid-stream: every request completes and the
    generated streams match the unperturbed pool token-for-token — the
    committed prefix replayed on the survivor resumes the exact
    trajectory (greedy sampling depends only on the prefix, and
    per-request f32 outputs are batching-invariant)."""
    cfg, params = family
    ecfg = _ecfg(depth)
    _, base = _run_pool(cfg, params, ecfg, None)
    pool, chaos = _run_pool(cfg, params, ecfg, FaultPlan.parse("kill@3:r1"))
    assert pool.stats.faults_injected == 1
    assert not pool.shed and set(chaos) == set(range(8)), \
        [(r.rid, r.reject_reason) for r in pool.shed]
    assert chaos == base, (cfg.name, depth)
    # the kill must actually have interrupted work: something on replica 1
    # was evacuated and re-entered the dispatch path
    assert pool.stats.redispatched_requests > 0
    assert pool.router.redispatched == pool.stats.redispatched_requests
    assert not pool.router.replicas[1].alive


def test_chaos_kill_exactness_stochastic(toy):
    """Same guarantee under temperature sampling: the packed sampler's keys
    fold (rid, pos) only and both replicas share the engine seed, so the
    replayed positions redraw the identical randomness."""
    cfg, params = toy
    ecfg = _ecfg(1, temperature=0.8, seed=7)
    _, base = _run_pool(cfg, params, ecfg, None)
    pool, chaos = _run_pool(cfg, params, ecfg, FaultPlan.parse("kill@3:r1"))
    assert not pool.shed and chaos == base
    assert pool.stats.redispatched_requests > 0


def test_evacuated_eos_request_not_regenerated(toy):
    """A request whose committed output already holds EOS at kill time is
    finalized by the checkpoint, not re-dispatched — re-running it would
    generate past EOS and break exactness."""
    r = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=6, eos_id=9)
    r.output = [4, 9, 5]          # EOS committed, one §5.3 overshoot token
    r.state = State.DECODE
    folded = r.checkpoint_redispatch()
    assert folded == 0 and r.state == State.FINISHED
    assert r.generated == [4, 9]  # stripped to EOS, overshoot dropped
    assert r.prompt == [1, 2, 3, 4, 9]


# ---------------------------------------------------------------------------
# graceful degradation (SLO admission, bounded, never hangs)
# ---------------------------------------------------------------------------
def test_slo_admission_sheds_explicitly(toy):
    """2x-saturation burst with a backlog cap: the overflow is rejected
    with an explicit reason at submit time, admitted requests all finish,
    and the run is bounded — submitted == completed + shed, no deadlock."""
    cfg, params = toy
    pcfg = PoolConfig(replicas=2, shed_backlog_tokens=30,
                      slo_ttft_ms=500.0)

    def mk():
        return ServeEngine(cfg, params, _ecfg(1))
    pool = ReplicaPool([mk(), mk()], pcfg, virtual_dt=0.01)
    # one burst far above what a 30-token backlog cap admits
    arrivals = [(0, Request(rid=i, prompt=list(range(3 + i, 19 + i)),
                            max_new_tokens=6)) for i in range(12)]
    results = pool.run_ticked(arrivals, max_ticks=400)
    assert pool.stats.ticks < 400, "deadlocked until the deadline"
    assert pool.stats.shed_requests > 0
    assert len(results) + len(pool.shed) == pool.stats.submitted == 12
    for r in pool.shed:
        assert r.state == State.REJECTED and r.reject_reason == "backlog"
    # admitted requests all completed
    assert all(len(r.output) > 0 for r in results.values())


def test_slo_ttft_admission_keeps_p99_within_slo(toy):
    """With a TTFT SLO and the service-rate estimator warmed up, the pool
    under-admits (slo_safety) so completed requests' p99 TTFT respects the
    SLO in virtual time; the overflow is shed with reason ttft_slo."""
    cfg, params = toy
    slo_ms = 80.0
    pcfg = PoolConfig(replicas=2, slo_ttft_ms=slo_ms, slo_safety=0.5)

    def mk():
        return ServeEngine(cfg, params, _ecfg(1))
    pool = ReplicaPool([mk(), mk()], pcfg, virtual_dt=0.01)
    # warm-up: a light wave measures the virtual service rate
    warm = [(0, Request(rid=100 + i, prompt=list(range(4, 12)),
                        max_new_tokens=4)) for i in range(2)]
    pool.run_ticked(warm, max_ticks=100)
    assert pool._rate is not None and pool._rate > 0
    # flood: far more work than slo_ttft_ms of backlog
    flood = [(pool.tick_count, Request(
        rid=i, prompt=list(range(3 + i, 19 + i)), max_new_tokens=6))
        for i in range(16)]
    pool.run_ticked(flood, max_ticks=pool.tick_count + 400)
    shed_flood = [r for r in pool.shed if r.rid < 100]
    assert shed_flood, "2x saturation never tripped admission"
    assert all(r.reject_reason == "ttft_slo" for r in shed_flood)
    done = [r for rid, r in pool.results.items()
            if rid < 100 and r.first_token_at is not None]
    assert done
    ttft = sorted((r.first_token_at - r.arrival) * 1e3 for r in done)
    assert ttft[-1] <= slo_ms, f"admitted p99 TTFT {ttft[-1]:.1f}ms > SLO"
    assert pool.stats.slo_violations == 0


# ---------------------------------------------------------------------------
# timeout / retry-with-backoff
# ---------------------------------------------------------------------------
def test_stall_timeout_retries_on_other_replica(toy):
    """Replica 0 stalls before its first step: its queued requests time
    out, back off, and complete on replica 1 — retries recorded, nothing
    lost."""
    cfg, params = toy
    pcfg = PoolConfig(replicas=2, request_timeout_s=0.05,
                      retry_limit=3, backoff_base_s=0.01)

    def mk():
        return ServeEngine(cfg, params, _ecfg(0))
    pool = ReplicaPool([mk(), mk()], pcfg,
                       fault_plan=FaultPlan.parse("stall@0:r0:10000"),
                       virtual_dt=0.01)
    results = pool.run_ticked(_arrivals(6, stagger=6), max_ticks=300)
    assert len(results) == 6 and not pool.shed
    assert pool.stats.timeouts > 0 and pool.stats.retries > 0
    moved = [r for r in results.values() if r.retries > 0]
    assert moved and all(r.replica == 1 for r in moved)


def test_retry_limit_sheds_never_hangs(toy):
    """Single replica stalled forever: the request cycles timeout -> backoff
    -> re-dispatch until retry_limit, then is shed with an explicit reason
    — bounded, not parked forever."""
    cfg, params = toy
    pcfg = PoolConfig(replicas=1, request_timeout_s=0.03,
                      retry_limit=2, backoff_base_s=0.01)
    pool = ReplicaPool([ServeEngine(cfg, params, _ecfg(0))], pcfg,
                       fault_plan=FaultPlan.parse("stall@0:r0:100000"),
                       virtual_dt=0.01)
    pool.run_ticked([(0, Request(rid=0, prompt=[1, 2, 3, 4],
                                 max_new_tokens=4))], max_ticks=300)
    assert pool.stats.ticks < 300
    assert len(pool.shed) == 1
    assert pool.shed[0].reject_reason == "retry_limit"
    assert pool.shed[0].retries == 3   # initial + retry_limit attempts


# ---------------------------------------------------------------------------
# elastic join / leave (zero dropped requests across a rescale)
# ---------------------------------------------------------------------------
def test_pool_join_leave_zero_drop(toy):
    """Scale up at tick 2, gracefully retire replica 0 at tick 4: every
    request completes token-exact vs an unperturbed pool (the drained
    pipeline commits, the remainder replays its committed prefix)."""
    cfg, params = toy
    ecfg = _ecfg(1)
    _, base = _run_pool(cfg, params, ecfg, None)
    pool, out = _run_pool(cfg, params, ecfg,
                          FaultPlan.parse("join@2,leave@4:r0"))
    assert pool.stats.joins == 1 and pool.stats.leaves == 1
    assert not pool.shed and set(out) == set(range(8))
    assert out == base
    assert len(pool.router.replicas) == 3
    assert not pool.router.replicas[0].alive
    assert pool.elastic.state.data == 2    # 2 + 1 join - 1 leave
    # a graceful leave is planned, not a failure
    assert pool.elastic.state.failed_hosts == 0


def test_leave_refuses_last_replica(toy):
    cfg, params = toy
    pool = ReplicaPool([ServeEngine(cfg, params, _ecfg(0))], PoolConfig())
    assert pool.leave_replica(0) == []
    assert pool.router.replicas[0].alive


def test_all_replicas_dead_sheds_instead_of_hanging(toy):
    cfg, params = toy
    pool = ReplicaPool([ServeEngine(cfg, params, _ecfg(0))], PoolConfig(),
                       virtual_dt=0.01)
    pool.fail_replica(0)
    assert pool.halted    # min_data floor: 1 -> 0 is a halt
    ok = pool.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=2))
    assert not ok and pool.shed[0].reject_reason == "pool_halted"
    assert pool.shed[0].state == State.REJECTED


# ---------------------------------------------------------------------------
# ElasticManager decision coverage (satellite)
# ---------------------------------------------------------------------------
def test_elastic_data_axis_rescale():
    mgr = ElasticManager(ClusterState(data=4, model=2))
    d = mgr.on_failure("data", 1)
    assert d.action == "rescale" and d.new_state.data == 3
    assert mgr.state.data == 3 and mgr.state.failed_hosts == 1


def test_elastic_min_data_halt_floor():
    mgr = ElasticManager(ClusterState(data=2, model=1), min_data=2)
    d = mgr.on_failure("data", 1)
    assert d.action == "halt"
    assert mgr.state.data == 2          # halt does not mutate the state


def test_elastic_model_axis_drops_pod_or_halts():
    mgr = ElasticManager(ClusterState(data=2, model=4, pods=3))
    d = mgr.on_failure("model", 1)
    assert d.action == "rescale" and d.new_state.pods == 2
    solo = ElasticManager(ClusterState(data=2, model=4, pods=1))
    d = solo.on_failure("model", 1)
    assert d.action == "halt" and "TP shard" in d.reason


def test_elastic_on_leave_planned_not_failed():
    mgr = ElasticManager(ClusterState(data=3, model=2), min_data=2)
    d = mgr.on_leave(1)
    assert d.action == "rescale" and mgr.state.data == 2
    assert mgr.state.failed_hosts == 0      # voluntary, not a failure
    assert mgr.on_leave(1).action == "halt"  # min_data floor applies too
    assert mgr.state.data == 2


def test_elastic_on_capacity_scales_up():
    mgr = ElasticManager(ClusterState(data=2, model=2))
    d = mgr.on_capacity(2)
    assert d.action == "rescale" and mgr.state.data == 4


# ---------------------------------------------------------------------------
# FaultPlan determinism + pool snapshot schema (satellites)
# ---------------------------------------------------------------------------
def test_fault_plan_parse_roundtrip():
    plan = FaultPlan.parse("kill@40:r1, stall@10:r0:20, degrade@5:r1:3,"
                           "join@60, leave@80:r0")
    assert len(plan) == 5
    assert plan.events[0] == FaultEvent(tick=5, kind="degrade",
                                        replica=1, arg=3)
    assert FaultPlan.parse(plan.describe()).events == plan.events
    # each event fires exactly once, at-or-after its tick
    assert [e.kind for e in plan.due(10)] == ["degrade", "stall"]
    assert plan.due(10) == []
    with pytest.raises(ValueError):
        FaultPlan.parse("explode@3:r0")


def test_fault_plan_seeded_deterministic():
    a = FaultPlan.seeded(seed=3, n_events=6, horizon=50, n_replicas=2)
    b = FaultPlan.seeded(seed=3, n_events=6, horizon=50, n_replicas=2)
    assert a.events == b.events and len(a) == 6
    assert a.events != FaultPlan.seeded(4, 6, 50, 2).events


def test_pool_snapshot_counter_schema(toy):
    cfg, params = toy
    pool, _ = _run_pool(cfg, params, _ecfg(0),
                        FaultPlan.parse("kill@3:r1"), n=4)
    snap = pool.snapshot()
    for k in ("submitted", "completed", "shed_requests", "retries",
              "redispatched_requests", "redispatched_tokens",
              "slo_violations", "timeouts", "faults_injected", "replicas",
              "service_rate_tok_s"):
        assert k in snap, k
    assert len(snap["replicas"]) == 2
    for rep in snap["replicas"]:
        for k in ("queue_depth", "queued_tokens", "inflight_tokens",
                  "kv_used_frac", "alive"):
            assert k in rep, k
    # engine-side evacuation counters surface in the engine snapshot too
    esnap = pool.router.replicas[1].engine.stats.snapshot()
    assert esnap["evacuated_requests"] == pool.stats.redispatched_requests
    assert esnap["evacuated_tokens"] == pool.stats.redispatched_tokens


def test_run_online_keeps_due_time_through_a_stalled_tick(toy):
    """A request due while a tick runs (here a whole virtual second) keeps
    the time it was due as ``arrival``, so the stall counts in its latency;
    it is scheduled, on the engine's (the pool's) clock, only after."""
    cfg, params = toy
    pool = ReplicaPool([ServeEngine(cfg, params, _ecfg(1))],
                       PoolConfig(replicas=1), virtual_dt=1.0)
    reqs = [Request(rid=i, prompt=list(range(5 + i, 15 + i)),
                    max_new_tokens=8) for i in range(2)]
    done = pool.run_online(reqs, [0.0, 0.25])
    assert done[1].arrival == 0.25
    assert done[0].scheduled_at == 1.0
    assert done[1].scheduled_at >= 2.0
    assert done[1].first_token_at >= done[1].scheduled_at
