"""Multi-replica serving fleet: the prefix-affinity `Router`.

Covers the placement policy (deterministic scoring, the affinity /
load / SLO trade-offs), session stickiness across elastic drain +
re-join, the 1-replica-fleet ≡ bare-engine identity, zero-loss
`drain_replica` under load, the `SchedulerStats.zero()` in-place reset
regression, and — in a forced-4-device subprocess (same pattern as
test_sharded_serving) — two TP-2 replicas behind the Router streaming
token-identical to one unsharded engine.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import repro.configs as C
from repro.models import build_model
from repro.serving import GenerationEngine, Router
from repro.serving.scheduler import SchedulerStats

KW = dict(max_seq=96, num_slots=4, page_size=8, prefill_chunk=8)


@pytest.fixture(scope="module")
def mp():
    cfg = C.get_smoke_config("qwen25-05b")
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0)), cfg


def _prompts(cfg, n, prefix_len=32, seed=0):
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, (prefix_len,)).astype(np.int32)
    return prefix, [np.concatenate(
        [prefix, rng.integers(0, cfg.vocab_size, (4,)).astype(np.int32)])
        for _ in range(n)]


# ---------------------------------------------------------------- placement

def test_placement_scores_deterministic(mp):
    """Scoring is pure in the fleet state: same request, same scores,
    same argmax — twice in a row, no mutation between calls."""
    m, params, cfg = mp
    router = Router([GenerationEngine(m, params, **KW) for _ in range(2)])
    _, prompts = _prompts(cfg, 1)
    s1 = router.placement_scores(prompts[0], prefix_id="sys")
    s2 = router.placement_scores(prompts[0], prefix_id="sys")
    assert s1 == s2
    assert router.place(prompts[0], prefix_id="sys") \
        == router.place(prompts[0], prefix_id="sys")
    # empty fleet, no affinity: ties break toward the lowest index
    assert router.place(prompts[0], prefix_id="sys") == 0


def test_affinity_beats_load_only_above_threshold(mp):
    """Replica 0 holds the prefix pages but also carries load; replica 1
    is empty. With the resident-page count at or above the threshold the
    affinity term dominates the load penalty (place on 0); raising the
    threshold past the page count suppresses the term and pure
    load-balancing wins (place on 1)."""
    m, params, cfg = mp
    warm = GenerationEngine(m, params, **KW)
    cold = GenerationEngine(m, params, **KW)
    prefix, prompts = _prompts(cfg, 3)
    warm.pin_prefix("sys")                     # sticky: warm run joins it
    warm.submit(prompts[0], 2, prefix_id="sys")
    warm.drain()
    pages = warm.prefix_reuse_pages(prompts[1], "sys")
    assert pages == len(prefix) // KW["page_size"]      # 4 full pages
    warm.submit(prompts[1], 16, prefix_id="sys")        # load, not stepped
    warm.submit(prompts[2], 16, prefix_id="sys")

    low = Router([warm, cold], affinity_threshold=pages)
    assert low.place(prompts[1], prefix_id="sys") == 0
    high = Router([warm, cold], affinity_threshold=pages + 1)
    assert high.place(prompts[1], prefix_id="sys") == 1
    warm.drain()


def test_interactive_avoids_batch_heavy_replica(mp):
    """SLO scoring: an interactive (priority>0) request must not land
    behind a batch backlog even when that replica holds its prefix."""
    m, params, cfg = mp
    warm = GenerationEngine(m, params, **KW)
    cold = GenerationEngine(m, params, **KW)
    _, prompts = _prompts(cfg, 1)
    router = Router([warm, cold])
    warm.pin_prefix("sys")
    router.submit(prompts[0], 2, prefix_id="sys")       # lands on 0 (tie)
    router.drain()
    # pile batch (priority 0) work onto replica 0 through the router so
    # the router's own ledger sees the backlog
    for _ in range(6):
        router.submit(prompts[0], 16, prefix_id="sys")
    assert router.place(prompts[0], prefix_id="sys") == 0   # batch: affinity
    assert router.place(prompts[0], prefix_id="sys",
                        priority=1) == 1                    # interactive
    router.drain()


# ------------------------------------------------- identity + drain / join

def test_one_replica_fleet_matches_bare_engine(mp):
    m, params, cfg = mp
    _, prompts = _prompts(cfg, 4)
    eng = GenerationEngine(m, params, **KW)
    refs = [eng.submit(p, 8, prefix_id="sys") for p in prompts]
    rout = eng.drain()
    want = [list(rout[r]) for r in refs]

    fleet = Router([GenerationEngine(m, params, **KW)])
    rids = [fleet.submit(p, 8, prefix_id="sys") for p in prompts]
    out = fleet.drain()
    assert [list(out[r]) for r in rids] == want


def test_drain_under_load_loses_nothing(mp):
    """`drain_replica` mid-flight: queued requests reroute under their
    original global rids, in-flight ones finish in place, and every
    stream comes back exactly once, byte-equal to bare-engine
    references."""
    m, params, cfg = mp
    _, prompts = _prompts(cfg, 6)
    eng = GenerationEngine(m, params, **KW)
    refs = [eng.submit(p, 8, prefix_id="sys") for p in prompts]
    rout = eng.drain()
    want = [list(rout[r]) for r in refs]

    fleet = Router([GenerationEngine(m, params, **KW) for _ in range(2)])
    # 12 requests > 2 fleets x 4 slots: some must queue
    rids = [fleet.submit(p, 8, prefix_id="sys") for p in prompts * 2]
    for _ in range(2):
        fleet.step()
    fleet.drain_replica(0)
    assert fleet._replicas[0].idle
    out = fleet.drain()
    assert sorted(out) == sorted(rids)          # exactly once, no extras
    assert [list(out[r]) for r in rids] == want + want
    assert fleet.router_stats.drains == 1
    assert fleet.router_stats.reroutes >= 1


def test_session_stickiness_survives_drain_and_rejoin(mp):
    """A session follows its replica until that replica drains, then
    re-homes; re-joining the drained replica must NOT steal the session
    back — its pages now live at the new home."""
    m, params, cfg = mp
    _, prompts = _prompts(cfg, 1)
    fleet = Router([GenerationEngine(m, params, **KW) for _ in range(2)])
    p = prompts[0]
    fleet.submit(p, 4, prefix_id="sys", session_id="alice")
    fleet.drain()
    home = fleet._sessions["alice"]
    i_home = next(i for i, r in enumerate(fleet._replicas) if r is home)
    assert fleet.place(p, session_id="alice") == i_home

    fleet.drain_replica(i_home)
    i_new = fleet.place(p, session_id="alice")
    assert i_new != i_home                      # draining replica avoided
    fleet.submit(p, 4, prefix_id="sys", session_id="alice")
    fleet.drain()
    assert fleet._sessions["alice"] is fleet._replicas[i_new]

    fleet.add_replica(fleet._replicas[i_home])  # re-join, pages warm
    assert fleet.place(p, session_id="alice") == i_new   # stays re-homed


def test_add_remove_replica_guards(mp):
    m, params, cfg = mp
    _, prompts = _prompts(cfg, 1)
    fleet = Router([GenerationEngine(m, params, **KW) for _ in range(2)])
    rid = fleet.submit(prompts[0], 4)           # tie-break: replica 0
    with pytest.raises(RuntimeError, match="not idle"):
        fleet.remove_replica(0)
    while not fleet.idle:                       # finish, but don't collect
        fleet.step()
    fleet.drain_replica(0)                      # already idle: no-op wait
    dropped = fleet.remove_replica(0)
    assert fleet.num_replicas == 1
    with pytest.raises(RuntimeError, match="last replica"):
        fleet.remove_replica(0)
    # the removed replica's finished stream was buffered on removal
    assert rid in fleet.collect()
    assert fleet.add_replica(dropped) == 1
    assert fleet.num_replicas == 2


# ----------------------------------------------------- stats reset (PR 10)

def test_reset_stats_zeroes_in_place(mp):
    """`reset_stats` must zero the live `SchedulerStats` object, not
    replace it: references taken before the reset keep seeing the live
    counters."""
    m, params, cfg = mp
    _, prompts = _prompts(cfg, 2)
    eng = GenerationEngine(m, params, **KW)
    for p in prompts:
        eng.submit(p, 4, prefix_id="sys")
    eng.drain()
    live = eng._scheduler.stats
    assert live.decode_steps > 0
    eng.reset_stats()
    assert eng._scheduler.stats is live         # identity preserved
    assert live.decode_steps == 0 and live.admitted == 0
    eng.submit(prompts[0], 2, prefix_id="sys")
    eng.drain()
    assert live.decode_steps > 0                # reference still live


def test_stats_zero_spares_no_default_fields():
    """The in-place reset only touches counters with declared defaults —
    a subclass binding live state at construction survives `zero()`,
    where the old ``type(stats)()`` rebuild would TypeError."""
    @dataclasses.dataclass
    class BoundStats(SchedulerStats):
        owner: object = dataclasses.field(kw_only=True)   # no default

    s = BoundStats(owner="engine-7")
    s.admitted, s.decode_steps, s.restore_time_s = 3, 11, 0.5
    s.zero()
    assert (s.admitted, s.decode_steps, s.restore_time_s) == (0, 0, 0.0)
    assert s.owner == "engine-7"                # untouched: no default
    with pytest.raises(TypeError):
        type(s)()                               # the rebuild the reset
        #                                         used to do would crash


# ------------------------------------------- forced-4-device sharded fleet

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import json
import jax
import numpy as np
import repro.configs as C
from repro.launch.specs import FleetSpec, ReplicaSpec
from repro.models import build_model
from repro.serving import GenerationEngine

cfg = dataclasses.replace(C.get_smoke_config("qwen25-05b"),
                          num_heads=8, num_kv_heads=4, head_dim=16)
m = build_model(cfg)
params = m.init(jax.random.PRNGKey(0))
out = {"device_count": jax.device_count()}

KW = dict(max_seq=64, num_slots=4, page_size=8, prefill_chunk=4)
rng = np.random.default_rng(0)
prefix = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
prompts = [np.concatenate([prefix,
                           rng.integers(0, cfg.vocab_size, (t,)
                                        ).astype(np.int32)])
           for t in (5, 12, 9, 3)]

ref_eng = GenerationEngine(m, params, **KW)
refs = [ref_eng.submit(p, 10, prefix_id="sys") for p in prompts]
rout = ref_eng.drain()
want = [[int(t) for t in rout[r]] for r in refs]

# two TP-2 replicas: each owns half the forced-4-device pool's devices
fleet = FleetSpec(replicas=2, replica=ReplicaSpec(
    mesh_axis=2, engine_kwargs=KW)).build(m, params)
out["model_axes"] = [s.model_axis for s in fleet.stats()]
out["replica_devices"] = [
    {k: sorted(d.id for d in v) for k, v in r.placement().items()}
    for r in fleet.replicas]
# a disaggregated replica: prefill and decode engines on their own pairs
ctrl = ReplicaSpec(disagg=True, prefill_mesh_axis=2, decode_mesh_axis=2,
                   engine_kwargs=KW).build(m, params, devices=jax.devices())
out["disagg_devices"] = [
    sorted(d.id for d in side.engine.placement()["pools"])
    for side in (ctrl.prefill, ctrl.decode)]
rids = [fleet.submit(p, 10, prefix_id="sys") for p in prompts]
fout = fleet.drain()
out["identical"] = [[int(t) for t in fout[r]] for r in rids] == want
out["spread"] = fleet.router_stats.placements >= len(prompts)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_result():
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], cwd=".",
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_two_tp2_replicas_match_unsharded_engine(sharded_result):
    """Two TP-2 replicas behind the Router stream token-identical to one
    unsharded engine on the forced-4-device host."""
    assert sharded_result["device_count"] == 4
    assert sharded_result["model_axes"] == [2, 2]
    assert sharded_result["identical"]
    assert sharded_result["spread"]


def test_fleet_places_replicas_on_disjoint_devices(sharded_result):
    """`FleetSpec.build` hands each TP-2 replica its own device pair, and
    a disaggregated replica's prefill and decode meshes do not overlap."""
    assert sharded_result["replica_devices"] == [
        {"params": [0, 1], "pools": [0, 1]},
        {"params": [2, 3], "pools": [2, 3]}]
    assert sharded_result["disagg_devices"] == [[0, 1], [2, 3]]
