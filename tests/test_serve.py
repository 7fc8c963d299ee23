"""Continuous-batching session-server tests: the robustness envelope.

The load-bearing claims, each pinned:

  * the packed tick is FREE — lane k of the batched dispatch bit-matches
    a standalone `SimSession` stepping the same chunks (replay parity),
    and the whole churning population shares ONE compiled executable;
  * nothing raises out of the serve loop — deadline expiry, retry
    exhaustion, shedding, and eviction all terminate sessions with a
    taxonomy reason and a well-formed partial `summary()` (property
    test);
  * overload degrades gracefully — bounded queues shed by policy with
    backpressure signals, sustained pressure enters coalesced degraded
    mode through a hysteresis band and exits it;
  * a mid-serve fault storm heals without dropping healthy sessions —
    the detector fires on packed-lane telemetry, the blocked re-placement
    swaps into every lane with zero recompiles, and every admitted
    session still completes and bit-matches its replay;
  * `SimSession.swap_placement` composes with ragged/`t_mask`-padded
    chunks — swap mid-stream between padded chunks bit-matches the
    two-phase unpadded run.

Everything is seeded and deterministic.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # minimal containers
    from hypothesis_fallback import given, settings, strategies as st

from repro.core import faults, traffic
from repro.core.gateway_controller import ControllerConfig
from repro.core.simulator import (Arch, SimConfig, SimSession,
                                  engine_stats, init_session_states,
                                  reset_engine_stats, selection_tables_jax,
                                  session_tick)
from repro.serve import policies as P
from repro.serve.engine import (SessionServer, _select_lanes,
                                replay_standalone)
from repro.serve.policies import ServerPolicy
from repro.serve.resilience import DegradationDetector, ResiliencePolicy
from repro.serve.scheduler import SessionRequest


def _sim() -> SimConfig:
    return SimConfig().with_arch(Arch.RESIPI)


def _storm_sim() -> SimConfig:
    """Controller pinned at 4 gateways so a dead router is a real capacity
    loss (same calibration as tests/test_resilience.py)."""
    base = _sim()
    return dataclasses.replace(base, ctl=ControllerConfig(
        l_m=base.ctl.l_m, max_gateways=4, min_gateways=4))


def _tr(seed: int, t: int, scale: float = 1.0) -> dict:
    tr = traffic.generate_trace("dedup", t, jax.random.PRNGKey(seed))
    if scale != 1.0:
        for k in ("ext_load", "mem_load", "int_load"):
            tr[k] = jnp.asarray(tr[k]) * scale
    return tr


RECORD_KEYS = ("latency", "power_mw", "g", "energy", "wavelengths")
PARITY_KEYS = ("mean_latency", "mean_power_mw", "mean_energy",
               "mean_gateways", "valid_intervals")


def _assert_replay_parity(sim, server):
    for sess in server.completed:
        ref = replay_standalone(sim, sess)
        mine = sess.summary()
        for k in PARITY_KEYS:
            assert float(ref[k]) == mine[k], (sess.id, k)


def _assert_well_formed(sess):
    s = sess.summary()
    assert s["termination_reason"] in P.TERMINAL_REASONS
    assert s["valid_intervals"] == float(s["served_intervals"])
    for k in ("mean_latency", "mean_power_mw", "mean_energy"):
        assert np.isfinite(s[k])
        if s["served_intervals"] == 0:
            assert s[k] == 0.0           # the additive identity, not a raise


# ---------------------------------------------------------------------------
# Policy / request validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [
    {"lanes": 0}, {"chunk_intervals": 0}, {"retry_backoff_ticks": 0},
    {"throttle_depth": 99}, {"max_queued_intervals": 2},
    {"degrade_hi": 0.2, "degrade_lo": 0.8}, {"degrade_min_priority": 7},
    {"default_deadline_ticks": 0}])
def test_server_policy_rejects_bad_parameters(kw):
    with pytest.raises(ValueError):
        ServerPolicy(**kw)


def test_session_request_rejects_bad_parameters():
    with pytest.raises(ValueError):
        SessionRequest(priority=9)
    with pytest.raises(ValueError):
        SessionRequest(deadline_ticks=0)


# ---------------------------------------------------------------------------
# The packed tick: one executable, bit-transparent lanes
# ---------------------------------------------------------------------------

def test_batched_tick_bit_matches_standalone_sessions():
    """The tentpole invariant at the simulator level: a [B, T] vmapped
    tick's per-lane records are bit-identical to B standalone sessions,
    from ONE scan-body trace."""
    sim = _sim()
    B, T = 3, 6
    trs = [_tr(i, T) for i in range(B)]
    batch = {
        "ext_load": np.stack([np.asarray(t["ext_load"]) for t in trs]),
        "mem_load": np.stack([np.asarray(t["mem_load"]) for t in trs]),
        "int_load": np.stack([np.asarray(t["int_load"]) for t in trs]),
        "ext_frac": np.stack([np.float32(t["ext_frac"]) for t in trs]),
        "t_mask": np.ones((B, T), np.float32),
    }
    states = init_session_states(sim, B)
    tables = selection_tables_jax(sim.cfg)
    reset_engine_stats()
    _, recs, sums = session_tick(states, batch, tables, sim)
    assert engine_stats()["simulate_traces"] == 1
    for i, tr in enumerate(trs):
        ref = SimSession.init(sim).step_chunk(tr)["records"]
        for k in RECORD_KEYS:
            assert np.array_equal(np.asarray(ref[k]), np.asarray(recs[k][i]))


def test_masked_lane_freezes_carry_and_sums_zero():
    sim = _sim()
    B, T = 2, 5
    tr = _tr(0, T)
    batch = {
        "ext_load": np.stack([np.asarray(tr["ext_load"])] * B),
        "mem_load": np.stack([np.asarray(tr["mem_load"])] * B),
        "int_load": np.stack([np.asarray(tr["int_load"])] * B),
        "ext_frac": np.full((B,), np.float32(tr["ext_frac"])),
        "t_mask": np.stack([np.zeros(T), np.ones(T)]).astype(np.float32),
    }
    states = init_session_states(sim, B)
    new_states, _, sums = session_tick(
        states, batch, selection_tables_jax(sim.cfg), sim)
    for a, b in zip(jax.tree.leaves(states), jax.tree.leaves(new_states)):
        assert np.array_equal(np.asarray(a)[0], np.asarray(b)[0]), \
            "masked lane's carry moved"
    assert all(float(v[0]) == 0.0 for v in sums.values())


def test_server_one_executable_across_ticks_and_replay_parity():
    """A churning population (mixed lengths, ragged tails, admissions
    mid-stream) serves end-to-end on ONE compiled executable, and every
    completed session bit-matches its standalone replay."""
    sim = _sim()
    server = SessionServer(sim, ServerPolicy(lanes=3, chunk_intervals=6,
                                             queue_capacity=10))
    reset_engine_stats()
    for i in range(4):
        server.submit(SessionRequest(trace=_tr(i, 5 + 4 * i)))
    server.run(2)
    for i in range(4, 7):                    # late arrivals mid-serve
        server.submit(SessionRequest(trace=_tr(i, 7)))
    server.drain()
    # <= 1: zero if an earlier test already compiled this [B, T] shape,
    # one on a cold cache — never one per tick.
    assert engine_stats()["simulate_traces"] <= 1, engine_stats()
    assert len(server.completed) == 7
    _assert_replay_parity(sim, server)


@pytest.mark.parametrize("admit", [(), (1,), (0, 1, 2)],
                         ids=["none", "one", "all"])
def test_masked_reset_matches_per_lane_set(admit):
    """Admission's one masked select gives, leaf for leaf and bit for bit,
    what one `.at[lane].set(fresh[0])` per admitted lane gave: admitted
    rows equal the fresh state, every other row is untouched."""
    sim = _sim()
    B, T = 3, 6
    trs = [_tr(i, T) for i in range(B)]
    batch = {
        "ext_load": np.stack([np.asarray(t["ext_load"]) for t in trs]),
        "mem_load": np.stack([np.asarray(t["mem_load"]) for t in trs]),
        "int_load": np.stack([np.asarray(t["int_load"]) for t in trs]),
        "ext_frac": np.stack([np.float32(t["ext_frac"]) for t in trs]),
        "t_mask": np.ones((B, T), np.float32),
    }
    fresh = init_session_states(sim, B)
    states, _, _ = session_tick(fresh, batch, selection_tables_jax(sim.cfg),
                                sim)
    take = np.zeros((B,), bool)
    take[list(admit)] = True
    got = _select_lanes(take, fresh, states)
    want = states
    for lane in admit:
        want = jax.tree.map(lambda b, f, lane=lane: b.at[lane].set(f[0]),
                            want, fresh)
    for g, w, s, f in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                          jax.tree.leaves(states), jax.tree.leaves(fresh)):
        g, w, s, f = (np.asarray(x) for x in (g, w, s, f))
        assert g.dtype == w.dtype and np.array_equal(g, w, equal_nan=True)
        for lane in range(B):
            ref = f[0] if take[lane] else s[lane]
            assert np.array_equal(g[lane], ref, equal_nan=True), lane


def test_churning_drain_keeps_host_f32_sums_and_one_select_program():
    """Admissions mid-serve plus one rolled-back retry: every session's
    sums are host float32 scalars, every summary bit-matches its
    standalone replay, and the lane select compiled at most once."""
    sim = _sim()
    fails = {"s_retry": 1}

    def hook(tick, sess):
        if tick >= 1 and fails.get(sess.id, 0) > 0:
            fails[sess.id] -= 1
            return True
        return False

    server = SessionServer(sim, ServerPolicy(
        lanes=3, chunk_intervals=6, queue_capacity=10, retry_limit=2),
        step_fault_hook=hook)
    compiled0 = _select_lanes._cache_size()
    server.submit(SessionRequest(trace=_tr(0, 14), session_id="s_retry"))
    for i in range(1, 4):
        server.submit(SessionRequest(trace=_tr(i, 5 + 4 * i)))
    server.run(2)
    for i in range(4, 7):                    # late arrivals mid-serve
        server.submit(SessionRequest(trace=_tr(i, 7)))
    server.drain()
    assert server.metrics()["retries"] == 1
    assert server.metrics()["admitted"] == 7
    assert len(server.completed) == 7
    for sess in server.completed:
        assert all(type(v) is np.float32 for v in sess.sums.values()), \
            {k: type(v) for k, v in sess.sums.items()}
    _assert_replay_parity(sim, server)
    assert _select_lanes._cache_size() - compiled0 <= 1


def _host(tr: dict) -> dict:
    """The trace a client holds on the host (numpy arrays, as submitted by
    the benchmark's serve runner)."""
    return {k: (v if isinstance(v, str) else np.asarray(v))
            for k, v in tr.items()}


def _assert_host_chunks(chunks):
    for ch in chunks:
        for k, v in ch.items():
            assert not isinstance(v, jax.Array), (k, type(v))


def test_chunks_stay_on_the_host_and_pack_reads_nothing_back():
    """Device-resident and host traces both queue host chunks, serve
    without a single device read in `_pack`, keep host chunks in their
    replay log, and bit-match their standalone replays."""
    sim = _sim()
    server = SessionServer(sim, ServerPolicy(lanes=3, chunk_intervals=6,
                                             queue_capacity=10))
    # 7, 9, 14 and 17 intervals all end in a padded 6-interval chunk.
    sids = []
    for i, t in enumerate((7, 9, 14, 17, 12)):
        tr = _tr(i, t)
        sids.append(server.submit(SessionRequest(
            trace=tr if i % 2 else _host(tr)))["session_id"])
    for sid in sids:
        _assert_host_chunks(server.sessions[sid].pending)
    server.drain()
    assert len(server.completed) == len(sids)
    for sess in server.completed:
        _assert_host_chunks(e["chunk"] for e in sess.served_log)
    assert server.counters["pack_device_reads"] == 0
    assert server.metrics()["pack_device_reads"] == 0
    _assert_replay_parity(sim, server)


def test_device_and_host_fed_sessions_serve_the_same_numbers():
    """The same values reach the same executable: a session fed a device
    trace and one fed its host copy give bit-identical summaries."""
    sim = _sim()
    server = SessionServer(sim, ServerPolicy(lanes=3, chunk_intervals=6,
                                             queue_capacity=10))
    tr = _tr(3, 11)
    a = server.submit(SessionRequest(trace=tr))["session_id"]
    b = server.submit(SessionRequest(trace=_host(tr)))["session_id"]
    server.drain()
    sa, sb = server.sessions[a].summary(), server.sessions[b].summary()
    for k in PARITY_KEYS:
        assert sa[k] == sb[k], k


def test_pack_counts_chunk_arrays_left_on_the_device():
    """`pack_device_reads` counts each chunk array `_pack` had to read back
    from the device, so a zero reading means none was there."""
    sim = _sim()
    server = SessionServer(sim, ServerPolicy(lanes=3, chunk_intervals=6,
                                             queue_capacity=10))
    sid = server.submit(SessionRequest(trace=_tr(0, 12)))["session_id"]
    sess = server.sessions[sid]
    sess.pending[0] = {k: (v if isinstance(v, str) else jnp.asarray(v))
                       for k, v in sess.pending[0].items()}
    server.tick()
    # ext_load, mem_load, int_load, ext_frac, t_mask of one chunk.
    assert server.metrics()["pack_device_reads"] == 5
    server.drain()
    assert server.metrics()["pack_device_reads"] == 5
    _assert_replay_parity(sim, server)


# ---------------------------------------------------------------------------
# Admission control: signals, shedding taxonomy, displacement, memory
# ---------------------------------------------------------------------------

def test_admission_signals_and_queue_full_shed():
    sim = _sim()
    server = SessionServer(sim, ServerPolicy(
        lanes=1, chunk_intervals=4, queue_capacity=2, throttle_depth=1))
    outs = [server.submit(SessionRequest(trace=_tr(i, 4)))
            for i in range(3)]
    assert outs[0]["signal"] == P.ACCEPT
    assert outs[1]["signal"] == P.THROTTLE          # depth crossed throttle
    assert outs[2]["signal"] == P.SHED
    assert outs[2]["reason"] == P.SHED_QUEUE_FULL
    shed = server.sessions[outs[2]["session_id"]]
    assert shed.termination_reason == P.SHED_QUEUE_FULL
    _assert_well_formed(shed)
    assert server.metrics()["shed_queue_full"] == 1


def test_premium_displaces_queued_batch_work():
    sim = _sim()
    server = SessionServer(sim, ServerPolicy(
        lanes=1, chunk_intervals=4, queue_capacity=2))
    ids = [server.submit(SessionRequest(
        trace=_tr(i, 4), priority=P.PRIORITY_BATCH))["session_id"]
        for i in range(2)]
    out = server.submit(SessionRequest(trace=_tr(9, 4),
                                       priority=P.PRIORITY_PREMIUM))
    assert out["signal"] in (P.ACCEPT, P.THROTTLE)
    # The youngest batch session was displaced; the premium one is queued.
    victim = server.sessions[ids[1]]
    assert victim.termination_reason == P.SHED_QUEUE_FULL
    assert server.metrics()["displaced"] == 1
    assert any(s.priority == P.PRIORITY_PREMIUM for s in server.queue)
    # An equal-priority submission cannot displace — it sheds instead.
    out2 = server.submit(SessionRequest(trace=_tr(10, 4),
                                        priority=P.PRIORITY_BATCH))
    assert out2["signal"] == P.SHED


def test_memory_budget_sheds_by_queued_intervals():
    sim = _sim()
    server = SessionServer(sim, ServerPolicy(
        lanes=1, chunk_intervals=4, queue_capacity=10,
        max_queued_intervals=8))
    a = server.submit(SessionRequest(trace=_tr(0, 8)))
    assert a["signal"] == P.ACCEPT
    b = server.submit(SessionRequest(trace=_tr(1, 8)))   # 16 > 8: refused
    assert b["signal"] == P.SHED and b["reason"] == P.SHED_MEMORY
    assert server.metrics()["shed_memory"] == 1
    _assert_well_formed(server.sessions[b["session_id"]])


# ---------------------------------------------------------------------------
# Deadlines: queued and mid-stream expiry with partial summaries
# ---------------------------------------------------------------------------

def test_deadline_expires_queued_and_running_sessions():
    sim = _sim()
    server = SessionServer(sim, ServerPolicy(
        lanes=1, chunk_intervals=4, queue_capacity=8))
    # One long resident session and two queued behind it, all deadline 2.
    ids = [server.submit(SessionRequest(
        trace=_tr(i, 16), deadline_ticks=2))["session_id"]
        for i in range(3)]
    server.run(4)
    running, q1, q2 = (server.sessions[i] for i in ids)
    # The resident session served 2 chunks then expired mid-stream.
    assert running.termination_reason == P.DEADLINE_EXPIRED
    assert 0 < running.served_intervals < 16
    _assert_well_formed(running)
    # The queued ones expired without serving anything — still well-formed.
    for sess in (q1, q2):
        assert sess.termination_reason == P.DEADLINE_EXPIRED
        assert sess.served_intervals == 0
        _assert_well_formed(sess)
    assert server.metrics()["deadline_expired"] == 3


# ---------------------------------------------------------------------------
# Retry: transient failures roll back, back off, and bound out
# ---------------------------------------------------------------------------

def test_transient_failures_retry_then_bit_match():
    """A lane whose first two step attempts fail retries with backoff and
    completes — and the session STILL bit-matches a clean standalone
    replay (the rollback restored the carry exactly)."""
    sim = _sim()
    fails = {"s_flaky": 2}

    def hook(tick, sess):
        if fails.get(sess.id, 0) > 0:
            fails[sess.id] -= 1
            return True
        return False

    server = SessionServer(
        sim, ServerPolicy(lanes=2, chunk_intervals=4, queue_capacity=4,
                          retry_limit=3),
        step_fault_hook=hook)
    server.submit(SessionRequest(trace=_tr(0, 8), session_id="s_flaky"))
    server.submit(SessionRequest(trace=_tr(1, 8), session_id="s_ok"))
    server.drain()
    m = server.metrics()
    assert m["retries"] == 2
    assert len(server.completed) == 2
    flaky = server.sessions["s_flaky"]
    assert flaky.termination_reason == P.COMPLETED
    assert flaky.served_intervals == 8
    _assert_replay_parity(sim, server)


def test_retry_exhaustion_terminates_with_partial_summary():
    sim = _sim()

    def hook(tick, sess):
        return sess.id == "s_dead" and len(sess.served_log) >= 1

    server = SessionServer(
        sim, ServerPolicy(lanes=2, chunk_intervals=4, queue_capacity=4,
                          retry_limit=2, retry_backoff_ticks=1),
        step_fault_hook=hook)
    server.submit(SessionRequest(trace=_tr(0, 12), session_id="s_dead"))
    server.submit(SessionRequest(trace=_tr(1, 12), session_id="s_ok"))
    server.drain()
    dead = server.sessions["s_dead"]
    assert dead.termination_reason == P.RETRY_EXHAUSTED
    assert dead.served_intervals == 4          # first chunk landed
    _assert_well_formed(dead)
    assert server.sessions["s_ok"].termination_reason == P.COMPLETED
    assert server.metrics()["retry_exhausted"] == 1
    _assert_replay_parity(sim, server)         # the healthy one


def test_exponential_backoff_parks_the_lane():
    """Backoff doubles per attempt: with base 2 and retry_limit 3, the
    failing session is parked (masked lane) on the expected ticks."""
    sim = _sim()
    attempts = []

    def hook(tick, sess):
        attempts.append(tick)
        return True

    server = SessionServer(
        sim, ServerPolicy(lanes=1, chunk_intervals=4, queue_capacity=2,
                          retry_limit=3, retry_backoff_ticks=2),
        step_fault_hook=hook)
    server.submit(SessionRequest(trace=_tr(0, 4)))
    server.run(16)
    # Attempts at t, then +2, +4, +8 (exponential), then exhausted.
    assert len(attempts) == 4
    assert [b - a for a, b in zip(attempts, attempts[1:])] == [2, 4, 8]
    assert server.metrics()["retry_exhausted"] == 1


# ---------------------------------------------------------------------------
# Idle eviction (open streams) and streaming feed
# ---------------------------------------------------------------------------

def test_open_stream_feed_close_and_idle_eviction():
    sim = _sim()
    server = SessionServer(sim, ServerPolicy(
        lanes=2, chunk_intervals=4, queue_capacity=4, idle_evict_ticks=3))
    # Stream A: fed, closed, completes. Stream B: starves, evicted.
    a = server.submit(SessionRequest(session_id="a"))
    b = server.submit(SessionRequest(session_id="b"))
    assert a["signal"] == P.ACCEPT and b["signal"] == P.ACCEPT
    server.feed("a", _tr(0, 8))
    server.feed("b", _tr(1, 4))
    server.run(2)
    server.close("a")
    server.run(6)
    assert server.sessions["a"].termination_reason == P.COMPLETED
    evicted = server.sessions["b"]
    assert evicted.termination_reason == P.IDLE_EVICTED
    assert evicted.served_intervals == 4       # what it fed, it got
    _assert_well_formed(evicted)
    assert server.metrics()["idle_evicted"] == 1


# ---------------------------------------------------------------------------
# Graceful degradation: hysteresis band + chunk coalescing
# ---------------------------------------------------------------------------

def test_degraded_mode_enters_coalesces_sheds_and_exits():
    sim = _sim()
    server = SessionServer(sim, ServerPolicy(
        lanes=2, chunk_intervals=4, queue_capacity=4, degrade_hi=0.5,
        degrade_lo=0.25, degrade_patience=2, degrade_coalesce=3,
        degrade_min_priority=P.PRIORITY_STANDARD))
    for i in range(6):
        server.submit(SessionRequest(trace=_tr(i, 12)))
    server.run(2)
    assert server.degraded, server.metrics()
    # While degraded: batch-class submissions shed at the door...
    out = server.submit(SessionRequest(trace=_tr(9, 4),
                                       priority=P.PRIORITY_BATCH))
    assert out["signal"] == P.SHED and out["reason"] == P.SHED_PRIORITY
    # ...and ticks coalesce chunks to drain residents faster.
    before = server.metrics()["coalesced_dispatches"]
    server.tick()
    assert server.metrics()["coalesced_dispatches"] > before
    server.drain()
    server.run(2 * 2)          # empty ticks let the hysteresis unlatch
    assert not server.degraded                 # pressure gone: mode exits
    m = server.metrics()
    assert m["degraded_ticks"] > 0 and m["shed_priority"] == 1
    # Degradation never dropped an admitted session.
    assert len(server.completed) == m["admitted"]
    _assert_replay_parity(sim, server)


# ---------------------------------------------------------------------------
# Fault storm mid-serve: heal without dropping healthy sessions
# ---------------------------------------------------------------------------

def test_fault_storm_heals_lanes_without_dropping_sessions():
    sim = _storm_sim()
    policy = ServerPolicy(lanes=2, chunk_intervals=8, queue_capacity=4)
    victims = SessionServer(sim, policy).placement[:2]
    horizon = 24 * 8
    env = faults.FaultInjector(
        [faults.GatewayFault(start=24, position=p) for p in victims],
        horizon)
    server = SessionServer(
        sim, policy, fault_env=env,
        resilience=ResiliencePolicy(threshold_frac=0.10, hysteresis=2,
                                    cooldown=1, search_generations=4,
                                    search_population=6))
    reset_engine_stats()
    for i in range(2):
        server.submit(SessionRequest(trace=_tr(i, 64, scale=2.0)))
    server.drain()
    m = server.metrics()
    # The storm was detected and healed off the dead routers, live.
    assert m["heals"] >= 1
    assert not (set(server.placement) & set(victims)), server.placement
    assert m["total_pcm_nj"] > 0.0
    # No healthy session dropped: everything admitted completed in full.
    assert len(server.completed) == 2
    assert all(s.served_intervals == 64 for s in server.completed)
    # Post-heal telemetry re-entered the band (availability recovered).
    post_heal = [e for e in server.events
                 if e.get("healed") is None and e["tick"] >
                 next(ev["tick"] for ev in server.events if ev.get("healed"))]
    assert any(not e["breach"] for e in post_heal)
    # Two executables max (the tick's clean and faulted programs), zero
    # recompiles from the swap.
    assert engine_stats()["simulate_traces"] <= 2, engine_stats()
    # And the storm-crossing sessions still bit-match their replay (same
    # shared frames, same placements, same order).
    _assert_replay_parity(sim, server)


# ---------------------------------------------------------------------------
# Satellite: SimSession.swap_placement under ragged/padded chunks
# ---------------------------------------------------------------------------

def test_swap_placement_between_padded_chunks_bit_matches_two_phase():
    """Swap mid-stream between two t_mask-padded chunks == the equivalent
    two-phase unpadded run (one chunk per phase), bit for bit."""
    sim = _sim()
    tr = _tr(0, 20)
    alt = ((1, 1), (2, 2), (1, 2), (2, 1))

    # Padded-chunk session: 8-interval chunks (last is 4 valid + 4 masked),
    # placement swapped after the second chunk (16 intervals in).
    padded = SimSession.init(sim)
    recs_p = []
    for i, ch in enumerate(traffic.chunk_trace(tr, 8, pad=True)):
        if i == 2:
            padded.swap_placement(alt)
        recs_p.append(padded.step_chunk(ch)["records"])
    cat = jax.tree.map(lambda *xs: jnp.concatenate(xs), *recs_p)

    # Two-phase reference: each phase one unpadded chunk (ext_frac is a
    # 0-d scalar and rides through unsliced).
    def phase(lo, hi):
        return {k: (v[lo:hi] if getattr(v, "ndim", 0) >= 1 else v)
                for k, v in tr.items()}

    ref = SimSession.init(sim)
    recs_a = ref.step_chunk(phase(0, 16))["records"]
    ref.swap_placement(alt)
    recs_b = ref.step_chunk(phase(16, 20))["records"]

    valid = np.concatenate([np.ones(16, bool), np.ones(4, bool),
                            np.zeros(4, bool)])
    for k in RECORD_KEYS:
        got = np.asarray(cat[k])[valid]
        want = np.concatenate([np.asarray(recs_a[k]), np.asarray(recs_b[k])])
        assert np.array_equal(got, want), f"records[{k}] diverged"
    for k in PARITY_KEYS:
        assert float(padded.summary()[k]) == float(ref.summary()[k]), k
    assert padded.intervals_seen == 20


def test_swap_placement_before_first_chunk_equals_fresh_session():
    sim = _sim()
    tr = _tr(1, 12)
    alt = ((0, 0), (3, 3), (0, 3), (3, 0))
    swapped = SimSession.init(sim)
    swapped.swap_placement(alt)
    fresh = SimSession.init(dataclasses.replace(
        sim, cfg=sim.cfg.with_placement(alt)))
    a = swapped.step_chunk(tr)["records"]
    b = fresh.step_chunk(tr)["records"]
    for k in RECORD_KEYS:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


# ---------------------------------------------------------------------------
# Detector extraction: ResilienceRuntime semantics preserved
# ---------------------------------------------------------------------------

def test_degradation_detector_threshold_hysteresis_cooldown():
    det = DegradationDetector(ResiliencePolicy(
        threshold_frac=0.10, hysteresis=2, cooldown=2))
    assert det.update(100.0)["breach"] is False      # seeds the baseline
    assert det.update(105.0)["breach"] is False      # in band: EWMA tracks
    assert det.update(130.0) == {
        "latency": 130.0, "baseline": det.baseline, "breach": True,
        "fire": False}
    out = det.update(130.0)
    assert out["breach"] and out["fire"]             # hysteresis met
    assert det.update(130.0)["fire"] is False        # cooldown holds fire
    assert det.update(130.0)["fire"] is False
    assert det.update(130.0)["fire"]                 # cooldown elapsed
    # Baseline froze through the whole breach run.
    assert det.baseline == pytest.approx(101.25)


# ---------------------------------------------------------------------------
# Property: the loop never raises; every ending is taxonomized + summary
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=1, max_value=5),
       st.integers(min_value=0, max_value=3),
       st.integers(min_value=1, max_value=4),
       st.integers(min_value=0, max_value=6),
       st.integers(min_value=0, max_value=1 << 16))
def test_property_every_session_ends_well_formed(
        n_sessions, queue_capacity, deadline, fail_mod, seed):
    """Whatever the arrival mix, deadlines, queue bound, and transient
    failure pattern: tick()/drain() never raise, every session ends with
    a taxonomy reason, and every summary is well-formed with
    valid_intervals == what was actually served."""
    rng = np.random.default_rng(seed)

    def hook(tick, sess):
        return fail_mod > 0 and (tick + hash(sess.id)) % (fail_mod + 2) == 0

    # Fixed lanes/chunk so every example reuses one compiled executable.
    server = SessionServer(
        _sim(), ServerPolicy(lanes=2, chunk_intervals=4,
                             queue_capacity=queue_capacity,
                             retry_limit=2, retry_backoff_ticks=1,
                             default_deadline_ticks=deadline),
        step_fault_hook=hook)
    for i in range(n_sessions):
        t = int(rng.integers(1, 10))
        server.submit(SessionRequest(trace=_tr(int(rng.integers(99)), t),
                                     priority=int(rng.integers(3))))
    server.drain()
    assert server.sessions_in_flight == 0 and len(server.queue) == 0
    assert len(server.sessions) == n_sessions
    for sess in server.sessions.values():
        assert sess.terminal
        _assert_well_formed(sess)
    m = server.metrics()
    assert m["completed"] + m["deadline_expired"] + m["retry_exhausted"] \
        + m["shed_queue_full"] + m["shed_memory"] + m["shed_priority"] \
        == n_sessions


# ---------------------------------------------------------------------------
# Destination-carrying sessions (PR 9: dest threads through the packed tick)
# ---------------------------------------------------------------------------

def _ring_dest(c: int) -> np.ndarray:
    """Each chiplet sends everything to its ring neighbour — maximally
    far from the uniform matrix the dest-free path assumes."""
    d = np.zeros((c, c), np.float32)
    for i in range(c):
        d[i, (i + 1) % c] = 1.0
    return d


def test_dest_session_completes_and_bit_matches_replay():
    sim = _sim()
    tr = dict(_tr(0, 8), dest=_ring_dest(sim.cfg.n_chiplets))
    server = SessionServer(sim, ServerPolicy(lanes=2, chunk_intervals=4))
    sid = server.submit(SessionRequest(trace=tr))["session_id"]
    server.drain()
    assert server.sessions[sid].status == "completed"
    _assert_replay_parity(sim, server)


def test_dest_session_numbers_differ_from_dest_free():
    sim = _sim()
    plain = SessionServer(sim, ServerPolicy(lanes=1, chunk_intervals=4))
    p = plain.submit(SessionRequest(trace=_tr(0, 8)))["session_id"]
    plain.drain()
    routed = SessionServer(sim, ServerPolicy(lanes=1, chunk_intervals=4))
    r = routed.submit(SessionRequest(
        trace=dict(_tr(0, 8), dest=_ring_dest(sim.cfg.n_chiplets))
    ))["session_id"]
    routed.drain()
    a, b = plain.sessions[p].summary(), routed.sessions[r].summary()
    assert any(a[k] != b[k] for k in PARITY_KEYS)


def test_mixed_dest_and_plain_lanes_both_complete_with_parity():
    """One server, one dest-free and one dest-carrying session: each lane
    group gets its own dispatch, both bit-match their standalone replays,
    and the dest lane leaves the plain lane's numbers untouched."""
    sim = _sim()
    server = SessionServer(sim, ServerPolicy(lanes=3, chunk_intervals=4))
    plain_sid = server.submit(SessionRequest(trace=_tr(1, 8)))["session_id"]
    dest_sid = server.submit(SessionRequest(
        trace=dict(_tr(2, 8), dest=_ring_dest(sim.cfg.n_chiplets))
    ))["session_id"]
    server.drain()
    assert server.sessions[plain_sid].status == "completed"
    assert server.sessions[dest_sid].status == "completed"
    _assert_replay_parity(sim, server)
    ref = SessionServer(sim, ServerPolicy(lanes=3, chunk_intervals=4))
    rid = ref.submit(SessionRequest(trace=_tr(1, 8)))["session_id"]
    ref.drain()
    mine = server.sessions[plain_sid].summary()
    theirs = ref.sessions[rid].summary()
    for k in PARITY_KEYS:
        assert mine[k] == theirs[k], k


def test_batched_dest_matrix_is_rejected():
    sim = _sim()
    tr = dict(_tr(0, 6),
              dest=np.stack([_ring_dest(sim.cfg.n_chiplets)] * 2))
    server = SessionServer(sim, ServerPolicy(lanes=1, chunk_intervals=4))
    with pytest.raises(ValueError, match="batched destination"):
        server.submit(SessionRequest(trace=tr))
