"""A closed loop of clients on one `SessionServer`: each session that
ends is replaced by the client's next one before the next tick, so all
lanes stay busy and the queue never degrades.

Mix keys: `clients`, `apps`, `min_intervals` / `max_intervals` (session
lengths, every whole length between), `priority_weights` and
`priority_block`, `policy` (ServerPolicy fields), `pool_sessions`,
`warmup_ticks` and `sample_sessions` (sessions completed in the window
compared with the plain reference, drawn from the seed, plus the
longest).

The session pool is stratified so that the seed changes the order of
the work and not its amount: it is drawn in blocks that each hold every
(app, length) pair once, in an order drawn from the seed, and the
priorities in blocks of `priority_block` that hold each class in
proportion to its weight. The traces' values come from the seed's keys.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

import harness

POOL_STREAM = 0x5E55


def stratified(rng, block: np.ndarray, n: int) -> np.ndarray:
    """n draws: whole blocks, each a permutation of `block`."""
    reps = -(-n // len(block))
    return np.concatenate([rng.permutation(block) for _ in range(reps)])[:n]


def priority_block(weights, size: int) -> np.ndarray:
    counts = np.rint(np.asarray(weights) * size).astype(int)
    if counts.sum() != size:
        raise ValueError(f"priority weights {weights} do not split a block "
                         f"of {size} into whole sessions")
    return np.repeat(np.arange(len(weights)), counts)


class Runner:
    def __init__(self, spec: dict, devices, seed: int):
        import jax
        from repro.core import traffic
        from repro.serve.engine import SessionServer
        from repro.serve.policies import ServerPolicy
        from repro.serve.scheduler import SessionRequest

        self.config, self.mix = spec["config"], spec["mix"]
        self.ref = spec["reference"]
        self.jax, self.traffic = jax, traffic
        self.SessionRequest = SessionRequest
        self.sim = harness.sim_config(self.config)
        self.server = SessionServer(self.sim,
                                    ServerPolicy(**self.mix.get("policy", {})))
        self.root_key = harness.seed_key_words(seed)
        mix = self.mix
        rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
        n = mix["pool_sessions"]
        self.apps = mix["apps"]
        self.tmax = mix["max_intervals"]
        lengths = np.arange(mix["min_intervals"], self.tmax + 1)
        pairs = stratified(rng, np.arange(len(self.apps) * len(lengths)), n)
        self.pool_app = pairs // len(lengths)
        self.pool_len = lengths[pairs % len(lengths)]
        self.pool_prio = stratified(
            rng, priority_block(mix["priority_weights"],
                                mix["priority_block"]), n)
        self.sample_rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), 2]))
        self.owner: Dict[str, tuple] = {}    # session id -> (pool idx, T)
        self.next_pool = 0
        self.ticks: List[tuple] = []

    def pool_keys(self):
        jnp = self.jax.numpy
        base = jnp.asarray(self.root_key, dtype=jnp.uint32)
        return self.jax.random.split(
            self.jax.random.fold_in(base, POOL_STREAM), len(self.pool_app))

    def make_pool(self) -> None:
        """Every pool trace, one batched generator call per app over all
        the pool's keys (a shape that does not depend on the seed)."""
        jax = self.jax
        keys = self.pool_keys()
        cfg = self.sim.cfg
        self.pool = {}
        self.pool_frac = np.zeros(len(self.pool_app), np.float32)
        for a, app in enumerate(self.apps):
            spec = self.traffic.ParsecSpec(app=app, n_intervals=self.tmax)

            def one(k, spec=spec):
                tr = self.traffic.generate(spec, k, cfg, jit=False)
                return {key: tr[key] for key in
                        ("ext_load", "mem_load", "int_load", "ext_frac")}
            out = jax.device_get(jax.jit(jax.vmap(one))(keys))
            sel = self.pool_app == a
            for k in ("ext_load", "mem_load", "int_load"):
                if k not in self.pool:
                    self.pool[k] = np.zeros_like(out[k])
                self.pool[k][sel] = out[k][sel]
            self.pool_frac[sel] = out["ext_frac"][sel]

    def request(self, j: int, length: Optional[int] = None):
        t = int(self.pool_len[j]) if length is None else int(length)
        tr = {k: v[j, :t] for k, v in self.pool.items()}
        tr["ext_frac"] = self.pool_frac[j]
        tr["app"] = self.apps[int(self.pool_app[j])]
        return self.SessionRequest(trace=tr,
                                   priority=int(self.pool_prio[j])), t

    def submit_next(self, length: Optional[int] = None) -> None:
        j = self.next_pool % len(self.pool_app)
        self.next_pool += 1
        req, t = self.request(j, length)
        out = self.server.submit(req)
        self.owner[out["session_id"]] = (j, t)

    def _tick(self, prof) -> None:
        s = self.server
        n_done, n_term = len(s.completed), len(s.terminated)
        nd = len(s._dispatch_wall_s)
        traced = prof.active
        a = time.perf_counter()
        with prof.annotate("bench.tick"):
            s.tick()
        b = time.perf_counter()
        with prof.annotate("bench.client"):
            for _ in range(len(s.completed) - n_done
                           + len(s.terminated) - n_term):
                self.submit_next()
        self.ticks.append((a, b, float(sum(s._dispatch_wall_s[nd:])),
                           traced, s.tick_count - 1))

    def setup(self) -> None:
        self.make_pool()
        # Warm-up: one session of every length the mix draws, so every
        # padded final-chunk shape is compiled before the window.
        lengths = list(range(self.mix["min_intervals"], self.tmax + 1))
        clients = self.mix["clients"]
        for t in lengths[:clients]:
            self.submit_next(t)
        rest = lengths[clients:]
        for _ in range(self.mix["warmup_ticks"]):
            n_done = len(self.server.completed)
            self.server.tick()
            for _ in range(len(self.server.completed) - n_done):
                self.submit_next(rest.pop(0) if rest else None)
        self.ticks = []

    def served(self) -> int:
        return int(sum(int(s.sums["valid_intervals"])
                       for s in self.server.sessions.values()))

    def window(self, seconds: float, prof) -> dict:
        self.first_tick = self.server.tick_count
        served0 = self.served()
        t_start = time.perf_counter()
        t_end = t_start
        while t_end - t_start < seconds:
            prof.update(t_end - t_start)
            self._tick(prof)
            t_end = time.perf_counter()
        prof.stop()
        walls = np.asarray([b - a for a, b, *_ in self.ticks])
        return {"steps": len(walls), "window_s": t_end - t_start,
                "slowest_step_ms": 1e3 * float(walls.max()),
                "lane_intervals": self.served() - served0,
                "end_to_end": {
                    "tick_p95_ms": float(np.percentile(walls, 95) * 1e3)}}

    def release(self) -> None:
        pass

    def window_sessions(self):
        s = self.server
        done = [x for x in s.completed if x.terminated_tick is not None
                and x.terminated_tick >= self.first_tick]
        lost = [x for x in s.terminated if x.terminated_tick is not None
                and x.terminated_tick >= self.first_tick]
        return done, lost

    def check(self, limits: dict, control=None) -> dict:
        ref = self.ref
        done, lost = self.window_sessions()
        k = self.mix["sample_sessions"]
        pick = set()
        if done:
            pick.add(int(np.argmax([x.served_intervals for x in done])))
            for r in self.sample_rng.permutation(len(done)):
                if len(pick) >= min(k, len(done)):
                    break
                pick.add(int(r))
        chosen = [done[i] for i in sorted(pick)]
        adm = ref.simulate_admissible(
            self.reference_lanes(chosen, ref.Precision()), self.config)
        if control is None:
            summ = [x.summary() for x in chosen]
            prog = {key: np.asarray([s[key] for s in summ], np.float64)
                    for key in ref.SUMMARY_KEYS}
        else:
            cq = ref.Precision(control)
            prog = ref.simulate(self.reference_lanes(chosen, cq),
                                self.config, cq)
        gaps, keys = ref.lane_gaps(prog, adm) if chosen \
            else (np.zeros(0), [])
        lim = limits["summary_gap"]
        worst = None
        if gaps.size:
            j = int(np.argmax(gaps))
            worst = f"session {chosen[j].id} {keys[j]}"
        top = float(gaps.max()) if gaps.size else float("inf")
        return {"numbers": {"summary_gap": (top, lim),
                            "sessions_lost": (float(len(lost)), 0.0)},
                "answers": int(gaps.size) + len(lost),
                "off": int(np.sum(~(gaps <= lim))) + len(lost),
                "worst": worst}

    def reference_lanes(self, sessions, q):
        ref = self.ref
        keys = np.asarray(self.pool_keys())
        profiles = self.config["traffic_profiles"]
        n, T = len(sessions), self.tmax
        C = self.config["n_chiplets"]
        ext = np.zeros((n, T, C))
        intra = np.zeros((n, T, C))
        mem = np.zeros((n, T))
        mask = np.zeros((n, T))
        for r, sess in enumerate(sessions):
            j, t = self.owner[sess.id]
            app = self.apps[int(self.pool_app[j])]
            tr = ref.parsec_trace(profiles[app],
                                  ref.parsec_draws(keys[j], T, C), q)
            ext[r, :t], intra[r, :t], mem[r, :t] = (
                tr["ext"][:t], tr["intra"][:t], tr["mem"][:t])
            mask[r, :t] = 1.0
        full = lambda v: np.full(n, v)                          # noqa: E731
        return ref.Lanes(
            ext=ext, intra=intra, mem=mem, t_mask=mask,
            chip_mask=np.ones((n, C), bool), l_m=full(self.config["l_m"]),
            wavelengths=full(self.config["wavelengths"]),
            max_gateways=full(self.config["max_gateways"]),
            min_gateways=full(self.config["min_gateways"]))

    def layer_context(self) -> dict:
        traced = [t for t in self.ticks if t[3]]
        return {"kind": "serve", "entry_jit": "_session_tick_jit",
                "ticks": len(traced),
                "tick_s": [b - a for a, b, *_ in traced],
                "host_s": [(b - a) - d for a, b, d, *_ in traced]}
