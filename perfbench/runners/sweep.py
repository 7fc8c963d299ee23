"""Batched design-space sweeps: N fresh PARSEC traces per call, crossed
with a K-point grid, through `sweep_batch` or `sweep_topology_batch`.

Mix keys: `entry` (the entry point), `apps` (one trace per app per
call), `grid` (swept field -> a list, {"linspace": [lo, hi, n]} or
{"range": [start, stop, step]}), `combine` ("product" crosses the fields
in key order, first key slowest; "zip" pairs them) and `sample_calls`
(calls of the window compared with the plain reference, drawn from the
seed). The harness blocks on each call's result before the next, as a
user who consumes each chunk of a sweep does.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

import harness


def grid_values(spec) -> np.ndarray:
    if isinstance(spec, list):
        return np.asarray(spec)
    if "linspace" in spec:
        lo, hi, n = spec["linspace"]
        return np.linspace(lo, hi, int(n))
    if "range" in spec:
        return np.arange(*spec["range"])
    raise ValueError(f"unknown grid spec {spec!r}")


def build_grid(mix: dict) -> Dict[str, np.ndarray]:
    """The swept fields, combined as the mix says."""
    axes = {k: grid_values(v) for k, v in mix["grid"].items()}
    if mix.get("combine", "zip") == "product":
        mesh = np.meshgrid(*axes.values(), indexing="ij")
        axes = {k: m.reshape(-1) for k, m in zip(axes, mesh)}
    lengths = {len(v) for v in axes.values()}
    if len(lengths) != 1:
        raise ValueError(f"grid axes differ in length: {axes}")
    out = {}
    for k, v in axes.items():
        out[k] = v.astype(np.float32) if k == "l_m" else v.astype(np.int32)
    return out


class Runner:
    ENTRIES = {"sweep_batch": "_sweep_batch_jit",
               "sweep_topology_batch": "_sweep_topology_batch_jit"}
    FIRST_CALL = 2          # calls 0 and 1 warm up

    def __init__(self, spec: dict, devices, seed: int):
        import jax
        from repro.core import simulator, traffic

        self.config, self.mix = spec["config"], spec["mix"]
        self.ref = spec["reference"]
        self.devices = devices
        self.jax, self.simulator, self.traffic = jax, simulator, traffic
        self.sim = harness.sim_config(self.config)
        self.T = harness.n_intervals(self.config)
        self.apps = self.mix["apps"]
        self.grid = build_grid(self.mix)
        unknown = set(self.grid) - set(self.ref.SWEPT_FIELDS)
        if unknown:
            raise ValueError(f"swept fields {sorted(unknown)}: the plain "
                             f"reference models {self.ref.SWEPT_FIELDS}")
        self.K = len(next(iter(self.grid.values())))
        self.N = len(self.apps)
        self.lanes = self.N * self.K
        self.jit_name = self.ENTRIES[self.mix["entry"]]
        self.entry = getattr(simulator, self.mix["entry"])
        self.gen_cfg = self.sim.cfg.with_topology(
            n_chiplets=self.config["n_chiplets"])
        self.specs = [traffic.ParsecSpec(app=a, n_intervals=self.T)
                      for a in self.apps]
        self.root_key = harness.seed_key_words(seed)
        self.spans: List[tuple] = []
        self.sample: Dict[int, object] = {}
        self.rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), 0x5A3]))

    # -- inputs ------------------------------------------------------------
    def call_keys(self, i: int):
        """The N trace keys of call `i` (the run's key stream)."""
        jnp = self.jax.numpy
        base = jnp.asarray(self.root_key, dtype=jnp.uint32)
        return self.jax.random.split(self.jax.random.fold_in(base, i),
                                     self.N)

    def inputs(self, i: int) -> dict:
        keys = self.call_keys(i)
        traces = [self.traffic.generate(s, keys[j], self.gen_cfg)
                  for j, s in enumerate(self.specs)]
        return self.simulator.stack_traces(traces)

    def call(self, batch):
        kw = dict(self.grid)
        if len(self.devices) > 1:
            kw["devices"] = self.devices
        return self.entry(batch, self.sim, **kw)

    # -- phases ------------------------------------------------------------
    def setup(self) -> None:
        for i in range(self.FIRST_CALL):
            self.jax.block_until_ready(self.call(self.inputs(i)))

    def window(self, seconds: float, prof) -> dict:
        k_sample = self.mix["sample_calls"]
        i = self.FIRST_CALL
        n = 0
        t_start = time.perf_counter()
        t_end = t_start
        while t_end - t_start < seconds:
            prof.update(t_end - t_start)
            traced = prof.active
            t0 = time.perf_counter()
            with prof.annotate("bench.tracegen"):
                batch = self.inputs(i)
            t1 = time.perf_counter()
            with prof.annotate("bench.entry"):
                out = self.call(batch)
            t2 = time.perf_counter()
            with prof.annotate("bench.block"):
                self.jax.block_until_ready(out)
            t_end = time.perf_counter()
            self.spans.append((i, t0, t1, t2, t_end, traced))
            # Reservoir sample of calls, drawn from the seed.
            kept = (i, out["summary"], out["records"]["g"])
            if n < k_sample:
                self.sample[n] = kept
            else:
                r = int(self.rng.integers(0, n + 1))
                if r < k_sample:
                    self.sample[r] = kept
            del out, batch
            i += 1
            n += 1
        prof.stop()
        walls = [s[4] - s[1] for s in self.spans]
        return {"steps": n, "window_s": t_end - t_start,
                "slowest_step_ms": 1e3 * max(walls),
                "lane_intervals": n * self.lanes * self.T}

    def release(self) -> None:
        """Pull the sampled summaries and gateway records to the host;
        drop device state."""
        host = {}
        for i, summ, g in self.sample.values():
            host[i] = {k: np.asarray(summ[k], np.float64).reshape(-1)
                       for k in self.ref.SUMMARY_KEYS}
            g = np.asarray(g)
            host[i]["g"] = g.reshape((self.lanes,) + g.shape[-2:])
        self.sample = host

    # -- the plain reference ---------------------------------------------
    def reference_lanes(self, i: int, q):
        """Call `i`'s lanes for the reference, traces computed at q."""
        ref = self.ref
        keys = np.asarray(self.call_keys(i))
        c_gen = self.config["n_chiplets"]
        profiles = self.config["traffic_profiles"]
        traces = [ref.parsec_trace(profiles[a],
                                   ref.parsec_draws(keys[j], self.T, c_gen), q)
                  for j, a in enumerate(self.apps)]
        lanes = {k: np.repeat(np.stack([t[k] for t in traces]), self.K,
                              axis=0) for k in ("ext", "intra", "mem")}
        L = self.lanes
        grid = {k: np.tile(v, self.N) for k, v in self.grid.items()}
        if "n_chiplets" in grid:
            chips = np.arange(c_gen)[None, :] < grid["n_chiplets"][:, None]
        else:
            chips = np.ones((L, c_gen), bool)
        max_g = np.full(L, self.config["max_gateways"])
        return ref.Lanes(
            **lanes, t_mask=np.ones((L, self.T)), chip_mask=chips,
            l_m=grid.get("l_m", np.full(L, self.config["l_m"])),
            wavelengths=grid.get("wavelengths",
                                 np.full(L, self.config["wavelengths"])),
            max_gateways=max_g,
            min_gateways=np.minimum(self.config["min_gateways"], max_g))

    def check(self, limits: dict, control=None) -> dict:
        """Compare every lane of the sampled calls with the reference.
        `control` puts the reference at that precision in the program's
        place."""
        ref = self.ref
        f64 = ref.Precision()
        worst = (0.0, None)
        gaps_all = []
        for i in sorted(self.sample):
            if control is None:
                prog = self.sample[i]
                guide = prog["g"]
            else:
                cq = ref.Precision(control)
                prog = ref.simulate(self.reference_lanes(i, cq),
                                    self.config, cq, record_g=True)
                guide = prog["g_trace"]
            adm = ref.simulate_guided(self.reference_lanes(i, f64),
                                      self.config, guide)
            gaps, keys = ref.lane_gaps(prog, adm)
            gaps_all.append(gaps)
            j = int(np.argmax(gaps))
            if gaps[j] >= worst[0]:
                worst = (float(gaps[j]), f"call {i} lane {j} {keys[j]}")
        gaps = np.concatenate(gaps_all)
        lim = limits["summary_gap"]
        return {"numbers": {"summary_gap": (float(gaps.max()), lim)},
                "answers": int(gaps.size),
                "off": int(np.sum(~(gaps <= lim))),
                "worst": worst[1]}

    # -- per-layer context ---------------------------------------------------
    def layer_context(self) -> dict:
        traced = [s for s in self.spans if s[5]]
        return {
            "kind": "sweep", "entry_jit": self.jit_name,
            "calls": len(traced),
            "tracegen_s": [s[2] - s[1] for s in traced],
            "entry_s": [s[3] - s[2] for s in traced],
            "shape": {"traces": self.N,
                      "intervals": self.T,
                      "chiplets_per_lane": self.chiplets_per_lane(),
                      "trace_chiplets": self.config["n_chiplets"],
                      "gateways": self.config["max_gateways_per_chiplet"],
                      "memory_gateways": self.config["memory_gateways"]},
        }

    def chiplets_per_lane(self) -> List[int]:
        c = self.grid.get("n_chiplets")
        per_point = ([int(x) for x in c] if c is not None
                     else [self.config["n_chiplets"]] * self.K)
        return per_point * self.N
