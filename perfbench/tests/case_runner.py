"""Drive one small benchmark run on the CPU, optionally with a fault
planted in the program underneath; prints the result line.

    python perfbench/tests/case_runner.py <sweep|topology|serve> <fault> <seed> <cache_dir>

<fault> is none, state_unchanged, half_batch, answer_altered, or control
(the plain reference at bfloat16 put in the program's place).

Used by test_perfbench_faults.py in a process of its own, so the faults,
the compile cache and JAX's state never reach other tests.
"""
import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import harness  # noqa: E402


def small_spec(kind: str) -> dict:
    """A cell of the benchmark's own kinds at a size a test run holds
    ("topology": the sweep runner on the padded topology path)."""
    if kind in ("sweep", "topology"):
        name = "table1.knob-sweep"
        spec = harness.load_cell(name)
        spec["mix"] = dict(spec["mix"], apps=["dedup", "canneal"],
                           grid={"l_m": [0.008, 0.016],
                                 "wavelengths": [4, 8]},
                           sample_calls=2)
        if kind == "topology":
            spec["mix"].update(entry="sweep_topology_batch",
                               combine="zip",
                               grid={"n_chiplets": [2, 3, 4]})
    else:
        name = "table1.serve"
        spec = harness.load_cell(name)
        spec["mix"] = dict(spec["mix"], clients=4, min_intervals=6,
                           max_intervals=10, pool_sessions=64,
                           warmup_ticks=8, sample_sessions=16,
                           policy={"lanes": 4, "chunk_intervals": 4,
                                   "queue_capacity": 8})
    spec["config"] = dict(spec["config"], sim_cycles=16_000_000)
    return name, spec


def plant(fault: str) -> None:
    """Break the timed path underneath the harness."""
    import jax.numpy as jnp
    from repro.core import simulator
    from repro.serve import engine

    simulator.clear_engine_caches()
    if fault == "none":
        return
    if fault == "state_unchanged":
        make_step = simulator.make_step

        def frozen(*a, **kw):
            step = make_step(*a, **kw)

            def run(state, tr):
                _, rec = step(state, tr)
                return state, rec
            return run
        simulator.make_step = frozen
    elif fault == "half_batch":
        # Half of each batch is left out; the means are taken over the
        # rest: sweeps keep the first half of each lane's intervals,
        # the server serves only the first half of its lanes.
        record_sums = simulator._record_sums

        def half_sums(recs, t_mask):
            n = t_mask.shape[-1] // 2
            keep = jnp.arange(t_mask.shape[-1]) < n
            recs = {k: v * keep.reshape((-1,) + (1,) * (v.ndim - 1))
                    for k, v in recs.items()}
            return record_sums(recs, t_mask * keep)
        simulator._record_sums = half_sums
        tick = engine.session_tick

        def half_tick(states, batch, *a, **kw):
            b = dict(batch)
            m = jnp.asarray(b["t_mask"])
            lanes = m.shape[0]
            b["t_mask"] = m * (jnp.arange(lanes) < lanes // 2)[:, None]
            return tick(states, b, *a, **kw)
        engine.session_tick = half_tick
    elif fault == "answer_altered":
        # One answer altered where it is produced, by 10 %: lane 0's
        # latency in the sweep entries' result, lane 0's chunk latency in
        # the server tick.
        def altering(entry):
            def altered_sweep(*a, **kw):
                out = entry(*a, **kw)
                e = out["summary"]["mean_latency"]
                summ = dict(out["summary"],
                            mean_latency=e.at[0, 0].multiply(1.1))
                return dict(out, summary=summ)
            return altered_sweep
        simulator.sweep_batch = altering(simulator.sweep_batch)
        simulator.sweep_topology_batch = altering(
            simulator.sweep_topology_batch)
        tick = engine.session_tick

        def altered_tick(*a, **kw):
            states, recs, sums = tick(*a, **kw)
            return states, recs, dict(
                sums, latency=sums["latency"].at[0].multiply(1.1))
        engine.session_tick = altered_tick
    else:
        raise ValueError(f"unknown fault {fault!r}")


def main(argv):
    kind, fault, seed, cache = argv
    name, spec = small_spec(kind)
    # The run as the benchmark makes it, on the CPU: the look for a chip
    # skipped, the small cell in place of the real one, its own cache.
    harness.CACHE_DIR = Path(cache)
    harness.load_cell = lambda _name: spec
    harness.require_accelerator = lambda jax, chips: jax.devices()[:chips]
    harness.start_jax()
    plant("none" if fault == "control" else fault)
    res = harness.run_cell(name, int(seed), 0.5, False, process_t0=T0,
                           control="bf16" if fault == "control" else None)
    print(json.dumps(res))


if __name__ == "__main__":
    main(sys.argv[1:])
