"""Program spans (`repro.runtime.spans`) under the JAX profiler on the CPU:
a 2-lane `SessionServer` and a 2-trace x 2-point `sweep_batch`, traced
and reduced by the benchmark's span reduction."""
import glob
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "perfbench") not in sys.path:
    sys.path.insert(0, str(ROOT / "perfbench"))

import span_reduce  # noqa: E402
from repro.core import simulator, traffic  # noqa: E402
from repro.core.simulator import SimConfig  # noqa: E402
from repro.runtime import spans  # noqa: E402
from repro.serve.engine import SessionServer  # noqa: E402
from repro.serve.policies import ServerPolicy  # noqa: E402
from repro.serve.scheduler import SessionRequest  # noqa: E402

APPS = ("dedup", "canneal")
NOT_REACHED = {"repro.sim.sweep_topology_batch"}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    sim = SimConfig()
    server = SessionServer(sim, ServerPolicy(lanes=2, chunk_intervals=4,
                                             queue_capacity=4))
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    specs = [traffic.ParsecSpec(app=a, n_intervals=6) for a in APPS]

    def sweep(i):
        batch = simulator.stack_traces(
            [traffic.generate(s, keys[i + j], sim.cfg)
             for j, s in enumerate(specs)])
        out = simulator.sweep_batch(batch, sim,
                                    l_m=jnp.asarray([0.01, 0.02]))
        return jax.block_until_ready(out)

    def submit(i):
        server.submit(SessionRequest(
            trace=traffic.generate(specs[0], keys[i], sim.cfg)))

    def stack_under_jit(trs):
        out = simulator.stack_traces(trs)
        return {k: v for k, v in out.items() if k != "app"}

    for i in range(2):              # compile everything the trace runs
        submit(i)
    server.tick()
    sweep(0)
    arrays = [{k: v for k, v in traffic.generate(s, keys[j], sim.cfg).items()
               if k != "app"} for j, s in enumerate(specs)]
    d = tmp_path_factory.mktemp("spans_trace")
    with jax.profiler.trace(str(d)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(2, 4):
                submit(i)
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.tick"):
                    server.tick()
            sweep(2)
            jax.block_until_ready(jax.jit(stack_under_jit)(arrays))
    path = Path(sorted(glob.glob(str(d / "**" / "*.xplane.pb"),
                                 recursive=True))[-1])
    return {"reduced": span_reduce.reduce_file(path, "_session_tick_jit"),
            "events": _program_events(path)}


def _program_events(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_every_reached_span_appears(traced):
    names = set(traced["reduced"]["spans"])
    assert names <= set(spans.SPANS)
    assert set(spans.SPANS) - NOT_REACHED <= names


def test_tick_phases_lie_inside_their_tick_and_share_its_id(traced):
    ev = traced["events"]
    ticks = [e for e in ev if e[0] == "repro.serve.tick"]
    assert len(ticks) == 3
    assert [t[3]["tick"] for t in ticks] == [1, 2, 3]
    phases = [e for e in ev if e[0].startswith("repro.serve.")
              and e[0] not in ("repro.serve.tick", "repro.serve.submit")]
    assert {p[0] for p in phases} >= {
        "repro.serve.housekeep", "repro.serve.admit", "repro.serve.pack",
        "repro.serve.dispatch", "repro.serve.outcome", "repro.serve.observe"}
    for name, a, b, ids in phases:
        owner = [t for t in ticks if t[1] <= a and b <= t[2]]
        assert len(owner) == 1, name
        assert ids == owner[0][3], name


def test_child_spans_carry_their_roots_id(traced):
    ev = sorted(traced["events"], key=lambda e: (e[1], -e[2]))
    roots = span_reduce.outermost([e[:3] for e in ev])
    stats = {e[:3]: e[3] for e in ev}
    for name, a, b, ids in ev:
        root = [r for r in roots if r[1] <= a and b <= r[2]]
        assert len(root) == 1, name
        assert ids == stats[root[0]], name
        assert ids, name
    submits = [e for e in ev if e[0] == "repro.serve.submit"]
    assert len(submits) == 2
    assert all(e[3]["session"].startswith("s") for e in submits)
    calls = [e[3]["call"] for e in ev if e[0] in
             ("repro.traffic.generate", "repro.sim.stack_traces",
              "repro.sim.sweep_batch")]
    # The sweep's generates, its stack and entry, two submits' generates,
    # and the stack traced under jit.
    assert len(calls) == len(set(calls)) == len(APPS) + 5


def test_one_fetch_per_stack_of_concrete_traces_none_under_jit(traced):
    ev = traced["events"]
    stacks = sorted((e for e in ev if e[0] == "repro.sim.stack_traces"),
                    key=lambda e: e[1])
    fetches = [e for e in ev if e[0] == "repro.sim.fetch_traces"]
    # The sweep's concrete traces fetch once; the jitted stack, none.
    assert [sum(s[1] <= f[1] and f[2] <= s[2] for f in fetches)
            for s in stacks] == [1, 0]
    assert len(fetches) == 1
    assert fetches[0][3] == stacks[0][3]
    assert traced["reduced"]["spans"]["repro.sim.fetch_traces"]["count"] \
        == 1


def test_self_time_never_exceeds_total(traced):
    for name, row in traced["reduced"]["spans"].items():
        assert 0.0 <= row["self_s"] <= row["total_s"] * (1 + 1e-12), name
        assert row["count"] >= 1, name


def test_eager_ops_counted_under_roots(traced):
    sp = traced["reduced"]["spans"]
    assert sp["repro.serve.tick"]["eager_ops"] > 0
    assert sp["repro.traffic.generate"]["eager_ops"] >= len(APPS)
    # Children are never roots; their dispatches count under the root.
    assert sp["repro.serve.outcome"]["eager_ops"] == 0
    assert sp["repro.sim.dispatch"]["eager_ops"] == 0


def test_spans_outside_a_root_carry_nothing():
    assert spans._ROOT_IDS.get() == {}
    with spans.root("serve.tick", tick=5):
        assert spans._ROOT_IDS.get() == {"tick": 5}
        with spans.root("sim.sweep_batch"):
            first = spans._ROOT_IDS.get()["call"]
        assert spans._ROOT_IDS.get() == {"tick": 5}
    assert spans._ROOT_IDS.get() == {}
    with spans.root("sim.sweep_batch"):
        assert spans._ROOT_IDS.get()["call"] > first


def test_root_as_decorator_numbers_each_call():
    seen = []

    @spans.root("sim.stack_traces")
    def f():
        seen.append(spans._ROOT_IDS.get()["call"])

    f()
    f()
    assert seen[1] > seen[0]


def test_the_benchmark_reads_only_spans_the_program_emits():
    import re
    text = "".join(p.read_text() for p in
                   (ROOT / "perfbench" / "metrics").glob("*.py"))
    read = set(re.findall(r"repro\.[a-z_]+\.[a-z_]+", text))
    read |= set(span_reduce.SWEEP_CALL_ROOTS) | {span_reduce.SERVE_TICK}
    assert read and read <= set(spans.SPANS)
