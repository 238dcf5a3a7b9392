"""The program's spans and counters (repro.tracing): nesting, the bounded
store, threads, compile counts, the spans of a Study run, the device
scopes of the simulator step, and the aggregation kernels' names."""

import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.core import make_quadratic
from repro.core.trainer import ClientSimulator
from repro.experiments import ExecutionConfig, Study, build_components
from repro.kernels.aggregate.aggregate import (
    masked_scaled_aggregate_kernel,
    masked_scaled_aggregate_update_kernel,
)
from repro.optim import sgd


def _parents(record):
    return [(s.name, s.parent) for s in record.spans]


def test_spans_nest_under_one_root_record():
    with tracing.span("outer") as record:
        with tracing.span("a"):
            with tracing.span("a.inner"):
                tracing.count("things", 2)
        with tracing.span("b"):
            tracing.count("things")
    assert tracing.runs()[-1] is record
    assert record.name == "outer"
    assert _parents(record) == [("a.inner", "a"), ("a", "outer"),
                                ("b", "outer"), ("outer", None)]
    assert record.counters == {"things": 3}
    root = record.root
    assert all(root.start_ns <= s.start_ns <= s.end_ns <= root.end_ns
               for s in record.spans)


def test_decorated_function_is_a_span_and_counts_outside_are_dropped():
    @tracing.span("deco")
    def f(x):
        return x + 1

    tracing.count("nowhere")  # no open span: kept nowhere
    assert f(1) == 2
    assert tracing.runs()[-1].name == "deco"
    assert all("nowhere" not in r.counters for r in tracing.runs())


def test_store_keeps_the_newest_records():
    for i in range(tracing.KEEP + 5):
        with tracing.span(f"r{i}"):
            pass
    names = [r.name for r in tracing.runs()]
    assert len(names) == tracing.KEEP
    assert names == [f"r{i}" for i in range(5, tracing.KEEP + 5)]


def test_threads_build_their_own_roots():
    barrier = threading.Barrier(2)
    records = {}

    def work(tag):
        with tracing.span(f"root.{tag}") as record:
            barrier.wait()
            with tracing.span(f"child.{tag}"):
                barrier.wait()
                tracing.count(f"n.{tag}")
        records[tag] = record

    threads = [threading.Thread(target=work, args=(t,)) for t in "xy"]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for tag in "xy":
        assert _parents(records[tag]) == [(f"child.{tag}", f"root.{tag}"),
                                          (f"root.{tag}", None)]
        assert records[tag].counters == {f"n.{tag}": 1}


def test_many_threads_keep_whole_records():
    n_threads, n_roots = 16, 25
    errors = []

    def work(tag):
        try:
            for _ in range(n_roots):
                with tracing.span(f"root.{tag}") as record:
                    with tracing.span(f"child.{tag}"):
                        tracing.count("n")
                    tracing.count("n")
                assert record.counters == {"n": 2}
                assert _parents(record) == [(f"child.{tag}", f"root.{tag}"),
                                            (f"root.{tag}", None)]
        except AssertionError as e:
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    kept = [r for r in tracing.runs() if r.name.startswith("root.")]
    assert len(kept) == tracing.KEEP
    assert all(r.counters == {"n": 2} and len(r.spans) == 2 for r in kept)


def test_compiles_count_a_fresh_shape_once():
    f = jax.jit(lambda a: a * 3 + 1)
    x = jnp.ones((3, 17, 5))
    with tracing.span("first") as first:
        f(x).block_until_ready()
    with tracing.span("again") as again:
        f(x).block_until_ready()
    assert first.counters.get("compiles") == 1
    assert first.counters.get("cache_loads", 0) == 0
    (note,) = first.notes
    assert note["counter"] == "compiles" and note["seconds"] >= 0
    assert "<lambda>" in note["fun_name"]
    assert again.counters.get("compiles", 0) == 0


def test_programs_loaded_from_the_persistent_cache_count_apart(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    def make():  # a new function object each call: no in-memory hit
        return jax.jit(lambda a: jnp.sin(a) * 7)

    x = jnp.ones((5, 19, 3))
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        with tracing.span("written") as written:
            make()(x).block_until_ready()
        with tracing.span("loaded") as loaded:
            make()(x).block_until_ready()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert written.counters == {"compiles": 1}
    assert loaded.counters == {"cache_loads": 1}


@pytest.fixture(scope="module")
def problem():
    return make_quadratic(jax.random.PRNGKey(3), n_clients=5, dim=4,
                          hetero=1.0)


def test_study_run_record_holds_the_engine_spans(problem):
    study = Study("traced", num_steps=3,
                  axes={"scheduler": ["alg1", "oracle"],
                        "arrivals": "periodic", "n_clients": 5}).axis(
                            "seeds", 2)
    with tracing.span("caller"):
        pass  # a root of its own, before the study
    study.run(grads_fn=lambda p, k, t: problem.all_grads(p, key=k,
                                                         noise=0.05),
              p=problem.p, optimizer=sgd(0.02), params0=jnp.zeros((4,)),
              config=ExecutionConfig())
    record = tracing.runs()[-1]
    assert record.name == "study.run"
    names = [s.name for s in record.spans]
    assert names.count("engine.execute") == 1
    assert names.count("engine.resolve") == 1
    for name in ("engine.dispatch", "engine.wait", "engine.collect"):
        assert names.count(name) == 2, name  # one each per group
    parents = dict(_parents(record))
    assert parents["engine.execute"] == "study.run"
    assert all(parents[n] == "engine.execute"
               for n in ("engine.resolve", "engine.dispatch",
                         "engine.collect"))
    # the group's cells are sliced out behind its program, then waited for
    assert parents["engine.wait"] == "engine.collect"
    per_group = [n for n in names if n.startswith("engine.")
                 and n not in ("engine.execute", "engine.resolve")]
    assert per_group == ["engine.dispatch", "engine.wait",
                         "engine.collect"] * 2  # in the order they closed
    # the first run of each group compiled inside its dispatch
    assert record.counters.get("compiles", 0) \
        + record.counters.get("cache_loads", 0) >= 2


def test_a_group_of_several_cells_waits_once_at_its_first_read(problem):
    # n_clients 4 and 5 pad into one structure group of two cells
    study = Study("ragged", num_steps=3,
                  axes={"scheduler": "alg1", "arrivals": "periodic",
                        "n_clients": [4, 5]}).axis("seeds", 2)
    grid = study.run(grads_fn=lambda p, k, t: problem.all_grads(
        p, key=k, noise=0.05), p=problem.p, optimizer=sgd(0.02),
        params0=jnp.zeros((4,)), config=ExecutionConfig())
    names = [s.name for s in tracing.runs()[-1].spans]
    assert names.count("engine.dispatch") == 1
    assert names.count("engine.wait") == 1
    assert len(grid) == 2  # and both cells were checked for divergence
    assert all(cell.diverged is not None for cell in grid.values())


@pytest.mark.parametrize("flat", [None, False], ids=["flat", "legacy"])
def test_simulator_step_scopes_reach_the_lowered_program(problem, flat):
    sch, en = build_components(scheduler="alg1", arrivals="periodic",
                               n_clients=5, horizon=5)
    sim = ClientSimulator(
        grads_fn=lambda p, k, t: {"w": problem.all_grads(p["w"], key=k)},
        p=problem.p, optimizer=sgd(0.02), scheduler=sch, energy=en,
        loss_fn=lambda p: problem.suboptimality(p["w"]), flat=flat)
    text = jax.jit(lambda k, p0: sim.run(
        k, p0, 4, eval_fn=lambda p: jnp.sum(p["w"]), eval_every=2)).lower(
            jax.random.PRNGKey(0), {"w": jnp.zeros((4,))}).as_text(
                debug_info=True)
    for scope in ("sim.schedule", "sim.grads", "sim.update", "sim.eval"):
        assert f"{scope}/" in text, scope


def _pallas_names(fn, *args):
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    walk(getattr(inner, "jaxpr", inner))

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def test_aggregation_kernels_keep_their_trace_names():
    g, w, m = jnp.ones((4, 256)), jnp.ones((4,)), jnp.ones((4,))
    params = jnp.ones((256,))
    cases = {
        "masked_scaled_aggregate": lambda: masked_scaled_aggregate_kernel(
            g, w, interpret=True),
        "masked_scaled_aggregate_masked":
            lambda: masked_scaled_aggregate_kernel(g, w, m, interpret=True),
        "masked_scaled_aggregate_update":
            lambda: masked_scaled_aggregate_update_kernel(
                g, w, 0.1, params, m, interpret=True),
        "masked_scaled_aggregate_update_delta":
            lambda: masked_scaled_aggregate_update_kernel(
                g, w, 0.1, None, m, interpret=True),
    }
    for name, fn in cases.items():
        assert _pallas_names(fn) == [name]
