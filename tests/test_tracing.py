"""repro.tracing: spans and compile counts, off by default, and the spans,
counts and named scopes the serving path, the scheduler and Session carry."""
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.api import Plan, Probe, Session, serving_tiny_config
from repro.core.timing import Measurement, Timer
from repro.models import transformer
from repro.serving import Engine
from repro.traffic.scheduler import ContinuousBatchingScheduler, EngineExecutor
from repro.traffic.traces import Request

CFG, RT = serving_tiny_config()


@pytest.fixture
def traced():
    """Tracing on for one test, and off with nothing left recorded after."""
    tracing.collect()
    tracing.enable()
    try:
        yield
    finally:
        tracing.disable()
        tracing.collect()


@pytest.fixture(scope="module")
def engine():
    params = transformer.init_lm(jax.random.PRNGKey(0), CFG)
    return Engine(params, CFG, RT, max_len=32)


def _named(spans, name):
    return [s for s in spans if s.name == name]


# ------------------------------------------------------------------ module
@pytest.mark.parametrize("name,attrs", [("repro.pool.step", {"active": 3}),
                                        ("repro.session.flush", {})])
def test_off_span_is_the_shared_null_context(name, attrs):
    tracing.disable()
    ctx = tracing.span(name, **attrs)
    assert ctx is tracing.span("another")
    with ctx:
        tracing.count("compile.backend")
    assert tracing.collect() == ([], [])


def test_on_records_nesting_parents_and_threads(traced):
    def worker():
        with tracing.span("repro.worker", op="w"):
            tracing.count("thing", 2)

    with tracing.span("repro.outer", uid=7):
        with tracing.span("repro.inner"):
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=30)
    assert not t.is_alive()
    spans, counts = tracing.collect()
    assert tracing.collect() == ([], [])
    by = {s.name: s for s in spans}
    main = threading.get_native_id()
    assert by["repro.outer"].parent is None
    assert by["repro.outer"].attrs == {"uid": 7}
    assert by["repro.inner"].parent == "repro.outer"
    assert by["repro.worker"].parent is None       # its own thread's stack
    assert by["repro.outer"].thread == by["repro.inner"].thread == main
    assert by["repro.worker"].thread != main
    outer, inner = by["repro.outer"], by["repro.inner"]
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert [(c.name, c.n, c.thread) for c in counts] == \
        [("thing", 2, by["repro.worker"].thread)]


def test_span_reuses_a_given_start(traced):
    import time

    t0 = time.perf_counter_ns()
    with tracing.span("repro.x", start_ns=t0):
        pass
    (s,), _ = tracing.collect()
    assert s.start_ns == t0 < s.end_ns


def test_compile_counter_counts_a_fresh_jit_once(traced):
    def tracing_counter_probe(x):
        return x * 3 + 1

    fn = jax.jit(tracing_counter_probe)
    x = np.arange(5, dtype=np.float32)
    fn(x).block_until_ready()
    _, counts = tracing.collect()
    names = [c.name for c in counts]
    assert names.count("compile.backend") == 1
    assert names.count("compile.trace") >= 1
    assert set(names) <= {"compile.trace", "compile.backend",
                          "compile.cache_load"}
    fn(x).block_until_ready()
    assert tracing.collect() == ([], [])


# ------------------------------------------------------------ serving path
def test_pool_and_scheduler_spans_carry_uid(engine, traced):
    ex = EngineExecutor(engine, 2)
    trace = [Request(uid=11, arrival_ns=0.0, prompt=(1, 2, 3), max_new=3),
             Request(uid=12, arrival_ns=0.0, prompt=(4, 5), max_new=2)]
    ContinuousBatchingScheduler(ex).run(trace)
    spans, _ = tracing.collect()
    assert sorted(s.attrs["uid"] for s in _named(spans, "repro.pool.admit")) \
        == [11, 12]
    assert sorted(s.attrs["uid"] for s in _named(spans, "repro.sched.admit")) \
        == [11, 12]
    assert {s.attrs["prompt_len"] for s in _named(spans, "repro.pool.admit")} \
        == {3, 2}
    for child in ("repro.pool.prefill", "repro.pool.write",
                  "repro.pool.first_token"):
        assert [s.parent for s in _named(spans, child)] == \
            ["repro.pool.admit"] * 2
    steps = _named(spans, "repro.pool.step")
    assert len(steps) == 2 and steps[0].attrs == {"active": 2}
    assert [s.parent for s in steps] == ["repro.sched.step"] * 2
    for child in ("repro.pool.step_inputs", "repro.pool.decode",
                  "repro.pool.tokens"):
        assert [s.parent for s in _named(spans, child)] == \
            ["repro.pool.step"] * 2
    assert not _named(spans, "repro.pool.sample")     # greedy pool


def test_pool_step_counts_the_live_cache_rows(engine, traced):
    pool = engine.slots(3)
    pool.admit(0, [1, 2, 3], uid=1, max_new=4)
    pool.admit(2, [4, 5], uid=2, max_new=4)
    pool.step()
    pool.step()
    _, counts = tracing.collect()
    live = [c.n for c in counts if c.name == "pool.kv_rows_live"]
    # rows at 3, 0 (free) and 2, then 4, 0 and 3: each reads pos + 1
    assert live == [4 + 1 + 3, 5 + 1 + 4]


def test_pool_step_counts_nothing_with_tracing_off(engine):
    tracing.disable()
    tracing.collect()
    pool = engine.slots(2)
    pool.admit(0, [1, 2], uid=1, max_new=2)
    pool.step()
    assert tracing.collect() == ([], [])


def test_pool_sample_span_on_the_temperature_path(engine, traced):
    pool = engine.slots(2)
    pool.temperature = 0.7
    pool.admit(0, [1, 2, 3], uid=5, max_new=3)
    pool.step()
    spans, _ = tracing.collect()
    (sample,) = _named(spans, "repro.pool.sample")
    assert sample.parent == "repro.pool.step"


@pytest.fixture(scope="module")
def decode_hlo(engine):
    pool = engine.slots(2)
    lowered = engine._decode.lower(engine.params, pool.cache,
                                   jnp.zeros((2, 1), jnp.int32),
                                   jnp.zeros((2,), jnp.int32))
    return lowered.compile().as_text()


def test_decode_step_module_is_named(decode_hlo):
    assert decode_hlo.startswith("HloModule jit_decode_step")


@pytest.mark.parametrize("scope", ["embed", "layers", "attn", "mlp", "head"])
def test_decode_step_op_names_carry_scope(decode_hlo, scope):
    op_names = re.findall(r'op_name="([^"]*)"', decode_hlo)
    assert any(scope in n.split("/") for n in op_names)


# ----------------------------------------------------------------- Session
class _Probe(Probe):
    category = "test"

    def __init__(self, op):
        self.op, self.opt_level, self.dtype = op, "O3", "float32"

    def prepare(self, ctx):
        return self.op

    def run_prepared(self, ctx, prepared):
        return self._record(ctx, Measurement(10.0, 1.0, 10.0, 5))


def test_pipelined_session_spans(traced):
    session = Session(timer=Timer(warmup=0, reps=2, clock_hz=1e9))
    res = session.run(Plan((_Probe("a"), _Probe("b"))), pipeline=True)
    assert len(res.measured) == 2
    spans, _ = tracing.collect()
    main = threading.get_native_id()
    (run,) = _named(spans, "repro.session.run")
    assert run.attrs == {"probes": 2} and run.thread == main
    assert [s.thread for s in _named(spans, "repro.session.setup")] == [main]
    waits = _named(spans, "repro.session.compile_wait")
    assert len(waits) == 2
    assert {(s.thread, s.parent) for s in waits} == {(main, "repro.session.run")}
    prepares = _named(spans, "repro.session.prepare")
    assert sorted(s.attrs["op"] for s in prepares) == ["a", "b"]
    assert all(s.thread != main and s.parent is None for s in prepares)
    times = _named(spans, "repro.session.time")
    assert [s.attrs["op"] for s in times] == ["a", "b"]
    assert {s.thread for s in times} == {main}
    assert len(_named(spans, "repro.session.flush")) == 2
