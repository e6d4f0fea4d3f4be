"""repro.obs: tracing core, counters, kernel utilization accounting."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.kernels import ops
from repro.plan import KernelConfig


# ----------------------------------------------------------------------
# tracing core
# ----------------------------------------------------------------------
def test_disabled_span_is_shared_noop():
    """The disabled fast path must not allocate: every span() call
    returns the same no-op object (the <2% overhead budget)."""
    assert not obs.enabled()
    s1 = obs.span("a", x=1)
    s2 = obs.span("b")
    assert s1 is s2
    with s1:
        pass          # and it is a usable context manager
    obs.event("dropped", v=3)   # no sink, no error


def test_capture_records_spans_and_events():
    with obs.capture() as sink:
        assert obs.enabled()
        with obs.span("work", step=3):
            pass
        obs.event("mark", rid=7)
    assert not obs.enabled()    # state restored
    kinds = [(r["type"], r["name"]) for r in sink.records]
    assert kinds == [("span", "work"), ("event", "mark")]
    span_rec = sink.records[0]
    assert span_rec["step"] == 3 and span_rec["dur_s"] >= 0.0
    assert sink.records[1]["rid"] == 7


def test_engine_spans_are_profiler_annotations(tmp_path):
    """With tracing on, every span is also a profiler annotation: a CPU
    trace of an engine run holds each ``serve.*`` span on the host
    plane, as the benchmark's reader loads it, and each annotation
    holds its sink record's interval."""
    from chipbench import trace as tr
    from repro.configs import get_config
    from repro.models import Ctx, build_model
    from repro.serve import Request, ServeEngine
    model = build_model(get_config("gemma-7b", reduced=True))
    params = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = ServeEngine(model, params, Ctx(plan="jnp", dtype=jnp.float32),
                      num_slots=2, max_len=32, page_size=4)

    def requests(base):
        return [Request(rid=base + i, prompt=[1, 2, 3, 4, 5, 6, 7 + i],
                        max_new_tokens=3) for i in range(2)]
    eng.run(requests(0))                 # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    with obs.capture() as sink:
        eng.run(requests(10))
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path), host_prefixes=("serve.",))
    spans = [r for r in sink.records if r["type"] == "span"]
    assert sorted(h[0] for h in t.host) == sorted(s["name"] for s in spans)
    assert {s["name"] for s in spans} == {
        "serve.step", "serve.admit", "serve.install", "serve.first_token",
        "serve.sync", "serve.dispatch", "serve.launch", "serve.emit"}
    for name in {s["name"] for s in spans}:
        notes = [h for h in t.host if h[0] == name]
        recs = sorted((s for s in spans if s["name"] == name),
                      key=lambda s: s["t"])
        for (_, start, end), rec in zip(notes, recs):
            assert end - start >= rec["dur_s"] * 1e9 - 1


def test_jsonl_sink_roundtrip(tmp_path):
    path = os.path.join(tmp_path, "trace.jsonl")
    obs.enable(trace_path=path)
    try:
        with obs.span("outer", k=2):
            obs.event("inner", v=1.5)
    finally:
        obs.disable()
    lines = [json.loads(l) for l in open(path)]
    assert [r["name"] for r in lines] == ["inner", "outer"]  # exit order
    assert lines[0]["v"] == 1.5 and "dur_s" in lines[1]


def test_enable_rejects_both_sink_and_path(tmp_path):
    with pytest.raises(ValueError, match="not both"):
        obs.enable(trace_path=os.path.join(tmp_path, "t.jsonl"),
                   sink=obs.ListSink())


def test_counters_always_on_and_prefixed():
    assert not obs.enabled()     # counters do NOT ride the switch
    obs.counter_inc("t.alpha")
    obs.counter_inc("t.alpha", 2)
    obs.counter_inc("t.beta")
    obs.counter_inc("other.gamma")
    try:
        assert obs.counters("t.") == {"t.alpha": 3, "t.beta": 1}
        obs.reset_counters("t.")
        assert obs.counters("t.") == {}
        assert obs.counters("other.") == {"other.gamma": 1}
    finally:
        obs.reset_counters("t.")
        obs.reset_counters("other.")


# ----------------------------------------------------------------------
# kernel watch: dispatch records -> utilization table
# ----------------------------------------------------------------------
def test_record_dispatch_aggregates_by_signature():
    obs.enable()
    cfg = KernelConfig(bm=128, bn=128, bk=128)
    for _ in range(3):
        obs.record_dispatch("matmul", M=256, N=256, K=256,
                            dtype="bfloat16", backend="pallas", config=cfg)
    obs.record_dispatch("matmul", M=256, N=256, K=512,   # different K
                        dtype="bfloat16", backend="pallas", config=cfg)
    recs = obs.recorded_ops()
    assert [(r.M, r.K, r.count) for r in recs] == [(256, 256, 3),
                                                   (256, 512, 1)]


def test_utilization_table_predicted_columns():
    obs.enable()
    obs.record_dispatch("matmul", M=512, N=512, K=512, dtype="bfloat16",
                        backend="pallas",
                        config=KernelConfig(bm=128, bn=128, bk=128))
    obs.record_dispatch("grouped_matmul", M=64, N=128, K=128,
                        dtype="bfloat16", backend="pallas", groups=4,
                        config=KernelConfig(bm=64, bn=128, bk=128))
    obs.record_dispatch("attention", M=64, N=32, K=64, dtype="float32",
                        backend="interpret", batch_heads=8)
    rows = obs.utilization_table()
    assert [r["op"] for r in rows] == ["matmul", "grouped_matmul",
                                      "attention"]
    for r in rows:
        assert r["predicted_s"] > 0
        assert 0 < r["predicted_util"] <= 1
        assert r["measured_s"] is None and r["measured_util"] is None
    # the default-config row (jnp/no-resolve dispatches) prices too
    assert rows[2]["config"] == "default"
    # a bigger GEMM on the same tiles must predict >= utilization
    obs.record_dispatch("matmul", M=64, N=64, K=64, dtype="bfloat16",
                        backend="pallas",
                        config=KernelConfig(bm=128, bn=128, bk=128))
    rows = obs.utilization_table()
    assert rows[0]["predicted_util"] >= rows[-1]["predicted_util"]


def test_measure_recorded_fills_measured_columns():
    obs.enable()
    obs.record_dispatch("matmul", M=16, N=16, K=16, dtype="float32",
                        backend="jnp")
    rows = obs.utilization_table(measure=True, repeats=1)
    (row,) = rows
    assert row["measured_s"] > 0
    assert row["measured_util"] > 0
    # the standalone replay must not observe itself: still one record
    assert len(obs.recorded_ops()) == 1


def test_ops_record_on_jnp_and_interpret_paths():
    obs.enable()
    a = jnp.ones((8, 24), jnp.float32)
    b = jnp.ones((24, 16), jnp.float32)
    ops.matmul(a, b)                      # auto -> jnp on CPU
    ops.matmul(a, b, config=KernelConfig(backend="interpret",
                                         bm=8, bn=8, bk=8))
    recs = obs.recorded_ops()
    assert [(r.backend, r.config is None) for r in recs] == [
        ("jnp", True), ("interpret", False)]
    assert recs[1].config.bm == 8
    # disabled -> no recording
    obs.disable()
    ops.matmul(a, b)
    assert len(obs.recorded_ops()) == 2


# ----------------------------------------------------------------------
# fallback counters (ops satellite)
# ----------------------------------------------------------------------
def test_fallback_counts_queryable_and_reset():
    assert ops.fallback_counts() == {}
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 8, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 12, 16))
    cfg = KernelConfig(backend="interpret")
    with pytest.warns(RuntimeWarning, match="falling back"):
        ops.attention(q, k, k, causal=True, config=cfg)
    # second occurrence: counted again, but warn-once stays silent
    ops.attention(q, k, k, causal=True, config=cfg)
    assert ops.fallback_counts() == {"attention_causal_unaligned": 2}
    ops.reset_fallback_warnings()
    assert ops.fallback_counts() == {}
    # and the counter lives in the obs namespace (exported surface);
    # after a reset the warn-once fires again too
    with pytest.warns(RuntimeWarning, match="falling back"):
        ops.attention(q, k, k, causal=True, config=cfg)
    assert obs.counters("ops.fallback.") == {
        "ops.fallback.attention_causal_unaligned": 1}
