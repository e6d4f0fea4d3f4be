"""The engine's per-layer metrics: ``step_idle_share.*`` on a synthetic
trace with the program's spans, ``prefix_hit_share`` on the engine's
counters, both on a program that lacks what they read, the entries in
BENCHMARK.json, and a traced run of a tiny cell on the CPU."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import bench
from chipbench.trace import Trace

HERE = Path(__file__).resolve().parents[1]
BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())
T0 = 5.0                    # host clock at the window's start, s
W0, W1 = 1000, 2000         # the traced window, ns


def _run(spans=None, devices=None, stats=None):
    """A run whose window opens at ``T0`` on the host and ``W0`` on the
    trace; ``spans``: ``(name, start, end, attrs)`` in ns from ``W0``."""
    recs = None
    if spans is not None:
        recs = [dict(type="span", name=n, t=T0 + s * 1e-9,
                     dur_s=(e - s) * 1e-9, **a) for n, s, e, a in spans]
        recs.append(dict(type="event", name="serve.retire", t=T0))
    trace = None if devices is None else Trace(devices=devices, host=[])
    return bench.Run(cell=None, dims=None, seed=0, seconds=1.0, t0=T0,
                     t_close=T0 + 1e-6, t_end=T0 + 1e-6, logs={},
                     stats=stats, num_slots=1, steps_per_dispatch=1,
                     setup_s=0.0, peak={}, chips=1, spans=recs, trace=trace,
                     trace_window=(W0, W1))


# chip 0 busy at 0-100, 300-400 and 700-800 ns of the window
OPS = [(W0 + a, W0 + b, "%fusion.1 = f32[8]{0} fusion()")
       for a, b in ((0, 100), (300, 400), (700, 800))]
STEPS = [
    ("serve.step", 50, 600, {"step": 1}),
    ("serve.dispatch", 60, 250, {}),
    ("serve.launch", 60, 100, {}),
    ("serve.sync", 100, 250, {"what": "decode_block"}),
    ("serve.emit", 250, 320, {}),
    ("serve.step", 650, 900, {"step": 2}),
    ("serve.admit", 660, 850, {"prompt_len": 9}),
    ("serve.install", 670, 700, {}),
    ("serve.first_token", 700, 840, {}),
    ("serve.sync", 710, 840, {"what": "first_token"}),
]


def test_step_idle_share_counts_idle_inside_steps_only():
    """Idle 100-300, 400-700 and 800-1000 ns; of it, 550 ns lie inside
    the two steps (600-650 and 900-1000 are between steps)."""
    run = _run(STEPS, [OPS])
    for name in ("step_idle_share.serve", "step_idle_share.batch"):
        read = bench.reader(name)
        assert Path(read.__code__.co_filename).name == "step_idle_share.py"
    assert read(run) == pytest.approx(55.0)
    from chipbench import trace
    idle = (W1 - W0) - trace.busy_s(run.trace, W0, W1) * 1e9
    assert idle == pytest.approx(700)


def test_step_idle_share_note_splits_by_innermost_span():
    run = _run(STEPS, [OPS])
    bench.reader("step_idle_share.serve")(run)
    note = next(n for n in run.notes if n.startswith("step_idle_share"))
    assert "inside 2 serve.step spans" in note
    want = {"serve.step": 260, "serve.sync:decode_block": 150,
            "serve.emit": 50, "serve.sync:first_token": 40,
            "serve.install": 30, "serve.admit": 20}
    got = {}
    for part in note.split("innermost span: ")[1].split(", "):
        label, secs, _, _ = part.split(" ")
        got[label] = float(secs) * 1e9
    assert got == pytest.approx(want, abs=1e-3)
    assert "serve.launch" not in got       # the device ran then


@pytest.mark.parametrize("spans,devices", [
    ([s for s in STEPS if s[0] != "serve.step"], [OPS]),   # a program
    (STEPS, []),                                # without steps; no chip
    (None, [OPS]),                              # no program spans
])
def test_step_idle_share_is_silent_without_its_inputs(spans, devices):
    assert bench.reader("step_idle_share.serve")(_run(spans, devices)) \
        is None


def test_prefix_hit_share_reads_the_engine_counters():
    from repro.serve import EngineStats
    read = bench.reader("prefix_hit_share")
    assert read(_run(stats=EngineStats(prefill_tokens=400,
                                       prefix_hit_tokens=352))) == 88.0
    assert read(_run(stats=EngineStats())) is None
    # a program without the counter
    assert read(_run(stats=SimpleNamespace(prefill_tokens=400))) is None


def test_the_three_entries():
    per = {m["name"]: m for m in BENCH["per_layer"]}
    assert per["step_idle_share.serve"] == {
        "name": "step_idle_share.serve", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "engine", "moves": "ttft_p50_ms",
        "workloads": ["dsc33b.repo-complete"]}
    assert per["step_idle_share.batch"] == {
        "name": "step_idle_share.batch", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "engine",
        "moves": "output_tok_s", "workloads": ["mistral123b.batch-gen"]}
    assert per["prefix_hit_share"] == {
        "name": "prefix_hit_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "paged pool",
        "moves": "ttft_p50_ms", "workloads": ["dsc33b.repo-complete"]}
    assert [m["name"] for m in BENCH["per_layer"][-3:]] == [
        "step_idle_share.serve", "step_idle_share.batch", "prefix_hit_share"]
    for name in per:
        assert callable(bench.reader(name))


def test_traced_tiny_run_reports_the_counter(run_tiny, monkeypatch, tmp_path):
    """A traced run of the tiny open-loop cell on the CPU (``run_tiny``
    for its compile-cache setting): the prefix counter is read; the
    step share needs a device and stays silent."""
    from chipbench.tests.conftest import tiny_cell
    monkeypatch.setattr(bench, "TRACE_DIR", tmp_path / "traces")
    cell = tiny_cell("repo-complete", 0.05)
    cell.per_layer = [m for m in BENCH["per_layer"]
                      if m["name"] in ("prefix_hit_share",
                                       "step_idle_share.serve")]
    out = bench.run(cell.name, 2 ** 33 + 21, 2.0, True, cell=cell,
                    require_tpu=False, backend="interpret", log=lambda _: 0)
    assert out["correct"]
    assert 0 < out["metrics"]["prefix_hit_share"]["value"] <= 100
    assert "step_idle_share.serve" not in out["metrics"]
