"""The reduction from a profiler trace to busy/idle time, kernel time,
roofline shares and the breakdown: on synthetic events, on events
recorded on a TPU v5e (``data/``), and the reader on a CPU trace."""

import json
from pathlib import Path

import pytest

from chipbench import bench, trace
from chipbench.trace import Trace

DATA = Path(__file__).resolve().parent / "data"
PEAK = json.loads((Path(bench.__file__).parent / "peaks.json").read_text())[
    "TPU v5 lite"]

MATMUL = ("%zero_stall_matmul_s4_ijk.1 = bf16[256,19456]{1,0:T(8,128)(2,1)S(1)}"
          " custom-call(bf16[256,7168]{1,0:T(8,128)(2,1)S(1)} %copy-done, "
          "bf16[7168,19456]{1,0:T(8,128)(2,1)} %pad.0), custom_call_target="
          "\"tpu_custom_call\", operand_layout_constraints={bf16[256,7168]{1,0}"
          ", bf16[7168,19456]{1,0}}, frontend_attributes={kernel_metadata={}}")
PAD = ("%pad.0 = bf16[7168,19456]{1,0:T(8,128)(2,1)} pad(bf16[7168,19200]"
       "{1,0:T(8,128)(2,1)} %b.1, bf16[]{:T(256)} %constant.2), "
       "padding=0_0x0_256")


def test_busy_is_the_union_of_overlapping_ops():
    ops = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (22, 24, "d")]
    t = Trace(devices=[ops], host=[])
    assert trace.busy_s(t, 0, 40) == pytest.approx(25e-9)
    assert trace.busy_s(t, 8, 25) == pytest.approx(12e-9)
    assert trace.busy_intervals(list(reversed(ops)), 0, 40) == [[0, 15],
                                                                 [20, 30]]


def test_busy_averages_over_chips():
    t = Trace(devices=[[(0, 10, "a")], [(0, 30, "a")]], host=[])
    assert trace.busy_s(t, 0, 100) == pytest.approx(20e-9)


def test_op_names_and_operand_shapes():
    assert trace.op_name(MATMUL) == "zero_stall_matmul_s4_ijk"
    assert trace.op_name(PAD) == "pad"
    assert trace.op_name("%fusion.12.3 = f32[2]{0} fusion(...)") == "fusion"
    out, ops = trace.operand_shapes(MATMUL)
    assert out == ("bf16", (256, 19456))
    assert ops == [("bf16", (256, 7168)), ("bf16", (7168, 19456))]


def test_matmul_attributed_to_the_unpadded_problem():
    rec = [(256, 19200, 7168, 256, 512, 128), (250, 19200, 7168, 256, 512, 128),
           (8, 7168, 7168, 8, 512, 128)]
    call = trace.matmul_call(MATMUL, rec)
    assert (call.M, call.N, call.K) == (250, 19200, 7168)   # the smallest
    padded = trace.matmul_call(MATMUL)
    assert (padded.M, padded.N, padded.K) == (256, 19456, 7168)
    assert trace.matmul_call(PAD) is None


def test_roofline_share_and_its_bound():
    rec = [(256, 19200, 7168, 256, 512, 128)]
    least, why = trace.least_time_s(trace.matmul_call(MATMUL, rec), PEAK)
    assert why == "compute"
    assert least == pytest.approx(2 * 256 * 19200 * 7168 / 197e12)
    share, bound = trace.roofline([(0, 961593, MATMUL)], rec, PEAK)
    assert share == pytest.approx(least / 961593e-9)
    assert set(bound) == {"compute"}
    decode = MATMUL.replace("256,", "8,")
    _, why = trace.least_time_s(trace.matmul_call(decode), PEAK)
    assert why == "memory"


def test_a_share_over_100_percent_is_reported_not_hidden():
    """An event faster than the chip's least time reads above 1: the
    operations or bytes are counted too high, and it must show."""
    share, _ = trace.roofline([(0, 1000, MATMUL)], (), PEAK)
    assert share > 1.0
    assert trace.roofline([(0, 1000, PAD)], (), PEAK) is None


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        bench.peaks("TPU v99")
    assert bench.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_breakdown_ops_and_idle_gaps_by_host_activity():
    ops = [(0, 10, MATMUL), (10, 12, PAD), (40, 50, MATMUL), (60, 62, PAD),
           (90, 100, PAD)]
    host = [("client.step", 0, 70), ("client.wait", 70, 90)]
    spans = [("serve.admit", 12, 30)]
    t = Trace(devices=[ops], host=host)
    assert trace.device_ops(t, 0, 100) == [
        ["zero_stall_matmul_s4_ijk", pytest.approx(20e-9)],
        ["pad", pytest.approx(14e-9)]]
    gaps = dict(trace.idle_gaps(t, 0, 100, spans))
    assert gaps == {"serve.admit": pytest.approx(28e-9),     # 12..40
                    "client.step": pytest.approx(10e-9),     # 50..60
                    "client.wait": pytest.approx(28e-9)}     # 62..90


def test_device_ops_count_a_loop_body_once():
    """A ``while`` event holds its body's events: each is counted for
    its own time only."""
    loop = "%while.4 = (s32[]) while(s32[] %x), body=%b"
    ops = [(0, 100, loop), (10, 30, MATMUL), (40, 50, PAD), (120, 130, PAD)]
    t = Trace(devices=[ops], host=[])
    got = {k: v for k, v in trace.device_ops(t, 0, 200)}
    assert got == {"while": pytest.approx(70e-9),
                   "zero_stall_matmul_s4_ijk": pytest.approx(20e-9),
                   "pad": pytest.approx(20e-9)}
    assert sum(got.values()) == pytest.approx(trace.busy_s(t, 0, 200))


def test_kernel_events_inside_the_window_only():
    ops = [(0, 10, MATMUL), (20, 30, PAD), (95, 105, MATMUL)]
    t = Trace(devices=[ops], host=[])
    assert [e[0] for e in trace.kernel_events(t, "zero_stall_matmul", 0, 100)
            ] == [0]


def test_recorded_v5e_trace():
    """Events trimmed from a traced run of dsc33b.repo-complete on one
    TPU v5e (see data/README in PERF.md): every matmul parses, shares
    stay under 100%, the window is mostly busy."""
    rec = json.loads((DATA / "dsc33b_trace_trim.json").read_text())
    t = Trace(devices=[[tuple(e) for e in rec["ops"]]],
              host=[tuple(h) for h in rec["host"]])
    w0, w1 = rec["window"]
    busy = trace.busy_s(t, w0, w1)
    assert 0 < busy <= (w1 - w0) * 1e-9
    events = trace.kernel_events(t, "zero_stall_matmul", w0, w1)
    assert events
    records = [tuple(r) for r in rec["matmul_records"]]
    assert all(trace.matmul_call(e[2], records) is not None for e in events)
    share, _ = trace.roofline(events, records, PEAK)
    assert 0.05 < share < 1.0
    names = [n for n, _ in trace.device_ops(t, w0, w1)]
    assert any(n.startswith("zero_stall_matmul") for n in names)


def test_load_reads_a_cpu_profile(tmp_path):
    """The reader on a real profile: no TPU plane on the CPU, so no
    device ops, and the client's annotations are found."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("client.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = trace.load(str(tmp_path))
    assert t.devices == []
    names = [h[0] for h in t.host]
    assert "bench.window" in names and "client.step" in names
    w0, w1 = trace.window(t)
    assert w1 > w0
