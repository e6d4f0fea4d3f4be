"""From a JAX profiler trace to device busy and idle time, per-kernel
device time, roofline shares and the ``breakdown`` of a result line.

A trace is first read into plain lists (:func:`load`): each device
operation as ``(start_ns, end_ns, text)`` per chip, where ``text`` is
the HLO instruction the TPU profiler names the event by (it carries the
operand shapes), and each host annotation as ``(name, start_ns,
end_ns)``.  Everything after that works on those lists, so the
reduction is tested on recorded and synthetic events alike.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_NAME = re.compile(r"^%?([A-Za-z0-9_.\-]+)")
_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "pred": 1}


@dataclasses.dataclass
class Trace:
    """Device operations per chip and host annotations, in ns."""

    devices: list            # [[(start, end, text), ...] per chip]
    host: list               # [(name, start, end), ...]


def load(path: str, host_prefixes=("client.", "bench.")) -> Trace:
    """Read an ``.xplane.pb`` file (or the newest one under a profile
    directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events)
            devices.append(sorted(ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefixes):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    return Trace(devices=devices, host=sorted(host, key=lambda h: h[1]))


def window(trace: Trace, name: str = "bench.window") -> tuple[float, float]:
    """The traced window: the host annotation ``name`` (first one)."""
    for n, s, e in trace.host:
        if n == name:
            return s, e
    raise ValueError(f"no {name!r} annotation in the trace")


def busy_intervals(ops, w0: float, w1: float) -> list:
    """Union of the device operations' intervals, clipped to the window."""
    out = []
    for s, e, _ in sorted(ops):
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_s(trace: Trace, w0: float, w1: float) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    if not trace.devices:
        return 0.0
    tot = sum(sum(e - s for s, e in busy_intervals(ops, w0, w1))
              for ops in trace.devices)
    return tot / len(trace.devices) * 1e-9


def op_name(text: str) -> str:
    """``%zero_stall_matmul_s4_ijk.1 = bf16[...] custom-call(...)`` ->
    ``zero_stall_matmul_s4_ijk``; ``%fusion.12 = ...`` -> ``fusion``."""
    m = _NAME.match(text)
    name = m.group(1) if m else text
    return re.sub(r"(\.\d+)+$", "", name)


def operand_shapes(text: str) -> tuple[tuple, list]:
    """(output (dtype, dims), [operand (dtype, dims), ...]) of an HLO
    instruction's text; layouts and tilings are ignored."""
    lhs, _, rhs = text.partition(" = ")
    out = _SHAPE.search(rhs)
    paren = rhs.find("(", rhs.find(" ") if " " in rhs else 0)
    args = rhs[paren + 1:] if paren >= 0 else ""
    args = args.split("),", 1)[0]
    ops = [(d, tuple(int(x) for x in dims.split(",") if x))
           for d, dims in _SHAPE.findall(args)]
    o = (out.group(1), tuple(int(x) for x in out.group(2).split(",") if x)
         ) if out else None
    return o, ops


def kernel_events(trace: Trace, prefix: str, w0: float, w1: float) -> list:
    """Device operations of one kernel family inside the window, on
    every chip: ``(start, end, text)``."""
    return [(s, e, t) for ops in trace.devices for s, e, t in ops
            if s >= w0 and e <= w1 and op_name(t).startswith(prefix)]


@dataclasses.dataclass(frozen=True)
class MatmulCall:
    M: int
    N: int
    K: int
    in_bytes: int
    out_bytes: int


def matmul_call(text: str, records=()) -> MatmulCall | None:
    """The problem a ``zero_stall_matmul`` event solved.

    The event's operands are padded to the kernel's tiles.  Where the
    program recorded the unpadded problems it traced (``records``:
    ``(M, N, K, bm, bn, bk)``), the event is attributed to the smallest
    recorded problem that pads to its shape, so that padding counts as
    time and never as work; with no such record, the padded shape
    itself is taken."""
    out, ops = operand_shapes(text)
    if out is None or len(ops) < 2 or len(ops[0][1]) != 2 \
            or len(ops[1][1]) != 2:
        return None
    (da, (Mp, Kp)), (_, (_, Np)) = ops[0], ops[1]
    ib, ob = _DTYPE_BYTES.get(da, 2), _DTYPE_BYTES.get(out[0], 2)

    def pads(n, b):
        return -(-n // b) * b
    hits = [(M * N * K, M, N, K) for M, N, K, bm, bn, bk in records
            if (pads(M, bm), pads(N, bn), pads(K, bk)) == (Mp, Np, Kp)]
    if hits:
        _, M, N, K = min(hits)
        return MatmulCall(M, N, K, ib, ob)
    return MatmulCall(Mp, Np, Kp, ib, ob)


def least_time_s(call: MatmulCall, peak: dict) -> tuple[float, str]:
    """The chip's least time for one call, from the peak table's entry,
    and what bounds it."""
    flops = 2.0 * call.M * call.N * call.K
    nbytes = (call.M * call.K + call.K * call.N) * call.in_bytes \
        + call.M * call.N * call.out_bytes
    rate = peak["int8_ops_per_s"] if call.in_bytes == 1 \
        else peak["bf16_flops_per_s"]
    tc, tm = flops / rate, nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def roofline(events, records, peak: dict):
    """Σ least time / Σ device time over ``events`` (share, 0..1),
    and the least time that compute and memory each bound.  None when
    there is no event."""
    least, spent = 0.0, 0.0
    bound = collections.Counter()
    for s, e, text in events:
        call = matmul_call(text, records)
        if call is None:
            continue
        t, why = least_time_s(call, peak)
        least += t
        bound[why] += t
        spent += (e - s) * 1e-9
    if spent <= 0:
        return None
    return least / spent, dict(bound)


def self_times(ops, w0: float, w1: float) -> collections.Counter:
    """Seconds by operation name, each operation's own time only: the
    profiler nests a loop's body operations inside the ``while`` event,
    so a parent's time less its children's is what it spent itself."""
    tot = collections.Counter()
    stack = []                    # [end, name, own ns]
    for s, e, t in sorted(((max(s, w0), min(e, w1), t) for s, e, t in ops
                           if min(e, w1) > max(s, w0)),
                          key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= s:
            end, name, own = stack.pop()
            tot[name] += own * 1e-9
        if stack and e <= stack[-1][0]:
            stack[-1][2] -= e - s
        stack.append([e, op_name(t), e - s])
    for end, name, own in stack:
        tot[name] += own * 1e-9
    return tot


def device_ops(trace: Trace, w0: float, w1: float, top: int = 10) -> list:
    """The operations that took most device time of their own, by name,
    averaged over the chips: ``[[name, seconds], ...]``."""
    tot = collections.Counter()
    for ops in trace.devices:
        tot.update(self_times(ops, w0, w1))
    n = max(len(trace.devices), 1)
    return [[k, v / n] for k, v in tot.most_common(top)]


def idle_gaps(trace: Trace, w0: float, w1: float, spans=(), top: int = 10
              ) -> list:
    """Device idle time inside the window (chip 0), by what the host
    was doing at the middle of each gap: the innermost host span
    (``(name, start_ns, end_ns)``: the trace's annotations plus
    ``spans``) that holds it.  ``[[name, seconds], ...]``."""
    if not trace.devices:
        return []
    busy = busy_intervals(trace.devices[0], w0, w1)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    host = sorted((h for h in list(trace.host) + list(spans)
                   if h[0] != "bench.window"), key=lambda h: h[1])
    tot = collections.Counter()
    active, j = [], 0
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        while j < len(host) and host[j][1] <= mid:
            active.append(host[j])
            j += 1
        active = [h for h in active if h[2] >= mid]
        name = min(active, key=lambda h: h[2] - h[1])[0] if active \
            else "host outside the client"
        tot[name] += (b - a) * 1e-9
    return [[k, v] for k, v in tot.most_common(top)]
