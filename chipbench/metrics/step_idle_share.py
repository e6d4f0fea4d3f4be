"""step_idle_share.serve and step_idle_share.batch (one name per kind of
cell, one reader): device idle time on chip 0 that falls inside the
program's ``serve.step`` spans, over the traced window, in %.

The program's span records are mapped onto the trace's clock with the
offset the breakdown uses (the window annotation's start less the host
clock at the window's start).  A note splits that idle over the
innermost span open at each instant: ``serve.launch``, ``serve.sync``
(by what it reads), ``serve.install``, ... and ``serve.step`` itself
for the step's code outside every child span.  None where the program
records no ``serve.step`` span or the trace holds no device.
"""

import collections

from chipbench import trace

STEP = "serve.step"


def mapped_spans(run) -> list:
    """The program's spans on the trace's clock: ``(label, start_ns,
    end_ns)``, a ``serve.sync`` labelled by what it reads."""
    w0, _ = run.trace_window
    off = w0 - run.t0 * 1e9
    out = []
    for s in run.spans or ():
        if s.get("type") != "span":
            continue
        label = s["name"]
        if "what" in s:
            label = f"{label}:{s['what']}"
        out.append((label, s["t"] * 1e9 + off,
                    (s["t"] + s["dur_s"]) * 1e9 + off))
    return out


def step_segments(spans) -> list:
    """Pieces of time inside a ``serve.step`` span, each with the
    innermost span open over it: ``[(start, end, label), ...]`` in
    order.  Spans of one thread nest, so a stack of open spans gives
    the innermost at every instant."""
    out, stack, cur = [], [], 0.0

    def close_until(t):
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, label = stack.pop()
            if end > cur:
                if any(lbl == STEP for _, lbl in stack) or label == STEP:
                    out.append((cur, end, label))
                cur = end

    for label, s, e in sorted(spans, key=lambda h: (h[1], -h[2])):
        close_until(s)
        if stack and s > cur and any(lbl == STEP for _, lbl in stack):
            out.append((cur, s, stack[-1][1]))
        cur = max(cur, s)
        stack.append((e, label))
    close_until(float("inf"))
    return out


def idle_in_steps(ops, w0: float, w1: float, segments) -> collections.Counter:
    """Device idle ns inside ``[w0, w1]`` that each segment's label
    holds (``ops``: one chip's ``(start, end, text)``)."""
    busy = trace.busy_intervals(ops, w0, w1)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    tot = collections.Counter()
    i = j = 0
    while i < len(gaps) and j < len(segments):
        a, b = gaps[i]
        s, e, label = segments[j]
        lo, hi = max(a, s, w0), min(b, e, w1)
        if hi > lo:
            tot[label] += hi - lo
        if b <= e:
            i += 1
        else:
            j += 1
    return tot


def read(run):
    if run.trace is None or not run.trace.devices or not run.spans:
        return None
    spans = mapped_spans(run)
    if not any(label == STEP for label, _, _ in spans):
        return None
    w0, w1 = run.trace_window
    by = idle_in_steps(run.trace.devices[0], w0, w1, step_segments(spans))
    idle = sum(by.values())
    steps = sum(1 for label, _, _ in spans if label == STEP)
    split = ", ".join(f"{k} {v * 1e-9:.9f} s ({100.0 * v / idle:.1f}%)"
                      for k, v in by.most_common()) if idle else "none"
    run.note(f"step_idle_share: {idle * 1e-9:.9f} s idle inside {steps} "
             f"serve.step spans; innermost span: {split}")
    return 100.0 * idle / (w1 - w0)
