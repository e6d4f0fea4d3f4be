"""The traffic generator and the end-to-end metric arithmetic."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench.gen import Traffic, draw_len, len_range

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def spec(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def open_reqs(seed, name="repo-complete", seconds=400.0, **over):
    s = {**spec(name), **over}
    return Traffic(s, seed, 32256).open_schedule(seconds)


def closed_reqs(seed, n=300, **over):
    s = {**spec("batch-gen"), **over}
    stream = Traffic(s, seed, 32768).closed_stream()
    return [next(stream) for _ in range(n)]


def test_same_seed_same_requests():
    for a, b in [(open_reqs(2 ** 33 + 1), open_reqs(2 ** 33 + 1)),
                 (closed_reqs(7), closed_reqs(7))]:
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert x.due == y.due and x.max_new_tokens == y.max_new_tokens
            assert np.array_equal(x.prompt, y.prompt)


def test_another_seed_other_tokens_same_schedule():
    """The run's seed draws the contents; the file's schedule_seed draws
    sizes and arrivals, so every seed offers the same work."""
    for a, b in [(open_reqs(1), open_reqs(2 ** 40 + 3)),
                 (closed_reqs(1), closed_reqs(2))]:
        assert [(r.due, len(r.prompt), r.max_new_tokens, r.group)
                for r in a] == [(r.due, len(r.prompt), r.max_new_tokens,
                                 r.group) for r in b]
        same = sum(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
        assert same == 0


def test_schedule_seed_changes_schedule():
    a = open_reqs(1)
    b = open_reqs(1, schedule_seed=5)
    assert [r.due for r in a] != [r.due for r in b]


@pytest.mark.parametrize("name", ["repo-complete", "batch-gen"])
def test_lengths_within_clips(name):
    s = spec(name)
    reqs = open_reqs(3) if s["loop"] == "open" else closed_reqs(3, n=2000)
    lo, hi = len_range(s["prompt_tokens"])
    plo, phi = len_range(s["prefix"]["tokens"]) if "prefix" in s else (0, 0)
    olo, ohi = len_range(s["output_tokens"])
    for r in reqs:
        assert plo + lo <= len(r.prompt) <= phi + hi
        assert olo <= r.max_new_tokens <= ohi
        assert len(r.prompt) + r.max_new_tokens <= s["engine"]["max_len"]
    assert r.prompt.dtype == np.int32


def test_draw_len_clips_every_distribution():
    rng = np.random.default_rng(0)
    for d in ({"dist": "lognormal", "median": 100, "sigma": 3.0, "min": 10,
               "max": 200},
              {"dist": "loguniform", "min": 5, "max": 9}):
        xs = [draw_len(rng, d) for _ in range(500)]
        assert min(xs) >= d["min"] and max(xs) <= d["max"]
    with pytest.raises(ValueError, match="unknown length distribution"):
        draw_len(rng, {"dist": "uniform", "min": 1, "max": 2})


def test_repo_requests_share_their_prefix_exactly():
    s = spec("repo-complete")
    t = Traffic(s, 11, 32256)
    reqs = t.open_schedule(400.0)
    assert len(t.prefixes) == s["prefix"]["groups"]
    groups = {r.group for r in reqs}
    assert len(groups) > 3
    for r in reqs:
        p = t.prefixes[r.group]
        assert np.array_equal(r.prompt[:len(p)], p)
    # the own part differs from any other request's
    own = {tuple(r.prompt[len(t.prefixes[r.group]):][:16]) for r in reqs}
    assert len(own) == len(reqs)


def test_batch_requests_share_nothing():
    reqs = closed_reqs(5, n=500)
    heads = {tuple(r.prompt[:16]) for r in reqs}
    assert len(heads) == len(reqs)
    assert all(r.group == -1 for r in reqs)


def test_zipf_popularity_orders_groups():
    reqs = open_reqs(1, seconds=5000.0)
    counts = np.bincount([r.group for r in reqs], minlength=8)
    assert counts[0] > counts[3] > counts[7]


def test_arrival_rate_matches_the_file():
    s = spec("repo-complete")
    reqs = open_reqs(1, seconds=5000.0)
    rate = len(reqs) / 5000.0
    assert rate == pytest.approx(s["arrivals"]["rate_per_s"], rel=0.08)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 5000.0


def test_poisson_gaps_and_unknown_process():
    """Poisson arrivals: exponential gaps, whose spread equals their
    mean; a process the generator lacks is refused."""
    gaps = np.diff([r.due for r in open_reqs(1, seconds=5000.0)])
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.1)
    s = spec("repo-complete")
    s["arrivals"] = {"process": "gamma", "rate_per_s": 0.5}
    with pytest.raises(ValueError, match="unknown arrival process"):
        Traffic(s, 1, 100).open_schedule(10.0)


def test_traffic_files_name_their_source():
    for name in ("repo-complete", "batch-gen"):
        assert spec(name)["source"]


def test_warmup_covers_groups_buckets_chunk_and_slots():
    a = Traffic(spec("repo-complete"), 1, 32256)
    w = a.warmup()
    assert len(w) == 8                       # 8 groups fill 8 slots
    assert all(len(r.prompt) > 256 for r in w)
    for r, p in zip(w, a.prefixes):
        assert np.array_equal(r.prompt[:len(p)], p)
    b = Traffic(spec("batch-gen"), 1, 32768)
    lens = sorted(len(r.prompt) for r in b.warmup())
    assert len(lens) == 32
    assert {64, 128, 256, 257} <= set(lens)


def _logs(specs):
    """``[(due, submit, [token times], queue wait)]`` -> client logs."""
    from chipbench.gen import Req
    from chipbench.loops import Log
    out = {}
    for rid, (due, submit, times, wait) in enumerate(specs):
        req = Req(rid=rid, prompt=np.zeros(4, np.int32),
                  max_new_tokens=len(times))
        out[rid] = Log(req=req, due=due, submit=submit, times=list(times),
                       tokens=[1] * len(times), queue_wait_s=wait)
    return out


class _Run:
    def __init__(self, logs, t0=0.0, t_end=1.0):
        self.logs, self.t0, self.t_end = logs, t0, t_end


def _read(metric, run):
    from chipbench import bench
    return bench.reader(metric)(run)


def test_ttft_counts_from_the_due_time_over_every_request():
    """Nine requests wait 1 s from due to first token (0.5 s of it
    before submit), one waits 10 s: the median is 1 s and the p90
    interpolates over all ten, from the due time, not from submit."""
    specs = [(i, i + 0.5, [i + 1.0], 0.0) for i in range(9)]
    specs.append((9, 9.5, [19.0], 0.0))
    run = _Run(_logs(specs))
    assert _read("ttft_p50_ms", run) == pytest.approx(1000.0)
    assert _read("ttft_p90_ms", run) == pytest.approx(1900.0)


def test_itl_tail_is_taken_over_every_gap_of_every_request():
    """50 gaps of 10 ms in one request, 50 of 30 ms in another: the p99
    of the pooled gaps is 30 ms (a mean of per-request tails would read
    20); tokens of one block share a time and make gaps of 0."""
    a = [0.01 * k for k in range(51)]
    b = [0.03 * k for k in range(51)]
    run = _Run(_logs([(0.0, 0.0, a, 0.0), (0.0, 0.0, b, 0.0)]))
    assert _read("itl_p99_ms", run) == pytest.approx(30.0)
    block = _Run(_logs([(0.0, 0.0, [1.0] * 4 + [2.0] * 4, 0.0)]))
    assert _read("itl_p99_ms", block) == pytest.approx(
        np.percentile([0.0] * 6 + [1000.0], 99))


def test_queue_wait_and_output_rate_count_everything():
    specs = [(0.0, 0.1 * i, [1.0, 1.5], 0.2) for i in range(10)]
    run = _Run(_logs(specs), t0=0.0, t_end=4.0)
    waits = [(0.1 * i + 0.2) * 1e3 for i in range(10)]
    assert _read("queue_wait_p90_ms", run) == pytest.approx(
        np.percentile(waits, 90))
    assert _read("output_tok_s", run) == pytest.approx(20 / 4.0)
