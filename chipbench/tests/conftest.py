"""Tests of the benchmark run on the CPU: JAX is held to it, and the
repository root and ``src/`` are importable from any working directory."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_REPO = Path(__file__).resolve().parents[2]
for p in (str(_REPO / "src"), str(_REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

import json  # noqa: E402

import pytest  # noqa: E402

_HERE = _REPO / "chipbench"


def tiny_cell(traffic: str, limit: float):
    """A cell of the repository's traffic and reference at a size the
    CPU runs in seconds: the deepseek configuration file with tiny
    widths, and the traffic with short prompts and a small engine."""
    from chipbench import bench
    cfg = json.loads((_HERE / "configs" / "deepseek-coder-33b.json")
                     .read_text())
    cfg.update(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, intermediate_size=256,
               vocab_size=512)
    t = json.loads((_HERE / "traffic" / f"{traffic}.json").read_text())
    if t["loop"] == "open":
        t.update(arrivals={"process": "poisson", "rate_per_s": 3.0},
                 prefix={"groups": 3, "zipf_s": 1.1, "tokens": {
                     "dist": "loguniform", "min": 32, "max": 64}},
                 prompt_tokens={"dist": "lognormal", "median": 12,
                                "sigma": 0.6, "min": 4, "max": 40},
                 output_tokens={"dist": "lognormal", "median": 10,
                                "sigma": 0.5, "min": 4, "max": 16})
        t["engine"].update(num_slots=4, max_len=128, num_pages=40,
                           prefill_chunk=32, bucket_sizes=[32])
    else:
        t.update(outstanding=8, prompt_tokens={
            "dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
            "max": 48}, output_tokens={"dist": "lognormal", "median": 12,
                                       "sigma": 0.5, "min": 4, "max": 30})
        t["engine"].update(num_slots=4, max_len=96, num_pages=30,
                           prefill_chunk=32, bucket_sizes=[8, 16, 32])
    return bench.Cell(
        name=f"tiny.{traffic}", chips=1, config_name="tiny", config=cfg,
        traffic=t, limits={"logit_gap": limit, "sample_tokens": 128,
                           "sample_requests": 8, "drain_s": 60},
        end_to_end=[{"name": "setup_s", "unit": "s"}], per_layer=[])


@pytest.fixture
def run_tiny(monkeypatch, tmp_path):
    """Drive a whole run of a tiny cell on the CPU (no chip check,
    Pallas kernels in interpret mode).  The compile-cache variable is
    set after JAX has started, so the run leaves JAX's cache setting
    alone and keeps nothing on disk."""
    import jax

    from chipbench import bench
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    prev = jax.config.jax_compilation_cache_dir
    lines = []

    def go(traffic, seed, limit=0.05, seconds=2.0, inspect=None):
        cell = tiny_cell(traffic, limit)
        return bench.run(cell.name, seed, seconds, False, cell=cell,
                         require_tpu=False, backend="interpret",
                         log=lines.append, inspect=inspect), lines
    yield go
    jax.config.update("jax_compilation_cache_dir", prev)
