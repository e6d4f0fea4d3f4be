"""No result without a chip or without the program, and the FLOP counts
against the program's own parameter count."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import flops
from chipbench.adapters.llama import model_config
from chipbench.reference.llama import Dims

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parent


def _run(cwd, env=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "dsc33b.repo-complete", "--seed", str(2 ** 33 + 1), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(out):
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_no_tpu_exits_nonzero_with_no_result():
    out = _run(REPO)
    assert out.returncode == 2, out.stderr[-2000:]
    assert "not a TPU" in out.stderr
    _no_result(out)


def test_without_the_program_there_is_no_result(tmp_path):
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    out = _run(tmp_path)
    assert out.returncode != 0
    _no_result(out)


@pytest.mark.parametrize("name", ["deepseek-coder-33b", "mistral-large-123b"])
def test_token_flops_agree_with_param_count(name):
    conf = json.loads((HERE / "configs" / f"{name}.json").read_text())
    dims = Dims.from_config(conf)
    cfg = model_config(name, dims)
    # every weight the program counts, less the embedding table (a
    # lookup) and the norm scales (elementwise), is multiplied once
    matmul_params = cfg.param_count() - dims.vocab * dims.d \
        - (2 * dims.layers + 1) * dims.d
    no_attention = flops.token_flops(dims, 0, head=True) \
        - flops.attention_flops(dims, 1)
    assert no_attention == 2 * matmul_params
    # one-position prompt == one token; a long prompt sums its tokens
    assert flops.prefill_flops(dims, 0, 1, head=True) == \
        flops.token_flops(dims, 0, head=True)
    assert flops.prefill_flops(dims, 100, 356, head=False) == sum(
        flops.token_flops(dims, p, head=False) for p in range(100, 356))


def test_published_sizes():
    """Both configurations hold their published widths; only the depth
    (and deepseek's RoPE scaling, which the program lacks) differ."""
    ds = Dims.from_config(json.loads(
        (HERE / "configs" / "deepseek-coder-33b.json").read_text()))
    assert dataclasses.astuple(ds) == (8, 7168, 56, 8, 128, 19200, 32256,
                                       1e-6, 1e5)
    ml = Dims.from_config(json.loads(
        (HERE / "configs" / "mistral-large-123b.json").read_text()))
    assert dataclasses.astuple(ml) == (4, 12288, 96, 8, 128, 28672, 32768,
                                       1e-5, 1e6)
    n = model_config("m", ml).param_count()
    assert n * 2 == pytest.approx(12.68e9, rel=0.01)
