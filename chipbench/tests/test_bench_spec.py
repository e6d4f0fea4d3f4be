"""BENCHMARK.json against the benchmark's contract, and the harness
finding cells, configurations and metrics by name in added files."""

import json
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
REPO = HERE.parent
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|^hidden_size$|intermediate|latent|"
                   r"state_size|proj|expan|experts_per_tok")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("chipbench/")
        assert all(NAME.match(k) and not WIDTH.search(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        if "roofline" in m["name"]:
            assert m["name"].split(".")[0].endswith("_roofline")
            assert m["unit"] == "%"


def cells_of(m):
    return m.get("workloads", [w["name"] for w in BENCH["workloads"]])


def test_every_cell_has_its_files_and_metrics():
    configs = {c["name"]: c for c in BENCH["configs"]}
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup, "setup_s is every cell's, later ones too"
    for w in BENCH["workloads"]:
        c = configs[w["config"]]
        assert (REPO / c["file"]).is_file()
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert (HERE / "limits" / f"{w['name']}.json").is_file()
        e2e = [m["name"] for m in BENCH["end_to_end"]
               if w["name"] in cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(w["name"] in cells_of(m) for m in BENCH["per_layer"])
    from chipbench import bench
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(bench.reader(m["name"]))
    for m in BENCH["per_layer"]:
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(cells_of(m)) <= set(cells_of(moved))


def test_config_files_list_what_they_changed():
    for c in BENCH["configs"]:
        conf = json.loads((REPO / c["file"]).read_text())
        assert set(conf["source_values"]) == set(c["reduced"])
        for k in c["reduced"]:
            assert conf[k] != conf["source_values"][k]
        assert (HERE / "reference" / f"{conf['reference']}.py").is_file()
        assert (HERE / "adapters" / f"{conf['reference']}.py").is_file()


def test_a_cell_and_a_metric_added_as_files_are_found(tmp_path):
    """A later cell brings its traffic, limits and metric files and an
    entry each in BENCHMARK.json; no existing file changes."""
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": "dsc33b.unique-complete", "config": "deepseek-coder-33b",
         "traffic": "unique-complete", "chips": 1, "why": "control"})
    bench["per_layer"].append(
        {"name": "prompt_tokens", "unit": "tokens", "better": "higher",
         "source": "host_clock", "layer": "client", "moves": "ttft_p50_ms",
         "workloads": ["dsc33b.unique-complete"]})
    bench["end_to_end"][0]["workloads"].append("dsc33b.unique-complete")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    traffic = json.loads((HERE / "traffic" / "repo-complete.json").read_text())
    del traffic["prefix"]
    (root / "chipbench" / "traffic" / "unique-complete.json").write_text(
        json.dumps(traffic))
    shutil.copy(HERE / "limits" / "dsc33b.repo-complete.json",
                root / "chipbench" / "limits" / "dsc33b.unique-complete.json")
    (root / "chipbench" / "metrics" / "prompt_tokens.py").write_text(
        "def read(run):\n    return float(sum(len(g.req.prompt) "
        "for g in run.logs.values()))\n")
    code = (
        "import sys; sys.path.insert(0, '.')\n"
        "from chipbench import bench\n"
        "c = bench.load_cell('dsc33b.unique-complete')\n"
        "print(c.config_name, 'prefix' in c.traffic,\n"
        "      [m['name'] for m in c.per_layer],\n"
        "      [m['name'] for m in c.end_to_end])\n"
        "class R: logs = {}\n"
        "print(bench.reader('prompt_tokens')(R()))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=root,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    lines = out.stdout.splitlines()
    assert lines[0] == ("deepseek-coder-33b False ['prompt_tokens'] "
                        "['ttft_p50_ms', 'setup_s']")
    assert lines[1] == "0.0"


def test_unknown_workload_is_refused():
    from chipbench import bench
    with pytest.raises(KeyError, match="unknown workload"):
        bench.load_cell("no.such-cell")


def test_a_split_metric_shares_its_family_reader():
    """``<family>.<part>`` names that only split a quantity by the cells
    it moves are read by one ``metrics/<family>.py``; a metric with no
    reader is refused."""
    from chipbench import bench
    def file_of(metric):
        return Path(bench.reader(metric).__code__.co_filename).name
    assert file_of("idle_share.serve") == file_of("idle_share.batch") \
        == "idle_share.py"
    assert file_of("zsmm_roofline.batch") == "zsmm_roofline.py"
    assert file_of("mfu.serve") == "mfu.serve.py"
    with pytest.raises(FileNotFoundError, match="no reader"):
        bench.reader("no_such_metric.serve")
