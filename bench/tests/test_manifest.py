"""BENCHMARK.json against the benchmark's contract, and the harness's
promise that a cell is added by adding files and entries alone."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import manifest as M

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def man():
    return M.load()


def test_top_level_keys_and_entries(man):
    assert set(man) == TOP
    assert man["command"] == ["python3", "bench/run.py"]
    assert man["paths"] == ["bench"]
    assert isinstance(man["run_seconds"], int) and \
        1 <= man["run_seconds"] <= 51
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in man["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}


def test_names_units_and_text(man):
    entries = man["configs"] + man["workloads"] + man["end_to_end"] + \
        man["per_layer"]
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in man[section]]
        assert len(names) == len(set(names)), section
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for w in man["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in man["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200
    assert len(json.dumps(man)) < 64 * 1024


def test_every_name_resolves_to_its_file(man):
    files = [c["file"] for c in man["configs"]]
    assert len(files) == len(set(files))
    for c in man["configs"]:
        assert c["file"].startswith("bench/")
        conf = M.config(man, c["name"])
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert M.reference_path(conf["reference"]).is_file()
    for w in man["workloads"]:
        traffic = M.read_json(M.traffic_path(w["traffic"]))
        assert M.driver_path(traffic["kind"]).is_file()
        limits = M.read_json(M.cell_path(w["name"]))["limits"]
        assert limits and set(limits) <= {"loss_gap", "grad_gap",
                                          "change_gap", "grad_diff_gap"}
        M.config_entry(man, w["config"])
    for m in man["per_layer"]:
        assert callable(M.load_module(M.metric_path(m["name"])).read)


# keys a configuration may cut: its depth, and the chip's share of a
# layer (experts held, a slice of the vocabulary); never a width
SCALE = re.compile(r"(^|_)(layers?|experts|vocab_size)$")


def test_reduced_keys_are_what_changed(man):
    """A configuration file records each changed key as [source, run],
    runs the second value, and cuts only depth or a chip's share, to a
    smaller number."""
    for c in man["configs"]:
        conf = M.config(man, c["name"])
        for key, (was, now) in conf["reduced"].items():
            assert conf[key] == now and was != now, key
            assert SCALE.search(key) and "per_tok" not in key, key
            assert isinstance(now, int) and 0 < now < was, key


def test_moves_and_cell_coverage(man):
    cells = [w["name"] for w in man["workloads"]]
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for cell in cells:
        reported = {m["name"] for m in M.metrics_for(man, cell,
                                                     "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        assert M.metrics_for(man, cell, "per_layer")
    used = {w["config"] for w in man["workloads"]}
    assert used == {c["name"] for c in man["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(cells) // 2)


def _cpu_run(root, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload", cell,
         "--seed", "2147483999", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)


def test_new_cell_is_found_without_code_edits(tmp_path):
    """A copy with one more traffic file, cell file and entry: the
    harness resolves the new cell from the files alone."""
    root = tmp_path / "checkout"
    shutil.copytree(M.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    man = M.load()
    traffic = M.read_json(M.traffic_path("exact_4x512"))
    traffic["argv"][traffic["argv"].index("--batch") + 1] = "3"
    traffic["step"]["batch"] = 3
    (root / "bench" / "traffic" / "exact_3x512.json").write_text(
        json.dumps(traffic))
    shutil.copy(M.cell_path("qwen3_train_exact"),
                root / "bench" / "workloads" / "qwen3_train_exact_b3.json")
    man["workloads"].append({
        "name": "qwen3_train_exact_b3", "config": "qwen3-1.7b",
        "traffic": "exact_3x512", "chips": 1, "why": "added by files"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    from bench.run import context
    ctx = context(root, M.load(root), "qwen3_train_exact_b3", 5, 1.0, 0, 1,
                  None)
    assert ctx.traffic["step"]["batch"] == 3
    assert ctx.conf["hidden_size"] == 2048
    assert {m["name"] for m in ctx.e2e} == {"tokens_per_s", "setup_s"}
    # the copy holds BENCHMARK.json and bench/ alone: no program, no chip
    proc = _cpu_run(root, "qwen3_train_exact_b3")
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_on_cpu_exits_nonzero_without_metrics():
    proc = _cpu_run(M.ROOT, "qwen3_train_exact")
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout and proc.stdout.strip() == ""
