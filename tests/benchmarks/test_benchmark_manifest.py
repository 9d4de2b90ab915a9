"""BENCHMARK.json held to the driver's rules before it is ever sent.

PR 22's benchmark was refused before any run for one ``layer`` written
as plain words. Everything the builder's contract says of the manifest
that can be checked without the chip is checked here, in tier-1.
"""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LAYERS = {"task_driver", "input", "trainer", "kernels", "mesh", "device"}
# widths may never be reduced: what the contract lists
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                   r"head_size|head_dim|expansion|experts_per_tok)")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode("utf-8")) <= 64 * 1024
    return json.loads(raw)


def one_line(text, limit=200):
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def test_top_level_keys_and_command(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    cmd, paths = manifest["command"], manifest["paths"]
    assert 1 <= len(cmd) <= 32 and all(one_line(w) for w in cmd)
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") \
            and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in cmd:
        assert not word.startswith("/") and ".." not in word.split("/")
        if os.path.exists(os.path.join(ROOT, word)):
            # a file of the repo named on the command line lies under paths
            assert any(word == p or word.startswith(p + "/")
                       for p in paths), word


def test_run_seconds_fits_a_full_check_at_24_cells(manifest):
    rs = manifest["run_seconds"]
    assert isinstance(rs, int) and not isinstance(rs, bool)
    assert 10 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(manifest):
    configs = manifest["configs"]
    assert 1 <= len(configs) <= 24
    names = [c["name"] for c in configs]
    files = [c["file"] for c in configs]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in configs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["name"] in used, "every configuration has a cell"
        assert PATH.match(c["file"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key)
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert held["name"] == c["name"] and held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]
        assert held["item"] in ("image", "token")
        if "reference" in held:
            # a file under paths, found as a feed is
            assert NAME.match(held["reference"])
            assert any(os.path.isfile(os.path.join(
                ROOT, p, "references", held["reference"] + ".py"))
                for p in manifest["paths"])
        if held["item"] == "token":
            # a row counts for its positions
            assert held["items_per_row"] == held["input_shape"][-1]
        else:
            assert held.get("items_per_row", 1) == 1
        conf = os.path.join(os.path.dirname(os.path.join(ROOT, c["file"])),
                            held["net"]["conf"])
        assert os.path.isfile(conf)
        from cxxnet_tpu.config import parse_config_string
        with open(conf) as f:
            pairs = dict(parse_config_string(f.read()))
        assert [int(v) for v in pairs["input_shape"].split(",")] \
            == held["input_shape"]
        assert pairs.get("compute_dtype", "float32") == held["compute_dtype"]


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    names = [w["name"] for w in cells]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in manifest["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs
        assert w["chips"] in (1, 4)
        assert one_line(w["why"])
        traffic = os.path.join(BENCH, "traffic", w["traffic"] + ".json")
        assert os.path.isfile(traffic)
        with open(traffic) as f:
            mix = json.load(f)
        assert mix["name"] == w["traffic"]
        assert isinstance(mix["rows_per_chip"], int)
        assert os.path.isfile(os.path.join(BENCH, "feeds",
                                           mix["feed"] + ".py"))
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def metric_ok(m, cells, extra):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source"} \
        | extra, m
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    if "workloads" in m:
        assert m["workloads"] and set(m["workloads"]) <= cells


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e, per = manifest["end_to_end"], manifest["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(per) <= 128
    names = [m["name"] for m in e2e + per]
    assert len(set(names)) == len(names)
    for m in e2e:
        metric_ok(m, cells, {"bound"})
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in {m["name"] for m in e2e}
    reports = {m["name"]: set(m.get("workloads", cells)) for m in e2e + per}
    for m in per:
        metric_ok(m, cells, {"layer", "moves"})
        assert NAME.match(m["layer"]), "a layer is a name: no spaces"
        assert m["layer"] in LAYERS
        assert m["moves"] in {e["name"] for e in e2e}
        # reported in every cell where this one is
        assert reports[m["name"]] <= reports[m["moves"]]
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
    for cell in cells:
        mine = {n for n, where in reports.items() if cell in where}
        assert "setup_s" in mine
        assert len(mine & {m["name"] for m in e2e}) >= 2
        assert mine & {m["name"] for m in per}


def test_no_space_in_any_name_or_unit(manifest):
    def walk(node, key=""):
        if isinstance(node, dict):
            for k, v in node.items():
                yield from walk(v, k)
        elif isinstance(node, list):
            for v in node:
                yield from walk(v, key)
        else:
            yield key, node
    for key, value in walk(manifest):
        if key in ("name", "config", "traffic", "layer", "moves", "unit",
                   "reduced", "workloads", "better") \
                and isinstance(value, str):
            assert " " not in value, (key, value)


def test_files_under_paths_are_named_from_name_characters(manifest):
    import subprocess
    for p in manifest["paths"]:
        listed = subprocess.run(
            ["git", "ls-files", "--cached", "--others",
             "--exclude-standard", p],
            cwd=ROOT, capture_output=True, text=True)
        files = listed.stdout.split() if listed.returncode == 0 else [
            os.path.relpath(os.path.join(d, f), ROOT)
            for d, _, fs in os.walk(os.path.join(ROOT, p)) for f in fs
            if "__pycache__" not in d and "/.cache" not in d]
        assert files
        for f in files:
            assert PATH.match(f), f
