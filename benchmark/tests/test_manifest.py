"""BENCHMARK.json and the files it names, held to the driver's checker as the
builder's contract words it.  PR 23 was refused for a layer written as plain
words: no name, unit or layer here may leave the checker's alphabet."""
import json
import re

import pytest

from conftest import BENCH, REPO, load_module

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")


def one_line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s \
        and "\t" not in s


def cells_of(metric, manifest):
    return metric.get("workloads", [w["name"] for w in manifest["workloads"]])


def test_top_level(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (REPO / p).is_dir()
    assert 1 <= len(manifest["command"]) <= 32
    for word in manifest["command"]:
        assert one_line(word) and not word.startswith("/") \
            and ".." not in word
        if "/" in word or (REPO / word).exists():
            assert any(word.startswith(p + "/") for p in manifest["paths"])
    # the full check must fit the driver's day with all 24 cells
    n_runs = 2 + 14 * 24
    assert n_runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_files_under_paths_are_named_from_the_alphabet(manifest):
    import subprocess
    out = subprocess.run(["git", "ls-files", "-co", "--exclude-standard",
                          *manifest["paths"]], cwd=REPO, text=True,
                         capture_output=True)
    if out.returncode != 0:
        pytest.skip("not a git checkout")
    for f in out.stdout.split():
        assert PATH.match(f), f


def test_configs(manifest):
    names = [c["name"] for c in manifest["configs"]]
    files = [c["file"] for c in manifest["configs"]]
    assert 1 <= len(names) <= 24
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        body = json.loads((REPO / c["file"]).read_text())
        assert body["reduced"] == c["reduced"]
        assert one_line(body["source"], 400)
        assert (BENCH / "generators" / f"{body['generator']}.py").is_file()
        for key in ("shape", "rows", "holdout", "params", "gate",
                    "leaf_count_slack_rows", "predict_check", "assumed",
                    "rehearse"):
            assert key in body, (c["name"], key)


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
        mix = [BENCH / "traffic" / (w["traffic"] + s) for s in DATA_SUFFIXES]
        mix = [m for m in mix if m.is_file()]
        assert len(mix) == 1, f"traffic file of {w['name']}"
        loop = json.loads(mix[0].read_text())["loop"]
        assert (BENCH / "loops" / f"{loop}.py").is_file()
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


def test_metrics(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    layer = {m["name"]: m for m in manifest["per_layer"]}
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    assert len(e2e) == len(manifest["end_to_end"])
    assert len(layer) == len(manifest["per_layer"])
    assert not set(e2e) & set(layer)
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in layer.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
        assert NAME.match(m["layer"]), f"layer of {m['name']}"
        assert m["moves"] in e2e
        moved = set(cells_of(e2e[m["moves"]], manifest))
        assert set(cells_of(m, manifest)) <= moved, m["name"]
    for m in (*e2e.values(), *layer.values()):
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["name"]
        assert m["better"] in ("lower", "higher")
        assert set(cells_of(m, manifest)) <= set(cells)
    for c in cells:
        mine = [n for n, m in e2e.items() if c in cells_of(m, manifest)]
        assert "setup_s" in mine and len(mine) >= 2, c
        assert any(c in cells_of(m, manifest) for m in layer.values()), c


def test_layer_readers_say_what_the_manifest_says(manifest):
    entries = {m["name"]: m for m in manifest["per_layer"]}
    files = sorted((BENCH / "layers").glob("*.py"))
    assert {f.name[:-3] for f in files} >= set(entries)
    for f in files:
        mod = load_module(f)
        assert mod.NAME == f.name[:-3]
        assert NAME.match(mod.NAME) and NAME.match(mod.LAYER), f.name
        assert UNIT.match(mod.UNIT), f.name
        assert callable(mod.read)
        if mod.NAME in entries:
            m = entries[mod.NAME]
            assert (mod.UNIT, mod.LAYER, mod.MOVES) == (
                m["unit"], m["layer"], m["moves"]), f.name


def test_run_py_names_no_cell_config_or_metric(manifest):
    text = (BENCH / "run.py").read_text()
    names = [e["name"] for k in ("configs", "workloads", "per_layer")
             for e in manifest[k]]
    names += [m["name"] for m in manifest["end_to_end"]
              if m["name"] not in ("setup_s", "peak_hbm_gb")]
    for n in names:
        assert n not in text, n
