"""BENCHMARK.json: every cell resolves to its files, and every name, unit
and key keeps to the benchmark's contract."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import spec  # noqa: E402

BM = spec.benchmark(ROOT)
CELLS = [w["name"] for w in BM["workloads"]]
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
FREE_TEXT = re.compile(r"^[^\n\t]{1,200}$")


def test_top_level_keys_are_exact():
    assert set(BM) == TOP_KEYS
    assert BM["command"] == ["python3", "bench/run.py"]
    assert 1 <= BM["run_seconds"] <= 51
    assert isinstance(BM["run_seconds"], int)


def test_paths_hold_only_the_benchmark():
    for p in BM["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    assert BM["command"][1].split("/")[0] in BM["paths"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    res = spec.resolve(BM, name, ROOT)
    conf, mix, lim = res["config"], res["traffic"], res["limits"]
    m = conf["model"]
    assert conf["name"] == res["cell"]["config"]
    for k in ("slots", "max_len", "prefill_chunk", "prefill_interleave",
              "wave_requests", "check_requests", "check_bucket"):
        assert isinstance(mix[k], int) and mix[k] > 0, k
    assert mix["max_len"] % mix["prefill_chunk"] == 0
    assert mix["prompt"]["max"] + mix["output"]["max"] <= mix["max_len"]
    assert m["vocab"] <= m["vocab_rows"]
    assert set(lim) == {"logit_gap"} and 0 < lim["logit_gap"] < 1
    for metric in spec.per_layer(BM, name):
        assert callable(spec.reader(metric["name"], ROOT).read)
    assert res["cell"]["chips"] in (1, 4)


def test_configs_are_used_and_files_distinct():
    used = {w["config"] for w in BM["workloads"]}
    assert used == {c["name"] for c in BM["configs"]}
    files = [c["file"] for c in BM["configs"]]
    assert len(set(files)) == len(files)
    for c in BM["configs"]:
        assert c["file"].split("/")[0] in BM["paths"]
        conf = spec.load_json(os.path.join(ROOT, c["file"]))
        assert c["reduced"] == conf["reduced"]
        assert set(conf["reduced"]) <= set(conf["published"])
        for k in conf["reduced"]:
            assert not re.search(r"(size|_dim|_rank|inner|embd|head)", k)


def test_names_units_and_text_fields():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BM[group]:
            assert spec.NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    for _, n in names:
        assert len(n) <= 64
    assert len(set(names)) == len(names)
    for e in BM["end_to_end"] + BM["per_layer"]:
        assert spec.UNIT.match(e["unit"]), e["unit"]
        assert e["better"] in ("lower", "higher")
    for e in BM["configs"] + BM["workloads"]:
        assert FREE_TEXT.match(e["why"])
    for w in BM["workloads"]:
        assert spec.NAME.match(w["config"]) and spec.NAME.match(w["traffic"])
    for c in BM["configs"]:
        assert FREE_TEXT.match(c["source"])
        for k in c["reduced"]:
            assert spec.NAME.match(k)
    assert len(json.dumps(BM)) < 64 * 1024


def test_metrics_keep_their_contract():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert set(e2e) == {"output_tok_s", "tpot_p95_ms", "ttft_p95_ms",
                        "setup_s"}
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["source"] == "device_trace"
        assert FREE_TEXT.match(m["layer"])
        assert set(m["workloads"]) <= set(CELLS)
    for c in CELLS:
        assert spec.per_layer(BM, c)
        assert "setup_s" in [m["name"] for m in spec.end_to_end(BM, c)]


def test_layers_are_named_in_perf_md():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in BM["per_layer"]:
        assert f"**{m['layer']}**" in perf, m["layer"]
