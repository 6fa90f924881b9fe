"""Kits: a configuration names the module that makes its weights, holds
its reference and counts its operations, and a configuration of a new kind
of layer comes in as new files alone."""
import hashlib
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run, spec, tracefile  # noqa: E402

import test_bench_run as tiny  # noqa: E402

BM = spec.benchmark(ROOT)

# a kit that is the dense kit and notes each of its names that is used
RECORDED_KIT = '''"""The dense kit, noting each use of its names."""
import os

from bench import spec

_dense = spec.kit({})
LOG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "recorded.log")


def _note(what):
    with open(LOG, "a") as f:
        f.write(what + "\\n")


class Generator(_dense.Generator):
    def __init__(self, model, weights):
        _note("Generator")
        super().__init__(model, weights)


class Reference(_dense.Reference):
    def __init__(self, model, gen, bucket, fmt=""):
        _note("Reference")
        super().__init__(model, gen, bucket, fmt)


def decode_mlp_rows(model, sparse_cfg, slots):
    _note("decode_mlp_rows")
    return _dense.decode_mlp_rows(model, sparse_cfg, slots)


def decode_token_flops(model, ctx, mlp_rows):
    _note("decode_token_flops")
    return _dense.decode_token_flops(model, ctx, mlp_rows)
'''


def test_config_without_kit_resolves_to_dense():
    dense = os.path.join(ROOT, "bench", "kits", "dense.py")
    assert spec.kit({}).__file__ == dense
    assert spec.kit({"kit": "dense"}).__file__ == dense
    for w in BM["workloads"]:
        res = spec.resolve(BM, w["name"], ROOT)
        assert "kit" not in res["config"]
        assert res["kit"].__file__ == dense


@pytest.mark.parametrize("name", ["../run", "dense/../dense", ""])
def test_kit_is_named_not_a_path(name):
    with pytest.raises(ValueError):
        spec.kit({"kit": name})


@pytest.mark.parametrize("name", ["Generator", "Reference",
                                  "decode_mlp_rows", "decode_token_flops"])
def test_dense_kit_exposes_the_interface(name):
    assert callable(getattr(spec.kit({}), name))


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def kit_root(tmp_path_factory):
    """A copy of the benchmark to which a new kind of layer is added as new
    files and new entries of BENCHMARK.json, nothing else."""
    r = tmp_path_factory.mktemp("kit_root")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), r)
    shutil.copytree(os.path.join(ROOT, "bench"), r / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(str(r))
    bm = json.loads((r / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tiny-rec", "source": "test",
                          "file": "bench/configs/tiny-rec.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny-rec.chat", "config": "tiny-rec",
                            "traffic": "tiny-rec", "chips": 1,
                            "why": "test"})
    for m in bm["per_layer"]:
        m["workloads"].append("tiny-rec.chat")
    (r / "BENCHMARK.json").write_text(json.dumps(bm))
    over = {k: tiny.TINY_MODEL[k] for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab")}
    conf = {"name": "tiny-rec", "kit": "recorded", "model": tiny.TINY_MODEL,
            "weights": tiny.TINY_WEIGHTS,
            "program": {"arch": "qwen3-8b", "overrides": dict(
                over, param_dtype="bfloat16", attn_chunk=64)}}
    new = {"bench/kits/recorded.py": RECORDED_KIT,
           "bench/configs/tiny-rec.json": json.dumps(conf),
           "bench/traffic/tiny-rec.json": json.dumps(tiny.TINY_MIX),
           "bench/limits/tiny-rec.chat.json": json.dumps(
               {"logit_gap": tiny.LIMIT})}
    for path, text in new.items():
        assert not (r / path).exists()
        (r / path).write_text(text)
    return str(r), before


def _used(root):
    with open(os.path.join(root, "bench", "kits", "recorded.log")) as f:
        return set(f.read().split())


def test_new_kit_is_served_and_checked(kit_root, capsys):
    root, before = kit_root
    run.main(["--workload", "tiny-rec.chat", "--seed", str(tiny.SEED),
              "--seconds", "1", "--trace", "0"], require_tpu=False,
             root=root)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert {"Generator", "Reference"} <= _used(root)
    after = _digests(root)
    changed = {p for p in before if after.get(p) != before[p]}
    assert changed == {"BENCHMARK.json"}
    bm = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for group in ("configs", "workloads"):
        assert bm[group][:len(BM[group])] == BM[group]


def test_new_kit_counts_the_traced_operations(kit_root):
    """``decode_mfu`` reads its operations through the kit."""
    from repro.core.sparse_mlp import SparseInferConfig
    root, _ = kit_root
    bm = spec.benchmark(root)
    res = spec.resolve(bm, "tiny-rec.chat", root)
    tr = tracefile.load(os.path.join(ROOT, "bench", "testdata",
                                     "chat_decode.xplane.pb.gz"))
    rec = {"plen": 100, "n_out": 1001, "t_first": -1e-3, "t_end": 1.0}
    cfg = type("Cfg", (), {"sparse": SparseInferConfig(enabled=True)})
    metrics, _ = run.traced_metrics(res, bm, "tiny-rec.chat", cfg, [tr],
                                    [rec], {"bf16_flops": 197e12}, root)
    assert metrics["decode_mfu"]["value"] > 0
    assert {"decode_mlp_rows", "decode_token_flops"} <= _used(root)
