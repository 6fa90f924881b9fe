"""A whole benchmark run on the CPU at a tiny size, past the harness's look
for a chip: the sound program comes out ``correct``; the int8 control and
faults planted in the timed path do not.  And the command refuses to run
without a TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import faults, run  # noqa: E402

SEED = 2      # tiny readings, CPU, seeds 1-4: program 0-0.0104 (seed 2:
LIMIT = 0.01  # 0.0006), int8 control 0.020-0.042 (0.036), faults 2.0-2.4

TINY_MODEL = {"n_layers": 2, "d_model": 1024, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 64, "d_ff": 2048, "vocab": 8000, "vocab_rows": 8192,
              "gated_mlp": True, "qk_norm": True, "rope_theta": 1e6,
              "norm_eps": 1e-6, "tie_embeddings": False, "group_size": 8}
TINY_WEIGHTS = {"struct_frac": 0.5, "bias_blocks": 2, "code_blocks": 6,
                "code_match": 3, "hot_group_frac": 0.19, "dc": 1.0,
                "cb": 2.0, "embed_std": 1.0, "final_gain": 1.0}
TINY_MIX = {"slots": 4, "max_len": 256, "prefill_chunk": 64,
            "prefill_interleave": 1,
            "prompt": {"median": 60, "sigma": 0.5, "min": 16, "max": 128},
            "output": {"median": 32, "sigma": 0.5, "min": 8, "max": 96},
            "wave_requests": 8, "check_requests": 8, "check_bucket": 64}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding one tiny cell of the real harness."""
    r = tmp_path_factory.mktemp("bench_root")
    bm = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bm["configs"] = [{"name": "tiny", "source": "test",
                      "file": "bench/configs/tiny.json", "reduced": [],
                      "why": "test"}]
    bm["workloads"] = [{"name": "tiny.chat", "config": "tiny",
                        "traffic": "tiny", "chips": 1, "why": "test"}]
    for d in ("configs", "traffic", "limits"):
        (r / "bench" / d).mkdir(parents=True)
    shutil.copytree(os.path.join(ROOT, "bench", "kits"), r / "bench" / "kits",
                    ignore=shutil.ignore_patterns("__pycache__"))
    json.dump(bm, open(r / "BENCHMARK.json", "w"))
    over = {k: TINY_MODEL[k] for k in ("n_layers", "d_model", "n_heads",
                                       "n_kv_heads", "head_dim", "d_ff",
                                       "vocab")}
    conf = {"name": "tiny", "model": TINY_MODEL, "weights": TINY_WEIGHTS,
            "program": {"arch": "qwen3-8b", "overrides": dict(
                over, param_dtype="bfloat16", attn_chunk=64)}}
    json.dump(conf, open(r / "bench" / "configs" / "tiny.json", "w"))
    json.dump(TINY_MIX, open(r / "bench" / "traffic" / "tiny.json", "w"))
    json.dump({"logit_gap": LIMIT},
              open(r / "bench" / "limits" / "tiny.chat.json", "w"))
    return str(r)


def _run(root, capsys, *extra):
    run.main(["--workload", "tiny.chat", "--seed", str(SEED),
              "--seconds", "1", "--trace", "0", *extra],
             require_tpu=False, root=root)
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_sound_run_is_correct(root, capsys):
    res, err = _run(root, capsys)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= TINY_MIX["wave_requests"]
    assert set(res["metrics"]) == {"output_tok_s", "tpot_p95_ms",
                                   "ttft_p95_ms", "setup_s"}
    assert err.strip().splitlines()[-1].startswith("check short_outputs")


def test_control_fails_the_check(root, capsys):
    """The reference with int8 weights, in the program's place: the
    harness's own comparison comes out not correct."""
    res, err = _run(root, capsys, "--control", "int8")
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > LIMIT
    assert "check logit_gap" in err


def test_logit_gaps_are_unchanged_bit_for_bit(root, capsys):
    """The tiny cell's readings at its seed, bit for bit: the program's gap
    and the int8 control's, through the kit's weights and reference.  A
    change to the harness that moves what it serves or compares shows
    here."""
    run.main(["--workload", "tiny.chat", "--seed", str(SEED), "--seconds",
              "1", "--trace", "0", "--control", "int8"], require_tpu=False,
             root=root)
    note, res = map(json.loads,
                    capsys.readouterr().out.strip().splitlines()[-2:])
    assert note["check"]["_program_gap"] == 0.0
    assert note["check"]["_argmax_equal"] == 1.0
    assert note["check"]["_served_tokens"] == 286
    assert res["checks"]["logit_gap"]["value"] == 0.03591346740722656


@pytest.mark.parametrize("fault", faults.NAMES)
def test_planted_fault_is_not_correct(root, capsys, monkeypatch, fault):
    faults.plant(fault, TINY_MODEL["n_layers"], TINY_MODEL["vocab"],
                 monkeypatch.setattr)
    jax.clear_caches()
    res, _ = _run(root, capsys)
    jax.clear_caches()
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > 3 * LIMIT


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-8b.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    p = _cli(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "repro" in p.stderr
