"""Bring-up smoke run of the serve path on a TPU.

    python chip_smoke.py             # one chip: kernels, gather serve, pallas serve
    python chip_smoke.py --chips 4   # four chips: sharded pallas decode only

Model: ProSparse-Llama2-7B (configs/prosparse_llama2_7b.py) at its published
widths (d_model 4096, 32x128 MHA, d_ff 11008, vocab 32000, ReLU gate, int8
KV) with bfloat16 random weights from a fixed seed.  float32 weights (the
config default) need ~27 GB and do not fit a 16 GiB chip.

One chip, in one process:
  1. device   — JAX must report a TPU; anywhere else the script exits 1
                and prints no result.
  2. kernels  — the fused predictor and the fused sparse MLP (with
                telemetry), compiled for the chip at the real widths,
                against their jnp oracles in kernels/ref.py.
  3. gather   — 4 requests through Server.serve, built as
                ``python -m repro.launch.serve`` builds it.
  4. pallas   — the same requests with strategy pallas, the controller, a
                two-rung capacity-bucket ladder (warmed) and the metrics
                hub: 4/4 completed, no retrace after warmup, and the
                server's own decode executable at the active bucket holds
                Mosaic kernels (``tpu_custom_call``); its outputs and the
                step's logits are finite.

Four chips (``--chips 4``): sparse pallas decode under ``--mesh-shape`` 1x4
and 2x2, each against the single-device emulation of the same (data,
model) shard grid, every step from the same tokens and caches.  All
float32, held to MESH_LOGIT_RTOL with no decision flip; then the served
configuration (bf16, int8 KV), whose mismatches are printed, not held to a
limit: in bf16 the decoded tokens depend on placement (DESIGN.md §8).

The last stdout line is ``{"ok": true, "device": {...}}``; any failed phase
exits non-zero.  Times printed are from a smoke run, not a measurement.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import predictor as P  # noqa: E402
from repro.core import selection as S  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import serve as SV  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.specs import model_module  # noqa: E402

ARCH = "prosparse-llama2-7b"
# Depth cut to 16 of 32 layers: compiled for a described v5e, the 32-layer
# decode step needs 16.45 GiB of the 15.75 GiB HBM (12.7 GiB of bf16
# weights plus ~0.1 GiB of weight-sized temporaries per layer); at 16
# layers the pallas step takes 6.86 GiB of arguments + 1.69 GiB of temps.
N_LAYERS = 16
MESH_LAYERS = 2          # four-chip phase: a correctness check, not a fit
SERVE_FLAGS = ["--arch", ARCH, "--requests", "4", "--batch", "4",
               "--prompt-len", "128", "--max-new", "32", "--max-len", "512"]
PALLAS_FLAGS = ["--strategy", "pallas", "--controller",
                "--capacity-buckets", "0.2,0.4", "--warm-buckets",
                "--metrics"]

# Stated tolerances, each with its reason.
# Predictor margins: alpha = 1.0 rows are exact integer arithmetic; for
# alpha = 1.03 the compiler may or may not contract N_neg - a * N_pos into
# an FMA, a difference of one rounding of a * N_pos <= 2^-23 * a * d.
MARGIN_ATOL_PER_D = 2.0 ** -23 * 1.03
# Fused MLP output vs the f32 "highest" oracle, relative to max |y_ref|: the
# kernel rounds h = act(x Wg) * (x Wu) to bf16 (the weight dtype) before
# the down-projection, 2^-9 relative per term, over random-sign sums.
MLP_RTOL = 2.0 ** -7
# In-kernel telemetry: `realized` depends only on margins and the selection
# and must be exact; `actual` / `false_neg` count gate signs, which a gate
# value within f32 accumulation-order rounding of zero may flip.
TEL_SIGN_SLACK = 2
# Mesh vs single-device emulation, relative to max |logits|.  The
# partitioned and the single-device programs sum in different orders, and
# every quantization step downstream turns that float32 noise into a jump
# the sign-bit predictor then turns into a different row selection: bf16
# activations (measured on four v5e chips: 0.55 of max |logit|, 15 of 16
# argmax mismatches at 1x4), the int8 KV cache and the TPU's one-pass bf16
# inputs to float32 matmuls (float32 activations alone: decision flips in
# 6 of 8 steps).  None of that is the shard grid's semantics, so the check
# runs float32 weights, activations and KV cache at "highest" matmul
# precision (the kernels' float32 matmuls refuse bf16 weights at that
# precision).  There the readings were 7.4e-7 (1x4) and 7.9e-7 (2x2) on
# four v5e chips and 8.2e-7 / 8.3e-7 on the CPU, with no decision flip in
# 8 steps: 1e-4 is ~100x above that noise, and a step whose integer
# telemetry differs fails the check.
MESH_LOGIT_RTOL = 1e-4
MESH_MAX_FLIP_STEPS = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} TPU chips, JAX "
                         f"reports {len(devs)}")
    return devs


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def phase_kernels(d: int, k: int, group_size: int, batch: int,
                  capacity_frac: float) -> dict:
    """The two main-path kernels at widths (d, k), compiled, against their
    jnp oracles."""
    from repro.core.sparse_mlp import SparseInferConfig
    g = group_size
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (batch, d)).astype(jnp.bfloat16)
    wg, wu, wd = ((jax.random.normal(kk, (k, d)) * d ** -0.5)
                  .astype(jnp.bfloat16) for kk in ks[1:])
    pw = P.pack_signs(wg)
    alpha = jnp.where(jnp.arange(batch) % 2 == 0, 1.0, 1.03)
    gm, cnt = ops.predict_group_margins(pw, x, d, alpha, group_size=g,
                                        interpret=False)
    gm_r, cnt_r = jax.jit(ref.predict_group_margins_ref,
                          static_argnums=(2, 4))(pw, x, d, alpha, g)
    gm, gm_r = np.asarray(gm), np.asarray(gm_r)
    exact_rows = np.asarray(alpha) == 1.0
    margin_err = float(np.abs(gm - gm_r).max())
    res = {"margin_max_abs_err": margin_err,
           "margin_exact_rows_equal": bool(
               np.array_equal(gm[exact_rows], gm_r[exact_rows])),
           "count_mismatches": int(np.sum(np.asarray(cnt)
                                          != np.asarray(cnt_r)))}
    check(res["count_mismatches"] == 0, f"predictor counts differ: {res}")
    check(res["margin_exact_rows_equal"], f"alpha=1 margins differ: {res}")
    check(margin_err <= MARGIN_ATOL_PER_D * d, f"margins: {res}")

    cap = SparseInferConfig(group_size=g,
                            capacity_frac=capacity_frac).capacity(k)
    sel = S.capacity_select(S.union_margin(jnp.asarray(gm_r)), cap)
    y, tel = ops.fused_sparse_mlp(x, wg, wu, wd, sel.indices, sel.count,
                                  jnp.asarray(gm_r), group_size=g,
                                  collect_stats=True, interpret=False)
    with jax.default_matmul_precision("highest"):
        y_r = jax.jit(ref.fused_sparse_mlp_ref,
                      static_argnames=("group_size",))(
            x, wg, wu, wd, sel.indices, sel.count, group_size=g)
        tel_r = jax.jit(ref.fused_mlp_telemetry_ref,
                        static_argnames=("group_size",))(
            x, wg, sel.indices, sel.count, jnp.asarray(gm_r), group_size=g)
    y, y_r = np.asarray(y), np.asarray(y_r)
    tel_d = np.abs(np.asarray(tel) - np.asarray(tel_r))
    res.update(capacity_groups=cap, selected_groups=int(sel.count),
               mlp_max_abs_err=float(np.abs(y - y_r).max()),
               mlp_rel_err=float(np.abs(y - y_r).max()
                                 / max(float(np.abs(y_r).max()), 1e-30)),
               telemetry_max_abs_diff=[int(v) for v in tel_d.max(0)])
    check(np.isfinite(y).all(), "fused MLP output not finite")
    check(res["mlp_rel_err"] <= MLP_RTOL, f"fused MLP: {res}")
    check(tel_d[:, 2].max() == 0, f"realized telemetry differs: {res}")
    check(tel_d[:, :2].max() <= TEL_SIGN_SLACK, f"gate telemetry: {res}")
    return res


def _served(srv, args, cfg) -> tuple:
    reqs = SV.make_requests(args, cfg, np.random.default_rng(1))
    t0 = time.perf_counter()
    done = srv.serve(reqs)
    dt = time.perf_counter() - t0
    n_ok = sum(1 for r in done if r.outcome == "completed"
               and r.out is not None and len(r.out) == args.max_new)
    return done, n_ok, dt


def phase_serve(name: str, argv: list, params: dict,
                n_layers: int) -> dict:
    """Serve the requests twice through the CLI's Server: the first drain
    compiles (set-up) and, with metrics on, arms the retrace watchdog; the
    second is the smoke throughput."""
    args = SV.build_parser().parse_args(argv)
    cfg = SV.build_config(args).replace(n_layers=n_layers,
                                        param_dtype="bfloat16")
    mod = model_module(cfg)
    srv = SV.build_server(args, cfg, mod, params, np.random.default_rng(0))
    _, n_warm, t_setup = _served(srv, args, cfg)
    done, n_ok, dt = _served(srv, args, cfg)
    toks = sum(len(r.out) for r in done if r.out is not None)
    res = {"completed": f"{n_ok}/{len(done)}",
           "setup_s_incl_compile": round(t_setup, 3),
           "smoke_tok_per_s": round(toks / dt, 3)}
    check(n_warm == n_ok == len(done) == args.requests,
          f"{name}: not every request completed ({n_warm}, {n_ok})")
    if srv.scfg.metrics.enabled:
        res["retraces_post_warmup"] = srv.metrics.watchdog.retraces_post_warmup
        srv.metrics.close()
        check(res["retraces_post_warmup"] == 0, f"{name}: retraced {res}")
    if cfg.sparse.strategy == "pallas":
        res.update(_pallas_step_check(srv, done))
    return res


def _prefilled(srv, prompts: list) -> tuple:
    """Batch caches for ``prompts`` built the way the scheduler admits
    them (batch-1 prefill + slot splice), plus each slot's first token."""
    caches = srv.mod.init_caches(srv.cfg, len(prompts), srv.scfg.max_len)
    first = []
    for i, p in enumerate(prompts):
        logits, one = srv.prefill_fn(srv.params, jnp.asarray(p)[None])
        caches = srv.splice_fn(caches, one, i)
        first.append(int(jnp.argmax(logits[0])))
    return caches, np.asarray(first, np.int32)[:, None]


def _decode_fn(srv, cfg=None):
    """The server's sparse decode step (at ``cfg``, default the server's)
    with logits and telemetry out; per-layer alphas optional."""
    cfg = cfg or srv.cfg
    return jax.jit(lambda p, t, c, n, a=None: srv.mod.decode_step(
        p, cfg, t, c, n, alphas=a, collect_stats=True))


def _pallas_step_check(srv, done) -> dict:
    """The server's own controller decode jit at its active capacity
    bucket (the executable serve() ran): lowered with the serve loop's
    argument shapes, its HLO must hold Mosaic kernels, and run on the
    served prompts its tokens must be in the vocabulary and its telemetry
    finite.  The logits, which that jit samples from and does not return,
    come from the same decode step at the same bucket and alphas with
    logits out; they must be finite and their argmax the served tokens."""
    prompts = [r.prompt for r in done]
    caches, tok = _prefilled(srv, prompts)
    lengths = np.full(len(prompts), len(prompts[0]), np.int32)
    alphas = srv._slot_alpha_matrix(np.array([srv._tier_of(r)
                                              for r in done]))
    jt, jl, ja = srv._put_slots(tok, lengths, alphas)
    compiled = srv.decode_ctrl_fn.lower(srv.params, jt, caches, jl,
                                        ja).compile()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    ntok, _, stats = compiled(srv.params, jt, caches, jl, ja)
    ntok = np.asarray(ntok)
    cfg_b = srv.cfg.replace(sparse=dataclasses.replace(
        srv.cfg.sparse, capacity_override=int(srv._active_cap)))
    logits = np.asarray(_decode_fn(srv, cfg_b)(srv.params, jt, caches,
                                               jl, ja)[0], np.float32)
    res = {"served_decode_tpu_custom_calls": n_kernels,
           "served_tokens_in_vocab": bool(((ntok >= 0)
                                           & (ntok < srv.cfg.vocab)).all()),
           "served_telemetry_finite": all(
               bool(np.isfinite(np.asarray(v)).all())
               for v in stats.values()),
           "decode_logits_finite": bool(np.isfinite(logits).all()),
           "served_tokens_eq_logit_argmax": int(np.sum(
               ntok.reshape(-1) == logits.argmax(-1))),
           "active_bucket_groups": int(srv._active_cap)}
    check(n_kernels > 0, "served decode step holds no tpu_custom_call")
    check(res["served_tokens_in_vocab"], f"served tokens: {res}")
    check(res["served_telemetry_finite"], f"served telemetry: {res}")
    check(res["decode_logits_finite"], "pallas decode logits not finite")
    check(res["served_tokens_eq_logit_argmax"] == len(done),
          f"served tokens are not the logits' argmax: {res}")
    return res


def run_one_chip() -> dict:
    out = {}
    t0 = time.perf_counter()
    out["kernels"] = phase_kernels(4096, 11008, 8, 4, 0.2)
    log(f"kernels: {json.dumps(out['kernels'])} "
        f"({time.perf_counter() - t0:.1f}s)")
    args = SV.build_parser().parse_args(SERVE_FLAGS)
    cfg = SV.build_config(args).replace(n_layers=N_LAYERS,
                                        param_dtype="bfloat16")
    t0 = time.perf_counter()
    params = jax.block_until_ready(SV.init_params(model_module(cfg), cfg))
    log(f"params: {N_LAYERS} layers bf16 initialized in "
        f"{time.perf_counter() - t0:.1f}s")
    for name, extra in (("gather", ["--strategy", "gather"]),
                        ("pallas", PALLAS_FLAGS)):
        out[name] = phase_serve(name, SERVE_FLAGS + extra, params, N_LAYERS)
        log(f"serve {name} (smoke run, not a measurement): "
            f"{json.dumps(out[name])}")
        gc.collect()
    return out


def compare_mesh(srv_e, srv_m, prompts: list, steps: int) -> dict:
    """Decode ``steps`` tokens on the single-device emulation; at every
    step run the mesh server's step from the SAME tokens and caches, so a
    difference cannot carry into later steps.  A step whose integer
    telemetry differs between the two saw a decision flip (a row selection
    or a gate sign, from an input element at zero): it is counted, and its
    logits are not held to the tolerance."""
    step_e, step_m = _decode_fn(srv_e), _decode_fn(srv_m)
    caches, tok = _prefilled(srv_e, prompts)
    lengths = np.full(len(prompts), len(prompts[0]), np.int32)
    errs, flips, tok_mis, tel_mis = [], 0, 0, {}
    scale = 0.0
    for _ in range(steps):
        lg_e, caches_next, st_e = step_e(srv_e.params, tok, caches, lengths)
        with srv_m._mesh_ctx():
            jt, jl, _ = srv_m._put_slots(tok, lengths)
            lg_m, _, st_m = step_m(srv_m.params, jt,
                                   jax.tree.map(np.asarray, caches), jl)
        lg_e = np.asarray(lg_e, np.float32)
        lg_m = np.asarray(lg_m, np.float32)
        scale = max(scale, float(np.abs(lg_e).max()))
        tok_mis += int(np.sum(lg_e.argmax(-1) != lg_m.argmax(-1)))
        flipped = False
        for k in st_e:
            n = int(np.sum(np.asarray(st_e[k]) != np.asarray(st_m[k])))
            tel_mis[k] = tel_mis.get(k, 0) + n
            flipped |= n > 0
        if flipped:
            flips += 1
        else:
            errs.append(float(np.abs(lg_e - lg_m).max()))
        tok = lg_e.argmax(-1).astype(np.int32)[:, None]
        caches, lengths = caches_next, lengths + 1
    err = max(errs, default=float("nan"))
    return {"grid": {"dp_shards": srv_m.cfg.sparse.dp_shards,
                     "tp_shards": srv_m.cfg.sparse.tp_shards},
            "steps": steps, "selection_flip_steps": flips,
            "logit_max_abs_err": err, "logit_scale": scale,
            "logit_rel_err": err / max(scale, 1e-30),
            "token_mismatches": tok_mis, "telemetry_mismatches": tel_mis,
            "logits_finite": bool(np.isfinite(lg_m).all())}


def _mesh_compare(shape: tuple, steps: int, **dtypes) -> dict:
    """Sparse pallas decode on a ``shape`` (data, model) mesh against the
    single-device emulation of the same shard grid, at ``dtypes``."""
    name = f"{shape[0]}x{shape[1]}"
    args = SV.build_parser().parse_args(
        SERVE_FLAGS + ["--strategy", "pallas", "--max-len", "256",
                       "--mesh-shape", name])
    cfg = SV.build_config(args).replace(n_layers=MESH_LAYERS, **dtypes)
    mod = model_module(cfg)
    params = SV.init_params(mod, cfg)
    prompts = [r.prompt for r in SV.make_requests(
        args, cfg, np.random.default_rng(1))]
    srv_m = SV.build_server(args, cfg, mod, params, np.random.default_rng(0),
                            mesh=make_mesh(shape, ("data", "model")))
    # same (data, model) semantics, emulated on one device
    srv_e = SV.build_server(args, srv_m.cfg, mod, params,
                            np.random.default_rng(0))
    res = compare_mesh(srv_e, srv_m, prompts, steps)
    del srv_m, srv_e, params
    gc.collect()
    return res


def run_four_chips(steps: int = 8) -> dict:
    out = {}
    for shape in ((1, 4), (2, 2)):
        name = f"{shape[0]}x{shape[1]}"
        with jax.default_matmul_precision("highest"):
            res = _mesh_compare(shape, steps, param_dtype="float32",
                                dtype="float32", kv_cache_dtype="float32")
        log(f"mesh {name} vs emulation, float32 ({MESH_LAYERS} layers, "
            f"{steps} decode steps): {json.dumps(res)}")
        check(res["logits_finite"], f"mesh {name}: logits not finite")
        check(res["selection_flip_steps"] <= MESH_MAX_FLIP_STEPS,
              f"mesh {name}: decisions differ from the emulation")
        check(res["logit_rel_err"] <= MESH_LOGIT_RTOL,
              f"mesh {name}: logits outside tolerance")
        served = _mesh_compare(shape, steps, param_dtype="bfloat16")
        log(f"mesh {name} vs emulation, served configuration (bf16, int8 "
            f"KV; reported, no limit): {json.dumps(served)}")
        check(served["logits_finite"],
              f"mesh {name}: served-configuration logits not finite")
        out[name] = {"float32": res, "served": served}
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded decode phase")
    n_chips = ap.parse_args().chips
    devs = require_tpu(n_chips)
    log(f"device: {devs[0].device_kind} x{len(devs)}; cache "
        f"{use_compile_cache()}")
    if n_chips == 1:
        log(f"model: {ARCH} at published widths, depth cut to "
            f"{N_LAYERS}/32 layers, bf16 weights (random, seed 0)")
        run_one_chip()
    else:
        log(f"model: {ARCH} at published widths, depth cut to "
            f"{MESH_LAYERS}/32 layers, random weights (seed 0): float32 "
            "weights, activations and KV cache, then bf16 with int8 KV")
        run_four_chips()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
