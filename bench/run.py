"""Run one benchmark cell: ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the checkout's root, on a TPU.

The cell names a configuration and a traffic mix (``BENCHMARK.json``); the
configuration names its kit (``bench/kits/``), which makes its weights,
holds its reference and counts its operations.  The run makes the weights
from the seed, builds the server the way
``python -m repro.launch.serve`` does (``build_config`` -> weights ->
``build_server``), warms every program the window runs, then hands waves of
requests to ``Server.serve`` until ``--seconds`` have passed.  The window
runs from the first wave's start to the last wave's end; each wave drains
its slots at its end, which is how the program serves a list.  Afterwards a
sample of the finished requests is compared with the kit's float32
reference and one JSON line is printed last on stdout.  With
``--trace 1`` a few seconds inside the window are traced and the line holds
the per-layer metrics instead of the end-to-end ones.

Options past the four above are for the limits' readings and are never used
by a benchmark run: ``--control`` puts the control's tokens in place of the
served ones in the comparison (the run then comes out not correct);
``--fault`` plants a fault of ``faults.py`` in the program; ``--seeds``
reads the check on several seeds in one process, one wave each;
``--keep-trace`` copies the first trace sample out.
"""
from __future__ import annotations

import time

T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import faults, spec  # noqa: E402

TRACE_SAMPLES = 4      # traced samples, spread evenly over --seconds
TRACE_SECONDS = 3.0    # their total length


def process_start() -> float:
    """``time.perf_counter()`` at this process's start (Linux), else at
    this module's import."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        age = up - ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(0.0, age)
    except (OSError, ValueError, IndexError):
        return T_IMPORT


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("", "int8", "fp8"), default="",
                    help="compare the tokens that the reference with its "
                         "weights in this format puts first")
    ap.add_argument("--fault", choices=("",) + faults.NAMES, default="",
                    help="plant this fault in the program")
    ap.add_argument("--seeds", default="",
                    help="comma list: read the check once per seed, one "
                         "wave each, in this process (limit readings)")
    ap.add_argument("--keep-trace", default="")
    return ap.parse_args(argv)


def pct(vals, q: float) -> float:
    """Nearest-rank percentile (the arithmetic of the program's
    ``runtime/metrics.py:nearest_rank_pct``)."""
    if not vals:
        return 0.0
    vals = sorted(vals)
    rank = math.ceil(round(q * len(vals), 9))
    return vals[min(len(vals) - 1, max(0, rank - 1))]


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"needs {chips} TPU chip(s); JAX has {len(devs)} "
            f"{devs[0].platform} device(s): no result")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


@dataclasses.dataclass
class Setup:
    srv: object
    gen: object
    cfg: object
    phases: dict


def build(res: dict, seed: int) -> Setup:
    """Weights from the seed and the server the cell's flags describe."""
    import jax
    from repro.launch import serve as LS
    from repro.launch.specs import model_module
    conf, mix = res["config"], res["traffic"]
    flags = ["--arch", conf["program"]["arch"],
             "--batch", str(mix["slots"]), "--max-len", str(mix["max_len"]),
             "--prefill-chunk", str(mix["prefill_chunk"]),
             "--prefill-interleave", str(mix["prefill_interleave"]),
             *mix.get("flags", [])]
    args = LS.build_parser().parse_args(flags)
    cfg = LS.build_config(args).replace(**conf["program"]["overrides"])
    mod = model_module(cfg)
    gen = res["kit"].Generator(conf["model"], conf["weights"])
    expected = jax.eval_shape(lambda k: mod.init_lm(k, cfg),
                              jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    params = jax.block_until_ready(gen.program_params(seed, expected))
    t1 = time.perf_counter()
    srv = LS.build_server(args, cfg, mod, params, np.random.default_rng(0))
    jax.block_until_ready(srv.params)
    phases = {"weights_s": t1 - t0, "server_s": time.perf_counter() - t1}
    return Setup(srv, gen, cfg, phases)


def to_requests(pairs: list, uid0: int) -> list:
    from repro.runtime.server import Request
    return [Request(uid=uid0 + i, prompt=p, max_new=n)
            for i, (p, n) in enumerate(pairs)]


class Compiles:
    """Counts executables prepared (compiled or read from the cache)."""

    def __init__(self):
        import jax
        self.n, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and ("backend_compile" in name
                        or "cache_retrieval" in name):
            self.n += 1


class Tracer(threading.Thread):
    """Traces ``n`` samples of ``length`` seconds, the i-th starting at
    ``start + i * every`` (host clock): the first opens with the window's
    first wave, whose admissions run prefill chunks."""

    def __init__(self, log_dir: str, n: int, length: float, every: float):
        super().__init__(daemon=True)
        self.log_dir, self.n, self.length, self.every = (log_dir, n, length,
                                                         every)
        self.start_at = None
        self.anchors = []

    def run(self):
        import jax
        from bench.tracefile import WINDOW_NAME
        for i in range(self.n):
            time.sleep(max(0.0, self.start_at + i * self.every
                           - time.perf_counter()))
            jax.profiler.start_trace(os.path.join(self.log_dir, str(i)))
            with jax.profiler.TraceAnnotation(WINDOW_NAME):
                self.anchors.append(time.perf_counter())
                time.sleep(self.length)
            jax.profiler.stop_trace()

    def samples(self) -> list:
        from bench import tracefile
        return [tracefile.load(tracefile.find(os.path.join(self.log_dir,
                                                           str(i))), t)
                for i, t in enumerate(self.anchors)]


def serve_window(srv, mix: dict, seed: int, seconds: float, vocab: int,
                 tracer=None, max_waves: int = 0) -> dict:
    """Waves of requests until ``seconds`` have passed (or ``max_waves``)."""
    from bench import traffic
    done, waves, uid = [], 0, 0
    t0 = time.perf_counter()
    if tracer is not None:
        tracer.start_at = t0
        tracer.start()
    while True:
        reqs = to_requests(traffic.wave(mix, seed, waves, vocab), uid)
        uid += len(reqs)
        done.extend(srv.serve(reqs))
        waves += 1
        if max_waves:
            if waves >= max_waves:
                break
            continue
        # whole waves only: stop where one more would end farther past
        # ``seconds`` than stopping now falls short of it
        spent = time.perf_counter() - t0
        if spent + 0.5 * spent / waves >= seconds:
            break
    t1 = time.perf_counter()
    if tracer is not None:
        tracer.join()
    return {"done": done, "waves": waves, "t0": t0, "t1": t1}


def records(done: list) -> list:
    out = []
    for r in done:
        if r.outcome != "completed":
            continue
        first = r.t_admit + r.ttft_s
        out.append({"plen": len(r.prompt), "n_out": len(r.out),
                    "max_new": r.max_new, "t_start": r.t_start,
                    "t_first": first, "t_end": r.t_end,
                    "queue_wait_s": r.queue_wait_s})
    return out


def end_to_end(recs: list, window_s: float) -> dict:
    tpot = [(r["t_end"] - r["t_first"]) / (r["n_out"] - 1)
            for r in recs if r["n_out"] > 1]
    return {
        "output_tok_s": sum(r["n_out"] for r in recs) / window_s,
        "tpot_p95_ms": 1e3 * pct(tpot, 0.95),
        "ttft_p95_ms": 1e3 * pct([r["t_first"] - r["t_start"]
                                  for r in recs], 0.95),
    }


def check(setup: Setup, res: dict, seed: int, done: list,
          control: str = "") -> dict:
    """Compare a seeded sample of finished requests with the reference:
    the widest gap by which a served token's reference logit lies below
    the reference's best, and requests that ended short.  With ``control``
    the gap is read for the tokens the control puts first at each position
    of the same prompts and served tokens, in the served tokens' place (the
    program's own gap stays under ``_program_gap``)."""
    from bench import traffic
    conf, mix, limits = res["config"], res["traffic"], res["limits"]
    fin = [r for r in done if r.outcome == "completed"]
    short = sum(1 for r in done if r.outcome != "completed"
                or len(r.out) != r.max_new)
    longest = max(range(len(fin)),
                  key=lambda i: len(fin[i].prompt) + len(fin[i].out))
    pick = traffic.sample(len(fin), mix["check_requests"], longest, seed)
    prompts = [fin[i].prompt for i in pick]
    outs = [fin[i].out for i in pick]

    def ref(fmt=""):
        return res["kit"].Reference(conf["model"], setup.gen,
                                    mix["check_bucket"], fmt)
    r0 = ref()
    rows = r0.served(seed, prompts, outs)
    gaps = [float((r[:, 0] - r[:, 1]).max()) for r in rows]
    served = sum(len(o) for o in outs)
    out = {
        "logit_gap": {"value": max(gaps), "limit": limits["logit_gap"]},
        "short_outputs": {"value": short, "limit": 0},
        "_served_tokens": served, "_requests": len(pick),
        "_argmax_equal": float(sum(int((r[:, 2] == o).sum())
                                   for r, o in zip(rows, outs)) / served),
        "_times": r0.times,
    }
    if control:
        firsts = [r[:, 2].astype(np.int32)
                  for r in ref(control).served(seed, prompts, outs)]
        crow = r0.served(seed, prompts, outs, tokens=firsts)
        out["_program_gap"] = out["logit_gap"]["value"]
        out["logit_gap"] = {"value": max(float((r[:, 0] - r[:, 1]).max())
                                         for r in crow),
                            "limit": limits["logit_gap"]}
    return out


def passed(checks: dict) -> bool:
    return all(v["value"] <= v["limit"] for k, v in checks.items()
               if not k.startswith("_"))


def free(setup: Setup) -> None:
    """Drop the server, its weights and its compiled programs."""
    import jax
    setup.srv = None
    gc.collect()
    jax.clear_caches()


def traced_metrics(res: dict, bm: dict, name: str, setup_cfg, samples,
                   recs: list, peaks: dict, root: str = spec.ROOT) -> tuple:
    """The cell's per-layer metrics, each from its reader.  The readers'
    ``ctx.token_flops(pos)`` is the kit's count of one decode token at
    live context ``pos`` and this cell's batch."""
    from bench import tracefile
    kit, model = res["kit"], res["config"]["model"]
    rows = kit.decode_mlp_rows(model, setup_cfg.sparse,
                               res["traffic"]["slots"])
    ctx = argparse.Namespace(
        samples=samples, device=0, records=recs, peaks=peaks,
        token_flops=lambda pos: kit.decode_token_flops(model, pos, rows))
    metrics = {}
    for m in spec.per_layer(bm, name):
        v = spec.reader(m["name"], root).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = {"device_ops": tracefile.top_ops(samples, 0),
                 "idle_gaps": tracefile.idle_gaps(samples, 0)}
    return metrics, breakdown


def emit_checks(checks: dict) -> dict:
    """Print each compared number beside its limit, last on stderr."""
    shown = {k: v for k, v in checks.items() if not k.startswith("_")}
    for k, v in shown.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    return shown


def run_seeds(res: dict, seeds: list, control: str, fault: str,
              info: dict) -> int:
    """Limit readings: one wave per seed, checked, in one process."""
    conf, mix = res["config"], res["traffic"]
    vocab = conf["model"]["vocab"]
    for s in seeds:
        setup = build(res, s)
        win = serve_window(setup.srv, mix, s, 0.0, vocab, max_waves=1)
        free(setup)
        checks = check(setup, res, s, win["done"], control)
        print(json.dumps({"seed": s, "control": control, "fault": fault,
                          "checks": checks, "device": info}), flush=True)
        del setup
        gc.collect()
    return 0


def main(argv=None, require_tpu: bool = True, root: str = spec.ROOT) -> int:
    t_proc = process_start()
    a = parse(argv)
    bm = spec.benchmark(root)
    res = spec.resolve(bm, a.workload, root)
    import repro.launch.serve  # noqa: F401  (no program, no run)
    import jax
    t_imp = time.perf_counter()
    info = device_info(res["cell"]["chips"], require_tpu)
    from bench import devices, traffic, tracefile
    if require_tpu:
        # one fixed directory inside the checkout: only a cell's first run
        # there compiles, and two checkouts share nothing
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache", "bench"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = devices.peaks(info["kind"]) if require_tpu else None
    conf, mix = res["config"], res["traffic"]
    vocab = conf["model"]["vocab"]
    if a.fault:
        faults.plant(a.fault, conf["model"]["n_layers"], vocab)
    if a.seeds:
        return run_seeds(res, [int(s) for s in a.seeds.split(",")],
                         a.control, a.fault, info)
    t_jax = time.perf_counter()
    setup = build(res, a.seed)
    t_warm = time.perf_counter()
    setup.srv.serve(to_requests(traffic.warmup(mix, vocab), 0))
    setup.phases.update(imports_s=t_imp - t_proc, devices_s=t_jax - t_imp,
                        warmup_s=time.perf_counter() - t_warm)
    compiles = Compiles()
    tracer = log_dir = None
    if a.trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        # samples start within the first 2/3 of --seconds, which the
        # window always spans (whole waves, see serve_window)
        tracer = Tracer(log_dir, TRACE_SAMPLES,
                        TRACE_SECONDS / TRACE_SAMPLES,
                        a.seconds * 2 / 3 / TRACE_SAMPLES)
    # the set-up's heap is frozen out of the collector for the window: a
    # full collection there scans only what the window allocates
    gc.collect()
    gc.freeze()
    compiles.on = True
    setup_s = time.perf_counter() - t_proc
    win = serve_window(setup.srv, mix, a.seed, a.seconds, vocab, tracer)
    gc.unfreeze()
    compiles.on = False
    window_s = win["t1"] - win["t0"]
    recs = records(win["done"])
    stats = jax.devices()[0].memory_stats() or {}
    info["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    failed = sum(1 for r in win["done"] if r.outcome != "completed")
    note = {"waves": win["waves"], "requests_per_wave":
            mix["wave_requests"], "window_s": window_s,
            "compiles_in_window": compiles.n,
            "completed": len(recs),
            "queue_wait_p50_s": pct([r["queue_wait_s"] for r in recs], 0.5),
            "queue_wait_p95_s": pct([r["queue_wait_s"] for r in recs],
                                    0.95),
            "hbm_peak_bytes": info["memory_peak_bytes"],
            "setup_phases": setup.phases}
    out = {"correct": False, "attempted": len(win["done"]),
           "failed": failed}
    if a.trace:
        samples = tracer.samples()
        if a.keep_trace:
            shutil.copy(tracefile.find(os.path.join(log_dir, "0")),
                        a.keep_trace)
        shutil.rmtree(log_dir, ignore_errors=True)
        metrics, breakdown = traced_metrics(res, bm, a.workload, setup.cfg,
                                            samples, recs, peaks, root)
        info["busy_s"] = tracefile.busy_s(samples, 0)
        info["window_s"] = tracefile.window_s(samples)
        note["modules_seen"] = tracefile.module_names(samples)
    else:
        e2e = end_to_end(recs, window_s)
        e2e["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in spec.end_to_end(bm,
                                                               a.workload)}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in e2e.items() if k in units}
    free(setup)
    t_check = time.perf_counter()
    checks = check(setup, res, a.seed, win["done"], a.control)
    note["check"] = {k: v for k, v in checks.items() if k.startswith("_")}
    note["check_s"] = time.perf_counter() - t_check
    print(json.dumps(note), flush=True)
    out["correct"] = failed == 0 and passed(checks)
    out["metrics"] = metrics
    out["device"] = info
    if a.trace:
        out["breakdown"] = breakdown
    out["checks"] = emit_checks(checks)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
