"""Run one benchmark cell once on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``); its metrics are the readers
``bench/metrics/<metric>.py`` of the metrics ``BENCHMARK.json`` lists for it:
the end-to-end ones with ``--trace 0``, the per-layer ones with ``--trace 1``
(a run of its own, traced by the profiler over the whole window).

One run: build the program's ``ModelConfig`` from the configuration file,
which refuses a key the program cannot take as stated (``bench/model.py``);
draw the weights from the seed on the device; build the engine;
compile every program the mix can use; prefill the mix's documents; serve
``warmup_s`` seconds of the traffic; open the window and serve ``--seconds``
more, stamping every token; close the window, read the device's peak memory,
free the engine, and compare a seeded sample of the finished requests with
the plain float32 reference (``bench/reference.py``). The last line of
standard output is one JSON object; the numbers compared, each beside its
limit, are the last lines of standard error and the result's last key.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits with code 3.
"""
from __future__ import annotations


def _process_age() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    import os
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))


import time  # noqa: E402

T_PROCESS = time.monotonic() - _process_age()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

EXIT_NO_CHIP = 3
DOC_UID = 1_000_000_000           # request ids of the document fill
CACHE_DIR = ROOT / ".jax_cache"


def say(*a):
    print(*a, flush=True)


class CompileClock:
    """Seconds JAX spends making programs (tracing, lowering, compiling or
    loading from the persistent cache), and how many it made."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.names: list[str] = []        # programs made, in order
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def compiles(self) -> int:
        return len(self.names)

    def _on(self, event: str, duration: float, fun_name: str = "?", **kw):
        if event in self.EVENTS:
            self.seconds += duration
            if event == self.EVENTS[2]:
                self.names.append(fun_name)


class Run:
    """What the metric readers read: the records of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.notes: list[str] = []

    def steps_in_window(self):
        ws, we = self.window
        return [s for s in self.steps if s.t0 >= ws and s.t1 <= we]

    def note(self, line: str):
        self.notes.append(line)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                       f"bench_metric_{name}").read


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a cell reports in a run of this kind."""
    if not trace:
        return [m for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
    reported = {m["name"] for m in cell_metrics(bench, cell, False)}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])
            and m["moves"] in reported]


def use_compile_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (``.jax_cache/``, whatever ``JAX_COMPILATION_CACHE_DIR`` says):
    two checkouts never share programs, and only a checkout's first run of
    a cell compiles."""
    import jax
    path = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def pick_checked(records, seed: int, tokens: int, max_requests: int):
    """The requests the reference checks: the longest finished one and then
    others drawn from the seed until ``tokens`` served tokens or
    ``max_requests`` requests."""
    fin = sorted((r for r in records if r.req.ok),
                 key=lambda r: r.req.uid)
    if not fin:
        return []
    longest = max(fin, key=lambda r: (r.req.new_tokens, r.req.uid))
    rest = [r for r in fin if r is not longest]
    order = np.random.default_rng([seed % 2 ** 64, 99]).permutation(len(rest))
    out = [longest]
    n = longest.req.new_tokens
    for i in order:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += rest[i].req.new_tokens
    return out


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, *, cfg: dict | None = None, mix: dict | None = None,
             t_process: float | None = None, trace_dir: str | None = None,
             control: bool = False) -> dict:
    """One run of one cell; returns the result object. ``cfg``/``mix``
    replace the cell's files (the tests' small sizes). ``control`` also
    reads the fp8 control's gaps over the same checked tokens and returns
    them under ``"control"`` (``--control 1``, to set the limit of
    ``correct``; benchmark runs never ask for it)."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench import model, roofline
    from bench.serve import Load, clock, warm_up
    from bench.trace import find_xplane, reduce_trace
    from bench.traffic import Traffic, load_mix
    from repro.serving import Request, ServingEngine

    t_process = T_PROCESS if t_process is None else t_process
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    cfg = cfg or model.load_config(cell["config"])
    mix = mix or load_mix(cell["traffic"])
    ekw = mix["engine"]
    # refuses a configuration the program cannot express, before any weight
    mcfg = model.model_config(cfg, ekw["max_len"])
    dev = jax.devices()[0]
    kind = dev.device_kind
    peaks = roofline.peaks_for(kind) if dev.platform == "tpu" else None
    say(f"cell {cell_name}: config {cfg['name']}, traffic {cell['traffic']}, "
        f"seed {seed}, {seconds} s, trace {int(trace)}")
    say(f"device {dev.platform} {kind!r} x{len(jax.devices())}; jax "
        f"{jax.__version__}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    clock_c = CompileClock()

    # -- set-up ------------------------------------------------------------
    t = clock()
    params = model.program_params(cfg, seed)
    jax.block_until_ready(params)
    model.check_layout(cfg, params)
    say(f"setup: weights {clock() - t:.3f} s")
    eps_fn = model.make_eps_fn(cfg["vocab_size"])
    eng = ServingEngine(mcfg, params, eps_fn=eps_fn, **ekw)
    traffic = Traffic(mix, seed, cfg["vocab_size"])
    t = clock()
    warm_up(eng, traffic)
    say(f"setup: warm-up compile {clock() - t:.3f} s "
        f"({clock_c.compiles} programs)")

    def make_request(spec):
        return Request(uid=spec.index, prompt=spec.prompt,
                       new_tokens=spec.new_tokens,
                       noise_seed=model.noise_seed(seed, spec.index))

    if traffic.documents:
        t = clock()
        for d, doc in enumerate(traffic.documents):
            eng.submit(Request(uid=DOC_UID + d, prompt=doc, new_tokens=1,
                               noise_seed=model.noise_seed(seed, DOC_UID + d)))
        while eng.step():
            pass
        m = eng.export_metrics()
        say(f"setup: {len(traffic.documents)} documents of "
            f"{sorted(len(d) for d in traffic.documents)} tokens prefilled "
            f"in {clock() - t:.3f} s; {m['blocks_in_use']} blocks in use, "
            f"{m['blocks_available']} available")

    load = Load(eng, traffic, make_request, log_steps=trace)
    t = clock()
    load.run(t + float(mix.get("warmup_s", 0)))
    say(f"setup: warm-up traffic {clock() - t:.3f} s, "
        f"{len(load.records)} requests")
    compiles_setup, compile_s = clock_c.compiles, clock_c.seconds

    # -- the window ----------------------------------------------------------
    tdir = None
    if trace:
        tdir = trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir)
    ws = clock()
    setup_s = ws - t_process
    first_step = len(load.steps)
    with TraceAnnotation("bench.window"):
        load.run(ws + seconds)
    we = clock()
    if trace:
        jax.profiler.stop_trace()
    window_compiles = clock_c.compiles - compiles_setup
    mem = dev.memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))
    rec_all = list(load.records.values())
    steps = load.steps
    say(f"window: {we - ws:.3f} s; programs made inside it: "
        f"{window_compiles} {clock_c.names[compiles_setup:][:20]}; "
        f"generator lateness p50/p95/max "
        f"{_q(load.lateness, 50)!r}/{_q(load.lateness, 95)!r}/"
        f"{max(load.lateness, default=0.0)!r} s; host slept "
        f"{load.sleep_s!r} s")
    em = eng.export_metrics()
    say(f"engine: rounds {em['rounds']}, mean window {em['mean_window']!r}, "
        f"mean accept/round {em['mean_accept_per_round']!r}, prefill calls "
        f"{em['prefill_calls']}, failed {em['requests_failed']}, rejected "
        f"{em['requests_rejected']}")

    # -- free the program's state, then reduce and check -----------------
    del eng, params, load
    gc.collect()
    summary = None
    if trace:
        t = clock()
        counts = [(s.prefill_calls, s.rounds) for s in steps[first_step:]]
        summary = reduce_trace(find_xplane(tdir), counts)
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
        else:
            with open(os.path.join(tdir, "counts.json"), "w") as f:
                json.dump(counts, f)
        say(f"trace: reduced in {clock() - t:.3f} s; window "
            f"{summary.window_s!r} s, busy {summary.busy_s!r} s; programs "
            f"classified {summary.classified}: "
            f"{summary.programs}; modules {summary.modules}; kernels "
            f"{summary.kernels}")
        for line in summary.notes:
            say(f"trace: {line}")

    run = Run(cfg=cfg, mix=mix, engine=ekw, records=rec_all, steps=steps,
              window=(ws, we), setup_s=setup_s, compile_s=compile_s,
              trace=summary, peaks=peaks)
    in_win = [r for r in rec_all if ws <= r.due < we]
    failed = sum(r.failed for r in rec_all)
    _describe(run, in_win)

    metrics = {}
    for m in cell_metrics(bench, cell_name, trace):
        v = load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    for line in run.notes:
        say(line)

    # -- correctness -------------------------------------------------------
    chk = mix["check"]
    sample = pick_checked([r for r in rec_all
                           if r.done_t is not None and ws <= r.done_t <= we],
                          seed, chk["tokens"], chk["max_requests"])
    seqs, mismatch = [], 0
    for r in sample:
        res = np.asarray(r.req.result)
        if (len(res) != r.prompt_len + r.req.new_tokens
                or not np.array_equal(res[:r.prompt_len], r.spec.prompt)):
            mismatch += 1
        seqs.append((res, r.prompt_len, r.req.seq_id))
    t = clock()
    reference = load_module(ROOT / "bench" / cfg["reference"],
                            "bench_reference_" + cfg["name"])
    gaps, cgaps = (reference.served_gaps(cfg, seed, eps_fn, seqs,
                                         ekw["max_len"], control=control)
                   if seqs else ([], None))
    gap = max((float(g.max()) for g in gaps if len(g)), default=float("inf"))
    n_tok = sum(len(g) for g in gaps)
    say(f"check: {len(seqs)} requests, {n_tok} served tokens against the "
        f"reference in {clock() - t:.3f} s; per request max gap "
        f"{[float(g.max()) for g in gaps if len(g)]}")
    limits = cfg.get("check", {})
    checks = {
        "logit_gap": {"value": gap, "limit": limits.get("logit_gap", 0.0),
                      "bound": "upper"},
        "checked_tokens": {"value": n_tok,
                           "limit": min(chk["tokens"], 64),
                           "bound": "lower"},
        "prompt_or_length_mismatch": {"value": mismatch, "limit": 0,
                                      "bound": "upper"},
        "failed_requests": {"value": failed, "limit": 0, "bound": "upper"},
    }
    correct = all(c["value"] <= c["limit"] if c["bound"] == "upper"
                  else c["value"] >= c["limit"] for c in checks.values())
    devices = jax.devices()
    out = {"correct": bool(correct), "attempted": len(in_win),
           "failed": sum(r.failed for r in in_win), "metrics": metrics,
           "device": {"platform": dev.platform, "kind": kind,
                      "count": len(devices),
                      "memory_peak_bytes": memory_peak}}
    if trace:
        out["device"]["busy_s"] = summary.busy_s
        out["device"]["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.device_ops,
                            "idle_gaps": summary.idle_gaps}
    if control:
        out["control"] = {"logit_gap": max(
            (float(g.max()) for g in cgaps or [] if len(g)),
            default=float("inf"))}
        say(f"control: fp8 gap {out['control']['logit_gap']!r}")
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} ({c['bound']} limit "
              f"{c['limit']!r})", file=sys.stderr, flush=True)
    return out


def _q(vals, p):
    return float(np.percentile(vals, p)) if len(vals) else 0.0


def _describe(run, in_win):
    """Sample counts and medians, printed before the result."""
    ws, we = run.window
    ttft = [(min(r.first_t, we) if r.first_t else we) - r.due
            for r in in_win]
    done = [r for r in in_win if r.done_t is not None and r.done_t <= we]
    say(f"requests: due in window {len(in_win)}, finished in window "
        f"{len(done)}, failed {sum(r.failed for r in run.records)}, records "
        f"{len(run.records)}")
    say(f"ttft: n {len(ttft)}, median {_q(ttft, 50) * 1e3!r} ms, p95 "
        f"{_q(ttft, 95) * 1e3!r} ms")
    gaps = []
    for r in run.records:
        st = [(t, n) for t, n in r.stamps if ws <= t <= we]
        if len(st) >= 2:
            gaps.append((st[-1][0] - st[0][0]) / sum(n for _, n in st[1:]))
    say(f"tpot: n {len(gaps)}, median {_q(gaps, 50) * 1e3!r} ms, p95 "
        f"{_q(gaps, 95) * 1e3!r} ms")
    toks = sum(n for r in run.records for t, n in r.stamps if ws <= t <= we)
    say(f"tokens: {toks} in {we - ws!r} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the fp8 control's gaps over the checked "
                    "tokens, after the window (to set the limit of correct)")
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="keep the raw trace and the harness's per-step "
                    "counts in DIR (to record a trace for the tests)")
    args = ap.parse_args(argv)
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        print(f"no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"this cell needs {cell['chips']} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return EXIT_NO_CHIP
    use_compile_cache()
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), control=bool(args.control),
                   trace_dir=args.keep_trace)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
