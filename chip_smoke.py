"""Smoke test of the served path on a TPU: the quickest proof that the system
still starts on the chip.

    python chip_smoke.py [--seed N]      # one chip
    python chip_smoke.py --chips 4       # the mesh path, on four chips

One process, no children. It refuses to run without a TPU: there is no CPU
fallback (the tests cover the CPU). Weights are random, drawn from
``--seed``, at the published widths of qwen3-1.7b (28 layers, d_model 2048,
GQA 16/8, vocab 151,936, bf16).

One chip:

* kernel phase: the fused paged flash-decode kernel at qwen3-1.7b widths,
  compiled by Mosaic (``tpu_custom_call`` in the program) and checked
  against ``kernels/paged_attention/ref.py``;
* serve phase: a ``ServingEngine`` (batch 8, window 8, max_len 1024,
  block 16) serves 16 requests of 32-512 prompt tokens and 64 new tokens
  through the paged kernel; one verify round's logits through the kernel
  must be close to the gather-view path's. A second engine, on the
  gather-view path and an f32 copy of the weights, serves the same requests
  and must match solo ``PredictiveSampler.generate`` replays bit for bit
  (in bf16 the chip rounds the batched and the solo programs' reductions
  differently, which flips near-tie tokens).

``--chips 4`` runs only the mesh path and what it is compared with: data=4
must have no collectives in its round program and, on an f32 copy of the
weights, match the one-device engine bit for bit; data=2,model=2 (tensor
parallel) must finish every request. Mesh engines attend through the
gather-view path (a compiled Mosaic kernel cannot be partitioned by
GSPMD), so the one-device engine they are compared with does too.

Each phase prints its wall time and the seconds spent compiling. The last
line of standard output is one JSON object, printed only when every gate
passed: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.analysis import check_engine_round  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.engine.spec_decode import PredictiveSampler  # noqa: E402
from repro.kernels import resolve_interpret  # noqa: E402
from repro.kernels.paged_attention.ops import paged_attention  # noqa: E402
from repro.kernels.paged_attention.ref import (  # noqa: E402
    paged_attention_fused_ref)
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import place_params  # noqa: E402
from repro.models.transformer import PagedView, TransformerLM  # noqa: E402
from repro.serving import Request, ServingEngine, ServingTopology  # noqa: E402

ARCH = "qwen3-1.7b"
BATCH, WINDOW, MAX_LEN, BLOCK = 8, 8, 1024, 16
# Kernel vs f32 reference (bf16 inputs, f32 accumulation): both outputs are
# rounded to bf16, whose spacing is 2**-6 for |x| in [2, 4); allow two such
# steps for the rounding plus the probabilities' rounding inside p @ v.
KERNEL_ATOL = 2.0 ** -5
# Verify-round logits, kernel vs gather-view attention over the same pool:
# each layer's attention output is rounded to bf16 (relative 2**-8) on both
# paths, and the difference is carried through 28 residual layers; allow
# 2**-5 of the logits' largest magnitude.
LOGITS_REL = 2.0 ** -5


class Gates:
    """Collects failed checks; the run is ok only if none failed."""

    def __init__(self):
        self.failed: list[str] = []

    def check(self, ok: bool, what: str) -> bool:
        print(f"  [{'pass' if ok else 'FAIL'}] {what}")
        if not ok:
            self.failed.append(what)
        return ok


class CompileClock:
    """Seconds JAX spends in the backend compiler (all programs)."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


class Phase:
    """Prints a phase's wall time and compile time when it ends."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        print(f"== {self.name}", flush=True)
        self.t0, self.c0 = time.perf_counter(), self.clock.seconds
        return self

    def __exit__(self, *exc):
        print(f"phase {self.name}: wall {time.perf_counter() - self.t0:.1f}s"
              f", compile {self.clock.seconds - self.c0:.1f}s", flush=True)


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------

def kernel_inputs(cfg, seed: int, *, batch: int, window: int, max_len: int,
                  block: int, dtype=jnp.bfloat16):
    """Random pools, window rows, queries and block tables at ``cfg``'s
    attention widths, with the engine's table width and pool size."""
    H, KV, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    nb = -(-(max_len + window) // block)
    P = 1 + batch * nb
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(k[0], (batch, window, H, d), dtype)
    k_pool = jax.random.normal(k[1], (P, block, KV, d), dtype)
    v_pool = jax.random.normal(k[2], (P, block, KV, d), dtype)
    k_new = jax.random.normal(k[3], (batch, window, KV, d), dtype)
    v_new = jax.random.normal(k[4], (batch, window, KV, d), dtype)
    rng = np.random.default_rng(seed)
    tables = 1 + rng.permutation(P - 1)[:batch * nb].reshape(batch, nb)
    lengths = rng.integers(1, nb * block - window, size=batch)
    return (q, k_pool, v_pool, k_new, v_new,
            jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32))


def kernel_phase(cfg, seed: int, gates: Gates):
    args = kernel_inputs(cfg, seed, batch=BATCH, window=WINDOW,
                         max_len=MAX_LEN, block=BLOCK)
    gates.check(resolve_interpret(None) is False,
                "Pallas kernels compile (resolve_interpret(None) is False)")
    compiled = jax.jit(
        lambda *a: paged_attention(*a, use_kernel=True)).lower(*args).compile()
    gates.check("tpu_custom_call" in compiled.as_text(),
                "paged kernel program holds a tpu_custom_call")
    out, kp, vp = jax.block_until_ready(compiled(*args))
    with jax.default_matmul_precision("highest"):
        ref, rk, rv = jax.jit(paged_attention_fused_ref)(*args)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    print(f"kernel max abs error vs f32 reference: {err:.6g} "
          f"(tolerance {KERNEL_ATOL:.6g})")
    gates.check(err <= KERNEL_ATOL, "kernel output within tolerance")
    # the fused writeback is pure selects: bitwise the reference scatter
    # everywhere but the reserved sink block 0
    same = bool(jnp.array_equal(kp[1:], rk[1:])
                & jnp.array_equal(vp[1:], rv[1:]))
    gates.check(same, "committed K/V pools bitwise equal the reference")


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------

def random_params(cfg, seed: int):
    # op by op, as launch/serve does: one small program per leaf shape (the
    # whole init as one jitted program took minutes to compile for the chip)
    return jax.block_until_ready(
        TransformerLM.init(jax.random.PRNGKey(seed), cfg))


def make_requests(cfg, seed: int, n: int, prompt_lo: int, prompt_hi: int,
                  new_tokens: int) -> list[tuple[int, np.ndarray, int]]:
    """``n`` (uid, prompt, new_tokens) triples drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    out = []
    for uid in range(n):
        L = int(rng.integers(prompt_lo, prompt_hi + 1))
        out.append((uid, rng.integers(0, cfg.vocab, size=L), new_tokens))
    return out


def new_engine(cfg, params, seed: int, **kw) -> ServingEngine:
    # adaptive=False pins W: every window width is one more compiled program
    return ServingEngine(cfg, params, batch=BATCH, window_max=WINDOW,
                         max_len=MAX_LEN, block_size=BLOCK,
                         eps_key=jax.random.PRNGKey(seed), adaptive=False,
                         **kw)


def submit_all(eng: ServingEngine, reqs) -> None:
    for uid, prompt, new in reqs:
        eng.submit(Request(uid=uid, prompt=prompt, new_tokens=new))


def results(eng: ServingEngine, reqs, gates: Gates, label: str) -> dict:
    """Gate an engine's run on every request served and no failure, retry
    or staging error; returns ``{uid: tokens}``."""
    m = eng.export_metrics()
    done = {r.uid: r for r in eng.done}
    served = {u: r.result for u, r in done.items() if r.ok}
    gates.check(len(served) == len(reqs),
                f"{label}: {len(served)}/{len(reqs)} requests served")
    for key in ("requests_failed", "requests_rejected", "staging_errors",
                "retries"):
        gates.check(m[key] == 0, f"{label}: {key} == {m[key]}")
    for r in done.values():
        if r.error is not None:
            print(f"  {label}: request {r.uid} failed: {r.error}")
    ok = [r for r in done.values() if r.ok]
    per_round = (sum(r.new_tokens for r in ok)
                 / max(1, sum(r.calls_used for r in ok)))
    print(f"{label}: accepted tokens per verify round per request "
          f"{per_round:.4f}; {m['rounds']} batch rounds")
    return served


def round_logits(eng: ServingEngine, use_kernel: bool) -> np.ndarray:
    """Logits of one verify round over the engine's current state, with
    attention through the paged kernel or the gather-view path, for the
    rows that hold a request (a free row's window lands in the garbage sink
    block)."""
    cfg, W = eng.cfg, eng.controller.window

    @jax.jit
    def f(params, paged, tables, cand, n):
        view = PagedView(tables, jnp.arange(tables.shape[0]), use_kernel)
        logits, _, _ = TransformerLM.decode_window_paged(
            params, cfg, cand, paged, view, n - 1)
        return logits.astype(jnp.float32)

    rows = [b for b, r in enumerate(eng.slots) if r is not None]
    return np.asarray(f(eng.params, eng.paged, eng._tables_device(),
                        eng.cand[:, :W], eng.n))[rows]


def f32_copy(cfg, params):
    """The same model in f32: config and an exact upcast of the weights."""
    return (dataclasses.replace(cfg, dtype="float32"),
            jax.tree.map(lambda x: x.astype(jnp.float32), params))


def solo_replay(sampler: PredictiveSampler, uid: int, prompt,
                new_tokens: int):
    """The request served alone by ``PredictiveSampler`` on its own noise
    stream (tests/serving/test_engine.py::_solo_reference)."""
    t, _ = sampler.generate(jnp.asarray(np.asarray(prompt)[None], jnp.int32),
                            new_tokens, seq_ids=jnp.asarray([uid], jnp.int32))
    return np.asarray(t[0, :len(prompt) + new_tokens])


def agreement(a: dict, b: dict, reqs) -> float:
    """Share of generated tokens on which two runs agree."""
    same = total = 0
    for uid, prompt, new in reqs:
        x, y = a[uid][len(prompt):], b[uid][len(prompt):]
        same += int(np.sum(x == y))
        total += new
    return same / total


def serve_phase(cfg, seed: int, gates: Gates, clock: CompileClock,
                *, n_requests: int = 16, prompt_lo: int = 32,
                prompt_hi: int = 512, new_tokens: int = 64,
                n_replays: int = 4):
    reqs = make_requests(cfg, seed, n_requests, prompt_lo, prompt_hi,
                         new_tokens)
    print(f"{n_requests} requests, prompt lengths "
          f"{sorted(len(p) for _, p, _ in reqs)}, {new_tokens} new tokens")
    with Phase("random weights", clock):
        params = random_params(cfg, seed)

    with Phase("serve: paged-kernel engine", clock):
        eng = new_engine(cfg, params, seed, use_attention_kernel=True)
        submit_all(eng, reqs)
        eng.step()                        # admit, prefill, first rounds
        kern = round_logits(eng, use_kernel=True)
        gath = round_logits(eng, use_kernel=False)
        t0 = time.perf_counter()
        eng.run()
        print(f"kernel engine run after the first step: "
              f"{time.perf_counter() - t0:.1f}s")
        hlo = eng._round_loop_fn(eng.controller.window, eng.rounds_per_sync) \
            .lower(*eng._round_args()).compile().as_text()
        gates.check("tpu_custom_call" in hlo,
                    "compiled round program holds a tpu_custom_call")
        kernel_tokens = results(eng, reqs, gates, "kernel engine")
        # an engine's jitted closures refer back to it: its pool and weights
        # leave the device only when the cycle collector runs
        del eng
        gc.collect()

    scale = float(np.abs(gath).max())
    diff = float(np.abs(kern - gath).max())
    print(f"verify-round logits, kernel vs gather-view: max abs diff "
          f"{diff:.6g}, max |logit| {scale:.6g} "
          f"(tolerance {LOGITS_REL:.6g} x max |logit|)")
    gates.check(diff <= LOGITS_REL * scale,
                "verify-round logits allclose across attention paths")

    # The exactness reference runs in f32. On the chip a reduction's rounding
    # depends on its shape, so in bf16 the batch-8 engine and a batch-1
    # replay of the same request differ in the last bit of some activations,
    # and a sampled token whose logits sit that close to a tie flips
    # (measured on a TPU v5e: 3 of 8 bf16 replays differed, in 1 to 6 of 64
    # tokens). An f32 copy of the same weights at the highest matmul
    # precision leaves only f32 rounding, far below the gaps between logits.
    cfg32, params32 = f32_copy(cfg, params)
    del params                            # the device holds one copy
    with jax.default_matmul_precision("highest"):
        with Phase("serve: gather-view engine, f32", clock):
            eng = new_engine(cfg32, params32, seed,
                             use_attention_kernel=False)
            submit_all(eng, reqs)
            eng.run()
            gather_tokens = results(eng, reqs, gates,
                                    "gather-view engine, f32")
            del eng
            gc.collect()

        with Phase(f"solo replays of {n_replays} requests, f32", clock):
            sampler = PredictiveSampler(cfg32, params32, window=WINDOW,
                                        max_len=MAX_LEN,
                                        eps_key=jax.random.PRNGKey(seed))
            replayed = reqs[:n_replays]
            solo = {uid: solo_replay(sampler, uid, prompt, new)
                    for uid, prompt, new in replayed}
    del sampler, params32
    for uid, _, _ in replayed:
        gates.check(
            uid in gather_tokens
            and np.array_equal(gather_tokens[uid], solo[uid]),
            f"f32 gather-view engine request {uid} bit-identical to its "
            "solo replay")
    if all(uid in kernel_tokens for uid, _, _ in replayed):
        print(f"kernel engine ({cfg.dtype}) token agreement with the f32 solo "
              f"replays: {agreement(kernel_tokens, solo, replayed):.4f}")
    if len(kernel_tokens) == len(gather_tokens) == len(reqs):
        print(f"kernel engine ({cfg.dtype}) vs f32 gather-view engine token "
              f"agreement over all requests: "
              f"{agreement(kernel_tokens, gather_tokens, reqs):.4f}")


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------

def mesh_phase(cfg, seed: int, gates: Gates, clock: CompileClock,
               *, n_requests: int = 8, prompt_len: int = 65,
               new_tokens: int = 64):
    # one prompt length: one prefill width to compile per engine
    reqs = make_requests(cfg, seed, n_requests, prompt_len, prompt_len,
                         new_tokens)
    with Phase("random weights", clock):
        params = random_params(cfg, seed)

    def serve(label, cfg, params, topo):
        with Phase(label, clock):
            p = place_params(params, topo) if topo.mesh is not None \
                else jax.device_put(params)
            eng = new_engine(cfg, p, seed, topology=topo,
                             use_attention_kernel=False)
            submit_all(eng, reqs)
            eng.run()
            tokens = results(eng, reqs, gates, label)
            report = check_engine_round(eng) if topo.mesh is not None \
                else None
            del eng, p
            gc.collect()
        return tokens, report

    one, _ = serve("one device", cfg, params, ServingTopology())
    dp, report = serve("mesh data=4", cfg, params,
                       ServingTopology(make_host_mesh(4, 1)))
    collectives = report.metrics.get("collectives", {})
    print(f"mesh data=4 round program collectives: {collectives}; "
          f"contract violations: {[v.rule for v in report.violations]}")
    gates.check(sum(collectives.values()) == 0,
                "data=4 round program has no collectives")
    if len(dp) == len(reqs):
        print(f"data=4 token agreement with one device ({cfg.dtype}): "
              f"{agreement(dp, one, reqs):.4f}")
    tp, _ = serve("mesh data=2,model=2", cfg, params,
                  ServingTopology(make_host_mesh(2, 2)))
    if len(tp) == len(reqs):
        print(f"data=2,model=2 token agreement with one device "
              f"({cfg.dtype}): {agreement(tp, one, reqs):.4f}")

    # Bit-identity is checked in f32, as in the serve phase: each data shard
    # runs a batch of 2 where the one-device engine runs 8, and in bf16 the
    # chip rounds those reductions differently. The f32 weights stay on the
    # host between engines so that one chip never holds two copies.
    cfg32, params32 = f32_copy(cfg, params)
    params32 = jax.device_get(params32)
    del params
    with jax.default_matmul_precision("highest"):
        one, _ = serve("one device, f32", cfg32, params32, ServingTopology())
        dp, _ = serve("mesh data=4, f32", cfg32, params32,
                      ServingTopology(make_host_mesh(4, 1)))
    gates.check(all(u in dp and np.array_equal(dp[u], one[u])
                    for u, _, _ in reqs),
                "f32 data=4 tokens bit-identical to the one-device engine")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh path, on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}; devices {devices}")
    print(f"platform {dev.platform}, device_kind {dev.device_kind}, "
          f"count {len(devices)}")
    if dev.platform != "tpu":
        print("no TPU: this smoke test runs only on the chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    print(f"compilation cache: {use_compile_cache()}")
    clock = CompileClock()
    gates = Gates()
    cfg = get_config(ARCH)

    t0 = time.perf_counter()
    if args.chips == 1:
        with Phase("kernel", clock):
            kernel_phase(cfg, args.seed, gates)
        serve_phase(cfg, args.seed, gates, clock)
    else:
        mesh_phase(cfg, args.seed, gates, clock)
    print(f"total: wall {time.perf_counter() - t0:.1f}s, "
          f"compile {clock.seconds:.1f}s")

    if gates.failed:
        print(f"{len(gates.failed)} gate(s) failed: {gates.failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
