"""Smoke test of the serving path on a TPU at Yi-9B's published widths.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # tp=4 against tp=1, four chips

The model is ``yi-9b`` (src/repro/configs/yi_9b.py) at its published
widths: d_model 4096, 32 heads over 4 KV heads, head_dim 128, d_ff 11008,
vocabulary 64000. Depth is cut to 24 of its 48 layers, one chip's half of
a two-stage pipeline. Weights are random, drawn from ``--seed``, in the
config's bfloat16. It is served by ``ServeEngine`` on the paged KV plane
with the block-walking Pallas decode kernel, 8 slots and max_len 2048.

One chip, in order:

1. checks that JAX sees a TPU, and exits non-zero before building
   anything when it does not;
2. places the persistent compilation cache (``repro.launch.compile_cache``);
3. serves 16 seeded greedy requests (prompts of 64 to 1024 tokens, 32 new
   tokens each) twice on one engine: cold, which compiles, then warm;
4. checks every request's tokens, that both passes agree, and that the
   engine's prefill and first-decode logits of two requests agree with a
   cache-free forward pass of the same tokens at "highest" precision;
5. serves four of the requests with the paper's kernels compiled
   (``act_impl`` and ``softmax_impl`` both ``cordic_pallas``) and with
   their pure-jnp twins (``cordic_fixed``), cold then warm on each
   engine, and compares the two.

``--four-chips`` runs only the sharded pair instead: the same
configuration at tp=4 on a ("data" 1, "model" 4) mesh, then at tp=1, and
compares their tokens and logits.

Any failed check raises. The last line of standard output is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every phase
passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: layers kept of yi-9b's 48: one chip's half of a two-stage pipeline
LAYERS = 24
SLOTS, MAX_LEN, BLOCK_LEN = 8, 2048, 16
N_REQUESTS, MIN_PROMPT, MAX_PROMPT, MAX_NEW = 16, 64, 1024, 32
#: max_len of the kernel-comparison engines, which serve the four shortest
#: prompts (< 304 tokens): cordic_fixed's jnp softmax materialises its
#: integer stages over (heads, prompt, max_len) scores, 4.5 GB of
#: temporaries at prompt 256 and max_len 2048
KERNEL_MAX_LEN = 512
#: Relative L2 distance allowed between two logits rows of the same tokens
#: computed two ways: the engine against a cache-free forward pass (paged
#: f32 pool, default against "highest" matmul precision), tp=4 against
#: tp=1 (sharded partial sums), and cordic_pallas against cordic_fixed
#: (the same Q2.14 functions, a few least significant bits apart: the
#: fused SwiGLU kernel rounds its product to bfloat16 once where the jnp
#: twin rounds twice, and the softmax kernel normalises by the R2-LVC
#: divide where the twin divides in fixed point).
#:
#: Two unrelated rows sit ~sqrt(2) apart, which is what a wrong weight,
#: cache position or shard gives. A rounding difference starts at an ulp
#: of bfloat16, but 24 layers of random weights amplify it down the
#: residual stream. This configuration narrowed to d_model 1024 on the
#: CPU: tp=4 against tp=1 0.161, cordic_pallas against cordic_fixed
#: 0.155, engine against the forward pass 0.026. At d_model 256 tp=4
#: against tp=1 is 0.173 at 24 layers, 1.8e-2 at 2 layers, and 1.2e-4 at
#: 24 layers in float32: the growth is depth amplifying rounding.
ROUNDING_TOL = 0.5


def tpu_devices():
    """The devices JAX found, after checking that they are TPUs."""
    devs = jax.devices()
    d = devs[0]
    print(f"[smoke] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{d.platform!r}); nothing was run")
    return devs


def yi_9b_cut():
    from repro import configs

    cfg = configs.get_config("yi-9b")
    return dataclasses.replace(cfg, num_layers=LAYERS,
                               block_pattern=cfg.block_pattern[:LAYERS])


class CompileClock:
    """Seconds XLA spends compiling, or reading a compile back from the
    persistent cache, summed from jax.monitoring's backend-compile events
    (tracing and lowering to StableHLO are not counted)."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def make_requests(vocab: int, seed: int):
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    # lengths spread evenly over [MIN_PROMPT, MAX_PROMPT), jittered and
    # ordered by the seed, so every seed covers short and long prompts
    step = (MAX_PROMPT - MIN_PROMPT) // N_REQUESTS
    lens = (MIN_PROMPT + step * np.arange(N_REQUESTS)
            + rng.integers(0, step, N_REQUESTS))
    rng.shuffle(lens)
    prompts = [rng.integers(0, vocab, int(n)).astype(np.int32) for n in lens]

    def fresh(rids=None, max_new=MAX_NEW):
        rids = range(N_REQUESTS) if rids is None else rids
        return [Request(rid=r, prompt=prompts[r], max_new_tokens=max_new)
                for r in rids]
    return prompts, fresh


def build_engine(cfg, params, *, slots=SLOTS, max_len=MAX_LEN, mesh=None):
    from repro.serve.engine import ServeEngine

    return ServeEngine(cfg, params, slots=slots, max_len=max_len,
                       kv_impl="paged", paged_attend_impl="pallas",
                       block_len=BLOCK_LEN, mesh=mesh)


def serve(eng, reqs):
    """Serve ``reqs`` to completion; returns (tokens by rid, seconds)."""
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    done = eng.run()
    secs = time.perf_counter() - t0
    bad = [r.rid for r in done if r.error]
    if bad or len(done) != len(reqs):
        raise RuntimeError(f"requests {bad} rejected, {len(done)} of "
                           f"{len(reqs)} returned")
    return {r.rid: list(map(int, r.out)) for r in done}, secs


class LogitsTap:
    """Records the logits an engine computes while it serves.

    The engine's decode step returns sampled tokens only, so the tap
    wraps the engine's two dispatch points: the paged prefill's own
    logits output, and, before each decode dispatch, one model apply on
    exactly the params, cache and tokens that dispatch receives. Rows
    are keyed by request id; ``decode[rid][t]`` is decode step t."""

    def __init__(self, eng):
        from repro.models import transformer as tf

        cfg = eng.cfg
        self.prefill, self.decode = {}, {}
        logits = jax.jit(lambda p, c, t: tf.apply(
            p, {"tokens": t}, cfg, cache=c)[0][:, -1])
        prefill, decode = eng._prefill, eng._decode

        def tap_prefill(*args):
            out = prefill(*args)
            slot = int(args[3][0])              # slot_ids; one row per call
            self.prefill[eng._active[slot].rid] = np.asarray(out[0][0])
            return out

        def tap_decode(params, caches, tokens, *rest):
            rows = np.asarray(logits(params, caches, tokens))
            for s, req in enumerate(eng._active):
                if req is not None:
                    self.decode.setdefault(req.rid, []).append(rows[s])
            return decode(params, caches, tokens, *rest)

        eng._prefill, eng._decode = tap_prefill, tap_decode


def rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def compare_runs(name, tol, toks_a, tap_a, toks_b, tap_b) -> dict:
    """Logits of two runs of the same requests, compared wherever their
    inputs agree: every prefill row, and decode step t of a request while
    the tokens it has emitted so far are identical in both runs."""
    worst, bitwise, n = 0.0, True, 0
    for rid in sorted(toks_a):
        pairs = [(tap_a.prefill[rid], tap_b.prefill[rid])]
        for t, (la, lb) in enumerate(zip(tap_a.decode[rid],
                                         tap_b.decode[rid])):
            if toks_a[rid][:t + 1] != toks_b[rid][:t + 1]:
                break
            pairs.append((la, lb))
        for la, lb in pairs:
            worst = max(worst, rel_l2(la, lb))
            bitwise &= bool(np.array_equal(la, lb))
            n += 1
    same = sum(toks_a[r] == toks_b[r] for r in toks_a)
    out = {"rows_compared": n, "max_rel_l2": worst,
           "logits_bit_identical": bitwise,
           "requests_with_identical_tokens": f"{same}/{len(toks_a)}"}
    print(f"[smoke] {name}: {json.dumps(out)}", flush=True)
    if not worst <= tol:
        raise RuntimeError(f"{name}: logits differ by relative L2 {worst} "
                           f"> {tol}")
    return out


def check_tokens(toks, vocab):
    for rid, out in toks.items():
        if len(out) != MAX_NEW or not all(0 <= t < vocab for t in out):
            raise RuntimeError(f"request {rid}: {len(out)} tokens, range "
                               f"[{min(out)}, {max(out)}]")


def check_against_reference(eng, params, prompts, rids) -> dict:
    """Engine prefill and first-decode logits of ``rids`` vs one cache-free
    ``tf.apply`` of prompt + first token under "highest" precision. This
    checks the serving path against the model's own forward pass; it is
    not an independent reference implementation."""
    import jax.numpy as jnp

    from repro.models import transformer as tf
    from repro.serve.engine import Request

    tap = LogitsTap(eng)
    toks, _ = serve(eng, [Request(rid=r, prompt=prompts[r], max_new_tokens=2)
                          for r in rids])
    # causal: the zero pad past each row's end is invisible to its rows;
    # a cache-free pass longer than attn_chunk runs in whole chunks
    cfg = eng.cfg
    width = max(len(prompts[r]) for r in rids) + 1
    if width > cfg.attn_chunk:
        width = -(-width // cfg.attn_chunk) * cfg.attn_chunk
    batch = np.zeros((len(rids), width), np.int32)
    for i, r in enumerate(rids):
        batch[i, :len(prompts[r]) + 1] = np.append(prompts[r], toks[r][0])
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: tf.apply(p, {"tokens": t}, cfg)[0])(
            params, jnp.asarray(batch))
    ref = np.asarray(ref)
    out = {}
    for i, r in enumerate(rids):
        n = len(prompts[r])
        for name, got, want in (("prefill", tap.prefill[r], ref[i, n - 1]),
                                ("decode", tap.decode[r][0], ref[i, n])):
            err = rel_l2(got, want)
            out[f"rid{r}_{name}"] = {
                "prompt_len": n, "rel_l2": err,
                "max_abs": float(np.abs(got - want).max()),
                "argmax_agrees": bool(got.argmax() == want.argmax())}
            if not err <= ROUNDING_TOL:
                raise RuntimeError(f"request {r} {name} logits: relative L2 "
                                   f"{err} from the reference > "
                                   f"{ROUNDING_TOL}")
    print(f"[smoke] reference: {json.dumps(out)}", flush=True)
    return out


def peak_memory(tag):
    for d in jax.devices():
        stats = d.memory_stats() or {}
        print(f"[smoke] {tag}: device {d.id} peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use')} bytes_limit="
              f"{stats.get('bytes_limit')}", flush=True)


def one_chip(cfg, params, seed, clock):
    prompts, fresh = make_requests(cfg.vocab_size, seed)
    print(f"[smoke] {N_REQUESTS} requests, prompt lengths "
          f"{sorted(len(p) for p in prompts)}, {MAX_NEW} new tokens each",
          flush=True)
    eng = build_engine(cfg, params)
    c0 = clock.seconds
    cold, cold_s = serve(eng, fresh())
    compile_s = clock.seconds - c0
    check_tokens(cold, cfg.vocab_size)
    c1 = clock.seconds
    warm, warm_s = serve(eng, fresh())
    if warm != cold:
        raise RuntimeError("the warm pass emitted other tokens than the "
                           "cold pass")
    n_tok = sum(len(t) for t in warm.values())
    print(f"[smoke] serve: cold pass {cold_s:.3f} s of which compile "
          f"{compile_s:.3f} s ({clock.cache_hits} persistent-cache hits so "
          f"far); warm pass {warm_s:.3f} s, compile {clock.seconds - c1:.3f} "
          f"s; {n_tok} tokens, {n_tok / warm_s:.1f} tok/s (smoke figure, "
          f"not a benchmark)", flush=True)
    peak_memory("after serve")

    by_len = sorted(range(N_REQUESTS), key=lambda r: len(prompts[r]))
    check_against_reference(eng, params, prompts, [by_len[0], by_len[-1]])
    del eng
    gc.collect()

    few = sorted(by_len[:4])
    runs = {}
    for impl in ("cordic_pallas", "cordic_fixed"):
        kcfg = dataclasses.replace(cfg, act_impl=impl, softmax_impl=impl)
        eng = build_engine(kcfg, params, slots=len(few),
                           max_len=KERNEL_MAX_LEN)
        t0 = clock.seconds
        cold, cold_s = serve(eng, fresh(few))
        compile_s = clock.seconds - t0
        warm, warm_s = serve(eng, fresh(few))
        # tapped last: the tap's extra forward per decode step stays out
        # of the timed passes
        tap = LogitsTap(eng)
        toks, _ = serve(eng, fresh(few))
        check_tokens(toks, cfg.vocab_size)
        if not cold == warm == toks:
            raise RuntimeError(f"{impl}: the three passes emitted different "
                               "tokens")
        runs[impl] = (toks, tap)
        print(f"[smoke] act+softmax {impl}: cold pass {cold_s:.3f} s of "
              f"which compile {compile_s:.3f} s; warm pass {warm_s:.3f} s",
              flush=True)
        del eng, tap
        gc.collect()
    compare_runs("cordic_pallas vs cordic_fixed", ROUNDING_TOL,
                 *runs["cordic_pallas"],
                 *runs["cordic_fixed"])
    peak_memory("end")


def four_chips(cfg, seed):
    from repro.distributed import sharding as shd
    from repro.launch import mesh as mesh_lib

    if jax.device_count() != 4:
        raise SystemExit(f"--four-chips needs 4 devices, JAX found "
                         f"{jax.device_count()}")
    prompts, fresh = make_requests(cfg.vocab_size, seed)
    few = sorted(sorted(range(N_REQUESTS), key=lambda r: len(prompts[r]))[:4])
    key = jax.random.PRNGKey(seed)
    runs = {}
    for tp in (4, 1):
        mesh = mesh_lib.mesh_or_none(tp)
        params = shd.init_serve_params(cfg, key, mesh)
        eng = build_engine(cfg, params, mesh=mesh)
        tap = LogitsTap(eng)
        toks, secs = serve(eng, fresh(few))
        check_tokens(toks, cfg.vocab_size)
        runs[tp] = (toks, tap)
        print(f"[smoke] tp={tp}: mesh "
              f"{dict(mesh.shape) if mesh is not None else None}, "
              f"{secs:.3f} s", flush=True)
        peak_memory(f"after tp={tp}")
        del eng, tap, params
        gc.collect()
    compare_runs("tp=4 vs tp=1", ROUNDING_TOL, *runs[4], *runs[1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only tp=4 against tp=1 on a four-chip host")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the requests")
    args = ap.parse_args(argv)
    devs = tpu_devices()

    from repro.distributed import sharding as shd
    from repro.launch import compile_cache

    print(f"[smoke] compilation cache: {compile_cache.setup()}", flush=True)
    clock = CompileClock()
    cfg = yi_9b_cut()
    print(f"[smoke] {cfg.name} cut to {cfg.num_layers} layers: d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, "
          f"head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, {cfg.dtype}, "
          f"{cfg.param_counts()['total'] / 1e9:.2f}B params", flush=True)
    if args.four_chips:
        four_chips(cfg, args.seed)
    else:
        t0 = time.perf_counter()
        params = shd.init_serve_params(cfg, jax.random.PRNGKey(args.seed))
        jax.block_until_ready(params)
        nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
        print(f"[smoke] params: {nbytes} bytes in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        one_chip(cfg, params, args.seed, clock)
    print(f"[smoke] total compile {clock.seconds:.3f} s, "
          f"{clock.cache_hits} persistent-cache hits", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
