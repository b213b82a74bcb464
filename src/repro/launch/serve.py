"""Serving launcher CLI.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \
        --requests 8 --slots 4

Boots the slot-based continuous-batching engine for a registered arch
(reduced config on CPU; the full-config decode distribution is what
repro.launch.dryrun lowers for the decode shapes).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro import obs as repro_obs
from repro.distributed import sharding as shd
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.serve.engine import Request, ServeEngine
from repro.serve.sampling import SamplingParams


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--act-impl", default="cordic_fixed")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (CORDIC datapath); 0 = greedy")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--kv-impl", default="dense", choices=["dense", "paged"],
                    help="decode KV layout: dense per-slot buffers or the "
                         "global block pool (serve/kv_pager.py)")
    ap.add_argument("--block-len", type=int, default=16,
                    help="positions per KV block / prefill bucket granularity")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="paged pool size incl. scratch (0 = worst-case "
                         "slots*max_len/block_len + 1)")
    ap.add_argument("--paged-attend-impl", default="gather",
                    choices=["gather", "pallas"],
                    help="paged decode attend: full-table gather (dense-"
                         "shaped transient) or the block-walking Pallas "
                         "kernel (O(block_len) transient; same tokens). "
                         "Requires --kv-impl paged")
    ap.add_argument("--kv-quant", default="none",
                    choices=["none", "int8", "q2_14"],
                    help="paged-pool storage format (core/kv_quant.py): "
                         "K/V quantized at pool-write time against per-"
                         "block-per-head scales and dequantized at every "
                         "read via the CORDIC linear-rotation multiply — "
                         "int8 cuts resident pool bytes ~4x, q2_14 ~2x. "
                         "Requires --kv-impl paged")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: prompts longer than this stream "
                         "in as block-aligned chunks interleaved with "
                         "decode steps (serve/scheduler.py; same tokens). "
                         "0 = off (single-shot bucketed prefill)")
    ap.add_argument("--prefill-batch", type=int, default=0,
                    help="max scheduled prefill rows packed into one "
                         "multi-row paged dispatch (0 = auto: slots when "
                         "chunking a paged engine, else 1)")
    ap.add_argument("--max-prefill-tokens", type=int, default=0,
                    help="per-iteration prefill token budget across "
                         "scheduled rows (0 = unlimited)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prompt-prefix cache over the paged "
                         "pool (serve/prefix_cache.py): admissions whose "
                         "prompt shares full KV blocks with an earlier "
                         "prompt bind those blocks instead of recomputing "
                         "them (same tokens). Requires --kv-impl paged. "
                         "The demo prepends a shared system prompt so "
                         "hits actually occur")
    ap.add_argument("--prefix-eviction", default="lru",
                    choices=["lru", "fifo"],
                    help="prefix-cache eviction order over idle cached "
                         "blocks when the pool runs dry")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel degree: shard params + KV over "
                         "the mesh 'model' axis (must divide the visible "
                         "device count; force CPU devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N). "
                         "Tokens are bit-identical to --tp 1. 0/1 = "
                         "unsharded single-device engine")
    ap.add_argument("--metrics-json", default=None,
                    help="write the engine's metrics-registry snapshot "
                         "(TTFT/TPOT/e2e histograms, queue depth, pool "
                         "occupancy, compile + saturation counters) to "
                         "this path")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace (Perfetto-loadable) JSON "
                         "of request lifecycles + engine phase spans to "
                         "this path")
    args = ap.parse_args(argv)
    compile_cache.setup()

    obs = (repro_obs.Observability(trace=args.trace_out is not None)
           if (args.metrics_json or args.trace_out) else None)
    cfg = (configs.get_smoke(args.arch, act_impl=args.act_impl) if args.smoke
           else configs.get_config(args.arch, act_impl=args.act_impl))
    if cfg.input_mode != "tokens":
        import dataclasses

        cfg = dataclasses.replace(cfg, input_mode="tokens")
    dev = jax.devices()
    print(f"[serve] platform={dev[0].platform} kind={dev[0].device_kind} "
          f"count={len(dev)}")
    print(f"[serve] arch={cfg.name} slots={args.slots} kv={args.kv_impl} "
          f"kv_quant={args.kv_quant} tp={args.tp or 1} dtype={cfg.dtype}")
    mesh = mesh_lib.mesh_or_none(args.tp)
    params = shd.init_serve_params(cfg, jax.random.PRNGKey(0), mesh)
    # temperature <= 0 resolves to greedy inside SamplingParams
    sampling = SamplingParams(temperature=args.temperature, top_k=args.top_k)
    eng = ServeEngine(cfg, params, slots=args.slots, max_len=args.max_len,
                      sampling=sampling, kv_impl=args.kv_impl,
                      block_len=args.block_len,
                      num_blocks=args.num_blocks or None,
                      paged_attend_impl=args.paged_attend_impl,
                      kv_quant=args.kv_quant,
                      prefill_chunk=args.prefill_chunk or None,
                      prefill_batch=args.prefill_batch or None,
                      max_prefill_tokens=args.max_prefill_tokens or None,
                      prefix_cache=args.prefix_cache,
                      prefix_eviction=args.prefix_eviction,
                      mesh=mesh,
                      obs=obs)
    if eng.mesh is not None:
        print(f"[serve] mesh: {dict(eng.mesh.shape)} over "
              f"{eng.mesh.size} devices")

    rng = np.random.default_rng(0)
    # with the prefix cache on, share a system prompt across requests so
    # later admissions hit the radix index instead of recomputing it
    sys_prompt = (rng.integers(0, cfg.vocab_size,
                               2 * args.block_len).astype(np.int32)
                  if args.prefix_cache else np.zeros(0, np.int32))
    for i in range(args.requests):
        tail = rng.integers(0, cfg.vocab_size,
                            int(rng.integers(4, 12))).astype(np.int32)
        eng.submit(Request(
            rid=i,
            prompt=np.concatenate([sys_prompt, tail]),
            max_new_tokens=args.max_new))
    t0 = time.time()
    if obs is not None:
        # count eager fixed-point boundary clips into the same registry
        with repro_obs.observe_saturation(obs.metrics):
            done = eng.run()
    else:
        done = eng.run()
    total = sum(len(r.out) for r in done)
    print(f"[serve] {len(done)} requests, {total} tokens, "
          f"{time.time() - t0:.1f}s")
    if eng.pager is not None:
        st = eng.pager.stats()
        print(f"[serve] pool: peak {st.peak_in_use}/{st.num_blocks - 1} "
              f"blocks x {eng.block_len} positions, "
              f"{st.allocs} allocs, {st.alloc_failures} backpressure waits")
    if eng.prefix is not None:
        print(f"[serve] prefix cache ({eng.prefix.policy}): "
              f"{eng.prefix.hits} hits / {eng.prefix.hit_blocks} blocks "
              f"reused, {eng.prefix.evicted_blocks} evicted")
    if obs is not None:
        ttft = obs.metrics.get("engine.ttft_ms")
        tpot = obs.metrics.get("engine.tpot_ms")
        print(f"[serve] ttft p50/p99 {ttft.quantile(0.5):.1f}/"
              f"{ttft.quantile(0.99):.1f} ms, tpot p50 "
              f"{tpot.quantile(0.5):.2f} ms "
              f"({int(obs.metrics.get('engine.tokens.emitted').value)} tok)")
        if args.metrics_json:
            obs.metrics.to_json(args.metrics_json)
            print(f"[serve] wrote metrics -> {args.metrics_json}")
        if args.trace_out:
            obs.trace.export(args.trace_out)
            print(f"[serve] wrote Chrome trace -> {args.trace_out} "
                  f"(load at ui.perfetto.dev)")
    assert len(done) == args.requests
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
