"""Batched serving demo: continuous batching with CORDIC activations.

    PYTHONPATH=src python examples/serve_lm.py [--requests 8] [--slots 4] \
        [--temperature 0.8] [--top-k 40]

Loads a small GQA LM (optionally from a train_lm.py checkpoint), submits a
queue of prompt requests, and serves them through the slot-based engine:
bucket-padded prefill per admission (compiles bounded by the bucket count,
not by distinct prompt lengths), then one jitted decode call per engine
step for all slots at once — slots refilled from the queue as requests
finish. ``--kv-impl paged`` swaps the per-slot dense caches for a global
block pool with per-slot block tables (serve/kv_pager.py); emitted tokens
are bit-identical either way. ``--paged-attend-impl pallas`` additionally
swaps the paged decode's full-table gather for the block-walking Pallas
kernel (kernels/paged_attention.py): each slot walks only its *live* KV
blocks — one block in VMEM per grid step, online softmax in f32 scratch —
so the per-step transient working set no longer scales with max_len, and
the emitted tokens are unchanged. ``--prefill-chunk`` turns on the
iteration-level scheduler's chunked prefill (serve/scheduler.py): long
prompts stream in as block-aligned chunks interleaved with decode steps,
so short requests' TTFT stays flat behind a long prompt — emitted tokens
still bit-identical. Sampling runs on the CORDIC datapath
too: temperature scaling is the linear-rotation multiply by the R2-LVC
reciprocal of T, with per-request temperature/top-k/greedy mixes in the
same batch. All sigmoid-family gates run the Q2.14 MR-HRC pipeline.
``--prefix-cache`` (paged only) turns on the radix-tree prompt-prefix
cache: the demo shares a system prompt across requests, so later
admissions bind the earlier request's KV blocks (refcounted, shared)
and resume prefill at the first uncached block — same tokens, fewer
prefill FLOPs and pool blocks (``--prefix-eviction lru|fifo`` picks
the reclaim order under pool pressure).
``--tp N`` shards the engine tensor-parallel over the mesh's ``model``
axis (params Megatron-style, the paged KV pool on its kv-heads dim); N
must divide the visible device count — on CPU force devices first, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=2 ... --tp 2`` — and
emitted tokens stay bit-identical to the unsharded engine.
``--metrics-json``/``--trace-out`` attach the repro.obs observability
layer: TTFT/TPOT/e2e latency histograms with p50/p99 readout, queue and
pool gauges, and a Chrome-trace (Perfetto-loadable) request-lifecycle
timeline — emitted tokens are bit-identical with or without it.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import numpy as np

from repro import obs as repro_obs
from repro.configs.base import ModelConfig
from repro.launch import compile_cache
from repro.models import transformer as tf
from repro.serve.engine import Request, ServeEngine
from repro.serve.sampling import SamplingParams


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--act", default="cordic_fixed")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 = greedy (default)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filtering; 0 = full vocab")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-impl", default="dense", choices=["dense", "paged"],
                    help="decode KV layout: dense per-slot buffers or the "
                         "paged global block pool (bit-identical tokens)")
    ap.add_argument("--block-len", type=int, default=16,
                    help="positions per KV block / prefill bucket granularity")
    ap.add_argument("--paged-attend-impl", default="gather",
                    choices=["gather", "pallas"],
                    help="paged decode attend: 'gather' assembles the full "
                         "block-table gather (dense-shaped transient), "
                         "'pallas' walks live blocks in place with the "
                         "paged-attention kernel (O(block-len) transient, "
                         "same tokens). Requires --kv-impl paged")
    ap.add_argument("--kv-quant", default="none",
                    choices=["none", "int8", "q2_14"],
                    help="paged-pool storage format: K/V quantized at "
                         "pool-write time, dequantized at every read via "
                         "the CORDIC linear-rotation multiply (int8 ~4x / "
                         "q2_14 ~2x fewer resident pool bytes). Requires "
                         "--kv-impl paged")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill: prompts longer than this stream "
                         "in as block-aligned chunks interleaved with "
                         "decode (same tokens). 0 = off")
    ap.add_argument("--prefill-batch", type=int, default=0,
                    help="max prefill rows per multi-row paged dispatch "
                         "(0 = auto)")
    ap.add_argument("--max-prefill-tokens", type=int, default=0,
                    help="per-iteration prefill token budget (0 = unlimited)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prompt-prefix cache over the paged "
                         "pool: admissions sharing full prompt KV blocks "
                         "with an earlier request reuse them instead of "
                         "recomputing (same tokens). Requires --kv-impl "
                         "paged; the demo shares a system prompt across "
                         "requests so hits occur")
    ap.add_argument("--prefix-eviction", default="lru",
                    choices=["lru", "fifo"],
                    help="prefix-cache eviction order over idle cached "
                         "blocks under pool pressure")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel degree over the mesh 'model' "
                         "axis (must divide the visible device count; "
                         "bit-identical tokens). 0/1 = unsharded")
    ap.add_argument("--metrics-json", default=None,
                    help="write the engine metrics snapshot (TTFT/TPOT "
                         "histograms, queue/pool gauges, counters) here")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace (Perfetto-loadable) JSON of "
                         "request lifecycles + engine phase spans here")
    args = ap.parse_args()
    compile_cache.setup()

    cfg = ModelConfig(
        name="serve-demo", family="dense",
        num_layers=4, d_model=256, num_heads=8, num_kv_heads=4,
        d_ff=768, vocab_size=4096, act_impl=args.act,
        rope_theta=1e4, dtype="float32",
    )
    print(f"[serve_lm] model {cfg.param_counts()['total'] / 1e6:.1f}M params, "
          f"act_impl={cfg.act_impl}, slots={args.slots}, "
          f"kv_impl={args.kv_impl}, kv_quant={args.kv_quant}, "
          f"T={args.temperature}, top_k={args.top_k}")
    params = tf.init(cfg, jax.random.PRNGKey(0))

    # temperature <= 0 resolves to greedy inside SamplingParams
    sampling = SamplingParams(temperature=args.temperature, top_k=args.top_k)
    obs = (repro_obs.Observability(trace=args.trace_out is not None)
           if (args.metrics_json or args.trace_out) else None)
    eng = ServeEngine(cfg, params, slots=args.slots, max_len=128,
                      sampling=sampling, seed=args.seed,
                      kv_impl=args.kv_impl, block_len=args.block_len,
                      paged_attend_impl=args.paged_attend_impl,
                      kv_quant=args.kv_quant,
                      prefill_chunk=args.prefill_chunk or None,
                      prefill_batch=args.prefill_batch or None,
                      max_prefill_tokens=args.max_prefill_tokens or None,
                      prefix_cache=args.prefix_cache,
                      prefix_eviction=args.prefix_eviction,
                      tp=args.tp or None,
                      obs=obs)
    if eng.mesh is not None:
        print(f"[serve_lm] mesh: {dict(eng.mesh.shape)} over "
              f"{eng.mesh.size} devices (tokens bit-identical to --tp 1)")
    rng = np.random.default_rng(0)
    # shared system prompt (two full KV blocks) so --prefix-cache has
    # something to hit; empty when the cache is off
    sys_prompt = (rng.integers(0, cfg.vocab_size,
                               2 * args.block_len).astype(np.int32)
                  if args.prefix_cache else np.zeros(0, np.int32))
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(4, 12))
        tail = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        r = Request(rid=i, prompt=np.concatenate([sys_prompt, tail]),
                    max_new_tokens=args.max_new)
        reqs.append(r)
        eng.submit(r)

    t0 = time.time()
    steps = 0
    while eng.step():
        steps += 1
    wall = time.time() - t0
    done = eng.run()
    total_new = sum(len(r.out) for r in reqs)
    print(f"[serve_lm] served {len(done)} requests / {total_new} tokens in "
          f"{steps} engine steps ({steps} batched decode dispatches), "
          f"{wall:.1f}s ({total_new / wall:.1f} tok/s on host CPU)")
    if eng.pager is not None:
        st = eng.pager.stats()
        print(f"[serve_lm] pool: peak {st.peak_in_use}/{st.num_blocks - 1} "
              f"blocks x {eng.block_len} positions "
              f"(dense would pin {args.slots * 128 // eng.block_len})")
    if eng.prefix is not None:
        print(f"[serve_lm] prefix cache ({eng.prefix.policy}): "
              f"{eng.prefix.hits} hits / {eng.prefix.hit_blocks} blocks "
              f"reused, {eng.prefix.evicted_blocks} evicted")
    if obs is not None:
        ttft = obs.metrics.get("engine.ttft_ms")
        print(f"[serve_lm] ttft p50/p99 {ttft.quantile(0.5):.1f}/"
              f"{ttft.quantile(0.99):.1f} ms over {ttft.count} requests")
        if args.metrics_json:
            obs.metrics.to_json(args.metrics_json)
            print(f"[serve_lm] wrote metrics -> {args.metrics_json}")
        if args.trace_out:
            obs.trace.export(args.trace_out)
            print(f"[serve_lm] wrote Chrome trace -> {args.trace_out} "
                  f"(load at ui.perfetto.dev)")
    for r in reqs[:3]:
        print(f"  req {r.rid}: prompt={r.prompt.tolist()} -> out={r.out}")
    assert all(r.done for r in reqs)
    assert sorted(r.rid for r in done) == sorted(r.rid for r in reqs)
    print("[serve_lm] OK — all requests completed.")


if __name__ == "__main__":
    main()
