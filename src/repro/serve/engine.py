"""Batched serving engine: bucketed prefill + decode steps and a slot-based
continuous-batching loop over a dense or *paged* KV memory plane.

`make_prefill_step`/`make_decode_step` are the functions the dry-run lowers
for the decode shapes (decode_32k / long_500k): one new token against a KV /
recurrent-state cache.

`ServeEngine` packs requests into fixed batch slots and refills them as
sequences finish (continuous batching at step granularity). Every engine
step issues exactly one jitted decode call regardless of occupancy (the
end-to-end dataflow picture and the full datapath selection matrix live
in ``docs/architecture.md``); the KV layout behind it is selected by
``cfg.kv_impl``:

``dense``  — one max_len K/V buffer per slot, stacked into a (slots, ...)
    pytree (models.transformer.stack_caches) and decoded as a vmap over the
    slot axis. Memory is slots x max_len whatever the real lengths are.
``paged``  — a global pool of ``block_len``-position KV blocks per layer
    (models.attention.*_init_paged_cache) with per-slot block tables, host
    allocation in the *refcounted* serve.kv_pager.KVPager. Admission
    allocates just the blocks a request can reach (bucketed prompt +
    max_new_tokens) minus any prefix-cache-shared blocks — its *unshared
    footprint* — and drops its references the step it finishes (a block
    rejoins the free list at refcount zero, so shared prefix blocks
    outlive individual requests), so memory follows the *actual* traffic;
    a request that does not fit stays queued (backpressure) instead of
    crashing. Decode gathers each slot's blocks through its table and masks
    past the per-slot length — bit-identical tokens to the dense path
    (greedy and seeded sampling), CI-enforced. ``cfg.paged_attend_impl``
    picks how that decode attends: ``gather`` (assemble the full table
    gather; dense-shaped transient) or ``pallas`` (the block-walking
    kernel in kernels/paged_attention.py: one KV block in VMEM per grid
    step, online softmax, transient independent of max_len — same emitted
    tokens, enforced per backend in tests/test_paged_attention.py).

Admission prefills are *bucketed*: prompts are padded to a small geometric
set of lengths (serve.kv_pager.bucket_lengths, 16/32/.../max_len) with the
real length masked back in (`transformer.override_cache_length`), so
serving N distinct prompt lengths compiles at most len(buckets) prefills —
not N — plus exactly two decode variants (argmax-only and sampling).
Bucketing (and with it the paged plane) is attention-family only: a
recurrent scan has no causal mask to hide a pad tail, so mamba/xlstm archs
prefill at exact length on the dense plane, exactly as before.

*When* prompts prefill is decided per iteration by the iteration-level
scheduler (serve.scheduler.IterationScheduler, Orca/Sarathi/vLLM shape):
every ``step()`` runs a prefill phase (chunk continuations first, then
FIFO admissions, under a ``max_prefill_tokens`` budget) before its single
batched decode dispatch. Two knobs extend the legacy one-prompt-per-
dispatch admission:

``prefill_chunk=C``   — prompts longer than C stream in as block-aligned
    C-wide chunks, one per iteration, interleaved with decode steps, so a
    long prompt no longer stalls every decoding slot for its whole
    prefill and short requests' TTFT stays flat. Mid-prefill slots are
    excluded from decode (their chunks re-pin the length counter each
    dispatch); the final chunk pins the true length and emits the first
    token. Compile widths stay bounded: {buckets <= C} ∪ {C}.
``prefill_batch=R``   — paged mode packs up to R scheduled rows into ONE
    multi-row prefill dispatch (make_paged_prefill_step binds R block-
    table rows by value; pad rows write to scratch), pow2-padded so
    compile batch dims are bounded by log2(R)+1.

``prefix_cache=True`` (paged only) adds the radix-tree prefix cache
(serve/prefix_cache.py): admission matches the prompt's token-id blocks
against previously prefilled prompts, binds the matched pool blocks into
the slot's table (refcounts keep them alive and shared), and prefill
*resumes at the first uncached block-aligned position* — a hit is
literally prefill chunks skipped, with the resumed row pinned like a
mid-chunk continuation. The divergent / partially-filled block is
copy-on-write by construction: shared blocks are never written (resumed
prefill writes only at positions >= its block-aligned start, decode only
at positions >= the pinned length — both land in the slot's fresh
blocks). Eviction (``prefix_eviction``: "lru" default, "fifo") reclaims
refcount-one radix leaves when the pool runs dry. Emitted tokens stay
bit-identical cache-on vs cache-off (tests/test_prefix_cache.py).

Both default off (chunk=None, batch=1): shapes, dispatch order, and tokens
are then bit-for-bit the legacy path. With them on, emitted tokens stay
bit-identical to the unchunked engine — the KV prefix written is the same
bytes, pad keys are causally invisible (exact 0.0 softmax weights), and
per-request key streams make sampling independent of scheduling — which
tests/test_scheduler.py enforces for greedy + seeded sampling, GQA + MLA,
dense + paged. One documented carve-out: capacity-factor MoE routing
(models/moe.py) computes its per-expert capacity from the dispatch width
(``C = ceil(S*K*cap/E)``) and queues tokens per apply, so a GShard MoE
arch's routing — like under any batch-size change — is not invariant to
how a prompt is split into chunks; the attention/KV plane is.

``submit()`` validates rather than trusting ``step()`` to survive: an
empty or over-max_len prompt, or a paged request whose worst-case block
footprint exceeds pool capacity (it could never be admitted and would
head-of-line-block the queue forever), is rejected immediately —
``req.error`` set, ``req.done=True``, returned from ``run()`` with the
finished requests — and the engine keeps serving everyone else.
Sampling (serve.sampling) stays per-slot: each request carries its own
SamplingParams, temperature scaling runs through the CORDIC linear-rotation
multiply by the R2-LVC reciprocal, and every request draws from its own rng
key stream fold_in(fold_in(base, rid), t) — making the emitted tokens
independent of slot placement, batch composition, and KV layout.

Observability (repro.obs): construct the engine with ``obs=Observability()``
(optionally ``trace=True`` for a Chrome-trace/Perfetto request-lifecycle +
engine-phase timeline) and read ``obs.metrics.snapshot()`` afterwards. All
instrumentation is host-side (each phase span is also a profiler span,
``engine.<phase>``, so ``jax.profiler.trace(dir)`` shows the engine's
phases beside the device's ops): nothing here feeds a jitted function, so
compile counts and emitted tokens are bit-identical with observability on
or off (CI-enforced in tests/test_obs.py). The full metric-name reference
(every ``engine.*`` / ``kv.pool.*`` / ``prefix.*`` / ``fixed_point.*``
series, with types, units, and emission points) lives in
``docs/observability.md`` — the handles themselves are registered in
``_bind_obs_handles`` and ``KVPager.attach_metrics``, and CI's docs lane
cross-checks the doc against the registration code in both directions.

Sharding contract (``tp=N`` / ``mesh=``): the engine runs SPMD on a
("data","model") mesh (launch.mesh.make_host_mesh). Decode is still ONE
jitted dispatch per step; the GSPMD partitioner splits it across shards.
Emitted tokens are bit-identical per shard count (TP=1 == TP=2 == TP=4;
greedy + seeded sampling, GQA + MLA, dense/paged/pallas, chunked +
unchunked — tests/test_sharded_serving.py), and the collective schedule
is exactly one all-gather per decode step, at the logits, with none
inside the attention datapath (the HLO-cost lane asserts this):

    leaf / tensor                 PartitionSpec          why
    ----------------------------  ---------------------  -------------------
    wq / wk / wv / wo             heads on "model"       Megatron column/row
    mlp w_in / w_gate / w_out     d_ff on "model"        Megatron column/row
    embed table                   replicated (forced)    jnp.take must stay
                                                         shard-local
    lm_head table (untied)        vocab on "model"       -> the ONE logits
                                                         all-gather/step
    dense cache k / v             KH axis on "model"     head-parallel GQA
    paged k_pool / v_pool         (N,L,KH/tp,hd)/shard   head-parallel GQA
    MLA c_kv_pool / k_rope_pool   replicated             latent is head-less
    block tables / lens / idx     replicated             host metadata; the
                                                         refcounted KVPager
                                                         (and with it the
                                                         prefix cache's
                                                         block sharing)
                                                         stays shard-
                                                         agnostic: one
                                                         logical block id
                                                         space, every shard
                                                         holds a head-slice
                                                         of every block
    tokens/rids/steps/temps/...   replicated             tiny host state
    logits                        replicated (pinned in  sampling tail runs
                                  transformer.apply)     shard-local, bit-
                                                         identical per tp

Tied-embeddings models replicate the head too (carve-out: zero
all-gathers). The paged-attention Pallas kernel runs under shard_map
(kernels.paged_attention.shard_local_*) — per-shard head slices against
replicated tables, grid unchanged — since pallas_call is opaque to the
partitioner; engine init enforces head % tp == 0 on that path.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_lib
from repro.distributed import sharding as shd
from repro.models import transformer as tf
from repro.serve import kv_pager as kvp
from repro.serve import sampling as sp
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import IterationScheduler, PrefillRow


def make_prefill_step(cfg):
    def prefill(params, cache, batch):
        logits, _, cache = tf.apply(params, batch, cfg, cache=cache)
        return logits[:, -1], cache
    return prefill


def make_bucketed_prefill_step(cfg):
    """Dense prefill over one bucket-padded prompt segment: runs the
    segment through the cache, pins the cache position counters to
    ``pin_len`` and hands back the logits row at ``logit_idx``.

    Single-shot (the legacy path): the segment is the whole bucket-padded
    prompt, ``pin_len`` is the real prompt length and ``logit_idx`` is its
    last real position — the pad tail is causally invisible to that row
    and is overwritten by decode writes, so padding never changes the
    emitted tokens. One compile per bucket width instead of one per
    distinct prompt length.

    Chunked prefill reuses the same function per chunk: a mid-prompt chunk
    pins ``pin_len`` to the chunk frontier (its logits are discarded, so
    ``logit_idx`` is any in-range row) and the final chunk pins the true
    length and indexes the last real position relative to its own start.
    """
    def prefill(params, cache, batch, pin_len, logit_idx):
        logits, _, cache = tf.apply(params, batch, cfg, cache=cache)
        cache = tf.override_cache_length(cache, pin_len)
        last = jax.lax.dynamic_index_in_dim(logits, logit_idx, axis=1,
                                            keepdims=False)
        return last, cache
    return prefill


def make_paged_prefill_step(cfg):
    """Multi-row prefill straight into pool blocks: R scheduled prompt
    segments (whole prompts, or ``prefill_chunk``-wide chunks of longer
    ones) run as ONE batch-R apply against the shared pools. No dense
    max_len cache is materialized and nothing is copied at insert.

    Per row ``r`` of the dispatch:
        tokens[r]     — (W,) bucket/chunk-padded segment tokens
        slot_ids[r]   — the seated slot
        view_rows[r]  — block-table row the apply reads/writes through:
                        the slot's real allocation up to the last block
                        holding a position this row can see, every entry
                        past that redirected to the scratch block (the
                        tail-write trim: pad positions scatter into
                        scratch instead of burning pool traffic on blocks
                        whose content is never read). Pad rows (R is
                        pow2-padded) are all-scratch.
        full_rows[r]  — the slot's real allocation, registered on the
                        device after the apply so decode writes land in
                        live blocks
        start_lens[r] — first position this segment covers (0 for a fresh
                        admission, the chunk frontier for a continuation);
                        block-aligned, feeds RoPE positions + pool-write
                        offsets
        pin_lens[r]   — length the slot is pinned to afterwards: the true
                        prompt length on a final row, the new chunk
                        frontier mid-prompt
        logit_idx[r]  — segment-relative row of the logits to return (the
                        last real position on final rows; discarded
                        otherwise)
        valid[r]      — False for pad rows: their slot registration is
                        masked out entirely, so a pad row may alias a live
                        slot id without clobbering it

    Tables/lens enter the apply *by value* (paged_pool_view) rather than
    through a device gather, so pad rows never read or corrupt real slot
    state; only the pools carry updates back (paged_pool_merge) and slot
    registration is a separate masked write (paged_set_rows)."""
    def prefill(params, caches, tokens, slot_ids, view_rows, full_rows,
                start_lens, pin_lens, logit_idx, valid):
        view = tf.paged_pool_view(cfg, caches, view_rows, start_lens)
        logits, _, nview = tf.apply(params, {"tokens": tokens}, cfg,
                                    cache=view)
        caches = tf.paged_pool_merge(cfg, caches, nview)
        caches = tf.paged_set_rows(cfg, caches, slot_ids, full_rows,
                                   pin_lens, valid)
        last = jax.vmap(lambda row, i: jax.lax.dynamic_index_in_dim(
            row, i, axis=0, keepdims=False))(logits, logit_idx)
        return last, caches
    return prefill


def make_decode_step(cfg, *, greedy: bool = True, temperature: float = 1.0):
    """Single-cache decode step (the shape the dry-run lowers; the engine
    itself uses make_batched_decode_step over stacked slot caches)."""
    def decode(params, cache, tokens, rng=None):
        """tokens: (B,1) int32 (or (B,1,d) embeds). Returns next token ids.

        Sampling decode consumes `rng` — the caller threads a fresh split
        per step; reusing one key would make every step/batch draw the
        same sample.
        """
        batch = ({"tokens": tokens} if cfg.input_mode == "tokens"
                 else {"embeds": tokens})
        logits, _, cache = tf.apply(params, batch, cfg, cache=cache)
        last = logits[:, -1]
        if greedy:
            nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        else:
            if rng is None:
                raise ValueError("sampling decode requires an rng key")
            nxt = jax.random.categorical(
                rng, last / temperature).astype(jnp.int32)
        return nxt, cache
    return decode


def _sample_step(last, rids, steps, temps, top_ks, greedy, base_key,
                 greedy_only: bool):
    """Shared tail of the batched decode variants: (S,V) last-position
    logits -> (S,) next tokens. ``greedy_only`` compiles the argmax-only
    datapath; greedy tokens are argmax of the raw logits in BOTH variants,
    so which one runs never changes the output."""
    if greedy_only:
        return jnp.argmax(last, axis=-1).astype(jnp.int32)
    keys = jax.vmap(lambda r, t: sp.request_key(base_key, r, t))(rids, steps)
    return sp.sample_batched(last, keys, temps, top_ks, greedy)


def make_batched_decode_step(cfg, *, greedy_only: bool = False):
    """One jitted decode for ALL slots of a *dense* stacked cache: vmap
    over the stacked (slots, 1, ...) cache axis.

    Arguments of the returned function (S = slot count):
        params        — model params (broadcast across slots)
        caches        — stacked (S, 1, ...) cache pytree (stack_caches)
        tokens        — (S, 1) int32 previous token per slot
        rids, steps   — (S,) int32: request id + token index, hashed into
                        per-slot keys fold_in(fold_in(base_key, rid), step)
        temps, top_ks, greedy — (S,) per-slot SamplingParams (traced, so a
                        changed request mix never recompiles)
        base_key      — engine-level PRNG key

    Returns ((S,) int32 next tokens, updated stacked caches). Inactive
    slots decode garbage tokens against their stale caches — the engine
    masks them on the host; their caches are re-prefilled at admission.
    """
    def decode(params, caches, tokens, rids, steps, temps, top_ks, greedy,
               base_key):
        def one(cache, tok):
            logits, _, nc = tf.apply(params, {"tokens": tok[None, :]}, cfg,
                                     cache=cache)
            return logits[0, -1], nc

        last, caches = jax.vmap(one)(caches, tokens)
        nxt = _sample_step(last, rids, steps, temps, top_ks, greedy,
                           base_key, greedy_only)
        return nxt, caches
    return decode


def make_paged_decode_step(cfg, *, greedy_only: bool = False):
    """One jitted decode for ALL slots of a *paged* cache: a single
    batch-``slots`` apply — the block pool is global, so there is no
    per-slot cache axis to vmap; per-slot positions live in the cache's
    ``lens`` leaves and each row attends its own table-gathered blocks.
    Same signature and same emitted tokens as make_batched_decode_step."""
    def decode(params, caches, tokens, rids, steps, temps, top_ks, greedy,
               base_key):
        logits, _, caches = tf.apply(params, {"tokens": tokens}, cfg,
                                     cache=caches)
        nxt = _sample_step(logits[:, -1], rids, steps, temps, top_ks, greedy,
                           base_key, greedy_only)
        return nxt, caches
    return decode


def make_score_step(cfg):
    """Teacher-forced per-token log-probs of a prompt.

    The log-softmax datapath follows ``cfg.loss_impl`` (exact | cordic |
    cordic_pallas — repro.train.losses), so served log-prob scoring uses
    the same CORDIC exp/log legs as the training loss.
    """
    from repro.train import losses

    logp_fn = losses.log_softmax_fn(getattr(cfg, "loss_impl", "exact"))

    def score(params, batch):
        """batch: {"tokens": (B,S)}. Returns (B,S-1) log p(token_t | <t)."""
        logits, _, _ = tf.apply(params, batch, cfg, cache=None)
        logp = logp_fn(logits[:, :-1])
        nxt = batch["tokens"][:, 1:]
        return jnp.take_along_axis(logp, nxt[..., None], axis=-1)[..., 0]

    return score


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray             # (S,) int32
    max_new_tokens: int = 16
    sampling: Optional[SamplingParams] = None   # None -> engine default
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    #: set when submit() rejects the request (over-long prompt, impossible
    #: block footprint, ...); a rejected request is done with out == []
    error: Optional[str] = None
    # lifecycle timestamps: absolute time.perf_counter() seconds, stamped
    # unconditionally (obs attached or not, so a post-warm-up attach_obs
    # still observes requests queued earlier); -1 = stage not reached
    t_enqueue: float = dataclasses.field(default=-1.0, repr=False)
    t_admit: float = dataclasses.field(default=-1.0, repr=False)
    t_first: float = dataclasses.field(default=-1.0, repr=False)
    t_finish: float = dataclasses.field(default=-1.0, repr=False)


class ServeEngine:
    """Slot-based continuous batching on top of bucketed prefill + one
    batched decode, over a dense or paged KV plane (see module docstring).

    Static batch of `slots`; each slot holds one request and an active-slot
    mask tracks occupancy. Admission pads the prompt to a length bucket,
    prefills it (into a fresh stacked-tree slot for ``dense``, straight
    into freshly allocated pool blocks for ``paged``), and emits the first
    token; every `step()` then advances ALL slots with exactly one jitted
    decode call and appends the sampled token to each active request.
    Finished slots release their blocks (paged) and are refilled from the
    queue between steps, head-of-queue first — a head that does not fit
    the pool blocks admission until something frees (FIFO backpressure).
    Per-request sampling params can mix greedy / temperature / top-k within
    one batch (see serve.sampling).
    """

    def __init__(self, cfg, params, *, slots: int = 4, max_len: int = 256,
                 eos_token: Optional[int] = None, greedy: bool = True,
                 temperature: float = 1.0, seed: int = 0,
                 sampling: Optional[SamplingParams] = None,
                 softmax_impl: Optional[str] = None,
                 loss_impl: Optional[str] = None,
                 kv_impl: Optional[str] = None,
                 block_len: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 paged_attend_impl: Optional[str] = None,
                 kv_quant: Optional[str] = None,
                 prefill_chunk: Optional[int] = None,
                 prefill_batch: Optional[int] = None,
                 max_prefill_tokens: Optional[int] = None,
                 prefix_cache: bool = False,
                 prefix_eviction: str = "lru",
                 obs: Optional[obs_lib.Observability] = None,
                 tp: Optional[int] = None,
                 mesh: Optional[Any] = None):
        assert cfg.input_mode == "tokens", "engine serves token LMs"
        self.obs = obs if obs is not None else obs_lib.NULL
        if softmax_impl is not None:
            cfg = dataclasses.replace(cfg, softmax_impl=softmax_impl)
        if loss_impl is not None:
            cfg = dataclasses.replace(cfg, loss_impl=loss_impl)
        if kv_impl is not None:
            cfg = dataclasses.replace(cfg, kv_impl=kv_impl)
        if block_len is not None:
            cfg = dataclasses.replace(cfg, kv_block_len=block_len)
        if paged_attend_impl is not None:
            cfg = dataclasses.replace(cfg, paged_attend_impl=paged_attend_impl)
        if kv_quant is not None:
            cfg = dataclasses.replace(cfg, kv_quant=kv_quant)
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.eos = eos_token
        self.kv_impl = getattr(cfg, "kv_impl", "dense")
        self.block_len = getattr(cfg, "kv_block_len", 16)
        if self.kv_impl not in ("dense", "paged"):
            raise ValueError(f"unknown kv_impl {self.kv_impl!r}")
        self.paged_attend_impl = getattr(cfg, "paged_attend_impl", "gather")
        if self.paged_attend_impl not in ("gather", "pallas"):
            raise ValueError(
                f"unknown paged_attend_impl {self.paged_attend_impl!r}")
        if self.paged_attend_impl == "pallas" and self.kv_impl != "paged":
            raise ValueError(
                "paged_attend_impl='pallas' selects the block-walking "
                "decode kernel over the paged KV plane; serve it with "
                "kv_impl='paged' (the dense plane has no block tables)")
        if self.paged_attend_impl == "pallas" and cfg.score_dtype != "f32":
            # fail at init, not mid-serving out of the first decode trace
            # (models.attention._paged_attend_impl enforces the same rule)
            raise ValueError(
                "paged_attend_impl='pallas' supports score_dtype='f32' "
                f"only (got {cfg.score_dtype!r})")
        # -- quantized paged-KV plane (core/kv_quant.py) --------------------
        from repro.core import kv_quant as kvq_mod

        self.kv_quant = getattr(cfg, "kv_quant", "none")
        self._kv_quant_spec = kvq_mod.spec_for(self.kv_quant)  # raises on typo
        if self._kv_quant_spec is not None:
            if self.kv_impl != "paged":
                raise ValueError(
                    "kv_quant quantizes the paged block pools; serve it "
                    "with kv_impl='paged' (the dense plane stays full-"
                    f"width), got kv_impl={self.kv_impl!r}")
            if getattr(cfg, "mla", None) is not None or any(
                    k.startswith("mla") for k in cfg.block_pattern):
                raise ValueError(
                    "kv_quant applies to GQA paged pools only; MLA layers "
                    "store the compressed latent unquantized")
        if self.paged_attend_impl == "pallas":
            # the kv_dtype seam the kernel replays is cfg.dtype — reject
            # unknown/integer dtypes at init instead of letting them fall
            # through to the pool dtype mid-serving (kernels validate too)
            from repro.kernels.paged_attention import canonical_kv_dtype

            canonical_kv_dtype(cfg.dtype)
        # -- tensor-parallel mesh (tentpole refactor; see docstring table) --
        # tp=N resolves to a ("data","model") host mesh with an N-wide
        # model axis; mesh=None/tp=1 is the legacy single-device path
        # byte-for-byte (mesh_or_none never builds a trivial mesh).
        if mesh is None and tp is not None:
            from repro.launch import mesh as mesh_lib

            mesh = mesh_lib.mesh_or_none(tp)
        self.mesh = mesh
        self.tp = int(mesh.shape["model"]) if mesh is not None else 1
        if mesh is not None and self.paged_attend_impl == "pallas":
            # The block-walking kernel runs under shard_map with a strict
            # head-axis split (pallas_call is opaque to GSPMD, so there is
            # no replicated fallback on this path — the gather/dense paths
            # fall back via spec_for_axes divisibility instead).
            from repro.models.attention import _padded_heads

            if getattr(cfg, "mla", None) is not None:
                n_heads, axis = cfg.num_heads, "num_heads"
            else:
                n_heads, axis = _padded_heads(cfg)[1], "kv heads (padded)"
            if n_heads % self.tp:
                raise ValueError(
                    f"paged_attend_impl='pallas' shards attention heads "
                    f"over the model axis: {axis}={n_heads} is not "
                    f"divisible by tp={self.tp}")
        if mesh is not None:
            self._param_sh = shd.serve_param_shardings(cfg, self.params, mesh)
            self.params = jax.device_put(self.params, self._param_sh)
            self._repl = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
        self.buckets = kvp.bucket_lengths(max_len, self.block_len)
        # Bucket-pad prefills only for attention-cache families: causal
        # attention makes the pad tail invisible to the last real position,
        # but recurrent blocks (mamba2/xlstm) would fold pad tokens into
        # their state. Recurrent/hybrid archs prefill at exact prompt
        # length (one compile per distinct length, as before) until the
        # scans learn position masking.
        blk_kinds = set(cfg.block_pattern) | (
            {cfg.shared_block} if cfg.shared_block is not None else set())
        self._bucketed = blk_kinds <= set(tf.PAGED_CACHE_FNS)
        self.default_sampling = (sampling if sampling is not None
                                 else SamplingParams(temperature=temperature,
                                                     greedy=greedy))
        self._base_key = jax.random.PRNGKey(seed)
        # iteration-level prefill policy (serve/scheduler.py): chunk
        # continuations + FIFO admissions under a token budget. With
        # chunk=None / batch=1 (the defaults) the plan degenerates to the
        # legacy one-single-shot-prompt-per-dispatch admission, bit-for-bit.
        self.scheduler = IterationScheduler(
            buckets=self.buckets if self._bucketed else None,
            block_len=self.block_len, max_len=max_len,
            prefill_chunk=prefill_chunk,
            max_prefill_tokens=max_prefill_tokens)
        self.prefill_chunk = prefill_chunk
        if prefill_batch is None:
            prefill_batch = (slots if (prefill_chunk is not None
                                       and self.kv_impl == "paged") else 1)
        if prefill_batch < 1:
            raise ValueError(f"prefill_batch must be >= 1, got {prefill_batch}")
        if prefill_batch > 1 and self.kv_impl != "paged":
            # dense prefill builds one fresh cache per request; batching
            # rows is a paged-plane feature (multi-row block-table binding)
            prefill_batch = 1
        self.prefill_batch = int(prefill_batch)

        if self.kv_impl == "paged":
            if not self._bucketed:
                # block-granular prefill writes need block-aligned (i.e.
                # bucket-padded) widths, and padding is only output-neutral
                # for attention; recurrent families keep the dense plane
                raise ValueError(
                    "paged KV requires an attention-cache-only arch "
                    f"(block pattern {sorted(blk_kinds)} includes recurrent "
                    "blocks); serve it with kv_impl='dense'")
            if max_len % self.block_len:
                raise ValueError(f"max_len {max_len} not a multiple of "
                                 f"block_len {self.block_len}")
            self.max_blocks = max_len // self.block_len
            if num_blocks is None:
                # worst-case default: every slot full-length, + scratch
                num_blocks = slots * self.max_blocks + 1
            self.pager: Optional[kvp.KVPager] = kvp.KVPager(
                num_blocks, self.block_len, slots,
                metrics=self.obs.metrics if self.obs.enabled else None)
            if prefix_cache:
                from repro.serve.prefix_cache import PrefixCache

                # block-table indirection + refcounts make sharing shard-
                # safe for free: one logical block id space per engine
                # regardless of tp (see module docstring table)
                self.prefix: Optional[PrefixCache] = PrefixCache(
                    self.pager, self.block_len, policy=prefix_eviction)
            else:
                self.prefix = None
            init_caches = functools.partial(
                tf.init_paged_cache, cfg, slots, num_blocks, self.block_len,
                self.max_blocks, jnp.float32)
            # head-sharded pools, everything else (tables/lens/latent)
            # replicated. Built by one jit, so each device makes only its
            # own shard: the pools are never staged whole on one device.
            self._cache_sh = (None if self.mesh is None else
                              shd.kv_cache_shardings(
                                  jax.eval_shape(init_caches), self.mesh))
            self._caches = jax.jit(init_caches,
                                   out_shardings=self._cache_sh)()
            # device bytes per block across layers (codes + quant scales):
            # feeds the pager's kv.pool.bytes_in_use gauge and the
            # kv.quant.bytes_per_token series the bench gates on
            self.pager.block_bytes = self.kv_pool_bytes() // num_blocks

            def _clear_fn(caches, slot):
                return tf.paged_set_slot(
                    cfg, caches, slot,
                    jnp.zeros((self.max_blocks,), jnp.int32),
                    jnp.zeros((), jnp.int32))

            if self.mesh is not None:
                # explicit in/out shardings on every jit so decode stays
                # ONE dispatch and cache state round-trips without
                # resharding (donation stays in place)
                repl = self._repl
                self._prefill = jax.jit(
                    make_paged_prefill_step(cfg), donate_argnums=(1,),
                    in_shardings=(self._param_sh, self._cache_sh)
                    + (repl,) * 8,
                    out_shardings=(repl, self._cache_sh))
                decode_sh = dict(
                    in_shardings=(self._param_sh, self._cache_sh)
                    + (repl,) * 7,
                    out_shardings=(repl, self._cache_sh))
                sample_fn = jax.jit(make_paged_decode_step(cfg), **decode_sh)
                greedy_fn = jax.jit(
                    make_paged_decode_step(cfg, greedy_only=True),
                    **decode_sh)
                self._clear_slot = jax.jit(
                    _clear_fn, donate_argnums=(0,),
                    in_shardings=(self._cache_sh, repl),
                    out_shardings=self._cache_sh)
            else:
                self._prefill = jax.jit(make_paged_prefill_step(cfg),
                                        donate_argnums=(1,))
                sample_fn = jax.jit(make_paged_decode_step(cfg))
                greedy_fn = jax.jit(
                    make_paged_decode_step(cfg, greedy_only=True))
                self._clear_slot = jax.jit(_clear_fn, donate_argnums=(0,))
        else:
            if prefix_cache:
                raise ValueError(
                    "prefix_cache shares KV pool blocks through the block-"
                    "table indirection; serve it with kv_impl='paged' (the "
                    "dense plane has per-slot buffers, nothing to share)")
            self.pager = None
            self.prefix = None
            self._caches = tf.stack_caches(
                [tf.init_cache(cfg, 1, max_len, jnp.float32)
                 for _ in range(slots)])
            if self.mesh is not None:
                self._cache_sh = shd.kv_cache_shardings(self._caches,
                                                        self.mesh)
                self._caches = jax.device_put(self._caches, self._cache_sh)
                # batch-1 per-request cache template (prefill in/out +
                # insert_slot's second arg): same KH-sharded leaves
                p1 = jax.eval_shape(
                    lambda: tf.init_cache(cfg, 1, max_len, jnp.float32))
                self._p1_sh = shd.kv_cache_shardings(p1, self.mesh)
                repl = self._repl
                self._prefill = jax.jit(
                    make_bucketed_prefill_step(cfg),
                    in_shardings=(self._param_sh, self._p1_sh, repl, repl,
                                  repl),
                    out_shardings=(repl, self._p1_sh))
                decode_sh = dict(
                    in_shardings=(self._param_sh, self._cache_sh)
                    + (repl,) * 7,
                    out_shardings=(repl, self._cache_sh))
                sample_fn = jax.jit(make_batched_decode_step(cfg),
                                    **decode_sh)
                greedy_fn = jax.jit(
                    make_batched_decode_step(cfg, greedy_only=True),
                    **decode_sh)

                def _insert_fn(stacked, cache, slot):
                    return jax.tree.map(
                        lambda full, one: jax.lax.dynamic_update_index_in_dim(
                            full, one.astype(full.dtype), slot, 0),
                        stacked, cache)

                # engine-owned mesh-aware insert (tf.insert_slot's module-
                # level jit carries no shardings; an explicit one keeps the
                # donated stacked tree's sharding stable across admissions)
                self._insert_jit = jax.jit(
                    _insert_fn, donate_argnums=(0,),
                    in_shardings=(self._cache_sh, self._p1_sh, repl),
                    out_shardings=self._cache_sh)
            else:
                self._prefill = jax.jit(make_bucketed_prefill_step(cfg))
                sample_fn = jax.jit(make_batched_decode_step(cfg))
                greedy_fn = jax.jit(
                    make_batched_decode_step(cfg, greedy_only=True))
                self._insert_jit = None

        def _dispatch(params, caches, tokens, rids, steps, temps, top_ks,
                      greedy, base_key):
            # all-greedy batches take the argmax-only compile (no sampling
            # datapath); tokens are identical either way, see _sample_step
            fn = greedy_fn if bool(np.asarray(greedy).all()) else sample_fn
            return fn(params, caches, tokens, rids, steps, temps, top_ks,
                      greedy, base_key)

        self._decode = _dispatch
        self._decode_jits = (greedy_fn, sample_fn)
        self._sample = jax.jit(sp.sample_batched)
        if self.mesh is not None:
            self._score = jax.jit(make_score_step(cfg),
                                  in_shardings=(self._param_sh, self._repl),
                                  out_shardings=self._repl)
        else:
            self._score = jax.jit(make_score_step(cfg))
        self._done: List[Request] = []
        self._active: List[Optional[Request]] = [None] * slots
        # per-slot full block-table rows (paged; built at admission, reused
        # by every chunk dispatch) and mid-prefill partial caches (dense
        # chunking; held host-side, inserted into the stacked tree only
        # when the final chunk lands)
        self._slot_rows: Dict[int, np.ndarray] = {}
        self._pending: Dict[int, Any] = {}
        self._next_tok = np.zeros((slots, 1), np.int32)
        # per-slot host state mirrored into the batched decode each step
        self._rids = np.zeros(slots, np.int32)
        self._steps = np.zeros(slots, np.int32)    # == len(req.out) per slot
        self._temps = np.ones(slots, np.float32)
        self._top_ks = np.zeros(slots, np.int32)
        self._greedy = np.ones(slots, bool)

        self._bind_obs_handles()

    def _bind_obs_handles(self) -> None:
        # observability handles (null no-ops when obs is disabled; the
        # metric name/type/unit table lives in the module docstring)
        m = self.obs.metrics
        self._m_submitted = m.counter("engine.requests.submitted",
                                      unit="requests")
        self._m_rejected = m.counter("engine.requests.rejected",
                                     unit="requests")
        self._m_finished = m.counter("engine.requests.finished",
                                     unit="requests")
        self._m_pre_disp = m.counter("engine.prefill.dispatches",
                                     unit="calls")
        self._m_pre_rows = m.counter("engine.prefill.rows", unit="rows")
        self._m_pre_chunks = m.counter("engine.prefill.chunks", unit="rows")
        self._m_pre_tokens = m.counter("engine.prefill.tokens", unit="tokens")
        # prefix-cache series (stay zero with the cache off; the bench
        # gate reads prefill.tokens + pool peak to prove the collapse)
        self._m_prefix_hits = m.counter("prefix.hit_tokens", unit="tokens")
        self._m_prefix_shared = m.gauge("prefix.blocks_shared",
                                        unit="blocks")
        self._m_blocks_saved = m.counter("kv.pool.blocks_saved",
                                         unit="blocks")
        self._m_tokens = m.counter("engine.tokens.emitted", unit="tokens")
        self._m_steps = m.counter("engine.steps", unit="steps")
        self._m_queue = m.gauge("engine.queue_depth", unit="requests")
        self._m_occ = m.gauge("engine.batch_occupancy", unit="slots")
        # mesh topology gauges: constant per engine lifetime, set once so
        # every metrics snapshot records what topology produced it
        self._m_mesh_tp = m.gauge("engine.mesh.tp", unit="shards")
        self._m_mesh_dev = m.gauge("engine.mesh.devices", unit="devices")
        self._m_mesh_tp.set(self.tp)
        self._m_mesh_dev.set(self.mesh.size if self.mesh is not None else 1)
        # quantized-KV series: format choice and pool geometry are fixed at
        # init, so (like the mesh gauges) these are set once per bind —
        # code_bits is the pool lane width (32 means unquantized), and
        # bytes_per_token is resident pool bytes per position of capacity
        # (codes + scales), the number the kv_quant bench section gates
        self._m_kvq_bits = m.gauge("kv.quant.code_bits", unit="bits")
        self._m_kvq_bpt = m.gauge("kv.quant.bytes_per_token", unit="bytes")
        spec = self._kv_quant_spec
        self._m_kvq_bits.set(spec.code_bits if spec is not None else 32)
        if self.pager is not None and self.pager.block_bytes:
            self._m_kvq_bpt.set(self.pager.block_bytes / self.block_len)
        self._m_ttft = m.histogram("engine.ttft_ms", unit="ms")
        self._m_tpot = m.histogram("engine.tpot_ms", unit="ms")
        self._m_e2e = m.histogram("engine.e2e_ms", unit="ms")
        self._m_prefill = m.histogram("engine.prefill_ms", unit="ms")
        self._m_step = m.histogram("engine.step_ms", unit="ms")
        # table entries the paged decode attend walks per dispatch: the
        # live blocks of every decodable slot (the kernel walks nothing
        # past a slot's length); against slots x max_blocks it is the
        # share of the block tables that holds live context
        self._m_live_blocks = m.histogram("engine.decode.live_blocks",
                                          unit="blocks")
        self._m_compiles = {
            "prefill": m.counter("engine.compiles.prefill", unit="compiles"),
            "decode": m.counter("engine.compiles.decode", unit="compiles"),
        }
        self._last_compiles = (self.compile_counts() if self.obs.enabled
                               else None)
        if self.pager is not None:
            self.pager.attach_metrics(m if self.obs.enabled else None)

    def attach_obs(self, obs: Optional[obs_lib.Observability]) -> None:
        """Attach (or replace, or with None detach) the observability
        handle mid-lifetime — e.g. after a warm-up pass, so compile walls
        stay out of the latency histograms. Metrics recorded so far stay
        in the previous handle's registry; compile counters restart from
        the current jit-cache sizes."""
        self.obs = obs if obs is not None else obs_lib.NULL
        self._bind_obs_handles()

    def kv_pool_bytes(self) -> int:
        """Resident device bytes of the paged pool leaves across layers —
        K/V code pools plus, under kv_quant, the per-block scale pools
        (every ``*_pool`` leaf). This is the footprint quantization
        shrinks; the kv_quant bench section compares it across formats at
        matched block count. 0 on the dense plane."""
        if self.kv_impl != "paged":
            return 0
        total = 0

        def one(path, leaf):
            nonlocal total
            name = getattr(path[-1], "key", None)
            if isinstance(name, str) and name.endswith("_pool"):
                total += leaf.size * jnp.dtype(leaf.dtype).itemsize

        jax.tree_util.tree_map_with_path(one, self._caches)
        return total

    def _obs_compiles(self) -> None:
        """Fold compile_counts() deltas into compile counters + trace
        instants — jit-cache growth observed from the host, never traced."""
        if not self.obs.enabled:
            return
        counts = self.compile_counts()
        for kind, n in counts.items():
            d = n - self._last_compiles[kind]
            if d > 0:
                self._m_compiles[kind].inc(d)
                if self.obs.trace is not None:
                    self.obs.trace.instant(f"compile:{kind}",
                                           self.obs.now_us(),
                                           args={"cache_size": n})
        self._last_compiles = counts

    def _obs_prefilled(self, req: Request, first: int) -> None:
        """Prefill-completion lifecycle record: prefill span (admit ->
        first token, chunk interleaving included), TTFT (enqueue -> first
        token, queueing included), first-token event + compiles. The
        timestamp is stamped whether obs is attached or not."""
        now = time.perf_counter()
        req.t_first = now
        if not self.obs.enabled:
            return
        self._m_prefill.observe((now - req.t_admit) * 1e3)
        if req.t_enqueue >= 0:
            self._m_ttft.observe((now - req.t_enqueue) * 1e3)
        self._m_tokens.inc()
        # stamps are absolute perf_counter values; the trace timeline is
        # relative to this obs handle's epoch (clamped: a request admitted
        # before a later attach_obs starts its span at the epoch)
        self.obs.request_span("prefill", req.rid,
                              max(0.0, req.t_admit - self.obs.epoch))
        self.obs.request_event("first_token", req.rid, {"token": first})
        self._obs_compiles()

    @property
    def _queue(self):
        """Pending (validated, unadmitted) requests — the scheduler's FIFO
        deque. Exposed for introspection; mutate only through submit()."""
        return self.scheduler.queue

    def _validate(self, req: Request) -> Optional[str]:
        """Reason this request can never be served, or None if admissible.
        Catching these at submit() keeps one bad request from killing (or
        permanently head-of-line-blocking) the serving loop: an over-long
        prompt used to raise ValueError out of bucket_for deep inside
        step(), and an over-capacity paged request was only detected once
        the engine went fully idle."""
        plen = len(req.prompt)
        if plen < 1:
            return "empty prompt"
        if plen > self.max_len:
            return (f"prompt length {plen} exceeds engine max_len "
                    f"{self.max_len}")
        if self.pager is not None:
            need = self._blocks_for(req)
            if need > self.pager.capacity:
                return (f"needs {need} KV blocks worst-case — admission "
                        f"budgets the unshared footprint, which with no "
                        f"prefix hit is the whole request — but the pool "
                        f"has {self.pager.capacity} allocatable")
        return None

    def _reject(self, req: Request, reason: str) -> None:
        req.error = f"rejected at submit: {reason}"
        req.done = True
        self._m_submitted.inc()
        self._m_rejected.inc()
        if self.obs.enabled:
            self.obs.request_event("reject", req.rid, {"reason": reason})
        self._done.append(req)

    def submit(self, req: Request) -> None:
        """Validate and enqueue one request. Inadmissible requests are
        rejected immediately (``req.error`` set, ``done=True``, surfaced in
        ``run()``'s result) — the engine keeps serving. Budgets that would
        decode past max_len are truncated here, once, not re-scanned per
        admission."""
        req.t_enqueue = time.perf_counter()
        err = self._validate(req)
        if err is not None:
            self._reject(req, err)
            return
        self._clamp_budget(req)
        self._m_submitted.inc()
        if self.obs.enabled:
            self.obs.request_event("enqueue", req.rid,
                                   {"prompt_len": len(req.prompt),
                                    "max_new_tokens": req.max_new_tokens})
        self.scheduler.enqueue(req)

    def score(self, prompt: np.ndarray) -> np.ndarray:
        """(S,) int32 prompt -> (S-1,) per-token log-probs (teacher-forced),
        through the cfg.loss_impl-selected log-softmax datapath."""
        toks = jnp.asarray(np.asarray(prompt, np.int32)[None, :])
        with shd.serving_mesh(self.mesh):
            out = self._score(self.params, {"tokens": toks})
        return np.asarray(out[0])

    @property
    def active_mask(self) -> np.ndarray:
        """(slots,) bool — which slots currently hold a request."""
        return np.asarray([a is not None for a in self._active])

    def compile_counts(self) -> Dict[str, int]:
        """Jit-cache sizes of the serving datapath — the bucketed-prefill
        guarantee made checkable: after serving any mix of prompt lengths,
        ``prefill <= len(self.buckets) * chunk-variants`` and
        ``decode <= 2`` (argmax-only + sampling variants). Unchunked
        single-row serving (the defaults) keeps the tight legacy bound
        ``prefill <= len(self.buckets)``; chunking adds at most the chunk
        width, and multi-row batching multiplies by the pow2 batch dims
        (<= log2(prefill_batch)+1) — still O(log), never per-prompt-length.
        The prefill bound holds for attention-family archs; recurrent
        archs prefill at exact length (see _bucketed)."""
        return {
            "prefill": int(self._prefill._cache_size()),
            "decode": int(sum(fn._cache_size() for fn in self._decode_jits)),
        }

    def _finish(self, req: Request) -> None:
        req.done = True
        req.t_finish = time.perf_counter()
        if self.obs.enabled:
            self._m_finished.inc()
            if req.t_enqueue >= 0:
                self._m_e2e.observe((req.t_finish - req.t_enqueue) * 1e3)
            if req.t_first >= 0 and len(req.out) > 1:
                # mean decode interval: first token is TTFT's, the rest
                # amortize the decode steps (the standard TPOT definition)
                self._m_tpot.observe((req.t_finish - req.t_first)
                                     / (len(req.out) - 1) * 1e3)
            self.obs.request_event("finish", req.rid,
                                   {"tokens": len(req.out)})
        self._done.append(req)

    def _release_slot(self, s: int) -> None:
        """Return slot ``s`` to the free state: paged mode hands its blocks
        back to the pool and resets the device-side table row to scratch
        zeros (a vacant slot must never scribble on blocks that get
        reallocated); sampling knobs reset to greedy defaults so a vacated
        sampling slot can't pin _dispatch off the cheap all-greedy compile."""
        self._active[s] = None
        self._slot_rows.pop(s, None)
        self._pending.pop(s, None)
        self.scheduler.drop_slot(s)
        if self.pager is not None:
            # drops the slot's reference on every block it bound; blocks
            # the prefix cache (or a sibling slot) still references stay
            # resident, the rest rejoin the free list
            self.pager.free(s)
            if self.prefix is not None:
                self._m_prefix_shared.set(self.pager.blocks_shared)
            self._caches = self._clear_slot(self._caches,
                                            jnp.asarray(s, jnp.int32))
        self._temps[s] = 1.0
        self._top_ks[s] = 0
        self._greedy[s] = True

    def _sample_first(self, req: Request, logits) -> int:
        """Sample the prefill-emitted token (step 0 of the request's key
        stream) with the request's own SamplingParams."""
        temp, top_k, greedy = (req.sampling or self.default_sampling).resolved()
        key = sp.request_key(self._base_key, req.rid, 0)
        tok = self._sample(logits[:1], key[None],
                           jnp.full((1,), temp, jnp.float32),
                           jnp.full((1,), top_k, jnp.int32),
                           jnp.full((1,), greedy, bool))
        return int(tok[0])

    def _blocks_for(self, req: Request) -> int:
        """Pool blocks a request can ever touch: the bucket-padded prefill
        width or prompt + full decode budget, clamped to max_len."""
        need_len = min(max(kvp.bucket_for(len(req.prompt), self.buckets),
                           len(req.prompt) + req.max_new_tokens),
                       self.max_len)
        return kvp.blocks_needed(need_len, self.block_len)

    def _register_slot(self, s: int, req: Request, first: int) -> None:
        """Host-side mirrors for an admitted request."""
        self._active[s] = req
        self._next_tok[s, 0] = first
        temp, top_k, greedy = (req.sampling
                               or self.default_sampling).resolved()
        self._rids[s] = req.rid
        self._steps[s] = len(req.out)
        self._temps[s] = temp
        self._top_ks[s] = top_k
        self._greedy[s] = greedy

    def _finishes_at_prefill(self, req: Request, first: int) -> bool:
        """A request whose first token already hits `eos_token` or whose
        budget is max_new_tokens=1 finishes at admission and never
        occupies a slot."""
        req.out.append(first)
        if (self.eos is not None and first == self.eos) or \
                len(req.out) >= req.max_new_tokens:
            self._finish(req)
            return True
        return False

    def _clamp_budget(self, req: Request) -> None:
        """Truncate max_new_tokens so decode can never write past max_len:
        positions written are prompt..prompt+max_new-2, so the budget caps
        at max_len - len(prompt) + 1. Without this the dense path clamps
        its update into the last position and the paged path's clipped
        table index overwrites a live block — garbage either way, and
        differently, which would break the bit-identity contract. Applied
        once at submit()."""
        req.max_new_tokens = min(req.max_new_tokens,
                                 self.max_len - len(req.prompt) + 1)

    # -- the per-iteration prefill phase ------------------------------------
    def _admit_slot(self, req: Request):
        """Scheduler seating callback: pick a free slot and (paged)
        allocate the request's blocks — with the prefix cache on, only its
        *unshared footprint*: matched pool blocks bind into the slot's
        table (match's pins transfer to the slot) and prefill resumes past
        them, so a hit allocates and computes only the uncached tail.
        Returns the slot id, ``(slot, start)`` on a prefix hit, or None
        when the request cannot be seated right now (no free slot, or
        pool backpressure — the head waits, FIFO)."""
        s = next((i for i in range(self.slots)
                  if self._active[i] is None), None)
        if s is None:
            return None
        need = 0
        start = 0
        if self.pager is not None:
            need_total = self._blocks_for(req)
            shared: List[int] = []
            if self.prefix is not None:
                matched = self.prefix.match(req.prompt)      # pinned for us
                start = self.scheduler.resume_start(
                    len(req.prompt), len(matched) * self.block_len)
                m_used = start // self.block_len
                if m_used < len(matched):
                    # row-geometry alignment used fewer blocks than the
                    # cache matched: drop the surplus pins right away
                    self.pager.release(matched[m_used:])
                shared = matched[:m_used]
            need = need_total - len(shared)
            if self.prefix is not None and not self.pager.can_alloc(need):
                self.prefix.evict_until(need)
            blocks = self.pager.alloc(s, need, shared=shared)
            if blocks is None:
                if shared:                # unwind the match pins; re-match
                    self.pager.release(shared)        # on the next attempt
                return None
            row = np.zeros(self.max_blocks, np.int32)
            row[:len(shared)] = shared
            row[len(shared):need_total] = blocks
            self._slot_rows[s] = row
            if start:
                self._m_prefix_hits.inc(start)
                self._m_blocks_saved.inc(len(shared))
            if self.prefix is not None:
                self._m_prefix_shared.set(self.pager.blocks_shared)
        self._active[s] = req
        req.t_admit = time.perf_counter()
        if self.obs.enabled:
            ev = {"slot": s}
            if self.pager is not None:
                ev["blocks"] = need
                if start:
                    ev["prefix_tokens"] = start
            self.obs.request_event("admit", req.rid, ev)
        return (s, start) if start else s

    def _complete_prefill(self, req: Request, s: int, logits) -> None:
        """Final prefill row landed: sample the first token; the slot joins
        decode next iteration (or frees immediately on eos / budget-1).
        With the prefix cache on, the prompt's full blocks are indexed
        here — KV bytes for a prefix are deterministic (chunked-vs-
        unchunked identity already enforces this), so the blocks are
        shareable the moment the last prompt position is written."""
        first = self._sample_first(req, logits)
        self._obs_prefilled(req, first)
        if self.prefix is not None:
            nfull = len(req.prompt) // self.block_len
            if nfull:
                self.prefix.insert(
                    req.prompt,
                    [int(b) for b in self._slot_rows[s][:nfull]])
        if self._finishes_at_prefill(req, first):
            self._release_slot(s)
        else:
            self._register_slot(s, req, first)

    def _dispatch_prefill_paged(self, group: List[PrefillRow]) -> None:
        """One multi-row prefill dispatch over up to ``prefill_batch``
        scheduled rows, pow2-padded with all-scratch pad rows so compile
        batch dims stay bounded (see make_paged_prefill_step)."""
        rp = 1
        while rp < len(group):
            rp *= 2
        width = max(r.width for r in group)
        toks = np.zeros((rp, width), np.int32)
        slot_ids = np.zeros(rp, np.int32)
        view_rows = np.full((rp, self.max_blocks), kvp.SCRATCH_BLOCK,
                            np.int32)
        full_rows = np.zeros((rp, self.max_blocks), np.int32)
        starts = np.zeros(rp, np.int32)
        pins = np.zeros(rp, np.int32)
        lidx = np.zeros(rp, np.int32)
        valid = np.zeros(rp, bool)
        for i, row in enumerate(group):
            plen = len(row.req.prompt)
            hi = min(plen, row.start + width)
            seg = np.asarray(row.req.prompt[row.start:hi], np.int32)
            toks[i, :len(seg)] = seg
            frow = self._slot_rows[row.slot]
            # tail-write trim: entries past the last block holding a
            # position this row can see go to scratch
            nb_live = kvp.blocks_needed(hi, self.block_len)
            view_rows[i, :nb_live] = frow[:nb_live]
            full_rows[i] = frow
            slot_ids[i] = row.slot
            starts[i] = row.start
            pins[i] = plen if row.final else row.start + row.width
            lidx[i] = (plen - 1 - row.start) if row.final else 0
            valid[i] = True
        self._m_pre_disp.inc()
        logits, self._caches = self._prefill(
            self.params, self._caches, jnp.asarray(toks),
            jnp.asarray(slot_ids), jnp.asarray(view_rows),
            jnp.asarray(full_rows), jnp.asarray(starts),
            jnp.asarray(pins), jnp.asarray(lidx), jnp.asarray(valid))
        for i, row in enumerate(group):
            if row.final:
                self._complete_prefill(row.req, row.slot, logits[i:i + 1])

    def _prefill_dispatch(self, rows: List[PrefillRow]) -> None:
        """One prefill dispatch (a paged group, or one dense row) under
        its ``prefill_dispatch`` phase span."""
        ob = self.obs
        with ob.phase("prefill_dispatch") as span:
            if ob.enabled:
                span.annotate(rows=len(rows),
                              tokens=sum(r.width for r in rows))
            if self.kv_impl == "paged":
                self._dispatch_prefill_paged(rows)
            else:
                self._dispatch_prefill_dense(rows[0])

    def _dispatch_prefill_dense(self, row: PrefillRow) -> None:
        """One dense prefill row. A fresh row starts from an empty batch-1
        cache; a chunk continuation resumes the host-held partial cache.
        The cache only enters the stacked decode tree (insert_slot) when
        the final chunk lands — mid-prefill state never rides in decode."""
        req, s = row.req, row.slot
        plen = len(req.prompt)
        cache = (tf.init_cache(self.cfg, 1, self.max_len, jnp.float32)
                 if row.fresh else self._pending.pop(s))
        toks = np.zeros((1, row.width), np.int32)
        hi = min(plen, row.start + row.width)
        seg = np.asarray(req.prompt[row.start:hi], np.int32)
        toks[0, :len(seg)] = seg
        pin = plen if row.final else row.start + row.width
        li = (plen - 1 - row.start) if row.final else row.width - 1
        self._m_pre_disp.inc()
        logits, cache = self._prefill(
            self.params, cache, {"tokens": jnp.asarray(toks)},
            jnp.asarray(pin, jnp.int32), jnp.asarray(li, jnp.int32))
        if row.final:
            if self._insert_jit is not None:
                self._caches = self._insert_jit(self._caches, cache,
                                                jnp.asarray(s, jnp.int32))
            else:
                self._caches = tf.insert_slot(self._caches, cache, s)
            self._complete_prefill(req, s, logits)
        else:
            self._pending[s] = cache

    def _prefill_phase(self) -> int:
        """Run this iteration's scheduled prefill rows; returns how many.
        Paged rows pack into multi-row dispatches of up to prefill_batch;
        dense rows dispatch one at a time (fresh cache per request)."""
        with self.obs.phase("plan"):
            rows = self.scheduler.plan(self._admit_slot)
        if not rows:
            return 0
        self._m_pre_rows.inc(len(rows))
        self._m_pre_tokens.inc(sum(r.width for r in rows))
        n_chunked = sum(1 for r in rows if not (r.fresh and r.final))
        if n_chunked:
            self._m_pre_chunks.inc(n_chunked)
        if self.kv_impl == "paged":
            # pack rows into multi-row dispatches, never letting a group's
            # shared width push any row past max_len: a resumed row's
            # start + its own width fits by construction (resume_start),
            # but a wider groupmate would widen it into scatter-index
            # clamping territory — flush the group instead
            group: List[PrefillRow] = []
            gw = 0
            for row in rows:
                w = max(gw, row.width)
                if group and (len(group) >= self.prefill_batch or any(
                        r.start + w > self.max_len for r in group + [row])):
                    self._prefill_dispatch(group)
                    group, w = [], row.width
                group.append(row)
                gw = w
            if group:
                self._prefill_dispatch(group)
        else:
            for row in rows:
                self._prefill_dispatch([row])
        return len(rows)

    def step(self) -> int:
        """One engine iteration: the scheduler's prefill phase (chunk
        continuations + admissions), then one batched decode step across
        all decodable slots. Returns the number of slots that advanced
        (decoded slots, or scheduled prefill rows on a prefill-only
        iteration) — 0 means no work was, or could be, done.

        At most ONE jitted decode call regardless of slot count: inactive
        and mid-prefill slots ride along (their output is ignored; dense
        slots are re-prefilled at insert, paged slots' garbage writes land
        in scratch or in positions a later chunk/decode write overwrites
        before the length mask exposes them), so the dispatch count and
        the compiled shape never depend on occupancy — and regardless of
        tp: a sharded engine still issues ONE dispatch, the partitioner
        runs it SPMD across the mesh. An iteration whose only work is
        prefill (e.g. a long prompt still chunking, nothing decodable
        yet) skips the decode dispatch entirely.
        """
        # every trace this iteration performs (prefill/decode/clear/insert)
        # sees the engine's mesh (or None) via the ambient context — model
        # code reads it to place the logits constraint / shard_map attention
        with shd.serving_mesh(self.mesh), self.obs.phase("step") as span:
            return self._step_impl(span)

    def _step_impl(self, span) -> int:
        ob = self.obs
        t_step = time.perf_counter()
        self._m_steps.inc()
        self._m_queue.set(len(self._queue))     # backlog before admission
        with ob.phase("admit"):
            n_rows = self._prefill_phase()
        chunking = self.scheduler.chunking
        decodable = [s for s in range(self.slots)
                     if self._active[s] is not None and s not in chunking]
        self._m_occ.set(len(decodable))
        if ob.enabled:
            span.annotate(decodable=len(decodable), prefill_rows=n_rows)
        if ob.trace is not None:
            ob.trace.counter("engine.load", ob.now_us(),
                             {"queue_depth": len(self._queue),
                              "batch_occupancy": len(decodable)})
        if not decodable:
            if n_rows == 0:
                if self._queue and self.pager is not None:
                    # defensive backstop: submit() rejects requests that
                    # can never fit, so a stuck idle queue means the pool
                    # invariants were bypassed
                    raise RuntimeError(
                        f"request {self._queue[0].rid} can never be "
                        f"admitted: needs "
                        f"{self._blocks_for(self._queue[0])} KV blocks "
                        f"worst-case (admission budgets the unshared "
                        f"footprint; with no prefix hit that is the whole "
                        f"request), pool has {self.pager.capacity} "
                        f"allocatable")
                return 0
            # prefill-only iteration: chunks advanced (or every admitted
            # request finished at prefill); no decode work exists yet
            if ob.enabled:
                self._m_step.observe((time.perf_counter() - t_step) * 1e3)
                self._obs_compiles()
            return n_rows
        if ob.enabled and self.pager is not None:
            # each decodable slot attends its prompt and every token so
            # far, the one this dispatch feeds included
            self._m_live_blocks.observe(sum(
                kvp.blocks_needed(len(self._active[s].prompt)
                                  + len(self._active[s].out), self.block_len)
                for s in decodable))
        # phase spans: dispatch ends when jax hands back async futures,
        # host_sync is the device->host block on the sampled tokens,
        # sample_copy is pure host bookkeeping over the decodable slots
        with ob.phase("dispatch"):
            nxt, self._caches = self._decode(
                self.params, self._caches, jnp.asarray(self._next_tok),
                jnp.asarray(self._rids), jnp.asarray(self._steps),
                jnp.asarray(self._temps), jnp.asarray(self._top_ks),
                jnp.asarray(self._greedy), self._base_key)
        # with a mesh too: the collectives' device time is in the
        # profiler trace, not in a host-clock wait
        with ob.phase("host_sync"):
            nxt = np.asarray(nxt)
        with ob.phase("sample_copy"):
            for s in decodable:
                req = self._active[s]
                tok = int(nxt[s])
                req.out.append(tok)
                self._next_tok[s, 0] = tok
                self._steps[s] = len(req.out)
                ob.request_event("token", req.rid,
                                 {"step": len(req.out), "token": tok})
                if (self.eos is not None and tok == self.eos) or \
                        len(req.out) >= req.max_new_tokens:
                    self._finish(req)
                    self._release_slot(s)
        if ob.enabled:
            self._m_tokens.inc(len(decodable))
            self._m_step.observe((time.perf_counter() - t_step) * 1e3)
            self._obs_compiles()
        return len(decodable)

    def run(self) -> List[Request]:
        """Serve until queue and slots drain; returns every submitted
        request in completion order — including requests submit() rejected
        (``req.error`` set, ``out == []``)."""
        while self._queue or any(a is not None for a in self._active):
            self.step()
        done, self._done = self._done, []
        return done
