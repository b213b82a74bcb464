"""Attention blocks: GQA (optionally biased QKV) and MLA (DeepSeek-V2 style
multi-head latent attention with compressed KV cache).

Both expose:
    *_spec(cfg)                    -> param spec tree (common.P leaves)
    *_apply(params, x, cfg, ...)   -> (y, new_cache)

Training/prefill uses query-chunked causal attention (flash-style memory
behaviour in pure jnp: no S x S materialization beyond a chunk row), which
also keeps the sequence dimension shardable for SP. Decode attends a single
query against the cache; MLA decode uses the absorbed-projection form so the
cache stays compressed (the whole point of MLA).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import sharding as shd
from repro.models import common as cm
from repro.models.common import P

NEG_INF = -1e30


def _softmax_fn(impl: str):
    """Row-softmax selected by cfg.softmax_impl.

    "exact"         — jax.nn.softmax (XLA transcendental lowering)
    "cordic_pallas" — fused CORDIC kernel (kernels/softmax_cordic.py):
                      max-subtract + CORDIC-exp + LVC normalize, one VMEM pass
    "cordic_fixed"  — same Q2.14 math in plain jnp (oracle / CPU path)
    """
    if impl in (None, "exact"):
        return jax.nn.softmax
    if impl == "cordic_pallas":
        from repro.kernels import ops as kops  # lazy: kernels optional at import

        return lambda s, axis=-1: kops.softmax(s, axis)
    if impl == "cordic_fixed":
        from repro.cordic_engine import functions as F

        return lambda s, axis=-1: F.softmax(s, axis)  # custom_jvp wrapper
    raise ValueError(f"unknown softmax_impl {impl!r}")


# ---------------------------------------------------------------------------
# Chunked causal attention core (shared by GQA / MLA prefill)
# ---------------------------------------------------------------------------
def _attend_block(q, k, v, q_pos, k_pos, scale, score_dtype: str = "f32",
                  softmax_impl: str = "exact"):
    """q: (B,c,KH,G,D)  k/v: (B,T,KH,D)  -> (B,c,KH,G,D), full-row softmax.

    score_dtype="f32": cast operands to f32 (exact reference; on bf16 caches
    this materializes an f32 copy of K/V — measurably bad at decode scale).
    score_dtype="bf16_mxu": keep operands in their storage dtype and
    accumulate in f32 via preferred_element_type — the MXU-native mode; no
    K/V copies, identical accumulation width.
    """
    if score_dtype == "f32":
        q32, k32, v32 = (t.astype(jnp.float32) for t in (q, k, v))
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q32, k32) * scale
    else:
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                       preferred_element_type=jnp.float32) * scale
    mask = (k_pos[None, :] <= q_pos[:, None])[None, None, None]  # (1,1,1,c,T)
    s = jnp.where(mask, s, NEG_INF)
    p = _softmax_fn(softmax_impl)(s, axis=-1)
    if score_dtype == "f32":
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v32)
    else:
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
    return o


def causal_attention(q, k, v, *, q_offset=0, k_len=None, chunk: int = 1024,
                     score_dtype: str = "f32", softmax_impl: str = "exact"):
    """Causal attention with query chunking.

    q: (B,S,KH,G,D) grouped queries; k/v: (B,T,KH,D).
    q_offset: absolute position of q[0] (decode/prefill continuation).
    k_len: number of valid cache positions (defaults to T).
    """
    B, S, KH, G, D = q.shape
    Dv = v.shape[-1]        # may differ from D (MLA: qk=192, v=128)
    T = k.shape[1]
    scale = 1.0 / np.sqrt(D)
    k_pos = jnp.arange(T)
    if k_len is not None:
        k_pos = jnp.where(jnp.arange(T) < k_len, jnp.arange(T), T + 1)

    if S <= chunk:
        q_pos = q_offset + jnp.arange(S)
        o = _attend_block(q, k, v, q_pos, k_pos, scale, score_dtype, softmax_impl)
        return o.astype(q.dtype)

    assert S % chunk == 0, (S, chunk)
    n = S // chunk
    qr = q.reshape(B, n, chunk, KH, G, D).transpose(1, 0, 2, 3, 4, 5)

    def body(i, qc):
        q_pos = q_offset + i * chunk + jnp.arange(chunk)
        return _attend_block(qc, k, v, q_pos, k_pos, scale, score_dtype, softmax_impl)

    o = jax.lax.map(lambda args: body(*args), (jnp.arange(n), qr))
    return o.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, KH, G, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Paged KV plumbing (kv_impl="paged"): a global block pool per layer plus
# per-slot block tables. Host-side allocation lives in serve/kv_pager.py;
# this is the device side — block-granular writes, table gathers, and a
# per-row-positioned attend that is bit-identical to the dense path.
# ---------------------------------------------------------------------------
def _paged_attend_impl(cfg) -> str:
    """cfg.paged_attend_impl with validation: how a paged decode attends.

    "gather" — assemble the full table gather and attend over dense shapes
               (_pool_gather + _attend_rows / _mla_absorbed_decode): the
               provably bit-identical reference.
    "pallas" — walk the block table in place with the block-walking decode
               kernel (kernels/paged_attention.py): a transient of one
               chunk of blocks instead of O(max_len); emitted tokens
               identical (enforced per backend in
               tests/test_paged_attention.py).  Applies to
               the single-query decode step only — paged *prefill* always
               takes the gather path.
    """
    impl = getattr(cfg, "paged_attend_impl", "gather")
    if impl not in ("gather", "pallas"):
        raise ValueError(f"unknown paged_attend_impl {impl!r}")
    if impl == "pallas" and cfg.score_dtype != "f32":
        # the kernels score in f32; a bf16_mxu gather attend would round
        # differently and the token-identity contract would silently break
        raise ValueError(
            "paged_attend_impl='pallas' supports score_dtype='f32' only "
            f"(got {cfg.score_dtype!r}); use the gather path for "
            "bf16_mxu scoring")
    return impl


def _pool_write(pool, tables, lens, new):
    """Write ``S`` new positions per batch row into the block pool.

    pool: (N, L, *f)  tables: (B, M) int32  lens: (B,) int32  new: (B, S, *f).

    S == 1      — decode: one scattered element per row at logical position
                  ``lens`` (block ``tables[b, lens//L]``, offset ``lens%L``).
                  Vacant slots carry an all-zero table, so their garbage
                  write lands in the reserved scratch block 0.
    S % L == 0  — block-aligned prefill starting at the (block-aligned)
                  position ``lens``: whole blocks are scattered through
                  table entries ``lens//L .. lens//L + S/L``. A fresh
                  admission writes from ``lens == 0`` (the first S/L
                  entries, exactly as before); a chunked-prefill
                  continuation resumes at the chunk frontier. The caller
                  guarantees ``lens % L == 0`` and ``lens + S`` within the
                  table, so the clip mode below never actually clips.
    """
    B, S = new.shape[:2]
    L = pool.shape[1]
    if S == 1:
        blk = jnp.take_along_axis(tables, (lens // L)[:, None], axis=1,
                                  mode="clip")[:, 0]
        return pool.at[blk, lens % L].set(new[:, 0].astype(pool.dtype))
    assert S % L == 0, f"prefill width {S} not a multiple of block_len {L}"
    nb = S // L
    idx = (lens // L)[:, None] + jnp.arange(nb)[None, :]        # (B, nb)
    blk = jnp.take_along_axis(tables, idx, axis=1, mode="clip")
    blocks = new.reshape((B * nb, L) + new.shape[2:]).astype(pool.dtype)
    return pool.at[blk.reshape(-1)].set(blocks)


def _pool_write_quant(pool, scale_pool, tables, lens, new, spec):
    """_pool_write for a quantized pool: quantize-at-write against
    per-block-per-head amax scales. Returns (pool, scale_pool) updated.

    pool: (N, L, KH, hd) integer codes; scale_pool: (N, 1, KH, 1) f32.

    S % L == 0  — prefill: each written block gets a fresh scale from its
                  own per-head amax (bucket padding rides along in the
                  amax — deterministic, and padded positions are masked at
                  attend time anyway), codes and scales scattered through
                  the same table entries.
    S == 1      — decode append into a possibly part-filled block: the
                  block scale is a running per-head max. A fresh block
                  (lens % L == 0) takes the new element's scale outright
                  (the old pool entry is a previous tenant's); otherwise
                  the scale can only grow, and when it does the block's
                  existing codes are re-quantized by the ratio old/new —
                  ratio <= 1, so the rescale itself never clips. When the
                  scale is unchanged the ratio is exactly 1.0 and integer
                  codes survive the round-trip bit-for-bit, which keeps
                  append-only decode deterministic across TP layouts.

    Vacant slots (all-zero tables) land both writes in scratch block 0,
    same as _pool_write — scratch contents are garbage by contract and
    masked at every read.
    """
    from repro.core import kv_quant as kvq  # lazy: quant optional at import

    B, S = new.shape[:2]
    L = pool.shape[1]
    fmt = spec.fmt
    if S == 1:
        blk = jnp.take_along_axis(tables, (lens // L)[:, None], axis=1,
                                  mode="clip")[:, 0]
        elem = new[:, 0].astype(jnp.float32)                     # (B, KH, hd)
        need = kvq.scale_for_amax(
            jnp.max(jnp.abs(elem), axis=-1)[:, None, :, None], spec)
        old = scale_pool[blk]                                    # (B,1,KH,1)
        fresh = (lens % L == 0)[:, None, None, None]
        new_scale = jnp.where(fresh, need, jnp.maximum(old, need))
        # re-quantize the block's existing codes to the (possibly grown)
        # scale; ratio <= 1 for live blocks so the clip below is only a
        # guard against stale garbage under a fresh block's ratio > 1
        ratio = old / new_scale
        cur = pool[blk].astype(jnp.float32)                      # (B,L,KH,hd)
        resc = jnp.clip(jnp.round(cur * ratio), fmt.min_int,
                        fmt.max_int).astype(pool.dtype)
        code = kvq.quantize(elem, spec, new_scale[:, 0])         # (B, KH, hd)
        pool = pool.at[blk].set(resc).at[blk, lens % L].set(code)
        return pool, scale_pool.at[blk].set(new_scale)
    assert S % L == 0, f"prefill width {S} not a multiple of block_len {L}"
    nb = S // L
    idx = (lens // L)[:, None] + jnp.arange(nb)[None, :]         # (B, nb)
    blk = jnp.take_along_axis(tables, idx, axis=1, mode="clip").reshape(-1)
    blocks = new.reshape((B * nb, L) + new.shape[2:]).astype(jnp.float32)
    scales = kvq.block_scale(blocks, spec)                       # (B*nb,1,KH,1)
    codes = kvq.quantize(blocks, spec, scales)
    return pool.at[blk].set(codes), scale_pool.at[blk].set(scales)


def _pool_gather(pool, tables):
    """Assemble each row's logical KV buffer from its block table:
    (N, L, *f) pool + (B, M) tables -> (B, M*L, *f). Entries past the
    slot's real length point at scratch/stale blocks and are masked by the
    caller (zero softmax weight, so their values never contribute).

    The gather spans the FULL table (M*L == max_len positions), trading
    transient working set for exactness: the attend then runs over the
    same shapes as the dense path, which is what keeps paged decode
    bit-identical to dense. Paging therefore shrinks *resident* KV (the
    pool) but not the per-step gather; ``cfg.paged_attend_impl="pallas"``
    swaps the decode step for the block-walking kernel in
    kernels/paged_attention.py, whose transient does not grow with max_len."""
    B, M = tables.shape
    L = pool.shape[1]
    return pool[tables].reshape((B, M * L) + pool.shape[2:])


def _pool_gather_dequant(pool, scale_pool, tables, spec):
    """_pool_gather for a quantized pool: gather codes and per-block
    scales through the same table, then CORDIC-dequantize elementwise.

    Returns (B, M*L, KH, hd) f32. This is the single dequant definition
    both the engine's gather attend and kernels/ref.py's oracle call, so
    the reference cannot drift from production: (code, scale) pairs are
    identical to what the Pallas kernel sees per block, and
    kv_quant.dequantize is elementwise-deterministic."""
    from repro.core import kv_quant as kvq  # lazy: quant optional at import

    L = pool.shape[1]
    codes = _pool_gather(pool, tables)                   # (B, M*L, KH, hd)
    scales = jnp.repeat(_pool_gather(scale_pool, tables),  # (B, M, KH, 1)
                        L, axis=1)                       # (B, M*L, KH, 1)
    return kvq.dequantize(codes, spec, scales)


def _attend_rows(q, k, v, q_pos, k_len, scale, score_dtype: str = "f32",
                 softmax_impl: str = "exact"):
    """_attend_block with per-batch-row positions: q: (B,S,KH,G,D),
    k/v: (B,T,KH,Dv), q_pos: (B,S) absolute query positions, k_len: (B,)
    valid key counts. Identical einsum contractions to _attend_block —
    only the mask gains a batch axis — so a paged decode step produces
    bit-identical outputs to the dense (vmapped per-slot) decode."""
    if score_dtype == "f32":
        q32, k32, v32 = (t.astype(jnp.float32) for t in (q, k, v))
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q32, k32) * scale
    else:
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                       preferred_element_type=jnp.float32) * scale
    T = k.shape[1]
    k_pos = jnp.arange(T)
    mask = ((k_pos[None, None, :] < k_len[:, None, None])
            & (k_pos[None, None, :] <= q_pos[:, :, None]))      # (B,S,T)
    s = jnp.where(mask[:, None, None], s, NEG_INF)              # (B,h,g,S,T)
    p = _softmax_fn(softmax_impl)(s, axis=-1)
    if score_dtype == "f32":
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v32)
    else:
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
    return o


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------
def _padded_heads(cfg):
    """(H', KH') after optional padding to a TP-friendly multiple.

    Padding head counts (MaxText-style) keeps the head dimension shardable
    on wide model axes when the arch's native count is not divisible
    (e.g. 40 q heads / 8 kv heads on 16-way TP). KV heads pad up to the
    multiple and q heads follow as H' = KH' * G (G = original group size),
    preserving the kv-major q->kv mapping for the real heads.

    Forward exactness: padded k/v projections are zero, so padded heads
    attend uniformly over zero values -> zero output -> zero contribution
    through wo, whatever its padded rows hold (asserted in tests). Under
    training the padded rows become extra capacity (documented in DESIGN).
    """
    H, KH = cfg.num_heads, cfg.num_kv_heads
    p = cfg.pad_heads_to
    if p and p > 1:
        G = H // KH
        KH = -(-KH // p) * p
        H = KH * G
    return H, KH


def gqa_spec(cfg) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.head_dim
    H, KH = _padded_heads(cfg)
    spec = {
        "wq": P((d, H, hd), ("embed", "heads", None)),
        "wk": P((d, KH, hd), ("embed", "kv_heads", None)),
        "wv": P((d, KH, hd), ("embed", "kv_heads", None)),
        "wo": P((H, hd, d), ("heads", None, "embed")),
    }
    if cfg.qkv_bias:
        spec["bq"] = P((H, hd), ("heads", None), init="zeros")
        spec["bk"] = P((KH, hd), ("kv_heads", None), init="zeros")
        spec["bv"] = P((KH, hd), ("kv_heads", None), init="zeros")
    return spec


def gqa_init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    _, KH = _padded_heads(cfg)
    hd = cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_len, KH, hd), dtype),
        "v": jnp.zeros((batch, max_len, KH, hd), dtype),
        "idx": jnp.zeros((), jnp.int32),
    }


def gqa_init_paged_cache(cfg, slots: int, num_blocks: int, block_len: int,
                         max_blocks: int, dtype=jnp.bfloat16):
    """Paged decode cache for one GQA layer: a global (num_blocks,
    block_len, KH, hd) K/V pool shared by every slot, per-slot block
    tables (slots, max_blocks) into it, and per-slot lengths. Block 0 is
    the scratch block (kv_pager.SCRATCH_BLOCK): vacant slots point at it.

    With ``cfg.kv_quant`` != "none" the K/V pools store integer codes in
    the format's lane dtype and two extra leaves carry the per-block
    per-head f32 scales, shape (num_blocks, 1, KH, 1) — the "_pool"
    suffix routes them through the same view/merge plumbing as the code
    pools, and dim -2 is KH so the TP kv-heads sharding rule covers them.
    Scales start at 1.0 (scratch/unwritten blocks dequantize to zero)."""
    from repro.core import kv_quant as kvq  # lazy: quant optional at import

    _, KH = _padded_heads(cfg)
    hd = cfg.head_dim
    spec = kvq.spec_for(getattr(cfg, "kv_quant", "none"))
    kv_dtype = dtype if spec is None else spec.code_dtype
    cache = {
        "k_pool": jnp.zeros((num_blocks, block_len, KH, hd), kv_dtype),
        "v_pool": jnp.zeros((num_blocks, block_len, KH, hd), kv_dtype),
        "tables": jnp.zeros((slots, max_blocks), jnp.int32),
        "lens": jnp.zeros((slots,), jnp.int32),
    }
    if spec is not None:
        cache["k_scale_pool"] = jnp.ones((num_blocks, 1, KH, 1), jnp.float32)
        cache["v_scale_pool"] = jnp.ones((num_blocks, 1, KH, 1), jnp.float32)
    return cache


def _gqa_paged_apply(params, x, cfg, cache, q, k, v):
    """Paged continuation of gqa_apply (cache holds a block pool).

    Decode (S==1): every row writes its new K/V element through its block
    table, then attends against the table-gathered (B, M*L, KH, hd) buffer
    masked past the per-slot length — or, with
    ``cfg.paged_attend_impl="pallas"``, walks its live blocks in place via
    the block-walking kernel (no gather is materialized). Prefill
    (S==bucket width, one row): whole-block writes, then the
    gather-and-attend — shape-identical to the dense path's full-cache
    attend, which keeps logits bit-equal.
    """
    B, S, _ = x.shape
    hd = cfg.head_dim
    KH = k.shape[2]
    G = q.shape[2] // KH
    lens, tables = cache["lens"], cache["tables"]

    positions = lens[:, None] + jnp.arange(S)[None, :]          # (B,S)
    q = cm.apply_rope(q, positions, cfg.rope_theta)
    k = cm.apply_rope(k, positions, cfg.rope_theta)

    from repro.core import kv_quant as kvq  # lazy: quant optional at import
    spec = kvq.spec_for(getattr(cfg, "kv_quant", "none"))
    if spec is None:
        kp = _pool_write(cache["k_pool"], tables, lens, k)
        vp = _pool_write(cache["v_pool"], tables, lens, v)
        ks = vs = None
    else:
        kp, ks = _pool_write_quant(cache["k_pool"], cache["k_scale_pool"],
                                   tables, lens, k, spec)
        vp, vs = _pool_write_quant(cache["v_pool"], cache["v_scale_pool"],
                                   tables, lens, v, spec)
    qg = q.reshape(B, S, KH, G, hd)

    if S == 1 and _paged_attend_impl(cfg) == "pallas":
        # Block-walking decode kernel: never materializes the table gather.
        from repro.kernels import ops as kops  # lazy: kernels optional
        from repro.kernels import paged_attention as PA

        attend = functools.partial(
            kops.paged_attend_gqa, scale=1.0 / np.sqrt(hd),
            softmax_impl=getattr(cfg, "softmax_impl", "exact"),
            kv_dtype=x.dtype,
            kv_quant=getattr(cfg, "kv_quant", "none"))
        mesh = shd.active_serving_mesh()
        if mesh is not None:
            # pallas_call is opaque to GSPMD — run the kernel shard-local
            # over the model axis: per-shard KH slice of q and the pools,
            # tables/lens replicated, no collective inside attention.
            # ServeEngine init guarantees KH % tp == 0 on this path.
            o = PA.shard_local_gqa(attend, mesh, qg[:, 0], kp, vp,
                                   tables, lens + 1,
                                   k_scale_pool=ks,
                                   v_scale_pool=vs)[:, None]
        else:
            o = attend(qg[:, 0], kp, vp, tables, lens + 1,
                       k_scale_pool=ks, v_scale_pool=vs)[:, None]
    else:
        if spec is None:
            k_full = _pool_gather(kp, tables).astype(x.dtype)
            v_full = _pool_gather(vp, tables).astype(x.dtype)
        else:
            k_full = _pool_gather_dequant(kp, ks, tables, spec).astype(x.dtype)
            v_full = _pool_gather_dequant(vp, vs, tables, spec).astype(x.dtype)
        o = _attend_rows(qg, k_full, v_full, positions, lens + S,
                         1.0 / np.sqrt(hd), cfg.score_dtype,
                         getattr(cfg, "softmax_impl", "exact"))
    o = o.astype(qg.dtype).reshape(B, S, KH * G, hd)
    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
    new_cache = {"k_pool": kp, "v_pool": vp, "tables": tables,
                 "lens": lens + S}
    if spec is not None:
        new_cache["k_scale_pool"] = ks
        new_cache["v_scale_pool"] = vs
    return y, new_cache


def gqa_apply(params, x, cfg, *, cache: Optional[dict] = None,
              positions: Optional[jax.Array] = None) -> Tuple[jax.Array, Optional[dict]]:
    """x: (B,S,d). With cache: writes S new positions at cache['idx']."""
    B, S, d = x.shape
    hd = cfg.head_dim
    if cfg.pad_heads_to:
        # padded layout keeps the kv-major grouping: H' = KH' * G_orig
        KH = _padded_heads(cfg)[1]
        G = cfg.num_heads // cfg.num_kv_heads
        H = KH * G
    else:
        H, KH = cfg.num_heads, cfg.num_kv_heads
        G = H // KH

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)

    if cache is not None and "k_pool" in cache:
        return _gqa_paged_apply(params, x, cfg, cache, q, k, v)

    if positions is None:
        offset = cache["idx"] if cache is not None else 0
        positions = offset + jnp.arange(S)[None, :]
    q = cm.apply_rope(q, positions, cfg.rope_theta)
    k = cm.apply_rope(k, positions, cfg.rope_theta)

    if cache is not None:
        from jax.sharding import PartitionSpec as PS

        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, cache["idx"], 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, cache["idx"], 0, 0))
        if cfg.kv_shard == "seq_model":
            # flash-decode SP: pin cache seq dim to the model axis; the
            # softmax/output reductions over seq become small all-reduces
            cands = [PS(("pod", "data"), "model", None, None),
                     PS("data", "model", None, None),
                     PS(None, "model", None, None)]
            ck = cm.maybe_shard(ck, *cands)
            cv = cm.maybe_shard(cv, *cands)
        new_cache = {"k": ck, "v": cv, "idx": cache["idx"] + S}
        k_full, v_full = ck.astype(x.dtype), cv.astype(x.dtype)
        k_len = cache["idx"] + S
        q_offset = cache["idx"]
    else:
        new_cache, k_full, v_full, k_len, q_offset = None, k, v, None, 0

    qg = q.reshape(B, S, KH, G, hd)
    o = causal_attention(qg, k_full, v_full, q_offset=q_offset, k_len=k_len,
                         chunk=cfg.attn_chunk, score_dtype=cfg.score_dtype,
                         softmax_impl=getattr(cfg, "softmax_impl", "exact"))
    o = o.reshape(B, S, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------
def mla_spec(cfg) -> Dict[str, Any]:
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    return {
        "wq": P((d, H, m.qk_nope_dim + m.qk_rope_dim), ("embed", "heads", None)),
        "wkv_a": P((d, m.kv_lora_rank + m.qk_rope_dim), ("embed", None)),
        "kv_norm": cm.rmsnorm_spec(m.kv_lora_rank),
        "wkv_b": P((m.kv_lora_rank, H, m.qk_nope_dim + m.v_dim),
                   (None, "heads", None)),
        "wo": P((H, m.v_dim, d), ("heads", None, "embed")),
    }


def mla_init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), dtype),
        "idx": jnp.zeros((), jnp.int32),
    }


def _mla_project_q(params, x, cfg, positions):
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = cm.apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_compress(params, x, cfg, positions):
    m = cfg.mla
    kv = jnp.einsum("bsd,dk->bsk", x, params["wkv_a"].astype(x.dtype))
    c_kv, k_rope = kv[..., : m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    c_kv = cm.rmsnorm(params["kv_norm"], c_kv)
    k_rope = cm.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return c_kv, k_rope


def mla_init_paged_cache(cfg, slots: int, num_blocks: int, block_len: int,
                         max_blocks: int, dtype=jnp.bfloat16):
    """Paged decode cache for one MLA layer: global block pools over the
    *compressed* latent (c_kv) and the shared rope key, plus per-slot
    block tables/lengths (layout mirrors gqa_init_paged_cache)."""
    if getattr(cfg, "kv_quant", "none") not in (None, "none"):
        # quantizing the compressed latent is a different design (error
        # amplifies through the absorbed up-projection); the engine
        # rejects this combination at init, this guard covers direct users
        raise ValueError("kv_quant applies to GQA paged pools only; MLA "
                         "layers store the compressed latent unquantized")
    m = cfg.mla
    return {
        "c_kv_pool": jnp.zeros((num_blocks, block_len, m.kv_lora_rank), dtype),
        "k_rope_pool": jnp.zeros((num_blocks, block_len, m.qk_rope_dim), dtype),
        "tables": jnp.zeros((slots, max_blocks), jnp.int32),
        "lens": jnp.zeros((slots,), jnp.int32),
    }


def _mla_absorbed_decode(q_nope, q_rope, cc, cr, wk_b, wv_b, scale, valid,
                         score_dtype, softmax_impl):
    """Absorbed-form single-query MLA decode against a compressed buffer:
    q_nope/q_rope (B,1,H,·), cc/cr (B,T,·), ``valid`` broadcastable to the
    (B,H,1,T) score mask. One implementation shared by the dense and paged
    branches — only the mask differs — so the two stay bit-identical by
    construction. Returns o (B,1,H,v_dim) in f32."""
    q_eff = jnp.einsum("bshk,lhk->bshl", q_nope, wk_b)          # (B,1,H,L)
    if score_dtype == "f32":
        s = (jnp.einsum("bshl,btl->bhst", q_eff.astype(jnp.float32),
                        cc.astype(jnp.float32))
             + jnp.einsum("bshk,btk->bhst", q_rope.astype(jnp.float32),
                          cr.astype(jnp.float32))) * scale
    else:
        s = (jnp.einsum("bshl,btl->bhst", q_eff, cc.astype(q_eff.dtype),
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bshk,btk->bhst", q_rope, cr.astype(q_rope.dtype),
                          preferred_element_type=jnp.float32)) * scale
    s = jnp.where(valid, s, NEG_INF)
    p = _softmax_fn(softmax_impl)(s, axis=-1)
    if score_dtype == "f32":
        o_lat = jnp.einsum("bhst,btl->bshl", p, cc.astype(jnp.float32))
    else:
        o_lat = jnp.einsum("bhst,btl->bshl", p.astype(cc.dtype), cc,
                           preferred_element_type=jnp.float32)
    return jnp.einsum("bshl,lhv->bshv", o_lat, wv_b.astype(jnp.float32))


def _mla_decompress_kq(q_nope, q_rope, cc, cr, m, H, wk_b, wv_b):
    """Decompress a (compressed-latent, rope-key) buffer into full k/v and
    build the grouped query for the chunked/row attends — the prefill
    counterpart of _mla_absorbed_decode, shared by the dense and paged
    branches so the two stay bit-identical by construction."""
    dtype = q_nope.dtype
    B, T = cc.shape[:2]
    S = q_nope.shape[1]
    k_nope = jnp.einsum("btl,lhk->bthk", cc.astype(dtype), wk_b)
    v = jnp.einsum("btl,lhv->bthv", cc.astype(dtype), wv_b)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(cr[:, :, None, :].astype(dtype),
                                  (B, T, H, m.qk_rope_dim))], axis=-1)
    q = jnp.concatenate([q_nope, jnp.broadcast_to(
        q_rope, (B, S, H, m.qk_rope_dim))], axis=-1)
    qg = q.reshape(B, S, H, 1, m.qk_nope_dim + m.qk_rope_dim)
    return k, v, qg


def _mla_paged_apply(params, x, cfg, cache):
    """Paged MLA: block-pool writes of the compressed latent + rope key,
    then absorbed decode (S==1) or decompress-and-attend prefill against
    the table-gathered buffer, masked past each row's length. Einsums
    mirror the dense branches exactly (bit-identical decode)."""
    B, S, d = x.shape
    m, H = cfg.mla, cfg.num_heads
    lens, tables = cache["lens"], cache["tables"]
    positions = lens[:, None] + jnp.arange(S)[None, :]

    q_nope, q_rope = _mla_project_q(params, x, cfg, positions)
    c_kv, k_rope = _mla_compress(params, x, cfg, positions)

    cp = _pool_write(cache["c_kv_pool"], tables, lens, c_kv)
    rp = _pool_write(cache["k_rope_pool"], tables, lens, k_rope)

    wkv_b = params["wkv_b"].astype(x.dtype)
    wk_b, wv_b = wkv_b[..., : m.qk_nope_dim], wkv_b[..., m.qk_nope_dim:]
    scale = 1.0 / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    k_len = lens + S

    if S == 1:
        if _paged_attend_impl(cfg) == "pallas":
            # Block-walking absorbed decode: the kernel accumulates the
            # latent output; wv_b projection mirrors _mla_absorbed_decode.
            from repro.kernels import ops as kops  # lazy: kernels optional
            from repro.kernels import paged_attention as PA

            q_eff = jnp.einsum("bshk,lhk->bshl", q_nope, wk_b)
            attend = functools.partial(
                kops.paged_attend_mla, scale=scale,
                softmax_impl=getattr(cfg, "softmax_impl", "exact"))
            mesh = shd.active_serving_mesh()
            if mesh is not None:
                # Shard-local over the model axis: per-shard H slice of
                # the absorbed queries against the replicated latent/rope
                # pools (they carry no head axis). Engine init guarantees
                # H % tp == 0 on this path.
                o_lat = PA.shard_local_mla(attend, mesh, q_eff[:, 0],
                                           q_rope[:, 0], cp, rp, tables,
                                           lens + 1)
            else:
                o_lat = attend(q_eff[:, 0], q_rope[:, 0], cp, rp, tables,
                               lens + 1)
            o = jnp.einsum("bshl,lhv->bshv", o_lat[:, None],
                           wv_b.astype(jnp.float32))
        else:
            # Absorbed decode against the gathered buffer; per-row mask.
            cc = _pool_gather(cp, tables)                       # (B,T,R)
            cr = _pool_gather(rp, tables)                       # (B,T,rope)
            T = cc.shape[1]
            valid = (jnp.arange(T)[None, :] < k_len[:, None])[:, None, None, :]
            o = _mla_absorbed_decode(q_nope, q_rope, cc, cr, wk_b, wv_b,
                                     scale, valid, cfg.score_dtype,
                                     getattr(cfg, "softmax_impl", "exact"))
    else:
        # Prefill: decompress the gathered buffer, per-row-positioned attend
        # (always the gather path — paged_attend_impl selects decode only).
        cc = _pool_gather(cp, tables)                           # (B,T,R)
        cr = _pool_gather(rp, tables)                           # (B,T,rope)
        k, v, qg = _mla_decompress_kq(q_nope, q_rope, cc, cr, m, H,
                                      wk_b, wv_b)
        o = _attend_rows(qg, k, v, positions, k_len, scale,
                         softmax_impl=getattr(cfg, "softmax_impl", "exact"))
        o = o.astype(qg.dtype).reshape(B, S, H, m.v_dim)

    y = jnp.einsum("bshv,hvd->bsd", o.astype(x.dtype), params["wo"].astype(x.dtype))
    new_cache = {"c_kv_pool": cp, "k_rope_pool": rp, "tables": tables,
                 "lens": k_len}
    return y, new_cache


def mla_apply(params, x, cfg, *, cache: Optional[dict] = None,
              positions: Optional[jax.Array] = None):
    """MLA attention. Prefill decompresses K/V per chunk; decode uses the
    absorbed form against the compressed cache. A paged cache (block pool
    + tables, see mla_init_paged_cache) takes the paged path instead."""
    B, S, d = x.shape
    m, H = cfg.mla, cfg.num_heads
    if cache is not None and "c_kv_pool" in cache:
        return _mla_paged_apply(params, x, cfg, cache)
    offset = cache["idx"] if cache is not None else 0
    if positions is None:
        positions = offset + jnp.arange(S)[None, :]

    q_nope, q_rope = _mla_project_q(params, x, cfg, positions)
    c_kv, k_rope = _mla_compress(params, x, cfg, positions)

    if cache is not None:
        cc = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv.astype(cache["c_kv"].dtype),
                                          (0, cache["idx"], 0))
        cr = jax.lax.dynamic_update_slice(cache["k_rope"], k_rope.astype(cache["k_rope"].dtype),
                                          (0, cache["idx"], 0))
        if cfg.kv_shard == "seq_model":
            from jax.sharding import PartitionSpec as PS

            cands3 = [PS(("pod", "data"), "model", None),
                      PS("data", "model", None), PS(None, "model", None)]
            cc = cm.maybe_shard(cc, *cands3)
            cr = cm.maybe_shard(cr, *cands3)
        new_cache = {"c_kv": cc, "k_rope": cr, "idx": cache["idx"] + S}
    else:
        new_cache = None

    wkv_b = params["wkv_b"].astype(x.dtype)
    wk_b, wv_b = wkv_b[..., : m.qk_nope_dim], wkv_b[..., m.qk_nope_dim:]
    scale = 1.0 / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)

    if cache is not None and S == 1:
        # Absorbed decode: score against the compressed cache directly.
        T = cc.shape[1]
        valid = (jnp.arange(T) < cache["idx"] + 1)[None, None, None, :]
        o = _mla_absorbed_decode(q_nope, q_rope, cc, cr, wk_b, wv_b, scale,
                                 valid, cfg.score_dtype,
                                 getattr(cfg, "softmax_impl", "exact"))
    else:
        # Prefill / train: decompress K,V and run the chunked causal core.
        src_c = cc if cache is not None else c_kv
        src_r = cr if cache is not None else k_rope
        k, v, qg = _mla_decompress_kq(q_nope, q_rope, src_c, src_r, m, H,
                                      wk_b, wv_b)
        k_len = (cache["idx"] + S) if cache is not None else None
        o = causal_attention(qg, k, v, q_offset=offset, k_len=k_len,
                             chunk=cfg.attn_chunk,
                             softmax_impl=getattr(cfg, "softmax_impl", "exact"))
        o = o.reshape(B, S, H, m.v_dim)

    y = jnp.einsum("bshv,hvd->bsd", o.astype(x.dtype), params["wo"].astype(x.dtype))
    return y, new_cache
