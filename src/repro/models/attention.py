"""GQA attention: chunked-causal training/prefill + KV-cache decode.

Training/prefill uses a query-chunked online computation (a jnp-level flash
attention) so the (S x S) score matrix is never materialized — peak transient
is (B, KV, G, q_chunk, S). The Pallas TPU kernel in ``repro.kernels`` is the
hardware-targeted version of the same algorithm; on the CPU container the
model path stays jnp so the dry-run can lower on the host backend.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.launch.axes import current_mesh, shard, _STATE
from repro.models.layers import apply_rope, dense_init

NEG_INF = -1e30


def _batch_spec_axes(mesh, batch: int):
    rules = _STATE["rules"] or {}
    axes, prod = [], 1
    for a in rules.get("batch", ()):
        if a in mesh.axis_names and batch % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
    return tuple(axes)


def flash_decode_shardmap(q, cache_k, cache_v, k_new, v_new, slot, kv_valid,
                          mesh):
    """Distributed one-token decode attention over a LENGTH-sharded KV cache,
    INCLUDING the ring-buffer cache write (a masked in-shard write — a
    dynamic_update_slice on the sharded dim would make GSPMD all-gather the
    cache, observed 2.2 GB/step: §Perf iteration B3).

    Shards combine softmax partials via pmax/psum of (max, sumexp,
    partial-out) — the flash-decode reduction; per-step traffic is
    O(B*H*hd), not O(cache).

    q/k_new/v_new: (B, 1, H|KV, hd) replicated over `model`;
    cache_k/cache_v: (B, L, KV, hd), L sharded over `model`.
    """
    from jax.sharding import PartitionSpec as P
    B, L, KV, hd = cache_k.shape
    H = q.shape[2]
    G = H // KV
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    def body(qb, kb, vb, knb, vnb, slot_, valid):
        Bs = qb.shape[0]
        Ls = kb.shape[1]
        idx = jax.lax.axis_index("model")
        pos = idx * Ls + jnp.arange(Ls)                     # global slots
        # ring-buffer write: only the owning shard takes the new k/v
        hit = (pos == slot_)[None, :, None, None]
        kb = jnp.where(hit, knb.astype(kb.dtype), kb)
        vb = jnp.where(hit, vnb.astype(vb.dtype), vb)
        qh = qb.reshape(Bs, KV, G, hd).astype(jnp.float32)
        s = jnp.einsum("bkgh,btkh->bkgt", qh, kb.astype(jnp.float32)) * scale
        s = jnp.where(pos[None, None, None, :] < valid, s, NEG_INF)
        m = jnp.max(s, -1, keepdims=True)                   # (Bs,KV,G,1)
        p = jnp.exp(s - m)
        l = jnp.sum(p, -1, keepdims=True)
        o = jnp.einsum("bkgt,btkh->bkgh", p, vb.astype(jnp.float32))
        m_g = jax.lax.pmax(m, "model")
        w = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * w, "model")
        o_g = jax.lax.psum(o * w, "model")
        out = o_g / jnp.maximum(l_g, 1e-30)
        return out.reshape(Bs, 1, H, hd).astype(qb.dtype), kb, vb

    ba = _batch_spec_axes(mesh, B)
    bspec = ba if ba else None
    rep = P(bspec, None, None, None)
    cache_spec = P(bspec, "model", None, None)
    in_specs = (rep, cache_spec, cache_spec, rep, rep, P(), P())
    out_specs = (rep, cache_spec, cache_spec)
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(q, cache_k, cache_v, k_new, v_new,
              jnp.asarray(slot, jnp.int32), jnp.asarray(kv_valid, jnp.int32))


def init_attention(key, cfg: ModelConfig, d_model: Optional[int] = None):
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, d, cfg.n_heads * hd, cfg.dtype),
        "wk": dense_init(k2, d, cfg.n_kv_heads * hd, cfg.dtype),
        "wv": dense_init(k3, d, cfg.n_kv_heads * hd, cfg.dtype),
        "wo": dense_init(k4, cfg.n_heads * hd, d, cfg.dtype),
    }


def _gqa_scores_chunk(q, k, v, q_start, kv_len_valid, sliding_window, causal):
    """q: (B, KV, G, qc, hd); k,v: (B, KV, S, hd) -> (B, KV, G, qc, hd)."""
    S = k.shape[2]
    scores = jnp.einsum("bkgqh,bkth->bkgqt", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    q_idx = q_start + jnp.arange(q.shape[3])
    k_idx = jnp.arange(S)
    mask = jnp.ones((q.shape[3], S), dtype=bool)
    if causal:
        mask = k_idx[None, :] <= q_idx[:, None]
    if sliding_window:
        mask = mask & (k_idx[None, :] > q_idx[:, None] - sliding_window)
    if kv_len_valid is not None:
        mask = mask & (k_idx[None, :] < kv_len_valid)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqt,bkth->bkgqh", probs, v)


def gqa_attention(q, k, v, *, causal=True, sliding_window=0, q_start=0,
                  kv_len_valid=None, q_chunk=1024):
    """q: (B, S_q, H, hd); k,v: (B, S_kv, KV, hd) -> (B, S_q, H, hd)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qh = q.reshape(B, Sq, KV, G, hd).transpose(0, 2, 3, 1, 4)  # (B, KV, G, Sq, hd)
    kh = k.transpose(0, 2, 1, 3)  # (B, KV, S, hd)
    vh = v.transpose(0, 2, 1, 3)
    if Sq <= q_chunk:
        out = _gqa_scores_chunk(qh, kh, vh, q_start, kv_len_valid, sliding_window, causal)
    else:
        assert Sq % q_chunk == 0
        nq = Sq // q_chunk
        qc = qh.reshape(B, KV, G, nq, q_chunk, hd).transpose(3, 0, 1, 2, 4, 5)

        def body(_, qblk_i):
            qblk, i = qblk_i
            o = _gqa_scores_chunk(qblk, kh, vh, q_start + i * q_chunk,
                                  kv_len_valid, sliding_window, causal)
            return None, o

        _, out = jax.lax.scan(body, None, (qc, jnp.arange(nq)))
        out = out.transpose(1, 2, 3, 0, 4, 5).reshape(B, KV, G, Sq, hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd)


def apply_attention(params, cfg: ModelConfig, x, positions,
                    cache: Optional[Dict] = None, cache_index=None,
                    ) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """x: (B, S, d). cache: {"k","v": (B, S_max, KV, hd)} for decode.

    Returns (out, new_cache). Train/prefill: cache None in -> cache built
    only when cache_index is not None (prefill); decode: S==1 updates cache.
    """
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (x @ params["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (x @ params["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)

    new_cache = None
    if cache is not None and S == 1:
        # Decode: write this token's k/v into the cache and attend over it.
        # The cache is a ring buffer: for sliding-window archs it is only
        # `window` long, so 500k-context decode stays O(window).
        L = cache["k"].shape[1]
        slot = cache_index % L
        kv_valid = jnp.minimum(cache_index + 1, L)
        mesh = current_mesh()
        use_flash_decode = (
            mesh is not None and "model" in mesh.axis_names
            and cfg.n_kv_heads % mesh.shape["model"] != 0
            and L % mesh.shape["model"] == 0)
        if use_flash_decode:
            out, ck, cv = flash_decode_shardmap(
                q, cache["k"], cache["v"], k, v, slot, kv_valid, mesh)
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
            ck = shard(ck, "batch", "cache_seq", "kv_heads", None)
            cv = shard(cv, "batch", "cache_seq", "kv_heads", None)
            out = gqa_attention(q, ck, cv, causal=False, sliding_window=0,
                                kv_len_valid=kv_valid, q_start=cache_index)
        new_cache = {"k": ck, "v": cv}
    else:
        out = gqa_attention(q, k, v, causal=True,
                            sliding_window=cfg.sliding_window)
        if cache is not None:  # prefill ("init" marker): emit cache
            new_cache = {"k": k, "v": v}
    out = out.reshape(B, S, cfg.n_heads * hd)
    out = shard(out, "batch", "seq", "qdim")
    return out @ params["wo"], new_cache
