"""Layer library: norms, rope, attention (GQA/MLA), MLPs, MoE, Mamba2-SSD.

Pure-JAX functional layers over parameter dicts. Conventions:
  * activations (B, S, D); attention heads layout (B, S, H, Dh);
  * params stored in ``cfg.dtype`` (bf16 default), matmuls accumulate fp32
    via ``preferred_element_type`` where it matters; norms/softmax/CE fp32;
  * every ``init_*`` returns a dict of arrays, every ``apply``-style fn is
    pure and jit/scan-safe.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig, MoEConfig, MLAConfig, SSMConfig

# A large-but-finite mask value: big enough to zero softmax weight, small
# enough that (-MASK) + finite stays finite in bf16/fp32.
MASK_VALUE = -1e9


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, d: int):
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), _dtype(cfg)),
                "bias": jnp.zeros((d,), _dtype(cfg))}
    return {"scale": jnp.ones((d,), _dtype(cfg))}


def apply_norm(p, x, cfg: ModelConfig):
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(x, scale, eps=1e-6):
    """Per-head qk-norm (Chameleon): RMS over the head dim."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return y.astype(x.dtype)


def softcap(x, cap):
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope_table(positions, dim: int, theta: float):
    """(..., S) int positions -> cos/sin tables (..., S, dim//2), fp32."""
    freqs = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """x: (B, S, H, D); cos/sin: (B, S, D/2) or (S, D/2). Pairs (even, odd)."""
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]  # (B,S,1,D/2)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = xf1 * cos - xf2 * sin
    o2 = xf2 * cos + xf1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention core (plain + chunked/online-softmax)
# ---------------------------------------------------------------------------

def _gqa_scores(q, k):
    """q: (B,Sq,Hkv,G,D), k: (B,Skv,Hkv,D) -> (B,Hkv,G,Sq,Skv) fp32."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(w, v):
    """w: (B,Hkv,G,Sq,Skv) fp32, v: (B,Skv,Hkv,D) -> (B,Sq,Hkv,G,D)."""
    return jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def _mask_bias(q_pos, kv_pos, *, causal, window, kv_len=None):
    """(Sq, Skv) additive fp32 mask from position vectors.

    window is a (possibly traced) scalar: number of positions attended
    (q - kv < window). kv_len masks invalid cache slots (decode).
    """
    valid = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), jnp.bool_)
    diff = q_pos[:, None] - kv_pos[None, :]
    if causal:
        valid &= diff >= 0
    if window is not None:
        valid &= diff < window
    if kv_len is not None:
        valid &= (kv_pos < kv_len)[None, :]
    return jnp.where(valid, 0.0, MASK_VALUE).astype(jnp.float32)


def attention_stub(q, k, v, scale):
    """Kernel-interface stand-in for roofline substitution: touches q, k, v
    once and writes an o-shaped result — exactly the HBM traffic of the
    Pallas flash kernel (kernels/flash_attention.py), whose FLOPs are added
    analytically by the dry-run. NEVER used for real computation."""
    dv = v.shape[-1]
    o = q[..., :dv].astype(jnp.float32) * scale
    o = o + jnp.mean(k.astype(jnp.float32), axis=(1, 2), keepdims=True)[..., :dv]
    o = o + jnp.mean(v.astype(jnp.float32), axis=(1, 2), keepdims=True)
    return o.astype(q.dtype)


def attention(q, k, v, *, q_pos, kv_pos, causal=True, window=None,
              kv_len=None, attn_softcap=None, scale=None,
              chunk_q: int = 0, chunk_kv: int = 0, impl: str = "xla"):
    """General GQA attention.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D); Hq % Hkv == 0.
    q_pos: (Sq,) int positions of queries; kv_pos: (Skv,).
    window: optional scalar (static or traced) sliding window size.
    kv_len: optional scalar — number of valid kv slots (decode caches).
    Chunked (online-softmax / FlashAttention-style, rematerialized by XLA)
    when chunk_q > 0 and Sq > chunk_q; otherwise one-shot.
    Returns (B, Sq, Hq, D) in q.dtype.
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if impl == "stub" and sq > 1:
        return attention_stub(q, k, v, scale)
    if impl == "flash" and sq > 1 and kv_len is None:
        from ..kernels import ops as _kops
        win = 0 if window is None or not isinstance(window, int) else window
        return _kops.flash_mha(q, k, v, causal=causal, window=win,
                               softcap=float(attn_softcap or 0.0))
    qg = q.reshape(b, sq, hkv, g, d)

    if not chunk_q or sq <= chunk_q or skv <= max(chunk_kv, 1):
        bias = _mask_bias(q_pos, kv_pos, causal=causal, window=window,
                          kv_len=kv_len)
        s = _gqa_scores(qg, k) * scale
        if attn_softcap is not None:
            s = softcap(s, attn_softcap)
        s = s + bias[None, None, None]
        w = jax.nn.softmax(s, axis=-1)
        o = _gqa_out(w, v)
        return o.reshape(b, sq, hq, dv).astype(q.dtype)

    # --- chunked path: scan q chunks; inner scan over kv chunks with an
    # online-softmax carry (m, l, acc). Exact, O(chunk^2) live memory.
    cq = chunk_q
    ckv = chunk_kv or chunk_q
    nq = -(-sq // cq) * cq
    nkv = -(-skv // ckv) * ckv
    qp = jnp.pad(qg, ((0, 0), (0, nq - sq), (0, 0), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nkv - skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nkv - skv), (0, 0), (0, 0)))
    qpos = jnp.pad(q_pos, (0, nq - sq), constant_values=-1)
    kpos = jnp.pad(kv_pos, (0, nkv - skv), constant_values=2**30)

    qc = qp.reshape(b, nq // cq, cq, hkv, g, d)
    kc = kp.reshape(b, nkv // ckv, ckv, hkv, d)
    vc = vp.reshape(b, nkv // ckv, ckv, hkv, dv)
    qpc = qpos.reshape(nq // cq, cq)
    kpc = kpos.reshape(nkv // ckv, ckv)

    def q_chunk(qi, qpi):
        # online softmax over kv chunks
        m0 = jnp.full((b, hkv, g, cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, cq, hkv, g, dv), jnp.float32)

        @jax.checkpoint
        def body(carry, kv):
            # rematerialized: the backward recomputes this chunk's scores
            # instead of saving (cq, ckv) f32 residuals per kv chunk —
            # without this, scan's saved residuals defeat flash attention.
            m, l, acc = carry
            kj, vj, kpj = kv
            bias = _mask_bias(qpi, kpj, causal=causal, window=window,
                              kv_len=kv_len)
            s = _gqa_scores(qi, kj) * scale
            if attn_softcap is not None:
                s = softcap(s, attn_softcap)
            s = s + bias[None, None, None]          # (b,hkv,g,cq,ckv)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            o = _gqa_out(p, vj)                      # (b,cq,hkv,g,d)
            acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + o
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (kc.swapaxes(0, 1), vc.swapaxes(0, 1), kpc))
        l = jnp.maximum(l, 1e-30)
        return acc / l.transpose(0, 3, 1, 2)[..., None]

    out = jax.lax.map(lambda args: q_chunk(*args), (qc.swapaxes(0, 1), qpc))
    out = out.swapaxes(0, 1).reshape(b, nq, hq, dv)[:, :sq]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer (covers dense/gemma2/chameleon/qwen/whisper self+cross)
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key, *, cross: bool = False):
    d, hd = cfg.d_model, cfg.head_dim_
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    sc = 0.02
    dt = _dtype(cfg)
    p = {
        "wq": (jax.random.normal(ks[0], (d, hq * hd)) * sc).astype(dt),
        "wk": (jax.random.normal(ks[1], (d, hkv * hd)) * sc).astype(dt),
        "wv": (jax.random.normal(ks[2], (d, hkv * hd)) * sc).astype(dt),
        "wo": (jax.random.normal(ks[3], (hq * hd, d))
               * sc / math.sqrt(2 * cfg.num_layers)).astype(dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    if cfg.o_bias:
        p["bo"] = jnp.zeros((d,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    del cross  # same parameter shapes; kv source differs at apply time
    return p


def attention_qkv(p, x, cfg: ModelConfig, *, kv_src=None, positions=None,
                  kv_positions=None):
    """Project to q, k, v (+bias, qk-norm, rope). Returns (q, k, v)."""
    b, s, _ = x.shape
    hd = cfg.head_dim_
    kv_src = x if kv_src is None else kv_src
    skv = kv_src.shape[1]
    q = (x @ p["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = (kv_src @ p["wk"]).reshape(b, skv, cfg.num_kv_heads, hd)
    v = (kv_src @ p["wv"]).reshape(b, skv, cfg.num_kv_heads, hd)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(1, 1, cfg.num_heads, hd)
        k = k + p["bk"].reshape(1, 1, cfg.num_kv_heads, hd)
        v = v + p["bv"].reshape(1, 1, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_head_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.pos_emb == "rope" and positions is not None:
        cos_q, sin_q = rope_table(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos_q, sin_q)
        kv_positions = positions if kv_positions is None else kv_positions
        cos_k, sin_k = rope_table(kv_positions, hd, cfg.rope_theta)
        k = apply_rope(k, cos_k, sin_k)
    return q, k, v


def attention_out(p, o, cfg: ModelConfig):
    b, s = o.shape[:2]
    y = o.reshape(b, s, cfg.num_heads * cfg.head_dim_) @ p["wo"]
    if cfg.o_bias:
        y = y + p["bo"]
    return y


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------

def init_mla(cfg: ModelConfig, key):
    m: MLAConfig = cfg.mla
    d, hq = cfg.d_model, cfg.num_heads
    qk_head = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 6)
    sc = 0.02
    dt = _dtype(cfg)
    return {
        "w_dq": (jax.random.normal(ks[0], (d, m.q_lora_rank)) * sc).astype(dt),
        "q_norm": jnp.ones((m.q_lora_rank,), dt),
        "w_uq": (jax.random.normal(ks[1], (m.q_lora_rank, hq * qk_head)) * sc).astype(dt),
        "w_dkv": (jax.random.normal(ks[2], (d, m.kv_lora_rank + m.qk_rope_dim)) * sc).astype(dt),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dt),
        "w_uk": (jax.random.normal(ks[3], (m.kv_lora_rank, hq * m.qk_nope_dim)) * sc).astype(dt),
        "w_uv": (jax.random.normal(ks[4], (m.kv_lora_rank, hq * m.v_head_dim)) * sc).astype(dt),
        "wo": (jax.random.normal(ks[5], (hq * m.v_head_dim, d))
               * sc / math.sqrt(2 * cfg.num_layers)).astype(dt),
    }


def mla_compress(p, x, cfg: ModelConfig, positions):
    """x -> (c_kv normed, k_rope roped): the MLA cache content."""
    m: MLAConfig = cfg.mla
    ckv_kr = x @ p["w_dkv"]
    c_kv = _norm_vec(ckv_kr[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = ckv_kr[..., m.kv_lora_rank:]               # (B, S, rope_dim)
    cos, sin = rope_table(positions, m.qk_rope_dim, cfg.rope_theta)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    return c_kv, k_rope


def mla_queries(p, x, cfg: ModelConfig, positions):
    m: MLAConfig = cfg.mla
    b, s, _ = x.shape
    qk_head = m.qk_nope_dim + m.qk_rope_dim
    cq = _norm_vec(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
    q = (cq @ p["w_uq"]).reshape(b, s, cfg.num_heads, qk_head)
    q_nope, q_rope = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    cos, sin = rope_table(positions, m.qk_rope_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def _norm_vec(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def mla_attention(p, x, cfg: ModelConfig, *, positions, q_pos, kv_pos,
                  c_kv=None, k_rope=None, kv_len=None, absorbed=False,
                  chunk_q=0, chunk_kv=0, impl: str = "xla"):
    """Full MLA attention. If (c_kv, k_rope) given they are the (cached)
    compressed KV; else computed from x. ``absorbed=True`` (decode) runs
    attention in the compressed space — never expanding K/V per position.
    """
    m: MLAConfig = cfg.mla
    b, s, _ = x.shape
    hq = cfg.num_heads
    if c_kv is None:
        c_kv, k_rope = mla_compress(p, x, cfg, positions)
    skv = c_kv.shape[1]
    q_nope, q_rope = mla_queries(p, x, cfg, positions)
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)

    if absorbed:
        # Absorb W_uk into q: scores = (q W_uk^T) c_kv + q_rope k_rope.
        w_uk = p["w_uk"].reshape(m.kv_lora_rank, hq, m.qk_nope_dim)
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk,
                           preferred_element_type=jnp.float32).astype(x.dtype)
        s_lat = jnp.einsum("bshr,bkr->bhsk", q_lat, c_kv,
                           preferred_element_type=jnp.float32)
        s_rope = jnp.einsum("bshd,bkd->bhsk", q_rope, k_rope,
                            preferred_element_type=jnp.float32)
        scores = (s_lat + s_rope) * scale
        bias = _mask_bias(q_pos, kv_pos, causal=True, window=None,
                          kv_len=kv_len)
        w = jax.nn.softmax(scores + bias[None, None], axis=-1)
        # (emit bhsr then transpose: the bshr output order is an
        #  unsupported transposed-GEMM on the XLA:CPU thunk runtime)
        o_lat = jnp.einsum("bhsk,bkr->bhsr", w.astype(x.dtype), c_kv,
                           preferred_element_type=jnp.float32)
        o_lat = o_lat.swapaxes(1, 2).astype(x.dtype)
        w_uv = p["w_uv"].reshape(m.kv_lora_rank, hq, m.v_head_dim)
        o = jnp.einsum("bshr,rhv->bshv", o_lat, w_uv,
                       preferred_element_type=jnp.float32).astype(x.dtype)
    else:
        k_nope = (c_kv @ p["w_uk"]).reshape(b, skv, hq, m.qk_nope_dim)
        v = (c_kv @ p["w_uv"]).reshape(b, skv, hq, m.v_head_dim)
        k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                    (b, skv, hq, m.qk_rope_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
        o = attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=True,
                      kv_len=kv_len, scale=scale,
                      chunk_q=chunk_q, chunk_kv=chunk_kv, impl=impl)
    y = o.reshape(b, s, hq * m.v_head_dim) @ p["wo"]
    return y, (c_kv, k_rope)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    dt = _dtype(cfg)
    sc = 0.02
    if cfg.act == "gelu_mlp":                      # plain 2-matrix MLP
        k1, k2 = jax.random.split(key)
        return {
            "w_in": (jax.random.normal(k1, (d, f)) * sc).astype(dt),
            "b_in": jnp.zeros((f,), dt),
            "w_out": (jax.random.normal(k2, (f, d))
                      * sc / math.sqrt(2 * cfg.num_layers)).astype(dt),
            "b_out": jnp.zeros((d,), dt),
        }
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": (jax.random.normal(k1, (d, f)) * sc).astype(dt),
        "w_up": (jax.random.normal(k2, (d, f)) * sc).astype(dt),
        "w_down": (jax.random.normal(k3, (f, d))
                   * sc / math.sqrt(2 * cfg.num_layers)).astype(dt),
    }


def _act(cfg: ModelConfig, x):
    if cfg.act in ("gelu", "gelu_mlp"):
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def apply_mlp(p, x, cfg: ModelConfig):
    if "w_in" in p:                                 # plain MLP
        h = _act(cfg, x @ p["w_in"] + p["b_in"])
        return h @ p["w_out"] + p["b_out"]
    h = _act(cfg, x @ p["w_gate"]) * (x @ p["w_up"])
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE (top-k routing, sort + capacity scatter, EP-shardable expert einsums)
# ---------------------------------------------------------------------------

def init_moe(cfg: ModelConfig, key):
    mo: MoEConfig = cfg.moe
    d, f, e = cfg.d_model, mo.d_expert, mo.num_experts
    dt = _dtype(cfg)
    sc = 0.02
    ks = jax.random.split(key, 5)
    p = {
        "router": (jax.random.normal(ks[0], (d, e)) * sc).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) * sc).astype(dt),
        "w_up": (jax.random.normal(ks[2], (e, d, f)) * sc).astype(dt),
        "w_down": (jax.random.normal(ks[3], (e, f, d))
                   * sc / math.sqrt(2 * cfg.num_layers)).astype(dt),
    }
    if mo.router_aux_free_bias:
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
    if mo.num_shared:
        p["shared"] = init_mlp(cfg, ks[4], d_ff=mo.d_expert * mo.num_shared)
    return p


def moe_capacity(tokens: int, moe: MoEConfig) -> int:
    cf = moe.capacity_factor or 1.25
    cap = int(math.ceil(tokens * moe.top_k / moe.num_experts * cf))
    return max(min(cap, tokens), 1)


def _moe_dispatch_compute(p, xt, cfg: ModelConfig, *, e_offset=0,
                          e_count=None, psum_axis=None):
    """Sort-based capacity dispatch over LOCAL tokens xt (T, d), computing
    the expert range [e_offset, e_offset + e_count) (EP shard), psumming
    the combined output over ``psum_axis`` when expert-sharded.

    Routing (router logits/top-k) is computed over the FULL expert set on
    every rank (router weights replicated — they are tiny); only the expert
    FFN is sharded.
    """
    mo: MoEConfig = cfg.moe
    t, d = xt.shape
    e, k = mo.num_experts, mo.top_k
    e_count = e_count or e

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T, E) fp32
    probs = jax.nn.softmax(logits, axis=-1)
    sel = probs + p["router_bias"] if mo.router_aux_free_bias else probs
    _, top_idx = jax.lax.top_k(sel, k)                        # (T, k)
    gates = jnp.take_along_axis(probs, top_idx, axis=-1)      # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # --- sort token-expert assignments by (global) expert id
    flat_e = top_idx.reshape(t * k)
    sort_idx = jnp.argsort(flat_e)                            # (T*k,)
    e_sorted = flat_e[sort_idx]
    tok_sorted = sort_idx // k
    counts = jnp.bincount(flat_e, length=e)                   # (E,)
    offsets = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                               jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(t * k) - offsets[e_sorted]
    cap = moe_capacity(t, mo)
    e_local = e_sorted - e_offset
    valid = (pos_in_e < cap) & (e_local >= 0) & (e_local < e_count)
    slot = jnp.where(valid, e_local * cap + pos_in_e, e_count * cap)

    buf = jnp.zeros((e_count * cap + 1, d), xt.dtype) \
        .at[slot].set(jnp.where(valid[:, None], xt[tok_sorted], 0))
    buf = buf[:e_count * cap].reshape(e_count, cap, d)

    # --- expert FFN (E_local batched einsum on this rank)
    h = _act(cfg, jnp.einsum("ecd,edf->ecf", buf, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"])

    y_flat = jnp.concatenate([y.reshape(e_count * cap, d),
                              jnp.zeros((1, d), y.dtype)], axis=0)
    y_sorted = y_flat[slot]                       # dropped/remote -> 0
    inv = jnp.argsort(sort_idx)
    y_k = y_sorted[inv].reshape(t, k, d)
    out = jnp.sum(y_k * gates[..., None].astype(y_k.dtype), axis=1)
    if psum_axis is not None:
        # combine expert-shard partial outputs (each token's k experts may
        # live on different ranks; in stationary mode also the FFN-dim
        # partial sums) — ONE psum per MoE layer, the EP analogue of the
        # paper's per-level reduction.
        out = jax.lax.psum(out, psum_axis)
    aux = moe_load_aux(probs, top_idx, e)
    return out, aux


def apply_moe(p, x, cfg: ModelConfig):
    """x: (B, S, D) -> (B, S, D). Expert-parallel MoE:

    With a mesh policy installed (production), dispatch runs inside
    ``shard_map``: tokens stay local to their DP shard, expert weights are
    sharded over the TP axis (EP), every rank computes its expert subset
    for its row's tokens and one psum combines — no global sort/scatter
    ever materializes. Without a policy (single device / unit tests) the
    same math runs with the full expert set locally.
    """
    from ..parallel import act as _act_mod
    mo: MoEConfig = cfg.moe
    b, s, d = x.shape
    e = mo.num_experts
    pol = _act_mod.current_policy()
    tp = "model"
    use_ep = (pol is not None and tp in pol.mesh.axis_names
              and e % pol.mesh.shape[tp] == 0)

    if not use_ep:
        out, aux = _moe_dispatch_compute(
            {k_: v for k_, v in p.items() if k_ != "shared"},
            x.reshape(b * s, d), cfg)
    else:
        mesh = pol.mesh
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        tp_size = mesh.shape[tp]
        e_loc = e // tp_size
        fsdp = tuple(a for a in pol.fsdp_axes if a in mesh.axis_names)
        fsdp_size = 1
        for a in fsdp:
            fsdp_size *= mesh.shape[a]
        stationary = (pol.moe_stationary and fsdp
                      and mo.d_expert % fsdp_size == 0)
        if stationary:
            # decode: weights stay put — experts over tp, FFN dim over the
            # fsdp axes; the (tiny) token set is replicated in and the
            # partial outputs psum over (tp + fsdp).
            pspecs = {
                "router": P(None, None),
                "w_gate": P(tp, None, fsdp),
                "w_up": P(tp, None, fsdp),
                "w_down": P(tp, fsdp, None),
            }
            x_spec = P(None, None, None)
            psum_axes = (tp,) + fsdp
        else:
            pspecs = {
                "router": P(None, None),
                "w_gate": P(tp, None, None),
                "w_up": P(tp, None, None),
                "w_down": P(tp, None, None),
            }
            x_spec = P(dp, None, None)
            psum_axes = (tp,)
        if "router_bias" in p:
            pspecs["router_bias"] = P(None)
        pl = {k_: p[k_] for k_ in pspecs}

        def body(xl, pw):
            tb, ts, _ = xl.shape
            tp_rank = jax.lax.axis_index(tp)
            out, aux = _moe_dispatch_compute(
                pw, xl.reshape(tb * ts, d), cfg,
                e_offset=tp_rank * e_loc, e_count=e_loc,
                psum_axis=psum_axes)
            if not stationary and dp:
                aux = jax.lax.pmean(aux, dp)
            return out.reshape(tb, ts, d), aux

        out, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(x_spec, pspecs),
            out_specs=(x_spec, P()),
            check_vma=False,
        )(x, pl)
        out = out.reshape(b * s, d)
        aux = aux.reshape(())

    if mo.num_shared:
        out = out + apply_mlp(p["shared"], x.reshape(b * s, d), cfg)
    return out.reshape(b, s, d), aux


def moe_load_aux(probs, top_idx, e):
    """Switch-style load-balance aux loss: E * sum_e f_e * p_e."""
    t, k = top_idx.shape
    hits = jnp.zeros((e,), jnp.float32).at[top_idx.reshape(-1)].add(1.0)
    f = hits / (t * k)
    pbar = jnp.mean(probs, axis=0)
    return e * jnp.sum(f * pbar)


# ---------------------------------------------------------------------------
# Mamba-2 (SSD, chunked) — faithful to the SSD dual form of arXiv:2405.21060
# ---------------------------------------------------------------------------

def init_ssm(cfg: ModelConfig, key):
    s: SSMConfig = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    h = d_in // s.head_dim
    conv_dim = d_in + 2 * s.n_groups * s.state_dim
    ks = jax.random.split(key, 4)
    dt = _dtype(cfg)
    sc = 0.02
    # in_proj emits [z, x, B, C, dt]
    zxbcdt = 2 * d_in + 2 * s.n_groups * s.state_dim + h
    return {
        "w_in": (jax.random.normal(ks[0], (d, zxbcdt)) * sc).astype(dt),
        "conv_w": (jax.random.normal(ks[1], (s.conv_width, conv_dim)) * sc).astype(dt),
        "conv_b": jnp.zeros((conv_dim,), dt),
        "a_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        "d_skip": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "norm": jnp.ones((d_in,), dt),
        "w_out": (jax.random.normal(ks[3], (d_in, d))
                  * sc / math.sqrt(2 * cfg.num_layers)).astype(dt),
    }


def _segsum(x):
    """x: (..., Q) -> (..., Q, Q) lower-tri cumulative sums over segments."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, seg, -jnp.inf)


def _ssd_chunked(xh, dt, a, bmat, cmat, chunk, init_state=None):
    """SSD scan. xh: (B,S,H,P); dt: (B,S,H) fp32; a: (H,) fp32 (negative);
    bmat/cmat: (B,S,G,N). Returns (y: (B,S,H,P), final_state (B,H,P,N))."""
    b, s_len, h, p_dim = xh.shape
    g, n = bmat.shape[2], bmat.shape[3]
    q = min(chunk, s_len)
    nc = -(-s_len // q)
    pad = nc * q - s_len
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    rep = h // g
    # chunked views: (B, NC, Q, ...)
    xc = xh.reshape(b, nc, q, h, p_dim).astype(jnp.float32)
    dtc = dt.reshape(b, nc, q, h)
    bc = bmat.reshape(b, nc, q, g, n).astype(jnp.float32)
    cc = cmat.reshape(b, nc, q, g, n).astype(jnp.float32)
    bh = jnp.repeat(bc, rep, axis=3)                  # (B,NC,Q,H,N)
    ch = jnp.repeat(cc, rep, axis=3)

    da = dtc * a[None, None, None, :]                 # (B,NC,Q,H) negative
    da_cum = jnp.cumsum(da, axis=2)                   # within-chunk cumsum
    da_total = da_cum[:, :, -1]                       # (B,NC,H)

    # 1) intra-chunk (dual quadratic form): Y_d = (C B^T . L) (dt x)
    l_mat = jnp.exp(_segsum(da.transpose(0, 1, 3, 2)))        # (B,NC,H,Q,Q)
    cb = jnp.einsum("bcqhn,bckhn->bchqk", ch, bh)
    att = cb * l_mat
    y_diag = jnp.einsum("bchqk,bckh,bckhp->bcqhp", att, dtc, xc)

    # 2) chunk states: S_c = sum_k exp(da_total - da_cum_k) dt_k B_k x_k
    decay = jnp.exp(da_total[:, :, None] - da_cum)            # (B,NC,Q,H)
    states = jnp.einsum("bcqh,bcqh,bcqhn,bcqhp->bchpn",
                        decay, dtc, bh, xc)                   # (B,NC,H,P,N)

    # 3) inter-chunk recurrence over chunk states
    def scan_fn(carry, inp):
        st, tot = inp                                  # (B,H,P,N), (B,H)
        new = st + carry * jnp.exp(tot)[:, :, None, None]
        return new, carry

    s0 = (jnp.zeros((b, h, p_dim, n), jnp.float32)
          if init_state is None else init_state.astype(jnp.float32))
    final, prev_states = jax.lax.scan(
        scan_fn, s0, (states.swapaxes(0, 1), da_total.swapaxes(0, 1)))
    prev_states = prev_states.swapaxes(0, 1)           # state BEFORE chunk c

    # 4) inter-chunk output: Y_off = C . exp(da_cum) . prev_state
    y_off = jnp.einsum("bcqhn,bcqh,bchpn->bcqhp",
                       ch, jnp.exp(da_cum), prev_states)

    y = (y_diag + y_off).reshape(b, nc * q, h, p_dim)[:, :s_len]
    return y, final


def apply_ssm(p, x, cfg: ModelConfig, *, conv_state=None, ssm_state=None,
              decode=False):
    """Mamba-2 block. Train/prefill: full sequence (chunked SSD). Decode:
    single-token recurrent update using (conv_state, ssm_state)."""
    s: SSMConfig = cfg.ssm
    b, seq, d = x.shape
    d_in = s.expand * cfg.d_model
    h = d_in // s.head_dim
    g, n = s.n_groups, s.state_dim
    conv_dim = d_in + 2 * g * n

    zxbcdt = x @ p["w_in"]
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in:d_in + conv_dim]
    dt_raw = zxbcdt[..., d_in + conv_dim:]            # (B,S,H)

    # causal depthwise conv over xbc
    w = p["conv_w"]                                    # (W, conv_dim)
    cw = s.conv_width
    if decode:
        # conv_state: (B, W-1, conv_dim) last inputs
        window = jnp.concatenate([conv_state, xbc], axis=1)   # (B, W, conv)
        new_conv_state = window[:, 1:]
        xbc = jnp.einsum("bwc,wc->bc", window, w)[:, None] + p["conv_b"]
    else:
        pad = jnp.zeros((b, cw - 1, conv_dim), xbc.dtype)
        xp = jnp.concatenate([pad, xbc], axis=1)
        new_conv_state = xp[:, -(cw - 1):] if cw > 1 else xp[:, :0]
        xbc = sum(xp[:, i:i + seq] * w[i] for i in range(cw)) + p["conv_b"]
    xbc = jax.nn.silu(xbc)

    xin = xbc[..., :d_in].reshape(b, -1, h, s.head_dim)
    bmat = xbc[..., d_in:d_in + g * n].reshape(b, -1, g, n)
    cmat = xbc[..., d_in + g * n:].reshape(b, -1, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])                           # (H,) negative

    if decode:
        # recurrent: state' = exp(dt a) state + dt B x ; y = C state' + D x
        rep = h // g
        bh = jnp.repeat(bmat[:, 0], rep, axis=1)       # (B,H,N)
        ch = jnp.repeat(cmat[:, 0], rep, axis=1)
        xf = xin[:, 0].astype(jnp.float32)             # (B,H,P)
        dt0 = dt[:, 0]                                  # (B,H)
        decay = jnp.exp(dt0 * a[None, :])[:, :, None, None]
        upd = (dt0[:, :, None] * xf)[..., None] * bh[:, :, None, :].astype(jnp.float32)
        new_state = ssm_state.astype(jnp.float32) * decay + upd
        y = jnp.einsum("bhpn,bhn->bhp", new_state, ch.astype(jnp.float32))
        y = y + p["d_skip"][None, :, None] * xf
        y = y[:, None].reshape(b, 1, d_in)
    else:
        yh, new_state = _ssd_chunked(xin, dt, a, bmat, cmat, s.chunk,
                                     init_state=ssm_state)
        yh = yh + p["d_skip"][None, None, :, None] * xin.astype(jnp.float32)
        y = yh.reshape(b, seq, d_in)

    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    y = _norm_vec(y, p["norm"], cfg.norm_eps)
    out = y @ p["w_out"]
    return out, (new_conv_state.astype(x.dtype), new_state)
