"""Shared EFM building blocks: norms, RoPE, GQA attention, MLPs, embeddings.

Conventions (repo-wide):
  * Parameters are plain pytrees (nested dicts of jax.Array) — no framework.
  * ``init_*`` builds params; the paired apply function is pure.
  * Layer stacks store params with a leading ``L`` axis (vmap-init) and are
    applied with ``lax.scan`` to bound HLO size at 60+ layers.
  * Weights are stored in ``param_dtype`` (bf16 for the big configs) and
    compute runs in ``compute_dtype``; reductions (norms, softmax) in fp32.
  * Attention layouts: activations (B, S, D_model), per-head (B, H, S, Dh).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Initialisers / linear
# ---------------------------------------------------------------------------


def init_linear(
    key: Array,
    d_in: int,
    d_out: int,
    *,
    bias: bool = False,
    dtype: jnp.dtype = jnp.float32,
    scale: Optional[float] = None,
) -> Params:
    if scale is None:
        scale = 1.0 / math.sqrt(d_in)
    p: Params = {
        "w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale)
        .astype(dtype)
    }
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: Params, x: Array, compute_dtype=jnp.float32) -> Array:
    y = jnp.dot(
        x.astype(compute_dtype),
        p["w"].astype(compute_dtype),
    )
    if "b" in p:
        y = y + p["b"].astype(compute_dtype)
    return y


def init_embedding(
    key: Array, vocab: int, d_model: int, dtype=jnp.float32
) -> Params:
    return {
        "table": (
            jax.random.normal(key, (vocab, d_model), jnp.float32) * 0.02
        ).astype(dtype)
    }


def embed(p: Params, tokens: Array, compute_dtype=jnp.float32) -> Array:
    return p["table"].astype(compute_dtype)[tokens]


def unembed(p: Params, x: Array, compute_dtype=jnp.float32) -> Array:
    """Tied unembedding: logits = x @ table^T (always fp32 out)."""
    return jnp.dot(
        x.astype(compute_dtype), p["table"].astype(compute_dtype).T
    ).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(
    d: int, *, parametric: bool = True, dtype=jnp.float32
) -> Params:
    """LayerNorm params. ``parametric=False`` (OLMo) has no learnables."""
    if parametric:
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    return {}


def layernorm(p: Params, x: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if "scale" in p:
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (rotate-half / NeoX-Llama convention)
# ---------------------------------------------------------------------------


def rope_cos_sin(
    positions: Array, head_dim: int, base: float = 10000.0
) -> Tuple[Array, Array]:
    """cos/sin tables for given positions. positions: (...,) int.

    Returns (..., head_dim/2) each.
    """
    half = head_dim // 2
    freqs = 1.0 / (
        base ** (jnp.arange(0, half, dtype=jnp.float32) / float(half))
    )
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """Apply rotary embedding. x: (..., S, Dh); cos/sin: (S, Dh/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos.astype(x.dtype)
    sin = sin.astype(x.dtype)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )


def remat_wrap(cfg, fn):
    """jax.checkpoint with the configured policy ("full" saves only layer
    inputs — the memory lever when dots-saveable still overflows HBM)."""
    import jax as _jax

    if cfg.remat_policy == "full":
        return _jax.checkpoint(fn)
    return _jax.checkpoint(
        fn, policy=_jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    )


# ---------------------------------------------------------------------------
# Decode-attention sharding (flash-decoding layout)
# ---------------------------------------------------------------------------


def ambient_mesh_axes() -> Dict[str, int]:
    """Axis sizes of the ambient (``jax.set_mesh``) mesh; {} when none."""
    m = jax.sharding.get_abstract_mesh()
    return {} if m.empty else dict(m.shape)


def decode_seq_shard(batch: int, n_kv_heads: int, skv: int):
    """Decide the decode-attention layout on the ambient mesh.

    When kv-heads don't divide the model axis the serve cache is sharded
    on its SEQ dim (launch/sharding.cache_spec_for). Without help GSPMD
    resolves the q(head-sharded) x KV(seq-sharded) einsum by all-gathering
    the cache (GBs per token); pinning the logits/probs to stay
    seq-sharded instead gathers only q and all-reduces the softmax stats
    (KBs) — the flash-decoding partitioning. Returns (batch_axes|None,)
    when the seq-sharded layout applies, else None.
    """
    ax = ambient_mesh_axes()
    model = ax.get("model", 1)
    if model <= 1 or n_kv_heads % model == 0 or skv % model != 0:
        return None
    dps = [a for a in ("pod", "data") if a in ax]
    for start in range(len(dps)):
        use = tuple(dps[start:])
        size = 1
        for a in use:
            size *= ax[a]
        if batch % size == 0:
            return (use,)
    return (None,)


def _wsc(x: Array, spec) -> Array:
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


class KVCache:
    """Functional KV cache — a dict pytree {'k','v'} of (B, Hkv, S, Dh)."""


def init_attention(
    key: Array,
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    *,
    qkv_bias: bool = False,
    dtype=jnp.float32,
) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": init_linear(k1, d_model, n_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "wk": init_linear(k2, d_model, n_kv_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "wv": init_linear(k3, d_model, n_kv_heads * head_dim, bias=qkv_bias, dtype=dtype),
        "wo": init_linear(k4, n_heads * head_dim, d_model, dtype=dtype),
    }


def _split_heads(x: Array, n_heads: int) -> Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1).transpose(0, 2, 1, 3)


def _merge_heads(x: Array) -> Array:
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def attention_full(
    p: Params,
    x: Array,  # (B, S, D)
    n_heads: int,
    n_kv_heads: int,
    *,
    positions: Optional[Array] = None,
    rope_base: float = 10000.0,
    causal: bool = True,
    backend: str = "ref",
    kv_ctx: Optional[Array] = None,  # cross-attention context (B, Sk, D)
    compute_dtype=jnp.float32,
    window: Optional[int] = None,  # sliding-window attention size
) -> Array:
    """Full-sequence attention (train / prefill). Returns (B, S, D)."""
    b, s, _ = x.shape
    q = _split_heads(linear(p["wq"], x, compute_dtype), n_heads)
    src = x if kv_ctx is None else kv_ctx
    k = _split_heads(linear(p["wk"], src, compute_dtype), n_kv_heads)
    v = _split_heads(linear(p["wv"], src, compute_dtype), n_kv_heads)
    head_dim = q.shape[-1]

    if positions is None:
        positions = jnp.arange(s)
    if kv_ctx is None and rope_base > 0:
        cos, sin = rope_cos_sin(positions, head_dim, rope_base)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    if backend == "pallas" and kv_ctx is None and window is None:
        from repro.kernels.flash_attention.kernel import (
            flash_attention_pallas,
        )

        o = flash_attention_pallas(q, k, v, causal=causal)
    elif backend == "chunked":
        group = n_heads // n_kv_heads
        o = attention_chunked(
            q,
            jnp.repeat(k, group, axis=1),
            jnp.repeat(v, group, axis=1),
            causal=causal and kv_ctx is None,
            window=window,
        )
    else:
        group = n_heads // n_kv_heads
        kr = jnp.repeat(k, group, axis=1)
        vr = jnp.repeat(v, group, axis=1)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, kr).astype(jnp.float32)
        logits = logits / math.sqrt(head_dim)
        sk = kr.shape[2]
        qpos = jnp.arange(s)[:, None]
        kpos = jnp.arange(sk)[None, :]
        mask = jnp.ones((s, sk), bool)
        if causal and kv_ctx is None:
            mask = kpos <= qpos
        if window is not None and kv_ctx is None:
            mask = mask & (kpos > qpos - window)
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(compute_dtype)
        o = jnp.einsum("bhqk,bhkd->bhqd", probs, vr)
    return linear(p["wo"], _merge_heads(o), compute_dtype)


def attention_prefill_cache(
    p: Params,
    x: Array,
    n_heads: int,
    n_kv_heads: int,
    *,
    rope_base: float = 10000.0,
    compute_dtype=jnp.float32,
    cache_dtype=jnp.bfloat16,
) -> Dict[str, Array]:
    """Build the KV cache for a prefix (keys already rotated)."""
    b, s, _ = x.shape
    k = _split_heads(linear(p["wk"], x, compute_dtype), n_kv_heads)
    v = _split_heads(linear(p["wv"], x, compute_dtype), n_kv_heads)
    if rope_base > 0:
        cos, sin = rope_cos_sin(jnp.arange(s), k.shape[-1], rope_base)
        k = apply_rope(k, cos, sin)
    return {"k": k.astype(cache_dtype), "v": v.astype(cache_dtype)}


def attention_decode(
    p: Params,
    x: Array,  # (B, 1, D) current-token activations
    cache: Dict[str, Array],  # {'k','v'}: (B, Hkv, S, Dh)
    pos: Array,  # scalar int32 — write/read position
    n_heads: int,
    n_kv_heads: int,
    *,
    rope_base: float = 10000.0,
    compute_dtype=jnp.float32,
    window: Optional[int] = None,
) -> Tuple[Array, Dict[str, Array]]:
    """One decode step against a KV cache. Returns (out (B,1,D), new cache)."""
    b = x.shape[0]
    q = _split_heads(linear(p["wq"], x, compute_dtype), n_heads)  # (B,H,1,Dh)
    k_new = _split_heads(linear(p["wk"], x, compute_dtype), n_kv_heads)
    v_new = _split_heads(linear(p["wv"], x, compute_dtype), n_kv_heads)
    head_dim = q.shape[-1]
    if rope_base > 0:
        cos, sin = rope_cos_sin(pos[None], head_dim, rope_base)
        q = apply_rope(q, cos, sin)
        k_new = apply_rope(k_new, cos, sin)

    ck = jax.lax.dynamic_update_slice(
        cache["k"], k_new.astype(cache["k"].dtype), (0, 0, pos, 0)
    )
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v_new.astype(cache["v"].dtype), (0, 0, pos, 0)
    )
    skv = ck.shape[2]
    group = n_heads // n_kv_heads
    kr = jnp.repeat(ck.astype(compute_dtype), group, axis=1)
    vr = jnp.repeat(cv.astype(compute_dtype), group, axis=1)
    seqsh = decode_seq_shard(b, n_kv_heads, skv)
    if seqsh is not None:
        (bax,) = seqsh
        kr = _wsc(kr, (bax, None, "model", None))
        vr = _wsc(vr, (bax, None, "model", None))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kr).astype(jnp.float32)
    logits = logits / math.sqrt(head_dim)
    if seqsh is not None:
        logits = _wsc(logits, (bax, None, None, "model"))
    kpos = jnp.arange(skv)
    mask = kpos <= pos
    if window is not None:
        mask = mask & (kpos > pos - window)
    logits = jnp.where(mask[None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(compute_dtype)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, vr)
    out = linear(p["wo"], _merge_heads(o), compute_dtype)
    return out, {"k": ck, "v": cv}


def attention_chunked(
    q: Array,  # (B, H, Sq, Dh)
    k: Array,  # (B, H, Sk, Dh)
    v: Array,  # (B, H, Sk, Dh)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_chunk: int = 1024,
    k_chunk: int = 1024,
) -> Array:
    """Online-softmax blockwise attention (Rabe–Staats) in pure jnp.

    The XLA twin of the Pallas flash kernel: never materialises the
    (Sq, Sk) probability matrix — peak attention memory drops from O(S^2)
    to O(S * chunk), which is what makes the 4k-train and 32k-prefill
    cells fit HBM. Numerics match the masked-softmax reference to fp
    tolerance (tests/test_kernels.py).
    """
    b, h, sq, dh = q.shape
    sk = k.shape[2]
    dv = v.shape[-1]
    qc = min(q_chunk, sq)
    kc = min(k_chunk, sk)
    sq_real, sk_real = sq, sk
    if sq % qc or sk % kc:  # pad to chunk multiples; padded keys masked
        sq_p = -(-sq // qc) * qc
        sk_p = -(-sk // kc) * kc
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        sq, sk = sq_p, sk_p
    scale = 1.0 / math.sqrt(dh)
    nq, nk = sq // qc, sk // kc
    f32 = jnp.float32

    qr = q.reshape(b, h, nq, qc, dh)

    def per_q_chunk(qi, q_blk):
        # scan over kv chunks with running (m, l, acc)
        def body(carry, ki):
            m, l, acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k, ki * kc, kc, axis=2)
            v_blk = jax.lax.dynamic_slice_in_dim(v, ki * kc, kc, axis=2)
            s = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_blk).astype(f32)
            s = s * scale
            qpos = qi * qc + jnp.arange(qc)
            kpos = ki * kc + jnp.arange(kc)
            mask = jnp.broadcast_to(kpos[None, :] < sk_real, (qc, kc))
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            if window is not None:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk
            ).astype(f32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, qc), -jnp.inf, f32)
        l0 = jnp.zeros((b, h, qc), f32)
        a0 = jnp.zeros((b, h, qc, dv), f32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), jnp.arange(nk))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(
        lambda i: per_q_chunk(i, qr[:, :, i]), jnp.arange(nq)
    )  # (nq, B, H, qc, Dv)
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, sq, dv)
    return out[:, :, :sq_real].astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(
    key: Array,
    d_model: int,
    d_ff: int,
    *,
    kind: str = "swiglu",
    dtype=jnp.float32,
) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    if kind == "swiglu":
        return {
            "gate": init_linear(k1, d_model, d_ff, dtype=dtype),
            "up": init_linear(k2, d_model, d_ff, dtype=dtype),
            "down": init_linear(k3, d_ff, d_model, dtype=dtype),
        }
    if kind == "gelu":
        return {
            "up": init_linear(k1, d_model, d_ff, dtype=dtype),
            "down": init_linear(k2, d_ff, d_model, dtype=dtype),
        }
    raise ValueError(kind)


def mlp(p: Params, x: Array, compute_dtype=jnp.float32) -> Array:
    if "gate" in p:
        h = jax.nn.silu(linear(p["gate"], x, compute_dtype)) * linear(
            p["up"], x, compute_dtype
        )
    else:
        h = jax.nn.gelu(linear(p["up"], x, compute_dtype))
    return linear(p["down"], h, compute_dtype)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------


def next_token_loss(
    logits: Array, tokens: Array, mask: Optional[Array] = None
) -> Array:
    """Mean next-token cross-entropy. logits (B,S,V); tokens (B,S)."""
    lg = logits[:, :-1]
    tg = tokens[:, 1:]
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tg[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    m = mask[:, 1:].astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
