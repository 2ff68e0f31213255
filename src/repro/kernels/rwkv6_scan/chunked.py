"""Chunked (matmul-form) RWKV6 scan — the TPU-native training/prefill path.

Sequential per-token recurrence is latency-bound on TPU (one tiny MXU op
per step). The chunked form processes C tokens at a time with dense
matmuls (the same blocking the Pallas kernel uses) and carries the (K, V)
state across chunks with a short lax.scan of T/C steps:

  intra-chunk:  o_t += sum_{s<t} (r_t . exp(We_t - W_s) . k_s) v_s  (exact,
                computed in log-space so strong decays never overflow)
                + (r_t . u . k_t) v_t                               (bonus)
  inter-chunk:  o_t += (r_t * exp(We_t)) @ S0
  state:        S'  = diag(exp(W_C)) S0 + (k_s * exp(W_C - W_s))^T v

W is the *within-chunk* inclusive cumsum of w_log (< 0), We the exclusive
one; every exponent above is <= 0, so the fp32 math is saturation-free
regardless of decay strength (the factorized r~/k~ trick is not: it splits
exp(We_t - W_s) into exp(We_t)*exp(-W_s) whose halves can under/overflow
in opposite directions).  The differences We_t - W_s and W_C - W_s are
summed directly over their own spans (s, t) and (s, C], never taken as a
difference of two prefix sums: one strong decay early in the chunk makes
both prefixes large, and their f32 difference would lose the small decays
that follow it.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def rwkv6_scan_chunked(
    r: Array,
    k: Array,
    v: Array,
    w_log: Array,
    u: Array,
    init_state: Optional[Array] = None,
    *,
    chunk: int = 32,
) -> Tuple[Array, Array]:
    """Same contract as rwkv6_scan_ref. r/k/w_log: (B,H,T,K); v: (B,H,T,V)."""
    b, h, t, dk = r.shape
    dv = v.shape[-1]
    c = min(chunk, t)
    t_pad = -(-t // c) * c
    if t_pad != t:
        # zero-k / zero-w_log padding steps are identities on the state
        pad = ((0, 0), (0, 0), (0, t_pad - t), (0, 0))
        r, k, v, w_log = (jnp.pad(a, pad) for a in (r, k, v, w_log))
    t_full, t = t, t_pad
    nc = t // c
    f32 = jnp.float32

    def cshape(x, d):
        return x.astype(f32).reshape(b, h, nc, c, d)

    rc, kc, wc = cshape(r, dk), cshape(k, dk), cshape(w_log, dk)
    vc = cshape(v, dv)
    uf = u.astype(f32)  # (H, K)

    W = jnp.cumsum(wc, axis=-2)  # inclusive within-chunk cumsum
    We = W - wc  # exclusive
    # log-space intra-chunk pair weights: expo[t,s] = sum_{s<j<t} w_j,
    # a running sum over t of the decays after s, shifted one step so that
    # t itself is excluded. Every term is <= 0, and the upper triangle
    # (masked below) stays 0.
    mask = jnp.tril(jnp.ones((c, c), bool), k=-1)  # [t, s]: s < t
    after_s = jnp.where(mask[:, :, None], wc[..., :, None, :], 0.0)
    incl = jnp.cumsum(after_s, axis=-3)  # sum_{s<j<=t} w_j
    expo = jnp.concatenate(
        [jnp.zeros_like(incl[..., :1, :, :]), incl[..., :-1, :, :]], axis=-3
    )
    # P[t,s] = sum_k r[t,k] k[s,k] exp(expo[t,s,k])
    p = jnp.einsum("bhntk,bhnsk,bhntsk->bhnts", rc, kc, jnp.exp(expo))
    o_intra = jnp.einsum("bhnts,bhnsv->bhntv", jnp.where(mask, p, 0.0), vc)
    bonus = jnp.einsum("bhntk,hk,bhntk->bhnt", rc, uf, kc)
    o_intra = o_intra + bonus[..., None] * vc

    r_dec = rc * jnp.exp(We)  # queries decayed to chunk start
    w_last = W[..., -1, :]  # (B,H,nc,K) total chunk decay
    # keys decayed to chunk end: sum_{s<j<C} w_j, a reversed exclusive cumsum
    rev = jnp.cumsum(wc[..., ::-1, :], axis=-2)[..., ::-1, :]  # sum_{j>=s}
    to_end = jnp.concatenate([rev[..., 1:, :], jnp.zeros_like(rev[..., :1, :])], -2)
    k_hat = kc * jnp.exp(to_end)

    if init_state is None:
        init_state = jnp.zeros((b, h, dk, dv), f32)

    def body(s, xs):
        rd, kh, vv, wl = xs  # (B,H,C,K) ... (B,H,K)
        o_inter = jnp.einsum("bhtk,bhkv->bhtv", rd, s)
        s_new = jnp.exp(wl)[..., None] * s + jnp.einsum(
            "bhtk,bhtv->bhkv", kh, vv
        )
        return s_new, o_inter

    xs = tuple(
        jnp.moveaxis(a, 2, 0) for a in (r_dec, k_hat, vc, w_last)
    )
    s_fin, o_inter = jax.lax.scan(body, init_state.astype(f32), xs)
    o = o_intra + jnp.moveaxis(o_inter, 0, 2)
    return o.reshape(b, h, t, dv)[:, :, :t_full], s_fin
