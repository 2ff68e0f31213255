"""Mixture-of-Experts FFN (DeepSeek V2-Lite / V3) — sort-based dispatch.

TPU adaptation: the GPU-typical ragged grouped-GEMM becomes a *static-shape
sort-and-capacity* dispatch (the MaxText/Switch lineage):

  1. router top-k -> (T*K) flat assignments;
  2. stable argsort by expert id groups assignments per expert;
  3. rank-within-expert from counts; assignments past the per-expert
     capacity C = ceil(T*K/E * cf) are dropped (token keeps its other
     experts; drop rate is logged via aux stats);
  4. one gather builds (E, C, D) expert inputs, a batched einsum against
     stacked per-expert weights (E, D, F) runs all experts in one MXU call,
     one scatter-add applies gate weights back to (T, D).

Everything is static-shaped, differentiable, and shards: the E axis is the
EP axis (sharded over 'model', or over ('data','model') for v3's 256
experts); XLA turns the gather/scatter into all-to-alls under GSPMD.

DeepSeek specifics: ``moe_shared`` always-on shared experts (a dense SwiGLU
of width shared*moe_d_ff) are added to the routed output; routing uses
softmax gates normalised over the selected top-k (V2 convention); an
auxiliary load-balance loss (Switch-style) is returned for the trainer.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers as L

Array = jax.Array
Params = Dict[str, Any]


def init_moe(key: Array, cfg: ModelConfig) -> Params:
    e, d, f = cfg.moe_experts, cfg.d_model, cfg.moe_d_ff
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p: Params = {
        "router": (
            jax.random.normal(k1, (d, e), jnp.float32) * scale
        ).astype(jnp.float32),  # router stays fp32 (numerics)
        "gate_w": (
            jax.random.normal(k2, (e, d, f), jnp.float32) * scale
        ).astype(cfg.pdt),
        "up_w": (
            jax.random.normal(k3, (e, d, f), jnp.float32) * scale
        ).astype(cfg.pdt),
        "down_w": (
            jax.random.normal(k4, (e, f, d), jnp.float32)
            * (1.0 / math.sqrt(f))
        ).astype(cfg.pdt),
    }
    if cfg.moe_shared:
        p["shared"] = L.init_mlp(
            k5, d, cfg.moe_shared * f, kind="swiglu", dtype=cfg.pdt
        )
    return p


def moe_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(
        math.ceil(
            n_tokens * cfg.moe_top_k / cfg.moe_experts * cfg.moe_capacity_factor
        )
    )
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def _route(p: Params, x2: Array, cfg: ModelConfig):
    """fp32 router + deepseek top-k renormalised gates + aux loss."""
    e, k = cfg.moe_experts, cfg.moe_top_k
    logits = jnp.dot(x2.astype(jnp.float32), p["router"])  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, k)  # (T, K)
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9
    )  # deepseek: renormalise over the selected experts
    frac_tokens = jnp.mean(
        (jax.nn.one_hot(eids, e).sum(axis=1) > 0).astype(jnp.float32), axis=0
    )
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * mean_prob)
    return gates, eids, aux


def _dispatch(x2: Array, gates: Array, eids: Array, e: int, c: int):
    """Sort-based capacity dispatch. Returns (xg (E,C,D), combine info)."""
    t, d = x2.shape
    k = eids.shape[1]
    eid_flat = eids.reshape(-1)  # (T*K,)
    tok_flat = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    gate_flat = gates.reshape(-1)

    order = jnp.argsort(eid_flat, stable=True)
    s_eid = eid_flat[order]
    s_tok = tok_flat[order]
    s_gate = gate_flat[order]

    counts = jnp.bincount(eid_flat, length=e)  # (E,)
    starts = jnp.cumsum(counts) - counts
    ranks = jnp.arange(t * k, dtype=jnp.int32) - starts[s_eid]
    keep = ranks < c
    slot = jnp.where(keep, s_eid * c + ranks, e * c)  # sentinel = E*C

    tok_by_slot = (
        jnp.zeros((e * c + 1,), jnp.int32).at[slot].set(s_tok)[: e * c]
    )
    gate_by_slot = (
        jnp.zeros((e * c + 1,), jnp.float32)
        .at[slot]
        .set(jnp.where(keep, s_gate, 0.0))[: e * c]
    )
    valid = jnp.zeros((e * c + 1,), bool).at[slot].set(keep)[: e * c]
    xg = x2[tok_by_slot].reshape(e, c, d) * valid.reshape(e, c, 1).astype(
        x2.dtype
    )
    return xg, (tok_by_slot, gate_by_slot, valid)


def _combine(y: Array, info, t: int, cdt) -> Array:
    tok_by_slot, gate_by_slot, valid = info
    e, c, d = y.shape
    y_flat = y.reshape(e * c, d) * gate_by_slot[:, None].astype(cdt)
    return (
        jnp.zeros((t, d), cdt)
        .at[tok_by_slot]
        .add(jnp.where(valid[:, None], y_flat, 0.0))
    )


def _expert_ffn(p: Params, xg: Array, cdt) -> Array:
    """Batched per-expert SwiGLU: (E, C, D) -> (E, C, D)."""
    xg = xg.astype(cdt)
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", xg, p["gate_w"].astype(cdt))
    ) * jnp.einsum("ecd,edf->ecf", xg, p["up_w"].astype(cdt))
    return jnp.einsum("ecf,efd->ecd", h, p["down_w"].astype(cdt))


def moe_ffn(
    p: Params, x: Array, cfg: ModelConfig
) -> Tuple[Array, Array]:
    """Routed MoE over (B, S, D). Dispatches on cfg.moe_impl."""
    if cfg.moe_impl == "ep":
        out = moe_ffn_ep(p, x, cfg)
        if out is not None:
            return out
    return moe_ffn_sort(p, x, cfg)


def moe_ffn_sort(
    p: Params, x: Array, cfg: ModelConfig
) -> Tuple[Array, Array]:
    """Single-program dispatch: global sort under GSPMD (the baseline).

    Simple and correct, but under pjit the global argsort/gather forces
    token all-gathers that dominate the collective roofline at scale —
    moe_ffn_ep is the production path (§Perf)."""
    b, s, d = x.shape
    t = b * s
    e = cfg.moe_experts
    c = moe_capacity(cfg, t)
    x2 = x.reshape(t, d)
    gates, eids, aux = _route(p, x2, cfg)
    xg, info = _dispatch(x2, gates, eids, e, c)

    cdt = cfg.cdt
    y = _expert_ffn(p, xg, cdt)
    out = _combine(y, info, t, cdt)
    if cfg.moe_shared:
        out = out + L.mlp(p["shared"], x2, cdt)
    return out.reshape(b, s, d).astype(x.dtype), aux


def _quant_all_to_all(x, ep_names, split_axis, concat_axis):
    """int8-quantized all-to-all (DeepSeek-V3 fp8-dispatch analogue).

    Per-slot (last-dim) symmetric scales ride along as fp32 — wire bytes
    drop ~2x vs bf16. Backward quantizes the cotangent the same way
    (custom_vjp), matching the fp8-both-ways recipe; the router's gating
    keeps the scheme stable (quantization error enters pre-gate).
    """

    def q(v):
        scale = jnp.max(jnp.abs(v), axis=-1, keepdims=True) / 127.0 + 1e-12
        q8 = jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)
        return q8, scale.astype(jnp.float32)

    def a2a(v, split, concat):
        return jax.lax.all_to_all(
            v, ep_names, split_axis=split, concat_axis=concat, tiled=True
        )

    @jax.custom_vjp
    def qa2a(v):
        q8, s = q(v)
        return (
            a2a(q8, split_axis, concat_axis).astype(v.dtype)
            * a2a(s, split_axis, concat_axis)
        ).astype(v.dtype)

    def fwd(v):
        return qa2a(v), None

    def bwd(_, g):
        q8, s = q(g)
        out = (
            a2a(q8, concat_axis, split_axis).astype(g.dtype)
            * a2a(s, concat_axis, split_axis)
        ).astype(g.dtype)
        return (out,)

    qa2a.defvjp(fwd, bwd)
    return qa2a(x)


# ---------------------------------------------------------------------------
# Expert-parallel shard_map dispatch (the production path)
# ---------------------------------------------------------------------------


def moe_ffn_ep(p: Params, x: Array, cfg: ModelConfig):
    """EP MoE: local routing + all-to-all token exchange (DeepSeek-style).

    Tokens stay on their device; only the capacity-bounded (E, C_loc, D)
    dispatch buffers cross the EP axis (two all-to-alls per direction of
    the pass) — this removes the token all-gathers the single-program
    sort dispatch suffers under GSPMD (measured 5.4 TB/device/step on
    deepseek-v3 train_4k; see EXPERIMENTS.md §Perf).

    Token layout inside the region: batch over the pure-DP axes, seq over
    the remaining EP axes, so every device owns a disjoint token slice.
    The mesh is the ambient one (``with jax.set_mesh(mesh):``).  Returns
    None when no suitable ambient mesh exists (single-host tests fall
    back to the sort impl).
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    ax = dict(mesh.shape)
    ep_names = (
        ("data", "model") if cfg.ep_axes == "dp_model" else ("model",)
    )
    if any(n not in ax for n in ep_names):
        return None
    n_ep = 1
    for n in ep_names:
        n_ep *= ax[n]
    e = cfg.moe_experts
    b, s, d = x.shape
    if n_ep == 1 or e % n_ep != 0 or s % n_ep != 0:
        return None
    e_loc = e // n_ep
    cdt = cfg.cdt

    # Token layout inside the region: MUST match the outer activation
    # sharding so shard_map inserts no reshard (a mismatched in_spec
    # replicates the batch — measured 2.8x WORSE than baseline; §Perf).
    all_axes = [n for n in ("pod", "data", "model") if n in ax]
    batch_axes = seq_axes = None
    if cfg.shard_strategy in ("dp", "fsdp"):
        # layout 1: batch sharded over a prefix covering every EP axis
        for start in range(len(all_axes)):
            use = tuple(all_axes[start:])
            size = int(np.prod([ax[n] for n in use]))
            if b % size == 0 and all(n in use for n in ep_names):
                batch_axes = use
                break
    if batch_axes is None:
        # layout 2 (small-batch prefill / tp): batch over the non-model
        # DP axes, seq over the model axis — tokens are disjoint across
        # every EP device as long as ep ⊆ batch_axes ∪ seq_axes.
        dp_names = tuple(n for n in ("pod", "data") if n in ax)
        for start in range(len(dp_names) + 1):
            use = dp_names[start:]
            size = int(np.prod([ax[n] for n in use])) if use else 1
            if b % size == 0:
                batch_axes = tuple(use) or None
                break
        if s % ax.get("model", 1) != 0:
            return None
        seq_axes = ("model",)
        covered = set(batch_axes or ()) | set(seq_axes)
        if not set(ep_names) <= covered:
            return None
    pod_extra = tuple(
        n for n in all_axes
        if n not in (batch_axes or ()) and n not in (seq_axes or ())
        and n not in ep_names
    )

    def region(x_loc, router, gate_w, up_w, down_w, shared):
        bl, sl, _ = x_loc.shape
        t = bl * sl
        c_loc = max(4, -(-int(t * cfg.moe_top_k / e
                              * cfg.moe_capacity_factor) // 4) * 4)
        x2 = x_loc.reshape(t, d)
        pp = {"router": router}
        gates, eids, aux = _route(pp, x2, cfg)
        xg, info = _dispatch(x2, gates, eids, e, c_loc)  # (E, C_loc, D)
        # exchange: peer i owns expert rows [i*e_loc, (i+1)*e_loc); send it
        # their slices, receive everyone's slices for MY experts.
        if cfg.moe_a2a_quant:
            xr = _quant_all_to_all(xg, ep_names, 0, 1)
        else:
            xr = jax.lax.all_to_all(
                xg, ep_names, split_axis=0, concat_axis=1, tiled=True
            )  # (e_loc, n_ep*C_loc, D)
        y = _expert_ffn(
            {"gate_w": gate_w, "up_w": up_w, "down_w": down_w}, xr, cdt
        )  # (e_loc, n_ep*C_loc, D)
        if cfg.moe_a2a_quant:
            y = _quant_all_to_all(y, ep_names, 1, 0)
        else:
            y = jax.lax.all_to_all(
                y, ep_names, split_axis=1, concat_axis=0, tiled=True
            )  # (E, C_loc, D), expert-major as dispatched
        out = _combine(y.astype(cdt), info, t, cdt)
        if cfg.moe_shared:
            out = out + L.mlp(shared, x2, cdt)
        mean_axes = tuple(
            dict.fromkeys((batch_axes or ()) + (seq_axes or ()) + pod_extra)
        )
        if mean_axes:
            aux = jax.lax.pmean(aux, mean_axes)
        return out.reshape(bl, sl, d).astype(x.dtype), aux

    x_spec = P(batch_axes, seq_axes, None)
    in_specs = (
        x_spec,
        P(),  # router replicated
        P(ep_names, None, None),
        P(ep_names, None, None),
        P(ep_names, None, None),
        P(),  # shared experts replicated
    )
    out_specs = (x_spec, P())
    fn = jax.shard_map(
        region, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    shared = p.get("shared", {"_": jnp.zeros((), cdt)})
    out, aux = fn(
        x, p["router"], p["gate_w"], p["up_w"], p["down_w"], shared
    )
    return out, aux
