"""Distribution-layer tests: sharding rules, chunked kernels, EP MoE,
HLO collective parsing, token packing. CPU-only; multi-device pieces run
in a subprocess with forced host devices (the main process has already
locked jax to one device)."""

from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.configs import get_config
from repro.launch import sharding as S
from repro.launch.hloparse import analyze_collectives
from repro.models import build_model
from repro.models import layers as L


# Minimal env for subprocess tests. JAX_PLATFORMS must be forwarded:
# without it jax probes for accelerator plugins at import, which hangs
# on CI machines with no device.
_SUB_ENV = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
for _k in ("JAX_PLATFORMS", "HOME"):
    if _k in os.environ:
        _SUB_ENV[_k] = os.environ[_k]


def _mesh(shape=(16, 16), axes=("data", "model")):
    try:  # jax >= 0.5: AbstractMesh(axis_sizes, axis_names)
        return jax.sharding.AbstractMesh(shape, axes)
    except TypeError:  # jax 0.4.x: AbstractMesh(((name, size), ...))
        return jax.sharding.AbstractMesh(tuple(zip(axes, shape)))


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------


class TestParamSpecs:
    def test_dense_tp_rules(self):
        cfg = get_config("olmo-1b")
        model = build_model(cfg)
        specs = S.param_specs(cfg, model.param_spec(), _mesh())
        P = jax.sharding.PartitionSpec
        assert specs["embed"]["table"] == P("model", None)
        assert specs["layers"]["attn"]["wq"]["w"] == P(None, None, "model")
        assert specs["layers"]["attn"]["wo"]["w"] == P(None, "model", None)
        assert specs["layers"]["mlp"]["down"]["w"] == P(None, "model", None)

    def test_kv_heads_not_divisible_replicates_kv(self):
        cfg = get_config("qwen2.5-3b")  # kv=2 < 16
        model = build_model(cfg)
        specs = S.param_specs(cfg, model.param_spec(), _mesh())
        P = jax.sharding.PartitionSpec
        assert specs["layers"]["attn"]["wk"]["w"] == P()
        assert specs["layers"]["attn"]["wq"]["w"] == P(None, None, "model")

    def test_expert_sharding_dp_model(self):
        cfg = get_config("deepseek-v3-671b")
        model = build_model(cfg)
        specs = S.param_specs(cfg, model.param_spec(), _mesh())
        got = specs["moe_layers"]["moe"]["gate_w"]
        assert got == jax.sharding.PartitionSpec(
            None, ("data", "model"), None, None
        )

    def test_dp_strategy_replicates_everything(self):
        cfg = get_config("olmo-1b").replace(shard_strategy="dp")
        model = build_model(cfg)
        specs = S.param_specs(cfg, model.param_spec(), _mesh())
        assert all(
            s == jax.sharding.PartitionSpec()
            for s in jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec)
            )
        )

    def test_zero1_adds_data_axis(self):
        cfg = get_config("olmo-1b")
        model = build_model(cfg)
        ospecs = S.opt_specs(cfg, model.param_spec(), _mesh())
        P = jax.sharding.PartitionSpec
        # mlp down (L, F, D): param (None, "model", None) + data on D
        assert ospecs.mu["layers"]["mlp"]["down"]["w"] == P(
            None, "model", "data"
        )
        assert ospecs.step == P()

    def test_zero1_never_duplicates_axis(self):
        cfg = get_config("deepseek-v3-671b")
        model = build_model(cfg)
        ospecs = S.opt_specs(cfg, model.param_spec(), _mesh())
        for spec in jax.tree.leaves(
            ospecs.mu,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
        ):
            seen = []
            for entry in spec:
                if entry is None:
                    continue
                seen += list(entry) if isinstance(entry, tuple) else [entry]
            assert len(seen) == len(set(seen)), spec

    def test_cache_seq_shard_when_heads_dont_divide(self):
        cfg = get_config("qwen2.5-3b")
        model = build_model(cfg)
        sshape = model.serve_spec(128, 32768)
        specs = S.serve_specs(cfg, sshape, _mesh(), 128)
        P = jax.sharding.PartitionSpec
        assert specs["k"] == P(None, ("data",), None, "model", None)

    def test_batch_specs_divisibility(self):
        cfg = get_config("olmo-1b")
        from repro.configs.base import ShapeSpec

        sp = S.batch_specs(cfg, ShapeSpec("x", "train", 4096, 256), _mesh())
        assert sp["tokens"] == jax.sharding.PartitionSpec(("data",), None)
        # batch=1 (long_500k) -> replicated
        sp = S.batch_specs(cfg, ShapeSpec("x", "decode", 1024, 1), _mesh())
        assert sp["tokens"] == jax.sharding.PartitionSpec(None, None)


# ---------------------------------------------------------------------------
# Chunked attention == reference softmax attention
# ---------------------------------------------------------------------------


def _ref_attention(q, k, v, causal, window):
    s = q.shape[2]
    sk = k.shape[2]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((s, sk), bool)
    if causal:
        mask = kpos <= qpos
    if window:
        mask = mask & (kpos > qpos - window)
    probs = jax.nn.softmax(jnp.where(mask, logits, -1e30), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@pytest.mark.parametrize("s,qc,kc", [(128, 64, 32), (96, 64, 64), (130, 64, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_attention_chunked_matches_ref(s, qc, kc, causal):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    q, k, v = (jax.random.normal(kk, (2, 3, s, 16)) for kk in ks)
    ref = _ref_attention(q, k, v, causal, None)
    out = L.attention_chunked(q, k, v, causal=causal, q_chunk=qc, k_chunk=kc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_attention_chunked_dv_neq_dk():
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (2, 2, 64, 24))
    k = jax.random.normal(k2, (2, 2, 64, 24))
    v = jax.random.normal(k3, (2, 2, 64, 40))  # MLA-style wider/narrower V
    ref = _ref_attention(q, k, v, True, None)
    out = L.attention_chunked(q, k, v, causal=True, q_chunk=32, k_chunk=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(
    s=st.sampled_from([32, 48, 64]),
    window=st.sampled_from([None, 16]),
    seed=st.integers(0, 2**30),
)
def test_attention_chunked_property(s, window, seed):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    q, k, v = (jax.random.normal(kk, (1, 2, s, 8)) for kk in ks)
    ref = _ref_attention(q, k, v, True, window)
    out = L.attention_chunked(q, k, v, causal=True, window=window,
                              q_chunk=16, k_chunk=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# Chunked linear-attention scans == sequential refs
# ---------------------------------------------------------------------------


@settings(max_examples=8, deadline=None)
@given(t=st.sampled_from([31, 64, 96]), seed=st.integers(0, 2**30))
def test_rwkv6_chunked_matches_ref(t, seed):
    from repro.kernels.rwkv6_scan.ops import rwkv6_scan

    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 5)
    B, H, K = 2, 2, 8
    r, k, v = (jax.random.normal(kk, (B, H, t, K)) for kk in ks[:3])
    w_log = -jnp.exp(jax.random.normal(ks[3], (B, H, t, K)) * 2)
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    o1, s1 = rwkv6_scan(r, k, v, w_log, u, backend="ref")
    o2, s2 = rwkv6_scan(r, k, v, w_log, u, backend="chunked", chunk=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=8, deadline=None)
@given(t=st.sampled_from([33, 64]), seed=st.integers(0, 2**30))
def test_mamba2_chunked_matches_ref(t, seed):
    from repro.kernels.mamba2_ssd.ops import mamba2_ssd

    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    B, H, N, P_ = 2, 2, 4, 8
    x = jax.random.normal(ks[0], (B, H, t, P_))
    a_log = -jnp.exp(jax.random.normal(ks[1], (B, H, t)))
    bm = jax.random.normal(ks[2], (B, t, N))
    cm = jax.random.normal(ks[3], (B, t, N))
    y1, s1 = mamba2_ssd(x, a_log, bm, cm, backend="ref")
    y2, s2 = mamba2_ssd(x, a_log, bm, cm, backend="chunked", chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# EP MoE == sort MoE (subprocess: needs >1 device)
# ---------------------------------------------------------------------------


def test_ep_moe_matches_sort_subprocess():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"]="--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.configs import get_smoke_config
        from repro.models import moe as MOE
        key = jax.random.PRNGKey(0)
        out = {}
        base = get_smoke_config("deepseek-v2-lite-16b").replace(
            moe_capacity_factor=8.0)
        mesh = jax.make_mesh((2,4), ("data","model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        B,S,D = 8, 16, base.d_model
        x = jax.random.normal(jax.random.fold_in(key,2), (B,S,D))*0.3
        for name, cfg in (
            ("tp", base.replace(ep_axes="model", shard_strategy="tp")),
            ("fsdp", base.replace(ep_axes="dp_model", shard_strategy="fsdp")),
        ):
            p = MOE.init_moe(jax.random.fold_in(key,1), cfg)
            y_ref, _ = MOE.moe_ffn_sort(p, x, cfg)
            with jax.set_mesh(mesh):
                y_ep, _ = jax.jit(lambda p,x: MOE.moe_ffn_ep(p,x,cfg))(p, x)
                g1 = jax.jit(jax.grad(
                    lambda p,x: MOE.moe_ffn_ep(p,x,cfg)[0].sum()))(p,x)
            g2 = jax.grad(lambda p,x: MOE.moe_ffn_sort(p,x,cfg)[0].sum())(p,x)
            gerr = max(float(jnp.abs(a-b).max())
                       for a,b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
            out[name] = [float(jnp.abs(y_ref-y_ep).max()), gerr]
        print(json.dumps(out))
    """)
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=500, env=_SUB_ENV,
        cwd="/root/repo",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    for name, (yerr, gerr) in res.items():
        assert yerr < 1e-5, (name, yerr)
        assert gerr < 1e-4, (name, gerr)


def test_ep_moe_int8_dispatch_subprocess():
    """int8-quantized all-to-all dispatch stays within fp8-regime error."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"]="--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, json
        from repro.configs import get_smoke_config
        from repro.models import moe as MOE
        key = jax.random.PRNGKey(0)
        base = get_smoke_config("deepseek-v2-lite-16b").replace(
            moe_capacity_factor=8.0, ep_axes="model")
        cfgq = base.replace(moe_a2a_quant=True)
        p = MOE.init_moe(jax.random.fold_in(key,1), base)
        x = jax.random.normal(jax.random.fold_in(key,2),
                              (8, 16, base.d_model))*0.3
        y_ref, _ = MOE.moe_ffn_sort(p, x, base)
        mesh = jax.make_mesh((2,4), ("data","model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        with jax.set_mesh(mesh):
            yq, _ = jax.jit(lambda p,x: MOE.moe_ffn_ep(p,x,cfgq))(p, x)
            gq = jax.jit(jax.grad(
                lambda p,x: MOE.moe_ffn_ep(p,x,cfgq)[0].sum()))(p,x)
        g2 = jax.grad(lambda p,x: MOE.moe_ffn_sort(p,x,base)[0].sum())(p,x)
        rel = float(jnp.abs(y_ref-yq).max()/jnp.abs(y_ref).max())
        grel = max(float(jnp.abs(a-b).max()/(jnp.abs(a).max()+1e-9))
                   for a,b in zip(jax.tree.leaves(gq), jax.tree.leaves(g2)))
        print(json.dumps([rel, grel]))
    """)
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=500, env=_SUB_ENV,
        cwd="/root/repo",
    )
    assert r.returncode == 0, r.stderr[-2000:]
    rel, grel = json.loads(r.stdout.strip().splitlines()[-1])
    assert rel < 0.03, rel
    assert grel < 0.1, grel


def test_ep_moe_falls_back_without_mesh():
    from repro.configs import get_smoke_config
    from repro.models import moe as MOE

    cfg = get_smoke_config("deepseek-v2-lite-16b").replace(moe_impl="ep")
    p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    y, aux = MOE.moe_ffn(p, x, cfg)  # no ambient mesh -> sort fallback
    assert y.shape == x.shape


# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------


HLO_SAMPLE = """HloModule test, num_partitions=4

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %ar = f32[8,8]{1,0} all-reduce(%x), replica_groups=[2,2]<=[4], to_apply=%add
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %w = (s32[], f32[8,8]) while(%t), condition=%c, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  %ag = f32[16,8]{1,0} all-gather(%y), replica_groups=[1,4]<=[4], dimensions={0}
}
"""


def test_hloparse_trip_count_multiplies():
    r = analyze_collectives(HLO_SAMPLE)
    # all-reduce inside the loop counted 10x: 10 * 8*8*4 bytes
    assert r["by_op"]["all-reduce"] == 10 * 8 * 8 * 4
    assert r["by_op"]["all-gather"] == 16 * 8 * 4
    assert r["counts"]["all-reduce"] == 10
    # ring factors: AR group=2 -> 2*(1/2)=1.0x; AG group=4 -> 3/4
    assert r["wire_bytes"] == pytest.approx(
        10 * 256 * 1.0 + 512 * 0.75
    )


def test_hloparse_upcast_detection():
    txt = HLO_SAMPLE.replace("all-reduce(%x)", "all-reduce(%convert_fusion)")
    r = analyze_collectives(txt)
    assert r["tpu_wire_bytes"] < r["wire_bytes"]


# ---------------------------------------------------------------------------
# Packing subsample
# ---------------------------------------------------------------------------


def test_pack_subsamples_uniformly_in_time():
    from repro.core import packing

    n, p = 100, 16
    rgb = jnp.zeros((n, p, p, 3))
    t = jnp.arange(n, dtype=jnp.float32)
    origin = jnp.zeros((n, 2))
    valid = jnp.ones((n,), bool)
    ts = packing.pack(rgb, t, origin, valid, 10, t_max=100.0)
    t_feat = np.asarray(ts.tokens[:, packing.THUMB * packing.THUMB * 3]) * 100
    assert t_feat[0] == 0 and t_feat[-1] == 99  # full span, no truncation
    assert np.all(np.diff(t_feat) > 5)  # roughly uniform
