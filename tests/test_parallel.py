"""Parallelism modules: mesh building, ring/Ulysses attention, pipeline, MoE.

No reference analogue (Horovod is DP-only, SURVEY §2.6); correctness oracles
are the dense single-device computations.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel import MeshConfig, build_mesh
from horovod_tpu.parallel import sharding as shd
from horovod_tpu.parallel.moe import (
    moe_layer,
    moe_layer_hvd,
    moe_layer_local,
    switch_route,
)
from horovod_tpu.parallel.pipeline import pipeline_apply
from horovod_tpu.parallel.ring_attention import (
    ring_self_attention,
    ulysses_attention_local,
)


def _dense_attention(q, k, v, causal):
    D = q.shape[-1]
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        Ls = q.shape[1]
        mask = np.tril(np.ones((Ls, Ls), bool))
        s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------

def test_mesh_config_auto():
    cfg = MeshConfig.auto(8)
    assert cfg.total == 8
    assert cfg.tp > 1 and cfg.dp > 1       # exercises at least tp+dp
    cfg32 = MeshConfig.auto(32)
    assert cfg32.total == 32


def test_build_mesh_axes():
    cfg = MeshConfig(dp=2, tp=2, sp=2)
    mesh = build_mesh(cfg)
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 2
    assert mesh.shape["pp"] == 1


def test_build_mesh_wrong_count():
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(dp=3))


def test_logical_sharding_rules():
    mesh = build_mesh(MeshConfig(dp=2, tp=2, sp=2))
    s = shd.logical_sharding(mesh, ("batch", "seq", "mlp"))
    assert s.spec == P(("dp", "fsdp"), "sp", "tp")
    with pytest.raises(KeyError):
        shd.spec_for(("nonexistent",))


# ---------------------------------------------------------------------------
# ring attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(causal):
    B, S, H, D = 2, 32, 4, 8
    rng = np.random.RandomState(0)
    q = rng.randn(B, S, H, D).astype(np.float32)
    k = rng.randn(B, S, H, D).astype(np.float32)
    v = rng.randn(B, S, H, D).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    sh = NamedSharding(mesh, P(None, "sp"))
    out = ring_self_attention(
        jax.device_put(q, sh), jax.device_put(k, sh), jax.device_put(v, sh),
        mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), _dense_attention(q, k, v, causal),
                               rtol=2e-4, atol=2e-5)


def test_ring_attention_grad_finite():
    B, S, H, D = 1, 16, 2, 4
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    sh = NamedSharding(mesh, P(None, "sp"))
    rng = np.random.RandomState(1)
    q = jax.device_put(rng.randn(B, S, H, D).astype(np.float32), sh)

    def loss(q_):
        o = ring_self_attention(q_, q_, q_, mesh, causal=True)
        return jnp.sum(o * o)

    g = jax.grad(loss)(q)
    assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_dense(causal):
    B, S, H, D = 2, 32, 8, 4   # H=8 divisible by sp=8
    rng = np.random.RandomState(2)
    q = rng.randn(B, S, H, D).astype(np.float32)
    k = rng.randn(B, S, H, D).astype(np.float32)
    v = rng.randn(B, S, H, D).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    sh = NamedSharding(mesh, P(None, "sp"))
    from functools import partial
    from jax import shard_map
    fn = jax.jit(shard_map(
        partial(ulysses_attention_local, causal=causal),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    out = fn(jax.device_put(q, sh), jax.device_put(k, sh),
             jax.device_put(v, sh))
    np.testing.assert_allclose(np.asarray(out), _dense_attention(q, k, v, causal),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# pipeline
# ---------------------------------------------------------------------------

def test_pipeline_matches_sequential():
    n_stage, M, mb, d = 8, 16, 4, 6
    rng = np.random.RandomState(3)
    # Stage s: x -> tanh(x @ W_s); stacked over stages.
    Ws = rng.randn(n_stage, d, d).astype(np.float32) * 0.3
    mesh = Mesh(np.array(jax.devices()), ("pp",))
    stacked = jax.device_put(Ws, NamedSharding(mesh, P("pp")))
    microbatches = rng.randn(M, mb, d).astype(np.float32)

    def stage_fn(W, x):
        return jnp.tanh(x @ W)

    out = pipeline_apply(stage_fn, stacked,
                         jax.device_put(microbatches,
                                        NamedSharding(mesh, P())),
                         mesh)
    # Sequential oracle.
    ref = microbatches.copy()
    for s in range(n_stage):
        ref = np.tanh(ref @ Ws[s])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=1e-5)


def test_pipeline_grad_flows():
    n_stage, M, mb, d = 8, 8, 2, 4
    rng = np.random.RandomState(4)
    Ws = rng.randn(n_stage, d, d).astype(np.float32) * 0.3
    mesh = Mesh(np.array(jax.devices()), ("pp",))
    mbs = jax.device_put(rng.randn(M, mb, d).astype(np.float32),
                         NamedSharding(mesh, P()))

    def loss(W):
        out = pipeline_apply(lambda w, x: jnp.tanh(x @ w),
                             W, mbs, mesh)
        return jnp.sum(out * out)

    g = jax.grad(loss)(jax.device_put(Ws, NamedSharding(mesh, P("pp"))))
    gn = np.asarray(g)
    assert np.isfinite(gn).all()
    assert (np.abs(gn) > 0).any(axis=(1, 2)).all(), \
        "every stage's params must receive gradient"


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def test_moe_layer_routes_and_combines():
    T, Dm, E = 64, 8, 8           # 8 experts over 8 devices
    rng = np.random.RandomState(5)
    tokens = rng.randn(T, Dm).astype(np.float32)
    router = rng.randn(Dm, E).astype(np.float32)
    # Expert e: x -> x @ We (per-expert matrix), stacked [E, Dm, Dm].
    We = rng.randn(E, Dm, Dm).astype(np.float32) * 0.5
    mesh = Mesh(np.array(jax.devices()), ("ep",))

    def expert_fn(w, x):
        return x @ w

    out, aux = moe_layer(
        jax.device_put(tokens, NamedSharding(mesh, P("ep"))),
        jax.device_put(router, NamedSharding(mesh, P())),
        expert_fn,
        jax.device_put(We, NamedSharding(mesh, P("ep"))),
        mesh, capacity_factor=8.0)   # ample capacity: nothing dropped
    out = np.asarray(out)
    aux = float(aux)

    # Oracle: top-1 routing with gate weighting, no drops.
    logits = tokens @ router
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    idx = p.argmax(-1)
    gate = p[np.arange(T), idx]
    expected = np.stack([gate[t] * (tokens[t] @ We[idx[t]])
                         for t in range(T)])
    np.testing.assert_allclose(out, expected, rtol=2e-3, atol=1e-4)
    assert aux > 0


def test_moe_capacity_drops_overflow():
    # Capacity factor so small most tokens drop: output for dropped tokens
    # must be exactly zero (residual recovers them in a real model).
    T, Dm, E = 64, 4, 8
    rng = np.random.RandomState(6)
    tokens = rng.randn(T, Dm).astype(np.float32)
    router = np.zeros((Dm, E), np.float32)  # uniform → all to expert 0
    We = np.stack([np.eye(Dm, dtype=np.float32)] * E)
    mesh = Mesh(np.array(jax.devices()), ("ep",))
    out, _ = moe_layer(
        jax.device_put(tokens, NamedSharding(mesh, P("ep"))),
        jax.device_put(router, NamedSharding(mesh, P())),
        lambda w, x: x @ w,
        jax.device_put(We, NamedSharding(mesh, P("ep"))),
        mesh, capacity_factor=0.25)
    out = np.asarray(out)
    zero_rows = (np.abs(out) < 1e-12).all(axis=1).sum()
    assert zero_rows > 0, "expected overflow drops with tiny capacity"


def test_switch_route_drop_mask_matches_overflow():
    # The explicit drop mask must name exactly the tokens past capacity:
    # dropped[t] <=> token t contributes nothing to dispatch/combine.
    T, E, C = 16, 4, 2
    logits = jnp.asarray(np.random.RandomState(3).randn(T, E), jnp.float32)
    dispatch, combine, _, dropped = switch_route(logits, C)
    kept_mass = np.asarray(dispatch).sum(axis=(1, 2))   # 1 kept, 0 dropped
    np.testing.assert_array_equal(np.asarray(dropped), kept_mass == 0.0)
    # Per-expert kept count never exceeds capacity.
    per_expert = np.asarray(dispatch).sum(axis=(0, 2))
    assert (per_expert <= C).all(), per_expert
    # The combine mass of dropped tokens is exactly zero.
    assert np.asarray(combine)[np.asarray(dropped)].sum() == 0.0


def test_moe_layer_counts_dropped_tokens():
    from horovod_tpu.obs import REGISTRY
    T, Dm, E = 64, 4, 8
    rng = np.random.RandomState(6)
    tokens = rng.randn(T, Dm).astype(np.float32)
    router = np.zeros((Dm, E), np.float32)  # uniform → all to expert 0
    We = np.stack([np.eye(Dm, dtype=np.float32)] * E)
    mesh = Mesh(np.array(jax.devices()), ("ep",))
    fam = REGISTRY.get("hvd_moe_dropped_tokens_total")
    before = fam.labels(layer="t_drop").value
    moe_layer(
        jax.device_put(tokens, NamedSharding(mesh, P("ep"))),
        jax.device_put(router, NamedSharding(mesh, P())),
        lambda w, x: x @ w,
        jax.device_put(We, NamedSharding(mesh, P("ep"))),
        mesh, capacity_factor=0.25, layer="t_drop")
    delta = fam.labels(layer="t_drop").value - before
    # All T tokens route to expert 0; its per-shard capacity is 1, so
    # every shard drops all but one of its tokens.
    assert delta == T - len(jax.devices()), delta


@pytest.mark.parametrize("ep", [1, 2, 4])
def test_moe_layer_parity_across_ep(ep):
    """moe_layer over ep ∈ {1,2,4} against the dense per-token oracle.

    Ample capacity (nothing drops — per-shard capacity changes with ep,
    so drop behavior is only comparable when it never engages).  fp32
    end to end; einsum dispatch vs direct matmul differ only in
    summation order, so 1e-5 bounds the drift."""
    T, Dm, E = 32, 8, 4
    rng = np.random.RandomState(11)
    tokens = rng.randn(T, Dm).astype(np.float32)
    router = rng.randn(Dm, E).astype(np.float32)
    We = rng.randn(E, Dm, Dm).astype(np.float32) * 0.5
    mesh = Mesh(np.array(jax.devices()[:ep]), ("ep",))
    out, aux = moe_layer(
        jax.device_put(tokens, NamedSharding(mesh, P("ep"))),
        jax.device_put(router, NamedSharding(mesh, P())),
        lambda w, x: x @ w,
        jax.device_put(We, NamedSharding(mesh, P("ep"))),
        mesh, capacity_factor=float(E))
    logits = tokens @ router
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    idx = p.argmax(-1)
    gate = p[np.arange(T), idx]
    expected = np.stack([gate[t] * (tokens[t] @ We[idx[t]])
                         for t in range(T)])
    np.testing.assert_allclose(np.asarray(out), expected,
                               rtol=1e-5, atol=1e-5)
    assert float(aux) > 0


def test_moe_layer_hvd_parity_with_drops():
    """The engine-verb path (`hvd.alltoall` dispatch/combine) against a
    per-rank dense oracle that replicates its capacity-drop rule: kept
    tokens match the oracle to fp32 tolerance, dropped tokens are
    exactly zero, and the total feeds the drop counter."""
    from horovod_tpu.obs import REGISTRY
    n = hvd.size()
    D, E, T, cf = 8, 16, 10, 1.25
    rng = np.random.RandomState(7)
    router = rng.randn(D, E).astype(np.float32)
    W = rng.randn(E, D, D).astype(np.float32) * 0.5
    toks = [rng.randn(T, D).astype(np.float32) for _ in range(n)]
    E_local = E // n
    params = [jnp.asarray(W[r * E_local:(r + 1) * E_local])
              for r in range(n)]
    fam = REGISTRY.get("hvd_moe_dropped_tokens_total")
    before = fam.labels(layer="t_hvd").value

    outs, aux, dropped = moe_layer_hvd(
        toks, router, lambda w, x: x @ w, params,
        capacity_factor=cf, layer="t_hvd")

    capacity = max(1, int(T * cf / E))
    oracle_drops = 0
    for r in range(n):
        logits = toks[r] @ router
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        idx = p.argmax(-1)
        gate = p[np.arange(T), idx]
        seen = {e: 0 for e in range(E)}
        for t in range(T):
            e = int(idx[t])
            if seen[e] < capacity:
                seen[e] += 1
                np.testing.assert_allclose(
                    np.asarray(outs[r][t]), gate[t] * (toks[r][t] @ W[e]),
                    rtol=1e-5, atol=1e-5)
            else:
                oracle_drops += 1
                np.testing.assert_array_equal(np.asarray(outs[r][t]), 0.0)
    assert dropped == oracle_drops and oracle_drops > 0
    assert fam.labels(layer="t_hvd").value - before == oracle_drops
    assert np.isfinite(aux) and aux > 0


def test_pipeline_1f1b_matches_autodiff_oracle():
    """1F1B schedule (pipeline_train_local): loss and every gradient must
    equal plain autodiff through the sequential stage composition."""
    from jax import shard_map
    from horovod_tpu.parallel.pipeline import pipeline_train_local

    n_stage, M, mb, d = 8, 8, 2, 4
    rng = np.random.RandomState(7)
    Ws = (rng.randn(n_stage, d, d) * 0.3).astype(np.float32)
    bias = rng.randn(d).astype(np.float32)
    mbs = rng.randn(M, mb, d).astype(np.float32)
    tgts = rng.randn(M, mb, d).astype(np.float32)
    mesh = Mesh(np.array(jax.devices()), ("pp",))

    def stage_fn(W, x):
        return jnp.tanh(x @ W), jnp.float32(0.0)

    def loss_head(hp, y, m):
        t = jnp.asarray(tgts)[m]
        return jnp.mean((y + hp - t) ** 2)

    def local(Wloc, hp, mb_in):
        W1 = Wloc[0]  # leading pp dim stripped to this stage's weight
        loss, aux, dmbs, dW, dhp = pipeline_train_local(
            stage_fn, W1, mb_in, loss_head, hp, axis_name="pp")
        return loss, dmbs, dW[None], dhp

    fn = jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P("pp"), P(), P()),
        out_specs=(P(), P(), P("pp"), P()),
        check_vma=False))
    loss, dmbs, dW, dhp = fn(jnp.asarray(Ws), jnp.asarray(bias),
                             jnp.asarray(mbs))

    # Oracle: plain autodiff through the sequential composition.
    def oracle(Ws_, hp, mbs_):
        def one(m):
            x = mbs_[m]
            for s in range(n_stage):
                x = jnp.tanh(x @ Ws_[s])
            return jnp.mean((x + hp - jnp.asarray(tgts)[m]) ** 2)
        return sum(one(m) for m in range(M)) / M

    oloss, (odW, odhp, odmbs) = jax.value_and_grad(oracle, argnums=(0, 1, 2))(
        jnp.asarray(Ws), jnp.asarray(bias), jnp.asarray(mbs))
    np.testing.assert_allclose(float(loss), float(oloss), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(dW), np.asarray(odW),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dhp), np.asarray(odhp),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dmbs), np.asarray(odmbs),
                               rtol=1e-4, atol=1e-6)
