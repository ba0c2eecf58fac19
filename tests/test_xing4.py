"""Xing4.0-29B-A4B (``models/xing4.py``): GLM-4.7-Flash's latent attention
and experts around a residual of four streams mixed by manifold-constrained
hyper-connections (``layers.hc_block``), YaRN on the rope columns.

On the CPU at a small size that keeps the four streams, two dense and two
expert layers, 8 experts and 2 a token: (1) the Sinkhorn rounds and the
YaRN tables against their formulas; (2) ``forward`` against the
benchmark's float32 reference (``chipbench/reference_xing4.py``, on the
weights it makes from a seed), in float32 and in bfloat16, and three
faults that the float32 comparison must catch; (3) the engine, prefill
then ticks through the interpreted latent kernel, against the reference
teacher-forced; (4) the refusals by name; (5) the region, the span
attribute and the gauge; (6) the cell's programs compiled for a v5e.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_xing4 as ref
from chipbench import weights_xing4 as wts
from horovod_tpu.models import falcon_h1 as F
from horovod_tpu.models import glm_moe_lite as G
from horovod_tpu.models import layers, llama, steps
from horovod_tpu.models import xing4 as X
from horovod_tpu.obs import REGISTRY
from horovod_tpu.serving import EngineConfig, ServingEngine

#: a configuration file's published keys at a small size, the published
#: stream constants and the YaRN form kept
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "kv_lora_rank": 32, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "n_group": 1, "n_routed_experts": 8, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 2, "num_experts_per_tok": 2,
    "num_hidden_layers": 4, "num_key_value_heads": 2,
    "num_nextn_predict_layers": 0, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 256,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 16, "vocab_size": 256}
CFG = X.Xing4Config.from_published(PUBLISHED, dtype=jnp.float32)
DIMS = wts.dims_of(PUBLISHED)
SEED = 2 ** 31 + 42
ENGINE = EngineConfig(block_size=4, num_blocks=40, max_active=2,
                      use_flash="interpret", prefill_buckets=(8, 16, 32))
PROMPTS = [np.random.RandomState(i).randint(0, 256, n).astype(np.int32)
           for i, n in enumerate((5, 11, 3))]
NEW = 6
#: float32 against float32: the program and the reference differ by the
#: order of their sums (the maps' product, the flash blocks, the experts'
#: tiles), a few units of 1e-6 of the logits' largest (4.8e-6 at seed
#: SEED); every fault below moves the logits by 1e-2 or more
TOL = dict(rtol=1e-4, atol_of_max=1e-4)


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: wts.stacked(k, DIMS, jnp.float32))(
        wts.root_key(SEED))


def _close(got, want, rtol=TOL["rtol"], atol_of_max=TOL["atol_of_max"]):
    want = np.asarray(want)
    return np.allclose(np.asarray(got), want, rtol=rtol,
                       atol=atol_of_max * float(np.abs(want).max()))


def _tokens(n=40, seed=7):
    return np.random.RandomState(seed).randint(0, 256, n).astype(np.int32)


@pytest.fixture(scope="module")
def reference_logits():
    return np.asarray(ref.forward(wts.root_key(SEED), _tokens(), DIMS,
                                  jnp.float32)[0])


# -- (1) the Sinkhorn rounds and YaRN -----------------------------------------

def test_config_reads_the_published_keys():
    assert CFG.hc == layers.HyperConnections(4, 20, 1e-6, (-30.0, 30.0))
    assert CFG.yarn == layers.Yarn(4.0, 256, 32.0, 1.0, 1.0, 1.0)
    assert (CFG.first_k_dense, CFG.n_layers, CFG.routed_scale) == (2, 4, 2.0)
    assert X.Xing4Config().softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2, rel=1e-12)
    for key, bad in (("rope_scaling", None), ("scoring_func", "softmax"),
                     ("n_group", 8)):
        with pytest.raises(NotImplementedError, match=key):
            X.Xing4Config.from_published(dict(PUBLISHED, **{key: bad}))


@pytest.mark.parametrize("spread", [0.5, 1.0])
def test_sinkhorn_rows_and_columns_sum_to_one(spread):
    h = spread * jax.random.normal(jax.random.PRNGKey(3), (64, 4, 4))
    m = layers.sinkhorn(jnp.exp(h), 20, 1e-6)
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)
    np.testing.assert_allclose(m, ref.sinkhorn(jnp.exp(h), 20, 1e-6),
                               rtol=1e-5, atol=1e-7)


def test_the_clamp_keeps_the_maps_finite():
    """Logits of +-1e3 reach ``exp`` clamped to +-30: the maps stay
    finite, the residual map's columns still sum to 1."""
    hc = CFG.hc
    D = 8
    Xs = jnp.ones((2, 3, 4, D))
    phi = jnp.full((4 * D, 24), 1e3 / (4 * D)) * jnp.sign(
        jax.random.normal(jax.random.PRNGKey(0), (1, 24)))
    pre, post, res = layers.hc_maps(Xs, phi, jnp.zeros(24), jnp.ones(3), hc)
    assert np.isfinite(np.asarray(res)).all()
    assert np.abs(np.asarray(phi.sum(0))).min() > 900     # clamped: |h| 1e3
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-5)
    assert np.isfinite(np.asarray(pre)).all() and \
        np.isfinite(np.asarray(post)).all()


def _yarn_by_hand(pos, dim=64, base=1e4, factor=64.0, orig=4096):
    """The published formula, written out once more: correction dims 10
    and 23 for the cell, the ramp between them, positions in float32 as
    the tables take them."""
    lo = math.floor(dim * math.log(orig / (32 * 2 * math.pi))
                    / (2 * math.log(base)))
    hi = math.ceil(dim * math.log(orig / (1 * 2 * math.pi))
                   / (2 * math.log(base)))
    assert (lo, hi) == (10, 23)
    i = np.arange(dim // 2)
    inv = base ** (-2.0 * i / dim)
    ramp = np.clip((i - lo) / (hi - lo), 0, 1)
    freq = (inv / factor * ramp + inv * (1 - ramp)).astype(np.float32)
    ang = np.float32(pos) * freq                     # float32, as the chip
    return np.cos(ang.astype(np.float64)), np.sin(ang.astype(np.float64))


def test_yarn_tables_are_the_formula():
    cell = X.Xing4Config()
    pos = jnp.asarray([[0, 4095, 4096, 100_000]])
    cos, sin = layers.rope_tables(pos, cell.rope_theta, cell.qk_rope_dim,
                                  cell.yarn)
    for j, p in enumerate((0, 4095, 4096, 100_000)):
        c, s = _yarn_by_hand(p)
        np.testing.assert_allclose(cos[0, j], c, atol=2e-6)
        np.testing.assert_allclose(sin[0, j], s, atol=2e-6)
    # the first ten frequencies kept, those from 23 on divided by 64
    inv = 1e4 ** (-np.arange(32) / 32)
    got = cell.yarn.inv_freq(1e4, 64)
    np.testing.assert_allclose(got[:10], inv[:10], rtol=1e-12)
    np.testing.assert_allclose(got[23:], inv[23:] / 64, rtol=1e-12)
    np.testing.assert_allclose(got, ref.yarn_inv_freq(wts.dims_of(dict(
        PUBLISHED, qk_rope_head_dim=64,
        rope_scaling=dict(PUBLISHED["rope_scaling"], factor=64,
                          original_max_position_embeddings=4096)))),
        rtol=1e-12)
    assert cell.yarn.table_factor == 1.0


def test_both_attention_forms_take_the_yarn_scale(params, monkeypatch):
    """The prompt's flash dispatch and the tick's latent kernel are handed
    ``(nope + rope)^-0.5 mscale^2``."""
    seen = []
    real_attention, real_kernel = G.attention, G.FA.mla_paged_attention

    def attention(*a, scale=None, **kw):
        seen.append(("prefill", scale))
        return real_attention(*a, scale=scale, **kw)

    def kernel(*a, scale, **kw):
        seen.append(("decode", scale))
        return real_kernel(*a, scale=scale, **kw)
    monkeypatch.setattr(G, "attention", attention)
    monkeypatch.setattr(G.FA, "mla_paged_attention", kernel)
    eng = ServingEngine(params, CFG, engine_cfg=ENGINE)
    eng.submit(PROMPTS[0], 2)
    eng.run()
    want = CFG.qk_dim ** -0.5 * (0.1 * math.log(4) + 1) ** 2
    assert {k for k, _ in seen} == {"prefill", "decode"}
    assert all(s == pytest.approx(want, rel=1e-12) for _, s in seen), seen
    assert ref.softmax_scale(DIMS) == pytest.approx(want, rel=1e-12)


# -- (2) the forward against the reference ------------------------------------

def test_program_leaves_are_the_benchmarks():
    mine = jax.eval_shape(lambda: X.init_params(CFG, jax.random.PRNGKey(0)))
    made = jax.eval_shape(lambda: wts.stacked(
        jax.random.PRNGKey(0), DIMS, jnp.float32))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), mine) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), made)
    shape = lambda leaves: {k: s for k, (s, _) in leaves.items()}
    for kind, dense in (("dense", True), ("moe", False)):
        assert shape(X.leaf_shapes(CFG, kind)) == \
            shape(wts.layer_shapes(DIMS, dense))


def test_forward_matches_the_reference_in_float32(params, reference_logits):
    got, stats = X.forward(params, jnp.asarray(_tokens())[None], CFG,
                           picks=True)
    assert _close(got[0], reference_logits)
    _, experts, _ = ref.forward(wts.root_key(SEED), _tokens(), DIMS,
                                jnp.float32)
    np.testing.assert_array_equal(
        np.sort(np.asarray(stats["experts"]).reshape(experts.shape), -1),
        np.sort(np.asarray(experts), -1))


def test_forward_in_bfloat16_stays_near_the_reference():
    """bf16 weights, both sides; the program in bf16, the reference in
    float32.  Routing is discrete: a near tie picks another expert and
    that token's logits then differ by a step (up to 1.5 in logits of
    unit spread at this seed), so the bound is on means: the mean
    absolute difference under 0.1 (0.03 read) and the mean gap of the
    program's pick under the reference's best under 0.05 (0.014)."""
    key = wts.root_key(SEED)
    p = jax.jit(lambda k: wts.stacked(k, DIMS, jnp.bfloat16))(key)
    got = np.asarray(X.forward(p, jnp.asarray(_tokens())[None], dataclasses
                               .replace(CFG, dtype=jnp.bfloat16))[0][0])
    want = np.asarray(ref.forward(key, _tokens(), DIMS, jnp.bfloat16)[0])
    assert np.abs(got - want).mean() < 0.1
    gap = want.max(-1) - want[np.arange(len(want)), got.argmax(-1)]
    assert gap.mean() < 0.05


def _zero_phi(params):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: jnp.zeros_like(a) if "_phi" in jax.tree_util
        .keystr(path) else a, params)


FAULTS = {
    # the streams mixed by the identity: no residual map at all
    "h_res_identity": lambda p, monkeypatch: (
        monkeypatch.setattr(layers, "sinkhorn", lambda m, iters, eps:
                            jnp.broadcast_to(jnp.eye(m.shape[-1]), m.shape)),
        (p, CFG))[1],
    # maps that do not read the token: every map a constant of its bias
    "phi_zero": lambda p, monkeypatch: (_zero_phi(p), CFG),
    # the plain rope and softmax scale
    "yarn_off": lambda p, monkeypatch: (p, dataclasses.replace(CFG,
                                                                yarn=None)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_fault_fails_the_float32_comparison(params, reference_logits,
                                                 monkeypatch, fault):
    p, cfg = FAULTS[fault](params, monkeypatch)
    got = X.forward(p, jnp.asarray(_tokens())[None], cfg)[0][0]
    assert not _close(got, reference_logits)
    assert np.abs(np.asarray(got) - reference_logits).max() > 1e-2


# -- (3) the engine against the reference -------------------------------------

def _serve(params, engine_cfg=ENGINE, prompts=PROMPTS, new=NEW):
    """The prompts through a ``ServingEngine`` whose prefill and decode
    programs hand back their logits: ``(engine, requests, served)`` with
    ``served[req_id][position]`` the logits the token after ``position``
    was picked from."""
    eng = ServingEngine(params, CFG, engine_cfg=engine_cfg)
    served: dict = {}
    prefill = jax.jit(lambda p, tok, last: steps.prefill_step(
        p, tok, CFG, last_pos=last))
    decode = jax.jit(lambda p, pools, tok, pos, tables, slots:
                     steps.decode_step_paged(
                         p, tok, pos, pools, tables, CFG,
                         use_flash=eng._use_flash, interpret=eng._interpret,
                         slots=slots))

    def spy_prefill(p, tokens, last_pos):
        logits, kept, stats = prefill(p, tokens, last_pos)
        req = next(r for r in eng._slots if r is not None and np.array_equal(
            r.prefill_tokens, np.asarray(tokens)[0, :int(last_pos[0]) + 1]))
        served.setdefault(req.req_id, {})[int(last_pos[0])] = \
            np.asarray(logits[0])
        return (jnp.argmax(logits, -1).astype(jnp.int32), stats), kept

    def spy_decode(p, pools, tok, pos, tables, slots):
        logits, pools, slots, stats = decode(p, pools, tok, pos, tables,
                                             slots)
        for i, r in enumerate(eng._slots):
            if r is not None:
                served[r.req_id][int(pos[i])] = np.asarray(logits[i])
        return (jnp.argmax(logits, -1).astype(jnp.int32), stats), pools, \
            slots

    eng._prefill, eng._decode = spy_prefill, spy_decode
    reqs = [eng.submit(p, new) for p in prompts]
    eng.run()
    return eng, reqs, served


@pytest.mark.parametrize("use_flash", ["interpret", "never"])
def test_served_logits_match_the_reference(params, use_flash):
    """Three prompts over two rows, prefill then ticks (through
    ``hvd_mla_paged_decode`` interpreted, or the XLA gather), each served
    logit row against the reference's full forward over the prompt and
    the served tokens; every served token is the reference's first
    choice."""
    eng, reqs, served = _serve(params, dataclasses.replace(
        ENGINE, use_flash=use_flash))
    assert eng.attention_path == {"interpret": "pallas-mla-interpret",
                                  "never": "gather"}[use_flash]
    assert max(r.req_id for r in reqs) == 2
    for r in reqs:
        assert len(r.generated) == len(served[r.req_id]) == NEW
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1],
                                                   np.int32)])
        want = np.asarray(ref.forward(wts.root_key(SEED), seq, DIMS,
                                      jnp.float32)[0])
        for pos, got in served[r.req_id].items():
            assert _close(got, want[pos]), (r.req_id, pos)
        P = len(r.prompt)
        assert r.generated == [int(want[P - 1 + j].argmax())
                               for j in range(NEW)]


# -- (4) refusals by name -----------------------------------------------------

def test_training_is_refused_by_name():
    import optax
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("dp",))
    with pytest.raises(NotImplementedError, match="multi-token prediction"):
        steps.make_train_step(CFG, mesh, optax.sgd(0.1), model=X)


def test_a_mesh_is_refused_by_name(params):
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 (virtual) devices")
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    with pytest.raises(NotImplementedError, match="mesh"):
        ServingEngine(params, CFG, engine_cfg=ENGINE, mesh=mesh)


# -- (5) the region, the span attribute, the gauge ----------------------------

def test_the_streams_are_a_region_of_each_half_and_of_the_head(params):
    eng = ServingEngine(params, CFG, engine_cfg=dataclasses.replace(
        ENGINE, use_flash="never"))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    for text in (eng._prefill.lower(params, i32(1, 8), i32(1)).as_text(
            debug_info=True),
                 eng._decode.lower(params, eng.pools, i32(2), i32(2),
                                   i32(2, 4), eng.slots).as_text(
            debug_info=True)):
        for path in ("hvd.block.mixer/hvd.hc", "hvd.block.mlp/hvd.hc",
                     "hvd.head/hvd.hc"):
            assert path in text, path


def test_spans_carry_the_streams_and_the_gauge_says_how_many(params,
                                                             monkeypatch):
    from horovod_tpu.serving import engine as E
    seen = []
    real = E._span

    def span(name, **attrs):
        seen.append((name, attrs))
        return real(name, **attrs)
    monkeypatch.setattr(E, "_span", span)
    eng = ServingEngine(params, CFG, engine_cfg=ENGINE)
    assert REGISTRY.get("hvd_serving_residual_streams").value == 4
    eng.submit(PROMPTS[0], 3)
    eng.run()
    for name in ("hvd.serve.prefill", "hvd.serve.decode"):
        got = [a for n, a in seen if n == name]
        assert got and all(a["hc_streams"] == 4 for a in got), name
    # a model in the plain frame: one stream, no attribute
    glm = G.GlmMoeLiteConfig.tiny()
    ServingEngine(G.init_params(glm, jax.random.PRNGKey(0)), glm,
                  engine_cfg=ENGINE)
    assert REGISTRY.get("hvd_serving_residual_streams").value == 1


#: each served model, and the names with a default that it gives itself
HOOKED = {
    "llama": (llama, llama.LlamaConfig.tiny,
              {"check_trainable", "train_step_1f1b", "shard_rules"}),
    "glm": (G, G.GlmMoeLiteConfig.tiny, {"check_trainable", "serve_stats"}),
    "falcon": (F, F.FalconH1Config.tiny, {"check_trainable", "state_rows"}),
    "xing4": (X, X.Xing4Config.tiny, {"check_trainable", "serve_stats",
                                      "serve_frame", "serve_readout"}),
}


@pytest.mark.parametrize("name", sorted(steps.DEFAULTS))
@pytest.mark.parametrize("kind", sorted(HOOKED))
def test_the_hooks_default_to_the_plain_frame(kind, name):
    """The one lookup: a model's own name where it gives one, else the
    default, which the model does not write again."""
    module, tiny, own = HOOKED[kind]
    cfg = tiny()
    got = getattr(steps.model_of(cfg), name)
    if name in own:
        assert got is getattr(module, name)
    else:
        assert not hasattr(module, name)
        assert got is steps.DEFAULTS[name]
    if name == "serve_frame":
        frame = got(cfg)
        assert frame.func is layers.hc_block if kind == "xing4" \
            else frame is layers.block
    if name == "serve_readout" and kind != "xing4":
        h = jnp.ones((1, 2, cfg.d_model))
        assert got(None, h, cfg) is h


# -- (6) the cell's programs for a v5e ----------------------------------------

def _v5e_spec(monkeypatch):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this machine
        pytest.skip(f"no compile-only TPU topology: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        tuple(shape), dtype, sharding=chip)


def test_the_cells_programs_fit_a_v5e(monkeypatch):
    """At the cell's size (seven layers, the pool of 15,000 blocks of 32):
    the 8,704-token prefill's temporaries and results beside the weights
    and the pool stay under the accepted cells' 15.45 GB; the 64-row tick
    runs the latent kernel once a layer run at 32 heads and its routed
    experts through one ``hvd_moe_decode`` in the expert layers' scan,
    and updates the donated pool in place."""
    spec = _v5e_spec(monkeypatch)
    monkeypatch.setattr(layers, "_flash_backend", lambda: True)
    cfg = X.Xing4Config(n_layers=7)
    assert all(X.prefill_path(cfg, b) == "flash" for b in (1024, 8704))
    assert X.paged_kernel_ok(cfg, None, 32)
    p = jax.tree.map(lambda s: spec(s.shape, s.dtype), jax.eval_shape(
        lambda: X.init_params(cfg, jax.random.PRNGKey(0))))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(p)) == \
        4_920_924_095
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(p))
    pool = 7 * 15000 * 32 * cfg.cache_row * 2

    def prefill(p, tok, last):
        logits, kept, stats = steps.prefill_step(p, tok, cfg, last_pos=last)
        return (jnp.argmax(logits, -1).astype(jnp.int32), stats), kept
    mem = jax.jit(prefill).lower(
        p, spec((1, 8704), jnp.int32), spec((1,), jnp.int32)
    ).compile().memory_analysis()
    peak = weights + pool + mem.temp_size_in_bytes + mem.output_size_in_bytes
    assert peak <= 15.45e9, (peak, mem)
    i32 = lambda *shape: spec(shape, jnp.int32)
    step = jax.jit(
        lambda p, tok, pos, pool, tables: steps.decode_step_paged(
            p, tok, pos, (pool,), tables, cfg, use_flash=True),
        donate_argnums=(3,))
    compiled = step.lower(p, i32(64), i32(64), spec((7, 15000, 32, 640)),
                          i32(64, 512)).compile()
    names = [ln.split(" = ")[0].strip()
             for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sorted(n.split(".")[0] for n in names) == [
        "%hvd_mla_paged_decode", "%hvd_mla_paged_decode",
        "%hvd_moe_decode"], names
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool
    assert mem.temp_size_in_bytes < 64 << 20, mem
