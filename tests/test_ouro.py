"""A looped decoder with sandwich norms (the Ouro family) through the
server, against the plain reference; on the CPU in float32.

A tiny model (hidden 64, 2 layers, 3 passes, 4 heads of 16, vocabulary
256, random gains so every norm matters) goes through ``ServingEngine``:
prefill, then paged decode through a pool of ``loops x n_layers`` cache
layers, small enough that one request is preempted and prefilled again.
The logits the engine's programs made at every served position are held
to ``chipbench.reference_ouro``'s full forward over prompt plus served
tokens, which has no cache and imports nothing from the program.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import reference_ouro as ref                    # noqa: E402
from horovod_tpu.models import layers, llama                   # noqa: E402
from horovod_tpu.ops import flash_attention as FA              # noqa: E402
from horovod_tpu.parallel import MeshConfig, build_mesh        # noqa: E402
from horovod_tpu.serving.disagg import migration               # noqa: E402
from horovod_tpu.serving.engine import EngineConfig, ServingEngine  # noqa: E402

LOOPS, LAYERS = 3, 2
CFG = llama.LlamaConfig.tiny(n_kv_heads=4, loops=LOOPS, sandwich_norm=True,
                             rms_eps=1e-6)
DIMS = dict(d_model=64, n_layers=LAYERS, n_heads=4, n_kv_heads=4,
            head_dim=16, d_ff=128, vocab_size=256, rope_theta=10000.0,
            rms_norm_eps=1e-6, loops=LOOPS, exit_threshold=1.0)
ENGINE = EngineConfig(block_size=4, num_blocks=12, max_active=3,
                      use_flash="never")
PROMPTS = [(np.arange(5, 5 + n) * 7) % 256 for n in (7, 12, 9)]
NEW = 10
NORMS = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")


@pytest.fixture(scope="module")
def params():
    p = llama.init_params(CFG, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    for name in NORMS:
        key, k = jax.random.split(key)
        p["layers"][name] = 1 + 0.3 * jax.random.normal(
            k, p["layers"][name].shape)
    p["final_norm"] = 1 + 0.3 * jax.random.normal(key, (CFG.d_model,))
    return p


def _reference_logits(params, tokens):
    """``reference_ouro.forward`` on the program's weights: the stacked
    leaves cut into a list of layers, an exit gate made here (the served
    program holds none)."""
    stack = [jax.tree.map(lambda a: a[i], params["layers"])
             for i in range(LAYERS)]
    outer = dict(
        embed=params["embed"], final_norm=params["final_norm"],
        lm_head=params["lm_head"], exit_gate_b=jnp.zeros(()),
        exit_gate_w=jax.random.normal(jax.random.PRNGKey(2), (64,)) / 8)
    return ref.forward(stack, outer, tokens, DIMS)


def _serve(params, cfg=CFG, engine_cfg=ENGINE):
    """The prompts through a ``ServingEngine`` whose prefill and decode
    programs hand back their logits: ``(requests, served)`` with
    ``served[req_id][position]`` the logits the engine picked the token
    after ``position`` from."""
    eng = ServingEngine(params, cfg, engine_cfg=engine_cfg)
    served: dict = {}
    prefill = jax.jit(lambda p, tok, last: llama.prefill_step(
        p, tok, cfg, last_pos=last))
    decode = jax.jit(lambda p, pools, tok, pos, tables:
                     llama.decode_step_paged(p, tok, pos, pools, tables,
                                             cfg))

    def spy_prefill(p, tokens, last_pos):
        logits, kept, stats = prefill(p, tokens, last_pos)
        req = next(r for r in eng._slots if r is not None and np.array_equal(
            r.prefill_tokens, np.asarray(tokens)[0, :int(last_pos[0]) + 1]))
        served.setdefault(req.req_id, {})[int(last_pos[0])] = \
            np.asarray(logits[0])
        return (jnp.argmax(logits, -1).astype(jnp.int32), stats), kept

    def spy_decode(p, pools, tok, pos, tables):
        logits, pools, stats = decode(p, pools, tok, pos, tables)
        for i, r in enumerate(eng._slots):
            if r is not None:
                served[r.req_id][int(pos[i])] = np.asarray(logits[i])
        return (jnp.argmax(logits, -1).astype(jnp.int32), stats), pools

    eng._prefill, eng._decode = spy_prefill, spy_decode
    reqs = [eng.submit(p, NEW) for p in PROMPTS]
    eng.run()
    return eng, reqs, served


def _worst_gap(params, reqs, served):
    """Largest |served - reference| logit over every served position."""
    worst = 0.0
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(r.generated, np.int32)])
        want, lams = _reference_logits(params, seq)
        P = len(r.prompt)
        for j in range(len(r.generated)):
            got = served[r.req_id][P - 1 + j]
            worst = max(worst, float(np.abs(got - want[P - 1 + j]).max()))
        # the running exit sum stays under 1 before the last pass
        assert float(ref.exit_distribution(lams, 1.0)["before_last"].max()) \
            < 1.0
    return worst


def test_served_logits_match_the_reference_and_generate(params):
    """(a) prefill, paged decode through the 6-layer cache, one request
    preempted and prefilled again: the reference's logits at every served
    position, and ``generate``'s tokens."""
    eng, reqs, served = _serve(params)
    assert eng.pools[0].shape == (LOOPS * LAYERS, 12, 4, 4, 16)
    assert sum(r.preemptions for r in reqs) >= 1
    assert all(len(r.generated) == NEW for r in reqs)
    assert _worst_gap(params, reqs, served) < 1e-4
    for r in reqs:
        out = llama.generate(params, jnp.asarray(r.prompt)[None], CFG,
                             max_new_tokens=NEW)
        assert list(np.asarray(out[0, len(r.prompt):])) == r.generated
    logits, _ = llama.forward(params, jnp.asarray(PROMPTS[0])[None], CFG)
    want, _ = _reference_logits(params, PROMPTS[0])
    np.testing.assert_allclose(np.asarray(logits[0]), want, atol=1e-4)


def _shared_cache(monkeypatch):
    real = llama.paged_attend

    def one_cache(cfg, *a, **k):
        attend = real(cfg, *a, **k)
        return lambda q, k1, v1, li, state: attend(
            q, k1, v1, li % cfg.n_layers, state)
    monkeypatch.setattr(llama, "paged_attend", one_cache)


def _norm_after_last_pass_only(monkeypatch):
    real = llama.looped
    monkeypatch.setattr(
        llama, "looped", lambda layer, carry, stacked, loops, renorm,
        xs=None, unroll=1: real(layer, carry, stacked, loops, lambda h: h,
                                xs, unroll))


FAULTS = {
    "one_cache_shared_by_all_passes": _shared_cache,
    "final_norm_after_the_last_pass_only": _norm_after_last_pass_only,
    "post_norms_left_out": None,
    "one_pass_too_few": None,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails_the_comparison(params, fault, monkeypatch):
    """(b) each fault, planted in the program, is over the limit of (a)."""
    served_params, cfg = params, CFG
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    elif fault == "post_norms_left_out":
        served_params = dict(params, layers={
            k: v for k, v in params["layers"].items()
            if not k.endswith("post_norm")})
    else:
        cfg = llama.LlamaConfig.tiny(n_kv_heads=4, loops=LOOPS - 1,
                                     sandwich_norm=True, rms_eps=1e-6)
    _, reqs, served = _serve(served_params, cfg)
    assert _worst_gap(params, reqs, served) > 1e-2


def _plain_serve_layers(params, tok, positions, cfg, mesh, attend,
                        state=None):
    """The skeleton as it stood before the loop: one scan over (layers,
    layer index), eps defaulted."""
    h = layers.embed_lookup(params["embed"], tok, cfg.dtype)
    tables = layers.rope_tables(positions, cfg.rope_theta, cfg.head_dim)

    def layer(carry, xs):
        h, state = carry
        lp, li = xs
        h, (state, out), _ = layers.block(
            h, lp, lambda x, lp: layers.gqa_mixer(
                x, lp, tables,
                lambda q, k, v: attend(q, k, v, li=li, state=state)),
            layers.dense_mlp)
        return (h, state), out

    (h, state), outs = lax.scan(
        layer, (h, state), (params["layers"], jnp.arange(cfg.n_layers)))
    return h, state, outs, {}


def test_one_pass_without_post_norms_is_the_plain_decoder_bit_for_bit(
        monkeypatch):
    """(c) ``loops == 1`` and no post-norm leaves: ``prefill_step`` and
    ``decode_step_paged`` give what the skeleton without the loop gives."""
    cfg = llama.LlamaConfig.tiny()
    p = llama.init_params(cfg, jax.random.PRNGKey(3))
    tok = jnp.asarray(PROMPTS[1])[None]
    pool = jax.random.normal(jax.random.PRNGKey(4), (2, 6, 4, 2, 16))
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0]], jnp.int32)
    args = (jnp.asarray([9, 77], jnp.int32), jnp.asarray([10, 5], jnp.int32),
            (pool, pool + 1), tables, cfg)

    new = (llama.prefill_step(p, tok, cfg), llama.decode_step_paged(p, *args))
    monkeypatch.setattr(llama, "_serve_layers", _plain_serve_layers)
    old = (llama.prefill_step(p, tok, cfg), llama.decode_step_paged(p, *args))
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_paged_kernel_reads_a_cache_layer_past_the_weights_depth(params):
    """(d) the kernel, interpreted, with KV == H and the layer index past
    ``n_layers``, equals the gather path; so does the whole decode tick."""
    L, NB, BS, H, Dh = LOOPS * LAYERS, 7, 4, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    kp = jax.random.normal(ks[0], (L, NB, BS, H, Dh))
    vp = jax.random.normal(ks[1], (L, NB, BS, H, Dh))
    q = jax.random.normal(ks[2], (2, H, Dh))
    tables = jnp.asarray([[3, 1, 6], [2, 5, 0]], jnp.int32)
    lengths = jnp.asarray([11, 6], jnp.int32)
    li = L - 1
    got = FA.paged_attention(q, kp, vp, li, tables, lengths, interpret=True)
    mask = (jnp.arange(3 * BS)[None, :] < lengths[:, None])[:, None, :]
    want = layers.cached_attend(
        q[:, None], layers.gather_blocks(kp[li], tables),
        layers.gather_blocks(vp[li], tables), mask, Dh ** -0.5)[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)

    tok, pos = jnp.asarray([9, 77], jnp.int32), lengths - 1
    outs = [llama.decode_step_paged(params, tok, pos, (kp, vp), tables, CFG,
                                    use_flash=flash, interpret=flash)
            for flash in (False, True)]
    for a, b in zip(*map(jax.tree.leaves, outs)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_cache_depth_follows_loops_times_layers(params):
    """(e) ``bytes_per_block``, the pool and a migrated request's payload
    are ``loops x n_layers`` deep, and the payload resumes elsewhere."""
    roomy = EngineConfig(block_size=4, num_blocks=32, max_active=3,
                         use_flash="never")
    eng = ServingEngine(params, CFG, engine_cfg=roomy)
    depth = LOOPS * LAYERS
    assert CFG.cache_layers == eng.cache.n_layers == depth
    assert [p.shape[0] for p in eng.pools] == [depth, depth]
    assert eng.cache.bytes_per_block(4) == 2 * depth * 4 * 4 * 16 * 4
    req = eng.submit(PROMPTS[1], NEW)
    eng.step()
    manifest, k_bytes, v_bytes = migration.export_request(eng, req)
    nb = eng.cache.blocks_for(req.context_len)
    assert manifest["n_layers"] == depth
    assert len(k_bytes) == len(v_bytes) == depth * nb * 4 * 4 * 16 * 4
    eng.run()

    other = ServingEngine(params, CFG, engine_cfg=roomy)
    moved = other.import_migrated(manifest, k_bytes, v_bytes)
    other.run()
    assert moved.generated == req.generated

    plain = ServingEngine(params, llama.LlamaConfig.tiny(n_kv_heads=4),
                          engine_cfg=roomy)
    with pytest.raises(ValueError, match="n_layers"):
        plain.import_migrated(manifest, k_bytes, v_bytes)


def test_exit_rule_from_the_gates_and_the_threshold():
    """(f) the exit distribution, the pass a token leaves at, and the
    running sum under 1 before the last pass on random gates."""
    lams = jnp.asarray([[0.5, 0.1], [0.5, 0.2], [0.9, 0.9], [0.3, 0.3]])
    out = ref.exit_distribution(lams, 0.7)
    np.testing.assert_allclose(
        np.asarray(out["p"]),
        [[0.5, 0.1], [0.25, 0.18], [0.225, 0.648], [0.025, 0.072]],
        atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["p"]).sum(0), 1.0, atol=1e-6)
    # 0.5, 0.75 -> leaves at the second pass; 0.1, 0.28, 0.928 -> the third
    assert list(np.asarray(out["exit_pass"])) == [1, 2]
    assert list(np.asarray(ref.exit_distribution(lams, 1.0)["exit_pass"])) \
        == [3, 3]
    # gates as seeded weights give them: w . z of unit variance
    gates = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(6),
                                             (4, 10_000)))
    out = ref.exit_distribution(gates, 1.0)
    assert float(out["before_last"].max()) < 1.0
    assert int(out["exit_pass"].min()) == 3


def test_make_train_step_refuses_a_looped_stack():
    """(g) no objective for the passes' exits is here: refused by name."""
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    with pytest.raises(NotImplementedError, match="exit distribution"):
        llama.make_train_step(CFG, mesh, optax.adamw(1e-3))
    llama.make_train_step(llama.LlamaConfig.tiny(), mesh, optax.adamw(1e-3))
