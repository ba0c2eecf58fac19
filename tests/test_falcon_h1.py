"""Falcon-H1 (``models/falcon_h1.py``): attention and a Mamba-2 mixer side
by side in every layer, served with a state slot a row beside the paged
K/V pools.

On the CPU at a small size, in float32: (1) the three forms of the scan
(``ops/ssd.py``) against the recurrence, chunk edges and rows without a
stream included, and the padding's ``dt = 0``; (2) ``forward`` against
the benchmark's float32 reference (``chipbench/reference_falcon_h1.py``,
on the weights it makes from a seed); (3) the engine against the
reference through prefill and ticks, a row freed by a finished request
taken by a new one, a preempted request prefilled again; (4) the
refusals by name; (5) the other served models' programs as they were
lowered before the state slots; (6) the region, spans and counters.
"""

from __future__ import annotations

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_falcon_h1 as ref
from chipbench import weights_falcon_h1 as wts
from horovod_tpu.models import falcon_h1 as F
from horovod_tpu.models import glm_moe_lite as G
from horovod_tpu.models import kimi_linear as KL
from horovod_tpu.models import xing4 as X
from horovod_tpu.models import layers, llama, steps
from horovod_tpu.obs import REGISTRY
from horovod_tpu.ops import ssd
from horovod_tpu.serving import EngineConfig, ServingEngine

#: a configuration file's published keys at a small size
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 8, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 16, "mamba_d_ssm": 64, "mamba_d_state": 8,
    "mamba_n_groups": 2, "mamba_n_heads": 4, "mamba_norm_before_gate": False,
    "mamba_proj_bias": False, "mamba_rms_norm": True, "mamba_use_mlp": True,
    "mlp_bias": False, "mlp_multipliers": [0.1767766952966369,
                                           0.011160714285714284],
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000.0,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 256}
CFG = F.FalconH1Config.from_published(PUBLISHED, dtype=jnp.float32)
DIMS = wts.dims_of(PUBLISHED)
SEED = 2 ** 31 + 40
ENGINE = EngineConfig(block_size=4, num_blocks=40, max_active=2,
                      use_flash="interpret", prefill_buckets=(8, 16, 32))
PROMPTS = [np.random.RandomState(i).randint(0, 256, n).astype(np.int32)
           for i, n in enumerate((5, 11, 3))]
NEW = 6


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: wts.stacked(k, DIMS, jnp.float32))(
        wts.root_key(SEED))


def _scan_inputs(b, T, H=4, P=16, G=2, N=8, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (b, T, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (b, T, H))),
            -jnp.exp(jax.random.uniform(k[2], (H,), maxval=2.0)),
            jax.random.normal(k[3], (b, T, G, N)),
            jax.random.normal(k[4], (b, T, G, N)))


# -- (1) the scan ------------------------------------------------------------

@pytest.mark.parametrize("T,chunk", [(16, 8), (17, 8), (15, 8), (5, 8),
                                     (1, 4), (40, 16)])
def test_chunked_scan_matches_the_recurrence(T, chunk):
    x, dt, A, B, C = _scan_inputs(2, T)
    S0 = jax.random.normal(jax.random.PRNGKey(9), (2, 4, 8, 16))
    y0, s0 = ssd.recurrent_ssd(x, dt, A, B, C, S0)
    y1, s1 = ssd.ssd_chunked(x, dt, A, B, C, chunk, S0)
    np.testing.assert_allclose(y1, y0, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s1, s0, rtol=1e-4, atol=1e-4)


def test_zero_dt_leaves_the_state_where_the_prompt_ended():
    """A bucket's padding takes ``dt = 0``: the state after it is the
    state at the last real token, whatever the padding holds."""
    x, dt, A, B, C = _scan_inputs(1, 24)
    _, exact = ssd.ssd_chunked(x[:, :13], dt[:, :13], A, B[:, :13],
                               C[:, :13], 8)
    padded = jnp.where(jnp.arange(24)[None, :, None] < 13, dt, 0.0)
    _, held = ssd.ssd_chunked(x, padded, A, B, C, 8)
    np.testing.assert_allclose(held, exact, rtol=1e-5, atol=1e-5)


def _tick_inputs(R, H=4, P=128, G=2, N=16, L=3, seed=1):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (L, R, H, N, P)).astype(jnp.bfloat16),
            jax.random.normal(k[1], (R, H, P)),
            jax.random.uniform(k[2], (R, H)),
            jax.random.normal(k[3], (R, G, N)),
            jax.random.normal(k[4], (R, G, N)))


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0], [0, 0, 1, 0, 0],
                                  [0, 0, 0, 0, 0], [1, 1, 1, 1, 1]])
def test_decode_kernel_is_the_step_and_skips_rows_without_a_stream(live):
    state, xdt, decay, B, C = _tick_inputs(5)
    live = jnp.asarray(live)
    y, new = ssd.ssd_decode(state, 1, live, xdt, decay, B, C, interpret=True)
    y_ref, s_ref = ssd.ssd_step(state[1], xdt, decay, B, C)
    on = np.asarray(live, bool)
    np.testing.assert_allclose(y[on], y_ref[on], rtol=1e-5, atol=1e-5)
    assert not np.asarray(y[~on]).any()
    np.testing.assert_allclose(
        np.asarray(new[1][on], np.float32),
        np.asarray(s_ref[on].astype(jnp.bfloat16), np.float32),
        rtol=1e-2, atol=1e-2)
    assert (new[1][~on] == state[1][~on]).all()        # no copy, no write
    assert (new[0] == state[0]).all() and (new[2] == state[2]).all()


def test_decode_kernel_waits_for_exactly_what_it_copies():
    """JAX's TPU interpreter counts the copies' bytes: a wait that does
    not take what a start sent would hang the chip."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as ipc
    from jax.experimental.pallas import tpu as pltpu
    state, xdt, decay, B, C = _tick_inputs(6)
    live = jnp.asarray([0, 1, 0, 1, 1, 0])
    y, new = ssd.ssd_decode(state, 2, live, xdt, decay, B, C,
                            interpret=pltpu.InterpretParams(
                                dma_execution_mode="on_wait",
                                detect_races=True))
    y_ref, _ = ssd.ssd_step(state[2], xdt, decay, B, C)
    on = np.asarray(live, bool)
    np.testing.assert_allclose(y[on], y_ref[on], rtol=1e-5, atol=1e-5)
    assert not ipc.races.races_found


def test_unit_heads_keep_an_even_count_a_row():
    assert ssd.unit_heads(32, 2) == 8                    # the cell's
    assert ssd.unit_heads(4, 2) == 2 and ssd.unit_heads(2, 1) == 1
    for H, G in ((32, 2), (4, 2), (8, 8), (6, 3)):
        hu = ssd.unit_heads(H, G)
        assert (H // G) % hu == 0 and (H // hu) % 2 == 0


def test_causal_conv_tail_continues_the_sequence():
    """The convolution of a sequence cut in two, the second half handed
    the first's tail, is the convolution of the whole; a padded
    sequence's tail ends at its last real row."""
    k = jax.random.split(jax.random.PRNGKey(4), 3)
    y = jax.random.normal(k[0], (2, 11, 6))
    w, b = jax.random.normal(k[1], (4, 6)), jax.random.normal(k[2], (6,))
    whole = layers.causal_conv(y, w, b)
    tail = layers.conv_tail(y[:, :7], 4)
    np.testing.assert_allclose(
        layers.causal_conv(y[:, 7:], w, b, tail=tail), whole[:, 7:],
        rtol=1e-6, atol=1e-6)
    one = whole[:, 7:8]                                  # a tick's form
    np.testing.assert_allclose(layers.causal_conv(y[:, 7:8], w, b, tail),
                               one, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        layers.conv_tail(y, 4, last=jnp.asarray([6, 2])),
        jnp.stack([y[0, 4:7], jnp.concatenate([jnp.zeros((0, 6)),
                                               y[1, 0:3]])]))
    np.testing.assert_array_equal(
        layers.conv_tail(y[:, :2], 4)[:, 0], jnp.zeros((2, 6)))


# -- (2) the forward against the reference ---------------------------------------

def test_config_reads_the_published_keys():
    assert (CFG.d_ssm, CFG.conv_dim, CFG.in_dim) == (64, 96, 164)
    assert F.FalconH1Config().in_dim == 9248            # the published
    assert F.FalconH1Config().conv_dim == 5120
    bad = dict(PUBLISHED, mamba_norm_before_gate=True)
    with pytest.raises(NotImplementedError, match="mamba_norm_before_gate"):
        F.FalconH1Config.from_published(bad)


def test_program_leaves_are_the_benchmarks():
    mine = jax.eval_shape(lambda: F.init_params(CFG, jax.random.PRNGKey(0)))
    made = jax.eval_shape(lambda: wts.stacked(
        jax.random.PRNGKey(0), DIMS, jnp.float32))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), mine) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), made)
    assert F.leaf_shapes(CFG) == wts.layer_shapes(DIMS)


def test_forward_matches_the_reference(params):
    toks = np.random.RandomState(7).randint(0, 256, 21).astype(np.int32)
    want = ref.forward(wts.root_key(SEED), toks, DIMS, jnp.float32)
    got = F.forward(params, jnp.asarray(toks)[None], CFG)[0]
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * float(jnp.abs(want).max()))


def test_bucketed_prefill_leaves_the_exact_prefills_row(params):
    """The state and the convolution's tail a 13-token prompt leaves are
    the same from its 16-token bucket as from its exact length, and so
    is the next token's logit row."""
    toks = np.random.RandomState(8).randint(0, 256, 13).astype(np.int32)
    exact = steps.prefill_step(params, jnp.asarray(toks)[None], CFG)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :13] = toks
    bucket = steps.prefill_step(params, jnp.asarray(padded), CFG,
                                last_pos=jnp.asarray([12]))
    np.testing.assert_allclose(bucket[0], exact[0], rtol=1e-5, atol=1e-6)
    assert len(exact[1]) == 4                     # K, V, state, tail
    for a, b in zip(bucket[1][2:], exact[1][2:]):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# -- (3) the engine against the reference -----------------------------------------

def _serve(params, engine_cfg=ENGINE, prompts=PROMPTS, new=NEW,
           ticks=None):
    """The prompts through a ``ServingEngine`` whose prefill and decode
    programs hand back their logits: ``(engine, requests, served)`` with
    ``served[req_id][position]`` the logits the token after ``position``
    was picked from, the head's multiplier in; ``ticks`` counts the
    decode dispatches."""
    eng = ServingEngine(params, CFG, engine_cfg=engine_cfg)
    served: dict = {}
    prefill = jax.jit(lambda p, tok, last: steps.prefill_step(
        p, tok, CFG, last_pos=last))
    decode = jax.jit(lambda p, pools, tok, pos, tables, slots:
                     steps.decode_step_paged(
                         p, tok, pos, pools, tables, CFG,
                         use_flash=eng._use_flash, interpret=eng._interpret,
                         slots=slots))
    scale = CFG.lm_head_multiplier

    def spy_prefill(p, tokens, last_pos):
        logits, kept, stats = prefill(p, tokens, last_pos)
        req = next(r for r in eng._slots if r is not None and np.array_equal(
            r.prefill_tokens, np.asarray(tokens)[0, :int(last_pos[0]) + 1]))
        served.setdefault(req.req_id, {})[int(last_pos[0])] = \
            np.asarray(logits[0]) * scale
        return (jnp.argmax(logits, -1).astype(jnp.int32), stats), kept

    def spy_decode(p, pools, tok, pos, tables, slots):
        if ticks is not None:
            ticks.append(1)
        logits, pools, slots, stats = decode(p, pools, tok, pos, tables,
                                             slots)
        for i, r in enumerate(eng._slots):
            if r is not None:
                served[r.req_id][int(pos[i])] = np.asarray(logits[i]) * scale
        return (jnp.argmax(logits, -1).astype(jnp.int32), stats), pools, \
            slots

    eng._prefill, eng._decode = spy_prefill, spy_decode
    reqs = [eng.submit(p, new) for p in prompts]
    eng.run()
    return eng, reqs, served


def _check_against_reference(reqs, served):
    for r in reqs:
        assert len(r.generated) == len(served[r.req_id])
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1],
                                                   np.int32)])
        want = np.asarray(ref.forward(wts.root_key(SEED), seq, DIMS,
                                      jnp.float32))
        P = len(r.prompt)
        for pos, got in served[r.req_id].items():
            np.testing.assert_allclose(got, want[pos], rtol=1e-4,
                                       atol=1e-4 * np.abs(want).max())
        # greedy: each served token is the reference's first choice
        assert r.generated == [int(want[P - 1 + j].argmax())
                               for j in range(len(r.generated))]


@pytest.mark.parametrize("use_flash", ["interpret", "never"])
def test_served_logits_match_the_reference(params, use_flash):
    """Three prompts over two rows: the third is admitted into the row
    the first one freed, its prefill writing the whole row; every tick
    through ``hvd_ssd_decode`` (interpreted) or the XLA step."""
    eng, reqs, served = _serve(params, dataclasses.replace(
        ENGINE, use_flash=use_flash))
    assert eng.attention_path == {"interpret": "pallas-interpret",
                                  "never": "gather"}[use_flash]
    assert [s.shape for s in eng.slots] == [(2, 2, 4, 8, 16), (2, 2, 3, 96)]
    assert max(r.req_id for r in reqs) == 2
    _check_against_reference(reqs, served)


@pytest.mark.parametrize("max_active", [1, 3])
def test_the_tick_runs_ahead_over_the_state_slots(params, max_active):
    """One tick in flight over the state slots: a request whose last
    token is in flight gives its row up, and the next request's prefill
    writes that row's state while the tick still runs; every tick but the
    first is dispatched ahead and the served logits are the
    reference's."""
    ticks: list = []
    before = REGISTRY.get("hvd_serving_decode_ahead_total").value
    eng, reqs, served = _serve(params, dataclasses.replace(
        ENGINE, max_active=max_active), ticks=ticks)
    assert REGISTRY.get("hvd_serving_decode_ahead_total").value - before \
        == len(ticks) - 1
    assert all(len(r.generated) == NEW for r in reqs)
    _check_against_reference(reqs, served)
    assert not eng.has_work()
    eng.pager.check_invariants()


def test_a_preempted_request_is_prefilled_again_and_agrees(params):
    """A pool too small for both rows' growth: ``grow`` preempts the
    youngest, which gives its row up and comes back as prompt plus what
    it had generated, prefilled into whichever row is free."""
    prompts = [np.random.RandomState(20 + i).randint(0, 256, 9).astype(
        np.int32) for i in range(2)]
    eng, reqs, served = _serve(params, dataclasses.replace(
        ENGINE, num_blocks=7), prompts=prompts, new=8)
    assert any(r.preemptions for r in reqs)
    _check_against_reference(reqs, served)


# -- (4) what the state slot cannot give, refused by name -------------------------

def _engine(params, **kw):
    return ServingEngine(params, CFG, engine_cfg=dataclasses.replace(
        ENGINE, **kw))


REFUSALS = {
    "prefix_cache": lambda p: _engine(p, prefix_cache=True),
    "spec_k": lambda p: ServingEngine(
        p, CFG, engine_cfg=dataclasses.replace(ENGINE, spec_k=2),
        draft_params=p, draft_cfg=CFG),
    "extend": lambda p: steps.extend_step_paged(
        p, jnp.zeros((1, 4), jnp.int32), jnp.zeros((1, 4), jnp.int32),
        jnp.ones((1, 4), bool), _engine(p).pools,
        jnp.zeros((1, 4), jnp.int32), CFG),
    "migrat": lambda p: _engine(p).submit(PROMPTS[0], 2,
                                          migrate_cb=lambda *a: None),
    "mesh": lambda p: F.check_servable(
        CFG, jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("tp",))),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_what_the_state_slot_cannot_give_is_refused_by_name(params, what):
    if what == "mesh" and len(jax.devices()) < 2:
        pytest.skip("needs 2 (virtual) devices")
    with pytest.raises(NotImplementedError, match=what):
        REFUSALS[what](params)


def test_import_of_a_migrated_request_is_refused(params):
    with pytest.raises(NotImplementedError, match="migrat"):
        _engine(params).import_migrated({}, b"", b"")


# -- (5) the other served models' programs, as lowered before the slots ------------

#: sha256 (first 12 hex digits) of the lowered text of each serving
#: program of the served kinds at a tiny size: the three without state
#: slots as made from the tree before the slots (a model without them
#: compiles what it did), Falcon-H1's as made from the tree before the
#: multi-stream residual frame (a model in the plain frame compiles what
#: it did); Falcon-H1 refuses the extend path.  GLM's interpreted tick
#: runs its routed experts through the interpreted ``hvd_moe_decode``
#: since the kernel was written (its XLA tick keeps the tile loop);
#: Xing4.0's as made from the tree before the steps every model goes
#: through moved out of ``models/llama.py`` into ``models/steps.py``
UNCHANGED = {
    "llama": {"prefill": "674b5f14b9ee", "scatter": "4f94cbfa9d52",
              "decode": "94e8a4ab1f82", "extend": "fef456b62af2",
              "decode.interpret": "40dda0906a07"},
    "ouro": {"prefill": "cb64230a4f96", "scatter": "f43a1036a7cb",
             "decode": "f733154f4c41", "extend": "0968fee8cf3c",
             "decode.interpret": "0a4f1415a716"},
    "glm": {"prefill": "b3588f7e4cd9", "scatter": "a88aac7443a7",
            "decode": "900c50c42809", "extend": "4da655702676",
            "decode.interpret": "53517f214909"},
    "falcon": {"prefill": "2134afb5fd09", "scatter": "0cb73678c692",
               "decode": "69d3b3057a43", "decode.interpret": "89ee5e9def1f"},
    "xing4": {"prefill": "1cfd4abaea98", "scatter": "454bb854e887",
              "decode": "385b783afa47", "extend": "58dc68d94254",
              "decode.interpret": "b699af455faa"},
}
SERVED = {
    "llama": lambda: llama.LlamaConfig.tiny(n_kv_heads=4),
    "ouro": lambda: llama.LlamaConfig.tiny(n_kv_heads=4, loops=2,
                                           sandwich_norm=True),
    "glm": lambda: G.GlmMoeLiteConfig.tiny(),
    "falcon": lambda: CFG,
    "xing4": lambda: X.Xing4Config.tiny(),
}


@pytest.mark.parametrize("kind", sorted(SERVED))
def test_the_other_served_models_programs_are_unchanged(kind):
    cfg = SERVED[kind]()
    model = steps.model_of(cfg)
    assert (model.state_rows(cfg) == ()) == (kind != "falcon")
    p = model.init_params(cfg, jax.random.PRNGKey(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    got = {}
    for flash in ("never", "interpret"):
        eng = ServingEngine(p, cfg, engine_cfg=EngineConfig(
            block_size=4, num_blocks=16, max_active=2, use_flash=flash))
        assert len(eng.slots) == len(model.state_rows(cfg))
        kept = tuple(jax.ShapeDtypeStruct(
            (cfg.cache_layers, 1, 8) + r, cfg.dtype) for r in eng.cache.rows
        ) + tuple(jax.ShapeDtypeStruct((s.shape[0], 1) + s.shape[2:],
                                       s.dtype) for s in eng.slots)
        low = {"prefill": eng._prefill.lower(p, i32(1, 8), i32(1)),
               "scatter": eng._scatter.lower(eng.pools, kept, i32(2),
                                             eng.slots, i32()),
               "decode": eng._decode.lower(p, eng.pools, i32(2), i32(2),
                                           i32(2, 4), eng.slots)}
        if not eng.slots:
            low["extend"] = eng._extend.lower(
                p, eng.pools, i32(1, 4), i32(1, 4),
                jax.ShapeDtypeStruct((1, 4), bool), i32(1, 4))
        for name, lowered in low.items():
            if flash == "never" or name == "decode":
                key = name if flash == "never" else name + ".interpret"
                got[key] = hashlib.sha256(
                    lowered.as_text().encode()).hexdigest()[:12]
    assert got == UNCHANGED[kind]


def test_the_cells_pools_keep_their_one_scatter():
    """The block-at-a-time write is for rows under 16 bytes; the three
    accepted served cells' pools keep the scatter they compiled to."""
    from horovod_tpu.serving.engine import _scatters_in_place
    pool = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    assert _scatters_in_place(pool(16, 4096, 16, 8, 128))        # Mistral
    assert _scatters_in_place(pool(192, 336, 16, 16, 128))       # Ouro
    assert _scatters_in_place(pool(7, 18432, 32, 640))           # GLM
    assert not _scatters_in_place(pool(6, 13440, 16, 4, 128))    # Falcon-H1


def test_kimi_calls_the_shared_convolution():
    assert not hasattr(KL, "_short_conv") and KL.causal_conv is \
        layers.causal_conv


# -- (6) what the slots add to the tracing ----------------------------------------

def test_the_ssm_is_a_region_of_the_mixer(params):
    eng = _engine(params, use_flash="never")
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    for text in (eng._prefill.lower(params, i32(1, 8), i32(1)).as_text(
            debug_info=True),
                 eng._decode.lower(params, eng.pools, i32(2), i32(2),
                                   i32(2, 4), eng.slots).as_text(
            debug_info=True)):
        assert "hvd.block.mixer/hvd.ssm" in text


def test_spans_and_counters_of_the_slots(params, monkeypatch):
    """The prefill span says what it wrote, the tick's what it read and
    wrote; the gauge says what a stream's slot holds, the counter counts
    rows written."""
    from horovod_tpu.serving import engine as E
    seen = []
    real = E._span

    def span(name, **attrs):
        seen.append((name, attrs))
        return real(name, **attrs)
    monkeypatch.setattr(E, "_span", span)
    writes = REGISTRY.get("hvd_serving_state_slot_writes_total")
    before = writes.value
    eng, reqs, _ = _serve(params)
    row = 2 * (4 * 8 * 16 + 3 * 96) * 4        # two layers, float32
    assert eng._state_row_bytes == row
    assert REGISTRY.get("hvd_serving_state_bytes_per_stream").value == row
    prefills = [a for n, a in seen if n == "hvd.serve.prefill"]
    assert len(prefills) == 3 and all(a["state_bytes"] == row
                                      for a in prefills)
    assert any(n == "hvd.serve.decode" for n, _ in seen)
    assert writes.value - before == 3 + sum(
        len(r.generated) - 1 for r in reqs)


def test_tick_span_carries_state_rows_and_bytes(params, monkeypatch):
    eng = _engine(params)
    got = []

    class Tick:
        def set_metadata(self, **kw):
            got.append(kw)
    real = eng._count_table
    monkeypatch.setattr(eng, "_count_table", lambda tick, tables, **more:
                        real(Tick(), tables, **more))
    eng.submit(PROMPTS[0], 3)
    eng.submit(PROMPTS[1], 5)               # its last ticks alone
    eng.run()
    assert got and all(
        g["state_bytes"] == 2 * g["state_rows"] * eng._state_row_bytes
        and g["state_rows"] == g["rows"] for g in got)
    assert {g["state_rows"] for g in got} == {1, 2}
