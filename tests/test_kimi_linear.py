"""Kimi-Linear on the CPU at a tiny size: the chunked KDA forms against the
token-by-token recurrence, the MLA block, the router and the expert layer
that holds a share, and the whole model and its train step against the
benchmark's plain float32 reference (``chipbench/reference_kimi_linear``,
which imports nothing from the program)."""

from __future__ import annotations

import dataclasses
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import reference_kimi_linear as ref            # noqa: E402
from chipbench import run as runmod                           # noqa: E402
from chipbench import weights_kimi_linear as wts              # noqa: E402
from chipbench.drivers.train_kimi_linear import kimi_config   # noqa: E402
from horovod_tpu.models import kimi_linear as KL, layers, llama, steps  # noqa: E402
from horovod_tpu.ops import kda                               # noqa: E402
from horovod_tpu.parallel import moe                          # noqa: E402

TINY_DIR = os.path.join(ROOT, "chipbench", "testdata", "tiny_kimi")
TINY = runmod.load_json(os.path.join(TINY_DIR, "BENCHMARK.json"))
# The tiny preset: d 64, 2 KDA heads x 16, conv 4, 16 experts top-2 of
# width 32, 5 layers in the published pattern, the first dense; here with
# all 16 experts held (the benchmark's tiny cell holds experts 4 to 7).
CONFIG = dict(runmod.load_json(os.path.join(TINY_DIR, "configs",
                                            "tiny-kimi.json")),
              num_experts=16, first_expert_held=0)
DIMS = wts.dims_of(CONFIG)
KCFG = kimi_config(CONFIG, DIMS, jnp.float32)
KEY = wts.root_key(2**31 + 29)


def _close(a, b, tol):
    scale = float(jnp.max(jnp.abs(b))) + 1e-30
    assert float(jnp.max(jnp.abs(a - b))) <= tol * scale, \
        (float(jnp.max(jnp.abs(a - b))), scale)


# -- the KDA core -------------------------------------------------------------

def _kda_inputs(strong: bool, B=2, S=64, H=2, K=16, V=16):
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, S, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, K)))
    v = jax.random.normal(ks[2], (B, S, H, V))
    a = jax.random.uniform(ks[3], (H,), minval=1.0, maxval=16.0)
    pre = jax.random.normal(ks[4], (B, S, H, K))
    pre = 3 * pre + 4 if strong else pre - 4
    g = -a[None, None, :, None] * jax.nn.softplus(pre)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, S, H)))
    return q, k, v, g, beta


FORMS = {"jnp": lambda *a: kda.chunk_kda_jnp(*a, chunk=16),
         "kernel": lambda *a: kda.chunk_kda(*a, 16)}
# What the reverse kernel's layout has to get right, beside the base
# case: heads that take more than one grid step of its head block (so
# ``dbeta``'s blocks and the states' are found per block), a state's
# cotangent carried across eight chunks, and values wider than keys.
SHAPES = {"base": {}, "heads6": dict(H=6, B=1, K=128, V=128),
          "chunks8": dict(S=128, B=1), "wide-v": dict(V=32, B=1)}


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("decay", ["weak", "strong"])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_chunk_kda_matches_the_recurrence(form, decay, shape, monkeypatch):
    """Outputs and all five input gradients; the ``kernel`` form's are
    ``hvd_kda_bwd``'s.  Strong: a chunk's decays sum to under -200, where
    exp(-G) overflows float32 (at 88)."""
    args = _kda_inputs(decay == "strong", **SHAPES[shape])
    B, S, H, K = args[0].shape
    if shape == "heads6":       # two grid steps of three heads each
        monkeypatch.setattr(kda, "_SCOPED_VMEM", 4 << 20)
        assert kda._head_block(H, 16, K, K, 4) == 3
    if decay == "strong":
        assert float(args[3].reshape(B, S // 16, 16, H, K).sum(2).min()) < -200
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    both = lambda fn: jax.value_and_grad(
        lambda *x: jnp.sum(fn(*x) * w), argnums=(0, 1, 2, 3, 4))(*args)
    (want, want_g), (got, got_g) = both(kda.recurrent_kda), both(FORMS[form])
    assert abs(float(got - want)) <= 1e-4 * abs(float(want)) + 1e-4
    for a, b in zip(got_g, want_g):
        _close(a, b, 2e-4)
    _close(FORMS[form](*args), kda.recurrent_kda(*args), 1e-4)


def test_forward_writes_the_state_each_chunk_starts_from():
    """The state-writing call of ``hvd_kda_fwd``: its states are the
    recurrence's state before each chunk's first token, and its ``o`` is
    the stateless call's bit for bit."""
    args = _kda_inputs(False, S=128, H=3, V=32)
    o, states = kda._kda_forward(*args, chunk=16, interpret=True,
                                 states=True)
    assert jnp.array_equal(
        o, kda._kda_forward(*args, chunk=16, interpret=True))
    assert states.shape == (2, 3, 8, 16, 32) and states.dtype == jnp.float32

    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)
    _, before = jax.lax.scan(
        lambda St, x: (kda._token_step(St, x)[0], St),
        jnp.zeros((2, 3, 16, 32)), jax.tree.map(f32, args))  # [S, B, H, K, V]
    assert float(jnp.max(jnp.abs(before[16]))) > 0
    _close(states, jnp.moveaxis(before[::16], 0, 2), 1e-5)


def test_gradient_lowers_to_the_reverse_kernel_and_no_scan():
    """Lowered for the TPU (nothing runs): the gradient of ``chunk_kda``
    is the two kernels, the forward writing its states, and no ``while``:
    the ``jnp`` form's scans are in no program."""
    spec = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    B, S, H, K = 1, 256, 6, 128
    grad = jax.grad(lambda *a: kda.chunk_kda(*a, 64, False).sum(),
                    argnums=(0, 1, 2, 3, 4))
    text = jax.jit(grad).trace(
        spec(B, S, H, K), spec(B, S, H, K), spec(B, S, H, K),
        spec(B, S, H, K), spec(B, S, H)
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert 'kernel_name = "hvd_kda_fwd"' in text
    assert 'kernel_name = "hvd_kda_bwd"' in text
    assert f"tensor<{B}x{H}x{S // 64}x{K}x{K}xf32>" in text    # the states
    assert "while" not in text


def _unit_instructions(jaxpr) -> int:
    """Matrix-unit instructions of every ``dot_general`` in ``jaxpr``, by
    the rule ``ops/kda.py`` is written to: a pass of ``[M, k] x [k, N]``
    is M/8 pushes and 16 latches a 128-wide tile of the contraction and
    of N; float32 operands (all at ``highest``) take six passes, bfloat16
    one.  On the parent of PR 32 this is the Mosaic dump's count of
    ``vmatmul`` and ``vlatch`` to the instruction."""
    total = 0
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _unit_instructions(sub)
        if eqn.primitive.name != "dot_general":
            continue
        a, b = (x.aval for x in eqn.invars)
        ((ca,), _), _ = eqn.params["dimension_numbers"]
        k = a.shape[ca]
        m, n = a.size // k, b.size // k
        if a.dtype == jnp.float32:
            assert eqn.params["precision"] is not None and all(
                p == jax.lax.Precision.HIGHEST
                for p in eqn.params["precision"]), eqn
            passes = 6
        else:
            assert a.dtype == b.dtype == jnp.bfloat16, eqn
            passes = 1
        total += passes * -(-k // 128) * -(-n // 128) * (m // 8 + 16)
    return total


def test_chunk_issues_fewer_matrix_unit_instructions():
    """What PR 32 is about, counted where it cannot drift: the chunk at
    the published shape (C 64, K = V = 128), forward and reverse (the
    ``jax.vjp`` the reverse kernel takes, dead code dropped as Mosaic
    drops it).  The parent counted 5,568 and 9,696, which is what its
    Mosaic dump showed; a product put back shows here."""
    from jax.interpreters import partial_eval as pe
    C, K = 64, 128
    z = lambda *s: jnp.zeros(s, jnp.float32)
    args = (z(K, K), z(C, K), z(C, K), z(C, K), z(C, K), z(C, 1))

    def reverse(*a):
        return jax.vjp(kda._chunk, *a)[1]((z(K, K), z(C, K)))

    fwd = jax.make_jaxpr(kda._chunk)(*args).jaxpr
    rev = jax.make_jaxpr(reverse)(*args).jaxpr
    rev, _ = pe.dce_jaxpr(rev, [True] * len(rev.outvars))
    parent_fwd, parent_rev = 5568, 9696
    assert _unit_instructions(fwd) == 3408 <= 4300 < parent_fwd
    assert _unit_instructions(rev) == 6960 <= 0.85 * parent_rev


@pytest.mark.parametrize("decay", ["weak", "strong"])
def test_kernels_chunk_is_the_plain_step_at_64_tokens(decay):
    """``_chunk`` at the published chunk, where three levels of the tree
    (8, 16, 32) push their upper halves' rows only, against the plain
    step of ``chunk_kda_jnp``, which pushes all: both results and all six
    cotangents.  (The recurrence tests run chunks of 16: one such level.)"""
    C = 64
    q, k, v, g, beta = (x[0, :, 0] for x in _kda_inputs(
        decay == "strong", B=1, S=C, H=1, K=16, V=32))
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    args = (jax.random.normal(ks[0], (16, 32)), q, k, v, g, beta[:, None])
    plain = lambda S0, q, k, v, g, beta: kda._chunk_step(
        S0, q, k, v, g, beta[:, 0])
    cot = (jax.random.normal(ks[1], (16, 32)),
           jax.random.normal(ks[2], (C, 32)))
    got, got_vjp = jax.vjp(kda._chunk, *args)
    want, want_vjp = jax.vjp(plain, *args)
    for a, b in zip(got + got_vjp(cot), want + want_vjp(cot)):
        _close(a, b, 2e-5)


@pytest.mark.parametrize("decay", ["weak", "strong"])
def test_signed_sums_are_exact(decay):
    """The one product below ``highest``: its left operand is 0 and +-1,
    the right one is split into three bfloat16 pieces that add up to it
    bit for bit, so nothing is lost: the running sum is a float64 one to
    float32 accumulation's own error, and the rebased difference is
    exactly 0 on the row it is rebased on."""
    C = 64
    g = _kda_inputs(decay == "strong", B=1, S=C, H=1, K=128)[3][0, :, 0]
    if decay == "strong":
        assert float(g.sum(0).min()) < -200
    hi, mid, lo = kda._split(g)
    assert hi.dtype == mid.dtype == lo.dtype == jnp.bfloat16
    f32 = lambda x: x.astype(jnp.float32)
    assert jnp.array_equal((f32(hi) + f32(mid)) + f32(lo), g)

    W = np.asarray(kda._signed_sums(C).astype(jnp.float32))
    assert set(np.unique(W)) == {-1.0, 0.0, 1.0}
    assert np.array_equal(W[:, :C], W[:, C:])          # the columns twice
    assert np.array_equal(
        np.asarray(kda._signed_sums(C, transposed=True).astype(jnp.float32)),
        W[:, :C].T)

    G, *Ds = kda._sums(g)
    g64 = np.asarray(g, np.float64)
    room = C * 2.0 ** -24 * np.abs(g64).sum(0)
    assert np.all(np.abs(np.asarray(G, np.float64) - np.cumsum(g64, 0))
                  <= room)
    t = np.arange(C)
    for m, D in zip(kda._levels(C), Ds):
        r = (t // (2 * m)) * (2 * m) + m
        want = np.cumsum(g64, 0) - np.cumsum(g64, 0)[r]
        assert np.all(np.abs(np.asarray(D, np.float64) - want) <= room)
        assert np.all(np.asarray(D)[r] == 0.0)             # rows t = r

    # its own transpose against autodiff of the plain running sums
    d = jax.random.normal(jax.random.PRNGKey(11), (len(Ds) + 1, C, 128))
    plain = lambda g: jnp.stack(
        [jnp.cumsum(g, 0)] + [jnp.cumsum(g, 0) - jnp.cumsum(g, 0)[
            (t // (2 * m)) * (2 * m) + m] for m in kda._levels(C)])
    got = jax.grad(lambda g: jnp.sum(jnp.stack(kda._sums(g)) * d))(g)
    _close(got, jax.grad(lambda g: jnp.sum(plain(g) * d))(g), 1e-5)


# -- the blocks against the reference -----------------------------------------

def _layer_weights(i):
    return jax.tree.map(lambda a: a.astype(jnp.float32),
                        wts.layer(KEY, i, DIMS, jnp.float32))


@pytest.mark.parametrize("path", ["dense", "flash"])
def test_mla_block_matches_the_reference(path, monkeypatch):
    """Keys 24 wide, values 16: through the XLA path and through the flash
    kernels (interpreted), which take the two widths."""
    monkeypatch.setattr(layers, "_FORCE_FLASH_INTERPRET", path == "flash")
    w = _layer_weights(3)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 128, DIMS["d_model"]))
    assert llama.attention_path((2, 128, 2, 24), 4, None, v_dim=16) == path
    got, _ = KL.layer_pair("mla_moe", KCFG, None)[0](x, w)
    want = jax.vmap(lambda h: ref.mla_mixer(w, h, DIMS))(x)
    _close(got, want, 1e-4)


def test_kda_block_matches_the_reference():
    w = _layer_weights(1)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, DIMS["d_model"]))
    got, _ = KL._kda_mixer(x, w, KCFG)
    want = jax.vmap(lambda h: ref.kda_mixer(w, h, DIMS))(x)
    _close(got, want, 1e-4)


@pytest.mark.parametrize("renormalize", [True, False])
def test_topk_route_against_numpy(renormalize):
    rng = np.random.default_rng(5)
    s = rng.uniform(size=(40, 16)).astype(np.float32)
    b = rng.normal(scale=0.2, size=16).astype(np.float32)
    experts, weights = moe.topk_route(jnp.asarray(s), jnp.asarray(b), 3,
                                      renormalize=renormalize, scale=2.446)
    want_e = np.argsort(-(s + b), axis=-1)[:, :3]
    want_w = np.take_along_axis(s, want_e, -1)
    if renormalize:
        want_w = want_w / want_w.sum(-1, keepdims=True)
    assert np.array_equal(np.sort(experts, -1), np.sort(want_e, -1))
    order = np.argsort(np.asarray(experts), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(weights), order, -1),
        np.take_along_axis(2.446 * want_w, np.argsort(want_e, -1), -1),
        rtol=1e-6)
    # the bias moves the choice, never the weight
    assert not np.array_equal(np.sort(want_e, -1),
                              np.sort(np.argsort(-s, -1)[:, :3], -1))


def _moe_weights():
    w = _layer_weights(1)
    experts = {"gate": w["e_gate"], "up": w["e_up"], "down": w["e_down"]}
    shared = {"w_gate": w["s_gate"], "w_up": w["s_up"], "w_down": w["s_down"]}
    return w, experts, shared


def _held(x, w, experts, lo, hi, shared, router=None):
    """Experts ``lo`` to ``hi`` of the 16, over the tokens ``x``."""
    part = jax.tree.map(lambda a: a[lo:hi], experts)
    return moe.moe_layer_held(
        x, w["router"] if router is None else router, w["router_bias"], part,
        (lo, hi), shared, k=DIMS["experts_per_token"],
        scale=DIMS["routed_scale"], tile=8)


def test_planted_skew_loses_no_token():
    """Every token to experts 5 and 6: both runs are twelve times a tile,
    every pair is held and the result is the two experts' plain sum."""
    w, experts, _ = _moe_weights()
    x = jax.random.normal(jax.random.PRNGKey(6), (96, DIMS["d_model"]))
    router = jnp.zeros_like(w["router"]).at[:, 5].set(
        jnp.ones(DIMS["d_model"]) * 0.01)
    bias = jnp.zeros(16).at[5].set(10.0).at[6].set(10.0)
    out, stats = _held(x, dict(w, router_bias=bias), experts, 4, 8, None,
                       router)
    assert int(stats["pairs_held"]) == 2 * 96
    assert stats["expert_counts"].tolist() == [0, 96, 96, 0]
    s = jax.nn.sigmoid(x @ router)[:, 5:7]
    wt = DIMS["routed_scale"] * s / s.sum(-1, keepdims=True)
    want = sum(wt[:, j:j + 1] * ref._swiglu(
        x, experts["gate"][e], experts["up"][e], experts["down"][e], None)
        for j, e in enumerate((5, 6)))
    _close(out, want, 1e-5)


def test_shares_add_up_to_the_uncut_reference_layer():
    """The share test: 16 experts over 4 shares; the partial results of
    all shares, the shared expert counted once, are the uncut layer."""
    w, experts, shared = _moe_weights()
    x = jax.random.normal(jax.random.PRNGKey(8), (64, DIMS["d_model"]))
    parts, pairs = [], 0
    for lo in range(0, 16, 4):
        out, stats = _held(x, w, experts, lo, lo + 4,
                           shared if lo == 0 else None)
        parts.append(out)
        pairs += int(stats["pairs_held"])
    assert pairs == 64 * DIMS["experts_per_token"]
    assert float(jnp.max(jnp.abs(parts[1]))) > 0
    _close(sum(parts), ref.moe_mlp(w, x, DIMS), 1e-5)


# -- the whole model ----------------------------------------------------------

def _params():
    """The program's tree and the reference's, with a selection bias large
    enough to change which experts the top 2 are."""
    program = jax.jit(lambda k: wts.stacked(k, DIMS, jnp.float32))(KEY)
    layers = [wts.layer(KEY, i, DIMS, jnp.float32)
              for i in range(DIMS["n_layers"])]
    bias = lambda i: 0.1 * jax.random.normal(jax.random.PRNGKey(100 + i),
                                             (DIMS["n_experts"],))
    for (_, first, count), stack in zip(wts.runs_of(DIMS), program["runs"]):
        if "router_bias" in stack:
            stack["router_bias"] = jnp.stack(
                [bias(first + j) for j in range(count)])
            for j in range(count):
                layers[first + j]["router_bias"] = bias(first + j)
    return program, (layers, wts.outer(KEY, DIMS, jnp.float32))


def test_skewed_router_is_the_reference_layer_and_loses_no_pair():
    """A router whose every expert has a large offset shared by all tokens
    sends nearly every token to the same experts (what seeded weights do
    on the chip): the fullest expert holds many times the mean, every pair
    is still held, and the layer is the reference's."""
    w, experts, shared = _moe_weights()
    d = DIMS["d_model"]
    x = jax.random.normal(jax.random.PRNGKey(12), (256, d)) + 2.0
    out, stats = _held(x, w, experts, 0, 16, shared)
    counts = np.asarray(stats["expert_counts"])
    assert counts.sum() == int(stats["pairs_held"]) == \
        256 * DIMS["experts_per_token"]
    assert counts.max() > 4 * counts.mean()
    _close(out, ref.moe_mlp(w, x, DIMS), 1e-5)


def test_model_loss_and_gradients_match_the_reference():
    program, plain = _params()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0,
                                DIMS["vocab_size"])
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        lambda p: KL.loss_fn(p, {"tokens": tokens}, dataclasses.replace(
            KCFG, remat=False)), has_aux=True))(program)
    want, (layer_g, outer_g) = jax.jit(jax.value_and_grad(
        lambda p: ref.forward_loss(*p, tokens, DIMS)))(plain)
    assert abs(float(loss - want)) < 1e-5 * float(want)
    assert stats["expert_counts"].shape == (4, 16)
    assert stats["pairs_held"].tolist() == [2 * 32 * 2] * 4
    for name in ("embed", "lm_head", "final_norm"):
        _close(grads[name], outer_g[name], 2e-4)
    for (kind, first, count), stack in zip(wts.runs_of(DIMS), grads["runs"]):
        for j in range(count):
            for leaf, g in layer_g[first + j].items():
                _close(stack[leaf][j], g, 5e-4)
    assert float(jnp.max(jnp.abs(grads["runs"][1]["router_bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(grads["runs"][1]["router"]))) > 0.0


def test_two_steps_through_make_train_step_match_the_reference():
    """The benchmark's tiny cell: two checked steps of the compiled step
    against the reference's two AdamW steps, leaf by leaf."""
    out = runmod.execute(TINY, "tiny-kimi", 2**31 + 11, 0.2, False,
                         require_tpu=False)
    assert out["correct"], out["checks"]
    assert out["checks"]["grad_norm_gap"]["value"] < 1e-4
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_selection_bias_takes_no_update():
    """Through ``kimi_linear.optimizer`` the bias is a buffer: with no
    gradient the router still decays, the bias does not move."""
    params = {"runs": [{"router": jnp.ones((4, 8)),
                        "router_bias": jnp.ones((8,))}]}
    tx = KL.optimizer(optax.adamw(1e-2, weight_decay=0.1))
    updates, _ = tx.update(jax.tree.map(jnp.zeros_like, params),
                           tx.init(params), params)
    assert float(jnp.max(jnp.abs(updates["runs"][0]["router_bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(updates["runs"][0]["router"]))) > 0.0


def test_llama_step_lowers_to_the_program_it_lowered_to():
    """``make_train_step`` takes the loss and the shardings from the
    model; with a ``LlamaConfig`` it must still be the step it was: the
    same lowered text as the closure it replaced, written out here."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from horovod_tpu.parallel import MeshConfig, build_mesh
    cfg = llama.LlamaConfig.tiny()
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    tx = optax.adamw(1e-3)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: llama.loss_fn(p, batch, cfg, mesh=mesh))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree.map(jnp.add, params, updates)
        return params, opt_state, loss

    pshard = llama.param_shardings(cfg, mesh)
    opt_shard = steps._opt_shardings(tx, cfg, mesh, steps.model_of(cfg))
    repl = NamedSharding(mesh, P())
    before = jax.jit(
        step, in_shardings=(pshard, opt_shard,
                            NamedSharding(mesh, P(("dp", "fsdp")))),
        out_shardings=(pshard, opt_shard, repl), donate_argnums=(0, 1))
    params = llama.init_params(cfg, jax.random.PRNGKey(0), mesh)
    args = (params, tx.init(params),
            {"tokens": jnp.zeros((2, 17), jnp.int32)})
    assert steps.make_train_step(cfg, mesh, tx).lower(*args).as_text() == \
        before.lower(*args).as_text()


#: sha256 (first 12 hex digits) of the lowered text of the tiny step, as
#: made from the tree before the decode tick's expert kernel: the
#: benchmark's tiny cell (a share of the experts held: the add form) and
#: every expert held (the list form).  The kernel is a decode tick's, so a
#: trainer's step, which differentiates the layer, lowers as it did.
STEP_BEFORE_THE_TICK_KERNEL = {"share": "87d5625c0e26", "all": "2e7dcff3a242"}


@pytest.mark.parametrize("held", sorted(STEP_BEFORE_THE_TICK_KERNEL))
def test_the_train_step_lowers_as_before_the_tick_kernel(held):
    from horovod_tpu.parallel import MeshConfig, build_mesh
    config = runmod.load_json(os.path.join(TINY_DIR, "configs",
                                           "tiny-kimi.json"))
    if held == "all":
        config = dict(config, num_experts=16, first_expert_held=0)
    dims = wts.dims_of(config)
    kcfg = kimi_config(config, dims, jnp.float32)
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    tx = KL.optimizer(optax.adamw(1e-3))
    params = wts.stacked(KEY, dims, jnp.float32)
    text = steps.make_train_step(kcfg, mesh, tx, model=KL).lower(
        params, tx.init(params),
        {"tokens": jnp.zeros((2, 33), jnp.int32)}).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:12] == \
        STEP_BEFORE_THE_TICK_KERNEL[held]
