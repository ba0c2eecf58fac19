"""The decode tick's routed experts through one kernel a layer,
``hvd_moe_decode`` (``ops/moe_decode.py``): against the tile loop's list
form on the tiny GLM-4.7-Flash and Xing4.0 configurations, its copies in
JAX's TPU interpreter, the rule that engages it (a tick under the
engine's kernels, and nothing else) and what the spans say of it.  On
the CPU, the kernel interpreted."""

from __future__ import annotations

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import glm_moe_lite as G
from horovod_tpu.models import llama, steps
from horovod_tpu.models import xing4 as X
from horovod_tpu.ops import moe_decode as MD
from horovod_tpu.parallel import moe

CONFIGS = {"glm": G.GlmMoeLiteConfig.tiny, "xing4": X.Xing4Config.tiny}
ROWS = 16


def _expert_run(cfg):
    """The expert layers' leaves of ``cfg``'s seeded weights: ``(layer
    0's leaves, the run's stack as the serving steps hand it over,
    layers)``."""
    run = steps.model_of(cfg).init_params(cfg, jax.random.PRNGKey(45))[
        "runs"][-1]
    n = run["router"].shape[0]
    stack = {k: run[leaf].reshape((-1,) + run[leaf].shape[2:])
             for k, leaf in G._EXPERT_LEAVES.items()}
    return jax.tree.map(lambda a: a[0], run), stack, n


# what each case does to the routing: the bias it plants, the picks a
# token makes, the layer of the stack it reads
_CASES = {
    "a-row-picks-the-last-expert": (lambda b, E: b.at[E - 1].set(10.0), None,
                                    0),
    "experts-no-row-took": (lambda b, E: b.at[: E // 2].set(-10.0), None, 0),
    "a-later-layer-of-the-stack": (lambda b, E: b, None, 1),
    "every-row-on-one-expert": (lambda b, E: b.at[3].set(10.0), 1, 0),
    "blocks-along-f": (lambda b, E: b, None, 1),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", _CASES)
@pytest.mark.parametrize("model", sorted(CONFIGS))
def test_the_kernel_is_the_list_form(model, case, dtype, monkeypatch):
    """The model's own expert layer at a tick's shape (one position a
    row), through the kernel and through the tile loop's list form: the
    same picks and the same sum of each row's weighted products.  In
    float32 to round-off; in bf16 the kernel keeps its products in
    float32 where the list form rounds each one (and the gate's and up's
    before them) to bf16, so against the float32 sum of the same bf16
    operands the kernel lies no further than the list form, and a bf16
    step of the output."""
    plant, k, layer = _CASES[case]
    cfg = CONFIGS[model](dtype=dtype)
    if k is not None:
        cfg = dataclasses.replace(cfg, experts_per_token=k)
    if case == "blocks-along-f":
        monkeypatch.setattr(MD, "block_f", lambda D, F, itemsize: F // 2)
    lp, stack, n = _expert_run(cfg)
    E = cfg.n_experts
    lp = dict(lp, router_bias=plant(lp["router_bias"], E),
              expert_row=jnp.asarray(layer * E))
    assert layer < n
    x = jax.random.normal(jax.random.PRNGKey(7), (ROWS, 1, cfg.d_model)
                          ).astype(dtype)
    mlp = lambda x, lp, stack, **kw: jax.jit(lambda x, lp, stack: G._moe_mlp(
        x, lp, cfg, stack=stack, **kw))(x, lp, stack)
    got, stats = mlp(x, lp, stack, use_flash=True, interpret=True)
    want, want_stats = mlp(x, lp, stack)
    counts = np.asarray(stats["expert_counts"])
    np.testing.assert_array_equal(counts, np.asarray(
        want_stats["expert_counts"]))
    assert counts.sum() == ROWS * cfg.experts_per_token
    if case == "a-row-picks-the-last-expert":
        assert counts[E - 1] == ROWS
    if case == "experts-no-row-took":
        assert not counts[: E // 2].any() and counts[E // 2:].any()
    if case == "every-row-on-one-expert":
        assert counts[3] == ROWS and np.count_nonzero(counts) == 1
    f32 = lambda a: np.asarray(a, np.float32)
    assert float(np.abs(f32(got)).max()) > 0
    if dtype == jnp.float32:
        np.testing.assert_allclose(f32(got), f32(want), rtol=0, atol=2e-6 *
                                   float(np.abs(f32(want)).max()))
        return
    up = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32) if
                                jnp.issubdtype(a.dtype, jnp.floating) else a,
                                t)
    exact = f32(mlp(up(x), up(lp), up(stack))[0])
    off = lambda a: float(np.abs(f32(a) - exact).max())
    assert off(got) <= off(want) + 2.0 ** -8 * float(np.abs(exact).max()), (
        off(got), off(want))


def _kernel_inputs(D=128, F=256, E=8, L=3, T=16, k=2, touched=4):
    ks = jax.random.split(jax.random.PRNGKey(D + touched), 6)
    stack = {"gate": jax.random.normal(ks[0], (L * E, D, F)) / np.sqrt(D),
             "up": jax.random.normal(ks[1], (L * E, D, F)) / np.sqrt(D),
             "down": jax.random.normal(ks[2], (L * E, F, D)) / np.sqrt(F)}
    x = jax.random.normal(ks[3], (T, D))
    experts = jnp.stack([jax.random.randint(ks[4], (T,), 0, max(touched, 1)),
                         jnp.full((T,), E - 1)], -1)[:, :k]
    weights = jax.random.uniform(ks[5], (T, k))
    counts = jnp.zeros((E,), jnp.int32).at[experts.reshape(-1)].add(
        1 if touched else 0)
    return x, experts, weights, counts, stack


def _per_row_sum(x, experts, weights, counts, stack, first_row):
    """The plain sum, float32, a pair at a time."""
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        for j in range(experts.shape[1]):
            e = first_row + int(experts[t, j])
            if not counts[int(experts[t, j])]:
                continue
            g = np.asarray(x[t] @ stack["gate"][e])
            u = np.asarray(x[t] @ stack["up"][e])
            h = g / (1 + np.exp(-g)) * u
            out[t] += float(weights[t, j]) * (h @ np.asarray(
                stack["down"][e]))
    return out


@pytest.mark.parametrize("fb,touched", [(None, 4), (128, 4), (64, 1),
                                        (None, 0)],
                         ids=["whole", "two-blocks", "four-blocks",
                              "nothing-touched"])
def test_the_kernels_copies_are_waited_for_exactly(fb, touched):
    """JAX's TPU interpreter makes a copy only when it is waited for and
    counts the bytes: a wait that does not take what a start sent would
    hang the chip, a copy never waited for leaves its slot stale.  The
    walk over the touched experts, whole or in blocks along F, gives the
    plain sum, and no two accesses race."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as ipc
    from jax.experimental.pallas import tpu as pltpu
    x, experts, weights, counts, stack = _kernel_inputs(touched=touched)
    got = MD.moe_decode(x, experts, weights, counts, stack, 8, fb=fb,
                        interpret=pltpu.InterpretParams(
                            dma_execution_mode="on_wait", detect_races=True))
    assert not ipc.races.races_found
    want = _per_row_sum(x, experts, weights, counts, stack, 8)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-4)
    assert (np.abs(want).max() > 0) == bool(touched)


def test_blocks_along_f_fit_the_slots():
    """An expert is copied whole while two slots of its three matrices
    fit the budget (both cells' widths), else in halves of whole lane
    tiles."""
    assert MD.block_f(2048, 1536, 2) == 1536           # GLM-4.7-Flash
    assert MD.block_f(3584, 1024, 2) == 1024           # Xing4.0
    assert MD.block_f(7168, 2048, 2) == 512
    assert MD.block_f(7168, 384, 2) == 384              # no smaller tile


def _kernel_calls(fn, *args) -> int:
    """``hvd_moe_decode`` calls in ``fn``'s jaxpr, nested ones too."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield str(eqn.params["name"]) == "hvd_moe_decode"
                continue
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from walk(sub)
    return sum(walk(jax.make_jaxpr(fn)(*args).jaxpr))


_STEPS = {        # step: (runs the kernel, a tick under the kernels)
    "tick": (True, True),
    "tick-without-kernels": (False, False),
    "prefill": (False, False),
    "verify": (False, False),
    "the-layer-at-a-tick": (True, True),
    "grad-of-the-layer-at-a-tick": (False, True),
}


@pytest.mark.parametrize("step", _STEPS)
def test_the_kernel_engages_on_a_tick_alone(step):
    """By the step's shape alone: a decode tick under the engine's
    kernels runs it (once, in the expert layers' scan), as the expert
    layer does at one position a row; the XLA tick, a prompt's prefill
    and a verify step keep the tile loop, and so does the layer under a
    gradient, whose forward rule is the list form's."""
    cfg = G.GlmMoeLiteConfig.tiny()
    p = G.init_params(cfg, jax.random.PRNGKey(0))
    pool = jnp.zeros((3, 8, 4, cfg.cache_row))
    tables = jnp.asarray([[1, 2, 3], [4, 5, 0], [0, 0, 0]], jnp.int32)
    tok, pos = jnp.asarray([9, 7, 0]), jnp.asarray([9, 5, 0])
    tick = lambda p, flash: steps.decode_step_paged(
        p, tok, pos, (pool,), tables, cfg, use_flash=flash,
        interpret=True)[0]
    lp, stack, _ = _expert_run(cfg)
    layer = lambda x: G._moe_mlp(x, dict(lp, expert_row=jnp.asarray(0)),
                                 cfg, stack=stack, use_flash=True,
                                 interpret=True)[0]
    x = jnp.ones((ROWS, 1, cfg.d_model))
    run, arg = {
        "tick": (lambda p: tick(p, True), p),
        "tick-without-kernels": (lambda p: tick(p, False), p),
        "prefill": (lambda p: steps.prefill_step(
            p, jnp.zeros((1, 8), jnp.int32), cfg)[0], p),
        "verify": (lambda p: steps.extend_step_paged(
            p, jnp.zeros((3, 2), jnp.int32), jnp.zeros((3, 2), jnp.int32),
            jnp.ones((3, 2), bool), (pool,), tables, cfg)[0], p),
        "the-layer-at-a-tick": (layer, x),
        "grad-of-the-layer-at-a-tick": (jax.grad(
            lambda x: layer(x).sum()), x),
    }[step]
    runs, kernel = _STEPS[step]
    assert _kernel_calls(run, arg) == runs
    assert moe.combine_form(cfg.n_experts, cfg.n_experts, kernel) == (
        "kernel" if kernel else "list")
    assert moe.combine_form(cfg.n_experts, 4 * cfg.n_experts, kernel) == \
        "add"


def _hvd_spans(trace_dir):
    path, = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return sorted(
        (e.start_ns, e.name, dict(e.stats))
        for plane in data.planes for line in plane.lines
        for e in line.events if e.name.startswith("hvd.serve."))


@pytest.mark.parametrize("use_flash", ["interpret", "never"])
def test_spans_say_which_form_the_experts_ran(use_flash, tmp_path):
    """A tiny GLM engine: every prefill span reads ``moe_combine`` of
    ``list``; every tick's fetched counts read ``kernel`` where the
    engine runs its kernels and ``list`` where it runs XLA's."""
    cfg = G.GlmMoeLiteConfig.tiny()
    params = G.init_params(cfg, jax.random.PRNGKey(0))
    session = serving.serve(params, cfg, block_size=4, num_blocks=64,
                            max_active=4, use_flash=use_flash,
                            prefill_buckets=(8, 16))
    rng = np.random.RandomState(45)
    submit = lambda n: session.submit(
        rng.randint(0, cfg.vocab_size, size=(n,)).astype(np.int32), 3)
    submit(6)
    session.drain()                           # warm
    with jax.profiler.trace(str(tmp_path)):
        for n in (5, 11):
            submit(n)
        session.drain()
    session.close()
    spans = _hvd_spans(tmp_path)
    prefills = [a for _, name, a in spans if name == "hvd.serve.prefill"]
    ticks = [a for _, _, a in spans if "experts_touched" in a]
    assert len(prefills) == 2 and ticks
    assert {a["moe_combine"] for a in prefills} == {"list"}
    assert {a["moe_combine"] for a in ticks} == {
        "kernel" if use_flash == "interpret" else "list"}
