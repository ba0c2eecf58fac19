"""What a remat'd layer keeps for the backward (``layers.remat`` with the
names ``llama.remat_plan`` picks): the rule at the training cells' shapes,
the gradients and the work of a step under each set, and the gauge."""

import dataclasses
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import layers, llama, steps
from horovod_tpu.parallel import MeshConfig, build_mesh

# What a v5e chip lends a program (``memory_stats()["bytes_limit"]``).
V5E_BYTES = 16_909_336_064

MISTRAL = llama.LlamaConfig(
    vocab_size=32768, d_model=4096, n_layers=5, n_heads=32, n_kv_heads=8,
    d_ff=14336, rope_theta=1e6, dtype=jnp.bfloat16, remat=True)


def _param_bytes(cfg):
    aval = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(aval))


@pytest.mark.parametrize("limit, want", [
    (V5E_BYTES, layers.MIXER_RESIDUALS),
    # All 16 GiB of the chip: the MLP's products (2.35 GB over the five
    # layers, 4.75 GB more temporaries by the AOT compile) still do not fit.
    (16 * 2 ** 30, layers.MIXER_RESIDUALS),
    (12e9, ()),
    (None, ()),
])
def test_the_rule_at_the_mistral_cell(limit, want):
    plan = llama.remat_plan(MISTRAL, 4, 2048, _param_bytes(MISTRAL), limit)
    assert tuple(plan) == want
    if want:
        # q and the flash output [4, 2048, 32, 128], k and v [.., 8, ..],
        # the row sums float32 [4 * 32, 2048], the mixer's output [4, 2048,
        # 4096]: 235.9 MB a layer.
        assert sum(plan.values()) == 5 * 235_929_600


def test_the_rule_keeps_nothing_where_the_layer_is_not_recomputed():
    for remat in (False, "dots"):
        cfg = dataclasses.replace(MISTRAL, remat=remat)
        assert llama.remat_plan(cfg, 4, 2048, _param_bytes(cfg),
                                V5E_BYTES) == {}


def test_the_rule_at_the_kimi_cell():
    """Fed the Kimi-Linear cell's candidates (its one latent-attention
    layer's mixer at B4 x S8192: q and the expanded k of 32 heads of 192,
    v and the flash output of 128, the row sums, the output of 2,304; its
    one dense layer's gate and up products of 9,216) and the memory its
    step leaves free (15.20 GB of arguments and temporaries by the AOT
    compile for a v5e), the rule keeps nothing."""
    T = 4 * 8192
    mixer = T * 32 * (192 + 192 + 128 + 128) * 2 + T * 32 * 4 + T * 2304 * 2
    groups = [(layers.MIXER_RESIDUALS, mixer),
              (layers.MLP_RESIDUALS, 2 * T * 9216 * 2)]
    assert layers.saved_residuals(groups, V5E_BYTES - 15.20e9) == ()
    assert layers.saved_residuals(groups, 1e12) == \
        layers.MIXER_RESIDUALS + layers.MLP_RESIDUALS


def _ops(jaxpr, counts=None):
    """Primitives of a jaxpr and its sub-jaxprs, a Pallas call by its
    kernel's name (its body not entered)."""
    counts = Counter() if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            counts[str(eqn.params["name"])] += 1
            continue
        counts[eqn.primitive.name] += 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    _ops(sub.jaxpr, counts)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    _ops(sub, counts)
    return counts


@pytest.fixture()
def flash(monkeypatch):
    monkeypatch.setattr(layers, "_FORCE_FLASH_INTERPRET", True)


@pytest.mark.parametrize("saved, fewer_dots, flash_fwds", [
    ((), 0, 2),
    (layers.MIXER_RESIDUALS, 4, 1),          # the q, k, v and wo products
    (layers.MLP_RESIDUALS, 2, 2),            # the gate and up products
    (layers.MIXER_RESIDUALS + layers.MLP_RESIDUALS, 6, 1),
])
def test_a_saved_set_gives_full_remats_gradients_with_less_work(
        flash, monkeypatch, saved, fewer_dots, flash_fwds):
    cfg = llama.LlamaConfig.tiny(n_layers=2, remat=True, d_model=256,
                                 n_heads=2, n_kv_heads=1, d_ff=256)
    assert llama.attention_path((2, 128, 2, 128), 4, None) == "flash"
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 129),
                                          0, cfg.vocab_size)}

    def run(names):
        monkeypatch.setattr(llama, "remat_plan",
                            lambda *a: {n: 0 for n in names})
        # A new function each time: make_jaxpr caches a trace by function.
        grad = jax.grad(lambda p: llama.loss_fn(p, batch, cfg))
        return grad(params), _ops(jax.make_jaxpr(grad)(params).jaxpr)

    full, full_ops = run(())
    got, got_ops = run(saved)
    for a, b in zip(jax.tree.leaves(full), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-6, atol=1e-7)
    # The layer scan's body is one jaxpr: counts are a layer's.
    assert full_ops["dot_general"] - got_ops["dot_general"] == fewer_dots
    assert got_ops["hvd_flash_fwd"] == flash_fwds
    assert got_ops["hvd_flash_bwd_dq"] == got_ops["hvd_flash_bwd_dkv"] == 1


def test_the_gauge_after_a_traced_step(monkeypatch):
    cfg = llama.LlamaConfig.tiny(remat=True)
    monkeypatch.setattr(llama, "device_bytes", lambda: V5E_BYTES)
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    tx = optax.adamw(1e-3)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), mesh)
    step = steps.make_train_step(cfg, mesh, tx)
    step.lower(params, tx.init(params),
               {"tokens": jnp.zeros((2, 17), jnp.int32)})
    plan = llama.remat_plan(cfg, 2, 16, _param_bytes(cfg), V5E_BYTES)
    assert tuple(plan) == layers.MIXER_RESIDUALS + layers.MLP_RESIDUALS
    for name, nbytes in plan.items():
        assert layers._m_saved.labels(name=name).value == nbytes > 0


#: sha256 (first 12 hex digits) of the lowered text of the train step of a
#: tiny Llama at B2 x S16 on a one-device mesh, remat on, as the chip's
#: memory lets it keep every named residual ("v5e") and as the CPU keeps
#: none ("cpu"): the text the step lowered to before the steps every model
#: goes through moved to ``models/steps.py``
TRAIN_STEP_LOWERED = {"cpu": "53851777151e", "v5e": "6337f284406c"}


@pytest.mark.parametrize("device", sorted(TRAIN_STEP_LOWERED))
def test_the_train_step_lowers_as_before(monkeypatch, device):
    import hashlib
    cfg = llama.LlamaConfig.tiny(remat=True)
    monkeypatch.setattr(llama, "device_bytes",
                        lambda: V5E_BYTES if device == "v5e" else None)
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    tx = optax.adamw(1e-3)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), mesh)
    text = steps.make_train_step(cfg, mesh, tx).lower(
        params, tx.init(params),
        {"tokens": jnp.zeros((2, 17), jnp.int32)}).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:12] == \
        TRAIN_STEP_LOWERED[device]
