"""The steps every model goes through, and what a model gives them.

A model is a module of this package, found by its configuration's class
(:func:`model_of`).  The training step (:func:`make_train_step`) and the
three serving steps (:func:`prefill_step`, :func:`decode_step_paged`,
:func:`extend_step_paged`, which :class:`horovod_tpu.serving.ServingEngine`
runs) ask it for what differs between models and nothing else.  A
required name that a model does not give raises ``NotImplementedError``,
naming it, where a path asks for it; the optional names and their
defaults are :data:`DEFAULTS`.  Kimi-Linear gives no serving names and
is trained; GLM-4.7-Flash, Falcon-H1 and Xing4.0 refuse in their
``check_trainable`` and are served.

To train: ``init_params(cfg, key)``, ``param_shardings(cfg, mesh)`` and
``loss_fn(params, batch, cfg, mesh=)``: the leaves, their layout, the
objective.

To serve:

- ``check_servable(cfg, mesh, engine_cfg=None, path=None)``: raises, by
  name, for what the steps cannot run (``path``: ``"extend"`` or
  ``"migration"``, asked where the engine takes one);
- ``serve_embed(embed, tokens, cfg)``: ``[B, S]`` tokens to the residual;
- ``serve_runs(params, cfg, step)``: the layer runs, ``[(stacked leaves,
  attend -> mixer, mlp)]``, for the step's :class:`ServeStep`;
- ``prefill_attend(cfg, mesh, P, last_pos)`` and ``paged_attend(cfg,
  mesh, tables, blk, off, mask, last, interpret)``: the ``attend`` of
  the prefill and of the two paged steps (:func:`_serve_layers`);
- ``cache_rows(cfg)`` and ``pool_dims(cfg)``: each pool's per-token
  shape and logical dims;
- ``paged_kernel_ok(cfg, mesh, block_size, interpret)`` and
  ``PAGED_KERNEL``: whether the tick may read the pools through the
  model's paged kernel, and that kernel's name;
- ``moe_layer_names(cfg)`` and ``moe_experts_scored(cfg)``, where
  ``serve_stats`` gives expert layers' counts.

The GQA attention's cache facts are written once, in
:mod:`~horovod_tpu.models.layers` beside ``gqa_mixer``, for every model
with that mixer.  ``forward``, ``from_published`` and ``prefill_path``
are the benchmark's, not the steps'.
"""

from __future__ import annotations

import sys
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.trace import region
from ..parallel import sharding as shd
from .layers import block, final_logits, looped, rmsnorm

#: the optional names, each with what a model that leaves it out gives
DEFAULTS = {
    # raises, by name, for what of ``cfg`` has no objective here
    "check_trainable": lambda cfg: None,
    # the loss returns ``(loss, aux)``, and so does the step, third
    "LOSS_HAS_AUX": False,
    # ``(cfg, mesh, tx) -> step``, unjitted, on the 1F1B schedule of a
    # ``pp > 1`` mesh (None: no pipelined forward, such a mesh refused)
    "train_step_1f1b": None,
    # the per-row shapes of the state slots beside the pools
    "state_rows": lambda cfg: (),
    # what a serving step returns beside its tokens, from the runs'
    # stacked mlp extras
    "serve_stats": lambda cfg, extras: {},
    # logical-rule overrides under a mesh
    "shard_rules": lambda cfg, mesh: None,
    # the frame of every served layer, ``frame(h, lp, mixer, mlp, eps)
    # -> (h, kept, extra)`` (with hyper-connections ``layers.hc_block``)
    "serve_frame": lambda cfg: block,
    # what the final norm reads of the last layer's residual
    "serve_readout": lambda params, h, cfg: h,
}
_REQUIRED = object()


class Model:
    """A model's module as the steps see it: each name the module's own,
    else its default; a required name it does not give raises, naming
    it."""

    def __init__(self, module):
        self.module, self.__name__ = module, module.__name__

    def __getattr__(self, name: str):
        found = getattr(self.module, name, DEFAULTS.get(name, _REQUIRED))
        if found is _REQUIRED:
            raise (AttributeError if name.startswith("__") else
                   NotImplementedError)(
                f"{self.__name__} gives no {name}, which the step asking "
                "for it needs (the names a model gives: models/steps.py)")
        return found


def model_of(cfg) -> Model:
    """The model ``cfg`` belongs to: the module that defines its class
    (:mod:`~horovod_tpu.models.llama` for a ``LlamaConfig``,
    :mod:`~horovod_tpu.models.glm_moe_lite` for its own)."""
    return Model(sys.modules[type(cfg).__module__])


# -- training -----------------------------------------------------------------

def _opt_shardings(tx, cfg, mesh: Mesh, model: Model):
    """Explicit shardings for the optimizer state: every param-shaped
    subtree (adam mu/nu, momentum, ...) mirrors the parameter shardings,
    anything else (step counters) replicates.

    jit with donated arguments needs these spelled out: leaving the opt
    state's shardings to inference lets the propagator pick layouts that
    disagree with the donated inputs on tp/sp meshes, and XLA aliasing
    fails at runtime with a sub-shape size mismatch."""
    pshard = model.param_shardings(cfg, mesh)
    repl = NamedSharding(mesh, P())
    params_aval = jax.eval_shape(partial(model.init_params, cfg),
                                 jax.random.PRNGKey(0))
    ptree = jax.tree.structure(params_aval)
    state_aval = jax.eval_shape(tx.init, params_aval)

    def is_param_subtree(x):
        try:
            return jax.tree.structure(x) == ptree
        except Exception:  # pragma: no cover - exotic leaves
            return False

    return jax.tree.map(
        lambda sub: pshard if is_param_subtree(sub)
        else jax.tree.map(lambda _: repl, sub),
        state_aval, is_leaf=is_param_subtree)


def make_train_step(cfg, mesh: Mesh, tx, *,
                    pipeline_schedule: str = "1f1b", model=None):
    """Jitted full training step over the mesh (GSPMD collectives for
    dp/fsdp/tp, explicit shard_map blocks for sp/ep; layer stack over pp)
    for the model of ``cfg`` (:func:`model_of`; ``model``, where given,
    must be that module).  Where the model sets ``LOSS_HAS_AUX`` the
    step's third output is ``(loss, aux)``.

    On pp>1 meshes ``pipeline_schedule`` selects "1f1b" (default: the
    model's ``train_step_1f1b``, activation memory bounded by 2*(pp-1)
    microbatches) or "gpipe" (autodiff through the model's fill-drain
    forward); a model without ``train_step_1f1b`` has neither."""
    found = model_of(cfg)
    assert model is None or model is found.module, (model, found.module)
    model = found
    model.check_trainable(cfg)       # raises, naming what is missing
    pipelined = mesh.shape.get("pp", 1) > 1
    if pipelined and model.train_step_1f1b is None:
        raise NotImplementedError(
            f"{model.__name__} has no pipelined forward; use a pp=1 mesh")
    pshard = model.param_shardings(cfg, mesh)
    if pipelined and pipeline_schedule == "1f1b":
        step = model.train_step_1f1b(cfg, mesh, tx)
    else:
        multi_device = any(s > 1 for s in mesh.shape.values())

        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: model.loss_fn(p, batch, cfg, mesh=mesh),
                has_aux=model.LOSS_HAS_AUX)(params)
            # Pin gradients to the parameter shardings: the backward
            # scan's per-layer dynamic-update-slice accumulators otherwise
            # get propagation-derived shardings that force involuntary
            # full rematerialization on the way into the optimizer update.
            # (On a single-device mesh the annotation is a no-op
            # semantically and only an XLA fusion barrier, so it is
            # skipped.)
            if multi_device:
                grads = jax.lax.with_sharding_constraint(grads, pshard)
            with region("optim"):
                updates, opt_state = tx.update(grads, opt_state, params)
                params = jax.tree.map(jnp.add, params, updates)
            return params, opt_state, loss

    opt_shard = _opt_shardings(tx, cfg, mesh, model)
    batch_shard = NamedSharding(mesh, P(("dp", "fsdp")))
    repl = NamedSharding(mesh, P())
    return jax.jit(step, in_shardings=(pshard, opt_shard, batch_shard),
                   out_shardings=(pshard, opt_shard, repl),
                   donate_argnums=(0, 1))


# -- serving (horovod_tpu/serving: continuous batching over a block-paged KV
# cache).  For the Llama family the math mirrors the batch
# ``llama.generate`` paths op for op, so greedy decode through the engine
# reproduces generate()'s tokens; only cache PLACEMENT differs (the engine
# owns the page pool).

class ServeStep(NamedTuple):
    """What a serving step tells its model's ``serve_runs``: the tokens'
    positions ``[B, S]`` and a decode tick's kernel setting (for a model
    whose mlp has a kernel; the attention's goes to ``paged_attend``)."""
    positions: jax.Array
    use_flash: bool = False
    interpret: bool = False


def _serve_layers(params, tok, positions, cfg, mesh, attend, state=None,
                  use_flash: bool = False, interpret: bool = False):
    """The skeleton under the three serving steps, for every served
    model: embed ``tok [B, S]``, then each of the model's layer runs
    (``serve_runs``: one for the Llama family, the dense layers and the
    expert layers for GLM-4.7-Flash) scanned through the frame over
    (layers, cache layer index), ``cfg.loops`` times over the same
    stacked weights (:func:`~horovod_tpu.models.layers.looped`).
    ``attend(..., li, state) -> (o, (state, out))`` is the step's own,
    handed what the run's mixer hands it (rotated q and grouped k, v; or
    q, the latent, the shared key and the up-projection): where the
    layer's cache entries go and what q attends over; ``li`` is the cache
    layer, ``t * n_layers + l`` in pass ``t``; ``state`` (the pools)
    rides the scan's carry, ``out`` (a layer's entries) is stacked,
    ``cache_layers`` deep (a model with state slots hands them on beside
    its pools in ``state``, and its prefill's ``out`` holds a row's state
    beside its entries).  The frame and what the final norm reads of the
    residual are the model's (``serve_frame``, ``serve_readout``);
    ``use_flash`` and ``interpret``, a decode tick's kernel setting, go
    to ``serve_runs`` in the :class:`ServeStep`.  Returns ``(h, state,
    outs, stats)``, ``stats`` what the model makes of its mlps' extras
    (the expert layers' counts, or nothing)."""
    model = model_of(cfg)
    frame = model.serve_frame(cfg)
    h = model.serve_embed(params["embed"], tok, cfg)
    if mesh is not None:
        h = shd.constrain(h, ("batch", None, None), mesh,
                          model.shard_rules(cfg, mesh))
    renorm = lambda h: rmsnorm(h, params["final_norm"], cfg.rms_eps)
    first, outs, extras = 0, [], []
    for stacked, mixer_of, mlp in model.serve_runs(
            params, cfg, ServeStep(positions, use_flash, interpret)):
        def layer(carry, xs):    # traced here, with this run's pair
            h, state = carry
            lp, li = xs
            h, (state, out), extra = frame(
                h, lp, mixer_of(partial(attend, li=li, state=state)), mlp,
                cfg.rms_eps)
            return (h, state), (out, extra)

        n = cfg.loops * jax.tree.leaves(stacked)[0].shape[0]
        (h, state), (out, extra) = looped(
            layer, (h, state), stacked, cfg.loops, renorm,
            jnp.arange(first, first + n))
        first += n
        outs.append(out)
        extras.append(extra)
    outs = outs[0] if len(outs) == 1 else jax.tree.map(
        lambda *a: jnp.concatenate(a), *outs)
    h = model.serve_readout(params, h, cfg)
    return h, state, outs, model.serve_stats(cfg, extras)


def prefill_step(params, tokens: jax.Array, cfg, *,
                 mesh: Optional[Mesh] = None,
                 last_pos: Optional[jax.Array] = None) -> tuple:
    """Prompt prefill for the serving engine.

    tokens [B, P] int32 → (next-token logits [B, V] fp32, the prompt's
    cache entries, one array ``[cache_layers, B, P, *row]`` for each pool
    of the model's cache (K and V for the Llama family, the latent rows
    for GLM-4.7-Flash), then for a model with state slots one array
    ``[cache_layers, B, *row]`` for each of them (the state at each row's
    ``last_pos``), the step's stats).
    ``last_pos`` [B] selects the logits position per row (bucketed
    prompts are right-padded: the real last token sits at ``len-1``, not
    ``P-1``); None means ``P-1``.
    Causality makes the padded tail inert for every real position, so a
    bucketed prefill emits the same token as an exact-length one."""
    model = model_of(cfg)
    B, P = tokens.shape
    h, _, kept, stats = _serve_layers(
        params, tokens, jnp.broadcast_to(jnp.arange(P), (B, P)), cfg, mesh,
        model.prefill_attend(cfg, mesh, P, last_pos))
    with region("head"):
        if last_pos is None:
            h_last = h[:, -1]
        else:
            h_last = jnp.take_along_axis(
                h, last_pos[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return (final_logits(params, h_last, cfg, ("batch", "vocab"), mesh,
                         model.shard_rules(cfg, mesh)), kept, stats)


def decode_step_paged(params, tok: jax.Array, positions: jax.Array,
                      pools: tuple, tables: jax.Array, cfg, *,
                      mesh: Optional[Mesh] = None, use_flash: bool = False,
                      interpret: bool = False, slots: tuple = ()) -> tuple:
    """One decode tick for the serving engine against the paged pools.

    tok [B] int32 (this tick's input token per slot); positions [B] its
    absolute position; ``pools`` the model's cache, each ``[cache_layers,
    NB, BS, *row]`` (K and V ``[.., KV, Dh]`` for the Llama family, one
    pool of latent rows for GLM-4.7-Flash); tables
    [B, n_cols] int32 block tables (inactive rows all-scratch).  Each
    layer writes its fresh entries into ``tables[b][positions[b] // BS]``
    at offset ``positions[b] % BS`` and attends over the table's logical
    window with a per-request ``<= position`` mask (stale slots masked).
    The attention reads the pool either through a contiguous gather (XLA
    path, GSPMD-shardable) or in the model's Pallas paged kernel, which
    copies each stream's live pages in by the scalar-prefetched table
    (``use_flash``; callers check the model's ``paged_kernel_ok``); a
    model's expert layers take the same setting (GLM-4.7-Flash's routed
    experts go through ``hvd_moe_decode`` under it).
    ``slots``: the model's state slot arrays ``[cache_layers, B, *row]``
    (its ``state_rows``; none for attention alone), row b read and
    written by row b's token.
    Returns (logits [B, V] fp32, pools, slots, stats) — pass the pools
    and slots donated so the writes land in place."""
    model = model_of(cfg)
    B = tok.shape[0]
    BS = pools[0].shape[2]
    T = tables.shape[1] * BS
    mask = (jnp.arange(T)[None, :] <= positions[:, None])[:, None, :]
    blk = tables[jnp.arange(B), positions // BS]                # [B]
    n = len(pools)
    h, state, _, stats = _serve_layers(
        params, tok[:, None], positions[:, None], cfg, mesh,
        model.paged_attend(cfg, mesh, tables, blk, positions % BS, mask,
                           positions if use_flash else None, interpret),
        tuple(pools) + tuple(slots), use_flash, interpret)
    logits = final_logits(params, h[:, 0], cfg, ("batch", "vocab"), mesh,
                          model.shard_rules(cfg, mesh))
    return logits, state[:n], state[n:], stats


def extend_step_paged(params, tok: jax.Array, positions: jax.Array,
                      valid: jax.Array, pools: tuple, tables: jax.Array,
                      cfg, *, mesh: Optional[Mesh] = None) -> tuple:
    """Multi-token paged forward: S tokens per row in ONE dispatch.

    The serving front door's verify-forward entry — it serves both
    (a) **prefix-hit tail prefill**: a prompt whose head is already in
    the pool (radix prefix cache) prefills only its tail while attending
    over the cached prefix, and (b) **speculative-decode verify**:
    the target model scores ``k + 1`` positions (last accepted token +
    k draft tokens) in one forward so the accepted prefix falls out of a
    single logits comparison.

    tok [B, S] int32; positions [B, S] absolute positions per token;
    valid [B, S] bool — False slots (right-padding, inactive verify
    rows) route their cache writes to scratch block 0 so a padded slot
    repeating a real position can never double-write a live (block,
    offset); their logits are meaningless and must be ignored.
    ``pools`` as :func:`decode_step_paged`'s; tables [B, n_cols] int32.

    Each layer writes all S fresh entries first, then attends over the
    table's logical window with the per-token causal mask ``pool_pos <=
    positions[b, s]`` — so token s sees the cached prefix AND the
    earlier tokens of this same call (their entries just landed in the
    pool), exactly the visibility a monolithic prefill gives it.  Reads
    go through the contiguous-gather path (GSPMD-shardable); the Pallas
    decode kernels are single-query and do not apply here.  Returns
    (logits [B, S, V] fp32, pools, stats) — donate the pools.  A model
    with state slots refuses it (``check_servable``'s ``"extend"``)."""
    model = model_of(cfg)
    model.check_servable(cfg, mesh, path="extend")
    BS = pools[0].shape[2]
    T = tables.shape[1] * BS
    mask = jnp.arange(T)[None, None, :] <= positions[:, :, None]  # [B,S,T]
    blk = jnp.where(valid,
                    jnp.take_along_axis(tables, positions // BS, axis=1),
                    0)                                             # [B,S]
    off = jnp.where(valid, positions % BS, 0)
    h, pools, _, stats = _serve_layers(
        params, tok, positions, cfg, mesh,
        model.paged_attend(cfg, mesh, tables, blk, off, mask), tuple(pools))
    return (final_logits(params, h, cfg, ("batch", None, "vocab"), mesh,
                         model.shard_rules(cfg, mesh)), pools, stats)
