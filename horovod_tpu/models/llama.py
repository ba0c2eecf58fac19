"""Llama-family transformer — the flagship model (BASELINE config 4).

The reference has no model engine (Horovod is a collective layer; its Llama
story would be "bring your own torch model"), so this is built TPU-first:

- **Layout**: params carry logical dimension names mapped to mesh axes by
  :mod:`horovod_tpu.parallel.sharding` — Megatron-style tp on heads/mlp,
  fsdp (ZeRO-3) on the embed dim at rest, layer stack over pp, experts over
  ep.  GSPMD inserts the tp/fsdp collectives; explicit ``shard_map`` blocks
  handle the two patterns compilers don't infer well: ring attention over sp
  and MoE dispatch over ep.
- **Compute**: bfloat16 activations/weights with fp32 RMSNorm/softmax/loss
  accumulation (MXU-native mix); RoPE; GQA; SwiGLU; optional Switch-MoE MLP.
- **Control flow**: one ``lax.scan`` over stacked layer params (single
  compiled layer body; compile time independent of depth) with
  ``jax.checkpoint`` rematerialization per layer.
- **One layer body**: :func:`horovod_tpu.models.layers.block` with
  :func:`~horovod_tpu.models.layers.gqa_mixer`.  Training, the pipelined
  regions, :func:`generate` and the three serving steps each pass their
  own ``attend`` (where K and V go, what q attends over) and nothing else.
"""

from __future__ import annotations

import dataclasses
import sys
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.trace import region
from ..ops import flash_attention as FA
from ..parallel import sharding as shd
from ..parallel.moe import moe_layer_local
from .layers import (  # noqa: F401  (attention_path: re-exported)
    POOL_DIMS,
    attention,
    attention_path,
    block,
    cached_attend,
    causal_lm_loss,
    dense_mlp,
    embed_lookup,
    gather_blocks,
    gqa_mixer,
    looped,
    remat,
    rmsnorm,
    rope_tables,
    sp_local_attention,
)

_THIS = sys.modules[__name__]     # make_train_step's default model


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    use_moe: bool = False
    n_experts: int = 8
    capacity_factor: float = 1.25
    # Rematerialization of the layer body: True = full per-layer remat
    # (least memory: the backward runs each layer's forward again),
    # False = save everything, or "dots" = jax.checkpoint with the
    # dots_with_no_batch_dims_saveable policy (matmul outputs saved, the
    # rest recomputed).  What each costs a step is ROADMAP Speed 3's to
    # measure on the training cells; no ledger line prices it yet.
    remat: Any = True
    moe_aux_weight: float = 0.01
    # pp microbatch count (None = auto: most M <= 2*pp dividing the local
    # batch).  More microbatches shrink the pipeline bubble
    # ((pp-1)/(M+pp-1) for both schedules); 1F1B keeps activation memory
    # flat in M, so large M is cheap there.
    pp_microbatches: Optional[int] = None
    # Sequence-parallel attention flavor on sp>1 meshes: "ring" (blockwise
    # KV rotation over ppermute — memory O(local_seq^2), any head count)
    # or "ulysses" (all_to_all heads<->sequence swap — full-sequence
    # attention on a head subset; needs local heads divisible by sp,
    # preferable when heads >> sp and the sequence fits).
    sp_attention: str = "ring"
    # Unroll factor for the layer scan in the non-pipelined forward
    # (lax.scan's ``unroll``).  1 = compile one layer body (compile time
    # independent of depth).  n_layers = fully unrolled: the rolled
    # scan's per-layer dynamic-update-slice copies of the stacked
    # residuals go and XLA fuses across layer boundaries, at a compile
    # time linear in depth.  Whether those copies cost a cell anything is
    # ROADMAP Speed 3's last question.
    scan_unroll: int = 1
    # Blockwise (online-softmax) cross-entropy (ops/losses.py): trades
    # one extra lm_head matmul for never materializing the [B,S,V] fp32
    # logits: an opt-in memory lever for configs that do not otherwise
    # fit.  Its price in tokens/s and in temporaries is ROADMAP Speed 8's
    # to measure on the chip.
    blockwise_ce: bool = False
    # RMSNorm's epsilon, every norm of the model.
    rms_eps: float = 1e-5
    # Sandwich norm: each layer norms its attention's and its MLP's output
    # once more (leaves attn_post_norm, mlp_post_norm) before the
    # residual takes it.
    sandwich_norm: bool = False
    # A looped stack: the n_layers layers run ``loops`` times over the
    # same weights, each pass closed by the final norm, the head on the
    # last pass's output.  A query of pass t attends to the keys and
    # values pass t made, so the serving cache is ``cache_layers`` deep.
    # Every token takes every pass (no early exit).  Served and
    # generated; training such a stack wants the objective over the
    # passes' exits, which is not here (make_train_step refuses).
    loops: int = 1

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def cache_layers(self) -> int:
        """Layers of K and V a token leaves in a cache: one for every
        (pass, layer)."""
        return self.loops * self.n_layers

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Test-scale config (fast CPU compile)."""
        base = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, d_ff=128, dtype=jnp.float32, remat=False)
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def llama2_7b(**kw) -> "LlamaConfig":
        base = dict(vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
                    n_kv_heads=32, d_ff=11008)
        base.update(kw)
        return LlamaConfig(**base)


# Logical dims for every parameter (leaf-name -> dims); layer-stacked leaves
# get a leading "stage" dim (mapped to pp).
def param_logical_dims(cfg: LlamaConfig) -> dict:
    layer = {
        "attn_norm": ("stage", None),
        "wq": ("stage", "embed", "heads", "head_dim"),
        "wk": ("stage", "embed", "kv_heads", "head_dim"),
        "wv": ("stage", "embed", "kv_heads", "head_dim"),
        "wo": ("stage", "heads", "head_dim", "embed"),
        "mlp_norm": ("stage", None),
    }
    if cfg.sandwich_norm:
        layer.update({"attn_post_norm": ("stage", None),
                      "mlp_post_norm": ("stage", None)})
    if cfg.use_moe:
        layer.update({
            "router": ("stage", None, None),
            "w_gate": ("stage", "experts", "embed", "expert_mlp"),
            "w_up": ("stage", "experts", "embed", "expert_mlp"),
            "w_down": ("stage", "experts", "expert_mlp", "embed"),
        })
    else:
        layer.update({
            "w_gate": ("stage", "embed", "mlp"),
            "w_up": ("stage", "embed", "mlp"),
            "w_down": ("stage", "mlp", "embed"),
        })
    return {
        "embed": ("vocab_rows", None),
        "layers": layer,
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }


def shard_rules(cfg: LlamaConfig, mesh: Optional[Mesh]) -> Optional[dict]:
    """Mesh-aware logical-rule overrides for this config.

    GQA configs where tp divides ``n_heads`` but not ``n_kv_heads`` (e.g.
    kv=2 on a tp=4 mesh) degrade the ``kv_heads`` rule to a dividing
    prefix or replication instead of failing init with an indivisible
    sharding — the flash path then keeps the kernel by expanding K/V at
    dispatch (see :func:`_attention`)."""
    if mesh is None:
        return None
    return shd.fitted_rules(mesh, {
        "heads": cfg.n_heads,
        "kv_heads": cfg.n_kv_heads,
    })


def param_shardings(cfg: LlamaConfig, mesh: Mesh) -> dict:
    rules = shard_rules(cfg, mesh)
    return jax.tree.map(
        lambda dims: shd.logical_sharding(mesh, dims, rules),
        param_logical_dims(cfg),
        is_leaf=lambda x: isinstance(x, tuple))


def init_params(cfg: LlamaConfig, key: jax.Array, mesh: Optional[Mesh] = None
                ) -> dict:
    """Initialize parameters, sharded per the logical rules when a mesh is
    given (init runs jitted with out_shardings so full weights never
    materialize on one device)."""
    L, D, H, KV, Dh, F = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                          cfg.n_kv_heads, cfg.head_dim, cfg.d_ff)

    def build(key):
        ks = jax.random.split(key, 12)
        scale = lambda fan_in: 1.0 / np.sqrt(fan_in)
        norm = lambda shape: jnp.ones(shape, jnp.float32)
        rnd = lambda k, shape, fan: (
            jax.random.normal(k, shape, jnp.float32) * scale(fan)
        ).astype(cfg.dtype)
        layers = {
            "attn_norm": norm((L, D)),
            "wq": rnd(ks[0], (L, D, H, Dh), D),
            "wk": rnd(ks[1], (L, D, KV, Dh), D),
            "wv": rnd(ks[2], (L, D, KV, Dh), D),
            "wo": rnd(ks[3], (L, H, Dh, D), H * Dh),
            "mlp_norm": norm((L, D)),
        }
        if cfg.sandwich_norm:
            layers.update({"attn_post_norm": norm((L, D)),
                           "mlp_post_norm": norm((L, D))})
        if cfg.use_moe:
            E = cfg.n_experts
            layers.update({
                "router": rnd(ks[4], (L, D, E), D).astype(jnp.float32),
                "w_gate": rnd(ks[5], (L, E, D, F), D),
                "w_up": rnd(ks[6], (L, E, D, F), D),
                "w_down": rnd(ks[7], (L, E, F, D), F),
            })
        else:
            layers.update({
                "w_gate": rnd(ks[5], (L, D, F), D),
                "w_up": rnd(ks[6], (L, D, F), D),
                "w_down": rnd(ks[7], (L, F, D), F),
            })
        return {
            "embed": rnd(ks[8], (cfg.vocab_size, D), D),
            "layers": layers,
            "final_norm": jnp.ones((D,), jnp.float32),
            "lm_head": rnd(ks[9], (D, cfg.vocab_size), D),
        }

    if mesh is None:
        return build(key)
    shardings = param_shardings(cfg, mesh)
    return jax.jit(build, out_shardings=shardings)(key)


_EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _expert_swiglu(w, x):
    """One expert's SwiGLU on its rows: ``w`` its leaves, ``x [cap, D]``."""
    g = jax.nn.silu(x @ w["w_gate"])
    u = x @ w["w_up"]
    return (g * u) @ w["w_down"]


def _expert_swiglu_tp(w, x):
    """The same inside a region manual over tp, the expert's hidden dim
    Megatron-sliced: the row-parallel product summed over tp."""
    return lax.psum(_expert_swiglu(w, x), "tp")


def _moe_mlp(h2, lp, cfg: LlamaConfig, mesh: Optional[Mesh]):
    """Switch-MoE MLP: SwiGLU experts over the ep axis."""
    B, S, D = h2.shape
    flat = h2.reshape(B * S, D)

    eparams = {k: lp[k] for k in _EXPERT_LEAVES}
    ep = mesh.shape.get("ep", 1) if mesh is not None else 1
    if ep > 1:
        # Manual over every mesh axis: dp/fsdp/ep all count as token
        # axes so each ep rank dispatches distinct local tokens
        # (mirroring the pp path), and the expert hidden dim is
        # Megatron-sliced over tp with an explicit row-parallel psum.
        all_axes = tuple(mesh.axis_names)

        def local_moe(tok, rk, pr):
            out, aux = moe_layer_local(
                tok, rk, _expert_swiglu_tp, pr, axis_name="ep",
                capacity_factor=cfg.capacity_factor)
            # pmean over every axis: data axes average the per-shard aux
            # into the global mean; replicated axes (tp/pp) are forward
            # no-ops that keep the transpose psum correctly 1/n-scaled.
            return out, lax.pmean(aux, all_axes)

        espec = {"w_gate": P("ep", None, "tp"),
                 "w_up": P("ep", None, "tp"),
                 "w_down": P("ep", "tp", None)}
        # Pin the token sharding OUTSIDE the region to the plain batch
        # axes: without the pin the boundary's dp·fsdp·ep spec propagates
        # an 8-way batch sharding back onto the residual stream, which
        # collides with the fsdp embed sharding of the dense weights
        # (involuntary full rematerialization).  The ep refinement then
        # happens at the shard_map boundary as a cheap slice.
        token_pin = NamedSharding(mesh, P(("dp", "fsdp")))
        flat = jax.lax.with_sharding_constraint(flat, token_pin)
        fn = shard_map(
            local_moe,
            mesh=mesh,
            in_specs=(P(("dp", "fsdp", "ep")), P(), espec),
            out_specs=(P(("dp", "fsdp", "ep")), P()),
            check_vma=False)
        out, aux = fn(flat, lp["router"].astype(jnp.float32), eparams)
        out = jax.lax.with_sharding_constraint(out, token_pin)
    else:
        # Single expert group: same math without the exchange.
        from ..parallel.moe import switch_route
        E = cfg.n_experts
        cap = max(1, int(flat.shape[0] * cfg.capacity_factor / E))
        with region("moe.route"):
            logits = flat.astype(jnp.float32) @ \
                lp["router"].astype(jnp.float32)
            dispatch, combine, aux, _drops = switch_route(logits, cap)
        with region("moe.experts"):
            einputs = jnp.einsum("tec,td->ecd", dispatch.astype(flat.dtype),
                                 flat)
            eouts = jax.vmap(_expert_swiglu)(eparams, einputs)
        with region("moe.combine"):
            out = jnp.einsum("tec,ecd->td", combine.astype(flat.dtype),
                             eouts)
    return out.reshape(B, S, D), aux


def _pick_microbatches(batch: int, mesh: Mesh,
                       requested: Optional[int] = None) -> int:
    """Microbatch count for the pipeline: ``requested``
    (cfg.pp_microbatches) when set, else the most <= 2*pp that divides
    the LOCAL batch (GPipe bubble (S-1)/(M+S-1); callers with large
    batches get M = 2*pp).  The microbatch split happens inside the
    manual region on per-device arrays, so M must divide
    batch/(dp*fsdp*ep); ep counts as a data axis there so MoE dispatch
    sees distinct local tokens per ep rank."""
    pp = mesh.shape.get("pp", 1)
    df = (mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
          * mesh.shape.get("ep", 1))
    if batch % df:
        raise ValueError(
            f"global batch {batch} must divide over dp*fsdp*ep = {df}")
    local = batch // df
    if requested is not None:
        if requested < 1 or local % requested:
            raise ValueError(
                f"pp_microbatches={requested} must divide the local batch "
                f"{local} (= global {batch} / dp*fsdp*ep {df})")
        return requested
    for m in range(min(2 * pp, local), 0, -1):
        if local % m == 0:
            return m
    return 1


def _layer_dims(cfg: LlamaConfig) -> dict:
    """Logical dims of one layer's leaves (the leading "stage" dropped)."""
    return {k: d[1:] for k, d in param_logical_dims(cfg)["layers"].items()}


def _layer_specs(cfg: LlamaConfig) -> dict:
    """PartitionSpecs of the stacked layer leaves at rest: the in/out
    specs of the manual regions."""
    return jax.tree.map(shd.spec_for, param_logical_dims(cfg)["layers"],
                        is_leaf=lambda x: isinstance(x, tuple))


def _gather_fsdp(lp, cfg: LlamaConfig):
    """ZeRO-3 gather inside a manual region: reassemble the embed dim of
    one layer's weights from their fsdp shards; transpose = reduce-scatter
    of the grads."""
    layer_dims = _layer_dims(cfg)
    out = {}
    for k, leaf in lp.items():
        for i, dname in enumerate(layer_dims[k]):
            if dname == "embed":
                leaf = lax.all_gather(leaf, "fsdp", axis=i, tiled=True)
        out[k] = leaf
    return out


def _tp_sum(f):
    """A mixer or mlp whose last product is row-parallel, inside a region
    manual over tp: the partial products summed over tp (Megatron)."""
    def summed(x, lp):
        y, extra = f(x, lp)
        return lax.psum(y, "tp"), extra
    return summed


def _check_stage_split(cfg: LlamaConfig, mesh: Mesh) -> None:
    pp, tp = mesh.shape["pp"], mesh.shape.get("tp", 1)
    if cfg.loops > 1:
        raise NotImplementedError(
            "a looped stack (loops > 1) has no pipelined form: every pass "
            "would cross all the stages; use a pp=1 mesh")
    if cfg.n_layers % pp:
        raise ValueError(
            f"pp={pp} must divide n_layers={cfg.n_layers} evenly")
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        raise ValueError(
            f"tp={tp} must divide n_heads={cfg.n_heads} and "
            f"n_kv_heads={cfg.n_kv_heads}")


def _pp_machinery(cfg: LlamaConfig, mesh: Mesh, causal: bool, S: int) -> dict:
    """Shared layer-stack machinery for the pipelined paths (GPipe forward
    and 1F1B training): the fully-manual layer body with Megatron-tp psums,
    ZeRO-3 fsdp gathers, ring attention over sp, MoE over ep — and the
    in/out specs matching the at-rest parameter shardings."""
    _check_stage_split(cfg, mesh)
    sp = mesh.shape.get("sp", 1)
    if S % sp:
        raise ValueError(f"sp={sp} must divide sequence length {S}")
    S_loc = S // sp

    def attend(q, k, v):
        # q is this rank's shard already (the region is manual over
        # every axis), so the mesh-free dispatch applies to it as is.
        if sp > 1:
            k, v = FA.gqa_expand(q, k, v)
            return sp_local_attention(cfg.sp_attention)(
                q, k, v, axis_name="sp", causal=causal), None
        return attention(q, k, v, None, causal), None

    def moe_mlp_local(x2, lp):
        Bq, Sq, Dq = x2.shape
        flat = x2.reshape(Bq * Sq, Dq)
        out, aux = moe_layer_local(
            flat, lp["router"].astype(jnp.float32), _expert_swiglu_tp,
            {k: lp[k] for k in _EXPERT_LEAVES},
            axis_name="ep", capacity_factor=cfg.capacity_factor)
        # pmean includes tp (a forward no-op — aux is tp-replicated) so the
        # aux gradient path is 1/tp-scaled per rank; the 1F1B step blanket-
        # psums replicated-param grads over tp, and without this the
        # routing-only aux path (which unlike the CE path has no tp-sharded
        # op on it) would count tp times.
        return (out.reshape(Bq, Sq, Dq),
                lax.pmean(aux, ("dp", "fsdp", "ep", "sp", "tp")))

    # Row-parallel wo and w_down; the expert layer sums over tp itself.
    mlp = moe_mlp_local if cfg.use_moe else _tp_sum(dense_mlp)

    def layer_body(h, lp, rope):
        h, _, aux = block(
            h, _gather_fsdp(lp, cfg),
            _tp_sum(partial(gqa_mixer, tables=rope, attend=attend)), mlp,
            cfg.rms_eps)
        return h, jnp.zeros((), jnp.float32) if aux is None else aux

    body = remat(layer_body, cfg.remat)

    def make_stage_fn(rows: int):
        # RoPE tables once per step (tick-invariant), not per tick, for
        # this sp rank's positions and ``rows`` sequences a microbatch.
        base = lax.axis_index("sp") * S_loc + jnp.arange(S_loc)
        rope = rope_tables(jnp.broadcast_to(base[None, :], (rows, S_loc)),
                           cfg.rope_theta, cfg.head_dim)

        def stage_fn(local_layers, x):
            # One pp rank's resident layers applied in sequence (scan: one
            # compiled body regardless of depth).
            def scan_body(carry, lp):
                hc, aux = carry
                hc, a = body(hc, lp, rope)
                return (hc, aux + a), None

            (out, aux), _ = lax.scan(
                scan_body, (x, jnp.zeros((), jnp.float32)), local_layers)
            return out, aux

        return stage_fn

    return {
        "make_stage_fn": make_stage_fn,
        "layer_specs": _layer_specs(cfg),
        "act_spec": P(("dp", "fsdp", "ep"), "sp", None),
        "S_loc": S_loc,
    }


def _head(params, h, cfg: LlamaConfig, dims=None,
          mesh: Optional[Mesh] = None, rules=None):
    """Final norm and lm_head on ``h [..., D]``: float32 logits, pinned to
    the logical ``dims`` under a mesh."""
    with region("head"):
        logits = jnp.einsum(
            "...d,dv->...v", rmsnorm(h, params["final_norm"], cfg.rms_eps),
            params["lm_head"])
        if mesh is not None:
            logits = shd.constrain(logits, dims, mesh, rules)
        return logits.astype(jnp.float32)


def _forward_pipelined(params: dict, tokens: jax.Array, cfg: LlamaConfig,
                       mesh: Mesh, causal: bool
                       ) -> tuple[jax.Array, jax.Array]:
    """pp>1 path: the layer stack runs as a real GPipe microbatch schedule
    (:func:`horovod_tpu.parallel.pipeline.pipeline_apply_local`) with each
    stage's parameters RESIDENT on its pp rank and activations handed over
    with ``ppermute`` — never a per-layer parameter gather across pp (the
    anti-pattern this replaces: scanning a pp-sharded layer stack makes
    GSPMD all-gather every layer's weights each step, turning the one axis
    meant to tolerate DCN into a per-layer DCN fetch).

    The pipeline shard_map is manual over ALL mesh axes (round-4 redesign:
    the previous pp-only-manual version nested a flash shard_map on the
    auto axes, whose gradients through the tick loop came out 1.4x off —
    full-manual removes the nesting entirely).  Inside the region the
    parallelism axes compose explicitly, Megatron-style:

    - tp: heads/mlp-hidden locally sliced, one ``psum`` after each row-
      parallel projection (wo, w_down);
    - fsdp: ZeRO-3 — weights arrive sharded on the embed dim and are
      ``all_gather``-ed per layer at use (re-gathered in the backward under
      remat), gradients exit via the all_gather transpose (reduce-scatter);
    - sp: ring attention (``ring_attention_local``) with RoPE positions
      offset per sp rank;
    - ep: the microbatch is sharded over dp×fsdp×ep so each ep rank owns
      distinct tokens, and MoE dispatch is ``moe_layer_local``'s a2a;
    - dp: pure batch sharding; weight-grad psums over replicated axes come
      from the shard_map transpose.

    Attention runs the Pallas flash kernel on TPU when the LOCAL shard
    shape supports it (direct call — no nested shard_map), ring attention
    when sp>1, dense XLA otherwise.
    """
    parts = _pp_machinery(cfg, mesh, causal, tokens.shape[1])
    make_stage_fn, S_loc = parts["make_stage_fn"], parts["S_loc"]
    from ..parallel.pipeline import pipeline_apply_local

    B, S = tokens.shape
    D = cfg.d_model
    h = embed_lookup(params["embed"], tokens, cfg.dtype)   # [B,S,D]
    h = shd.constrain(h, ("batch", "seq", None), mesh)
    M = _pick_microbatches(B, mesh, cfg.pp_microbatches)

    def local(local_layers, h_loc):
        # The microbatch split happens HERE, on the local shard: splitting
        # [B,S,D] -> [M,mb,S,D] outside the shard_map moves the batch
        # sharding onto the microbatch dim across a reshape GSPMD cannot
        # follow (involuntary full rematerialization at the boundary —
        # caught by the round-4 verify drive).
        B_loc = h_loc.shape[0]
        mbs = h_loc.reshape(M, B_loc // M, S_loc, D)
        out, aux = pipeline_apply_local(
            make_stage_fn(B_loc // M), local_layers, mbs, axis_name="pp",
            with_aux=True)
        return out.reshape(B_loc, S_loc, D), aux

    layer_specs, act_spec = parts["layer_specs"], parts["act_spec"]
    fn = shard_map(local, mesh=mesh, in_specs=(layer_specs, act_spec),
                   out_specs=(act_spec, P()), check_vma=False)
    h, aux = fn(params["layers"], h)
    h = shd.constrain(h, ("batch", "seq", None), mesh)
    return _head(params, h, cfg, ("batch", "seq", "vocab"), mesh), aux


def forward(params: dict, tokens: jax.Array, cfg: LlamaConfig, *,
            mesh: Optional[Mesh] = None, causal: bool = True,
            return_hidden: bool = False
            ) -> tuple[jax.Array, jax.Array]:
    """Logits for next-token prediction.  Returns (logits, moe_aux_loss);
    with ``return_hidden`` the final normed hidden states ``[B,S,D]``
    come back instead of logits (the blockwise-CE loss applies the
    lm_head itself, vocab block by vocab block)."""
    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        assert not return_hidden, "blockwise CE requires a pp=1 mesh"
        return _forward_pipelined(params, tokens, cfg, mesh, causal)
    B, S = tokens.shape
    h = embed_lookup(params["embed"], tokens, cfg.dtype)   # [B,S,D]
    h = shd.constrain(h, ("batch", "seq", None), mesh) if mesh else h
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    rope = rope_tables(positions, cfg.rope_theta, cfg.head_dim)
    if mesh is not None:
        # Per-layer rule shardings for the scanned slices (leading "stage"
        # dim dropped).  Pinning the slices inside the body stops GSPMD's
        # propagator from deriving batch-flavored shardings for loop-body
        # weights — the source of "involuntary full rematerialization"
        # resharding on every layer (round-2 verdict finding).
        layer_dims = _layer_dims(cfg)
        rules = shard_rules(cfg, mesh)

    mixer = partial(
        gqa_mixer, tables=rope,
        attend=lambda q, k, v: (attention(q, k, v, mesh, causal,
                                          cfg.sp_attention), None))
    mlp = partial(_moe_mlp, cfg=cfg, mesh=mesh) if cfg.use_moe else dense_mlp

    def layer_body(carry, lp):
        h, aux = carry
        if mesh is not None:
            lp = {k: shd.constrain(v, layer_dims[k], mesh, rules)
                  for k, v in lp.items()}
        h, _, moe_aux = block(h, lp, mixer, mlp, cfg.rms_eps)
        if moe_aux is not None:
            aux = aux + moe_aux
        if mesh is not None:
            h = shd.constrain(h, ("batch", "seq", None), mesh)
        return (h, aux), None

    body = remat(layer_body, cfg.remat)
    final_norm = lambda h: rmsnorm(h, params["final_norm"], cfg.rms_eps)

    (h, aux), _ = looped(lambda carry, x: body(carry, x[0]),
                         (h, jnp.zeros((), jnp.float32)), params["layers"],
                         cfg.loops, final_norm, unroll=cfg.scan_unroll)
    if return_hidden:
        with region("head"):
            return final_norm(h), aux
    return _head(params, h, cfg, ("batch", "seq", "vocab"), mesh), aux


def _pick_token(logits, step_key, temperature, dtype):
    """Greedy or temperature sampling from [B, V] fp32 logits."""
    with region("head"):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(dtype)
        return jax.random.categorical(
            step_key, logits / temperature, axis=-1).astype(dtype)


def _pin_kv(cfg: LlamaConfig, mesh: Optional[Mesh]):
    """Constraint for K/V ``[B, T, KV, Dh]``: batch over dp/fsdp, heads
    over tp.  Without the annotation the propagator happily replicates a
    cache — the largest live tensor of the whole decode — on every tp
    rank."""
    if mesh is None:
        return lambda c: c
    rules = shard_rules(cfg, mesh)
    return lambda c: shd.constrain(c, ("batch", None, "kv_heads", None),
                                   mesh, rules)


def _sample_loop(prefill, tick, head, prompt, max_new_tokens: int,
                 temperature: float, key):
    """The token loop of :func:`generate` on either kind of mesh.
    ``prefill(prompt) -> (h [B, P, D], caches)`` and ``tick(caches, tok
    [B], pos) -> (h [B, 1, D], caches)`` run the layer stack;
    ``head(h [B, D])`` gives the logits a token is picked from."""
    h, caches = prefill(prompt)
    key, k0 = jax.random.split(key)
    first_new = _pick_token(head(h[:, -1]), k0, temperature, prompt.dtype)

    def decode_step(carry, step_key):
        caches, tok, pos = carry
        h, caches = tick(caches, tok, pos)
        nxt = _pick_token(head(h[:, 0]), step_key, temperature, prompt.dtype)
        return (caches, nxt, pos + 1), nxt

    # max_new_tokens - 1 decode steps: the first new token came from the
    # prefill logits, and collecting each step's OUTPUT token means no
    # trailing step whose result would be discarded.
    carry0 = (caches, first_new, jnp.asarray(prompt.shape[1], jnp.int32))
    _, toks = lax.scan(decode_step, carry0,
                       jax.random.split(key, max_new_tokens - 1))
    new_toks = jnp.concatenate([first_new[:, None], toks.swapaxes(0, 1)],
                               axis=1)
    return jnp.concatenate([prompt, new_toks], axis=1)


def _cache_stack(cfg: LlamaConfig, h, layers, ck, cv, pos,
                 pin=lambda c: c, gather=lambda lp: lp,
                 row_parallel=lambda f: f, renorm=None):
    """The layer stack of the dense-cache decoders (:func:`generate` and
    its pp form) on ``h [B, S, D]``, the tokens at positions ``pos`` on,
    against the caches ``ck, cv [loops * L, B, T, KV, Dh]``: each layer's
    fresh K/V go into the cache of its pass (``pin`` constrains the
    result; ``renorm``, the final norm, stands between passes).  One token a
    row (a decode tick) scores q against the cache; a prompt scores it
    against its own keys only — scoring the zero-padded T-length cache
    would pay T/P times the prefill attention FLOPs on masked slots.
    Inside a manual region ``gather`` and ``row_parallel`` are
    :func:`_gather_fsdp` and :func:`_tp_sum`.  Returns ``(h, (ck, cv))``."""
    B, S = h.shape[:2]
    prompt = S > 1
    scale = 1.0 / np.sqrt(cfg.head_dim)
    tables = rope_tables(jnp.broadcast_to(pos + jnp.arange(S), (B, S)),
                         cfg.rope_theta, cfg.head_dim)
    mask = jnp.tril(jnp.ones((S, S), bool)) if prompt \
        else (jnp.arange(ck.shape[2]) <= pos)[None, :]              # [1, T]
    put = lambda c, new: pin(lax.dynamic_update_slice(c, new, (0, pos, 0, 0)))

    def layer(carry, xs):
        h, _ = carry
        lp, (ck, cv) = xs

        def attend(q, k1, v1):
            ck2, cv2 = put(ck, k1), put(cv, v1)
            keys, vals = (k1, v1) if prompt else (ck2, cv2)
            return cached_attend(q, keys, vals, mask, scale), (ck2, cv2)

        h, kept, _ = block(
            h, gather(lp),
            row_parallel(partial(gqa_mixer, tables=tables, attend=attend)),
            row_parallel(dense_mlp), cfg.rms_eps)
        return (h, None), kept

    (h, _), kept = looped(layer, (h, None), layers, cfg.loops, renorm,
                          (ck, cv))
    return h, kept


def _generate_pp(params: dict, prompt: jax.Array, cfg: LlamaConfig,
                 mesh: Mesh, max_new_tokens: int, temperature: float,
                 key: jax.Array) -> jax.Array:
    """generate() on pp meshes: the layer stack stays stage-RESIDENT
    (never gathered across pp) and the KV cache lives sharded
    [L/pp, B/(dp·fsdp), T, KV/tp, Dh] per rank.

    Prefill and each decode tick run one fully-manual shard_map over the
    whole mesh: the activation visits stages sequentially (python loop
    over pp with ``lax.cond`` so only the active stage computes, then a
    ``ppermute`` handoff — single-microbatch decoding cannot hide the
    pipeline bubble, so the schedule is a plain chain), with Megatron tp
    psums and per-layer fsdp weight gathers inside the stage exactly as
    in the training region (:func:`_pp_machinery`).  Embedding, loss
    head and sampling run OUTSIDE the region under automatic GSPMD, as
    in the 1F1B step.  MoE decode stays out of scope (ep is an expert-
    dispatch training axis; rejected in :func:`generate`)."""
    B, Plen = prompt.shape
    T = Plen + max_new_tokens
    pp = mesh.shape["pp"]
    tp = mesh.shape.get("tp", 1)
    KV, Dh = cfg.n_kv_heads, cfg.head_dim
    dpf = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    _check_stage_split(cfg, mesh)
    if B % dpf:
        raise ValueError(f"batch {B} must divide over dp*fsdp = {dpf}")
    layer_specs = _layer_specs(cfg)
    cache_spec = P("pp", ("dp", "fsdp"), None, "tp", None)
    act_spec = P(("dp", "fsdp"), None, None)
    perm = [(i, i + 1) for i in range(pp - 1)]

    def stages_local(layers_loc, ck, cv, h, pos):
        stage = lambda op: _cache_stack(
            cfg, *op, pos, gather=partial(_gather_fsdp, cfg=cfg),
            row_parallel=_tp_sum)
        idx = lax.axis_index("pp")
        for s_ in range(pp):
            h, (ck, cv) = lax.cond(
                idx == s_, stage, lambda op: (op[0], (op[2], op[3])),
                (h, layers_loc, ck, cv))
            if s_ < pp - 1:
                h = lax.ppermute(h, "pp", perm)
        # Replicate the last stage's output over pp (out_specs say so).
        return lax.psum(
            jnp.where(idx == pp - 1, h, jnp.zeros_like(h)), "pp"), ck, cv

    def prefill_local(layers_loc, h_loc):
        L_loc = jax.tree.leaves(layers_loc)[0].shape[0]
        ck0 = jnp.zeros((L_loc, h_loc.shape[0], T, KV // tp, Dh), cfg.dtype)
        return stages_local(layers_loc, ck0, ck0, h_loc, 0)

    def embed(tok):
        h = embed_lookup(params["embed"], tok, cfg.dtype)
        return shd.constrain(h, ("batch", None, None), mesh)

    def prefill(prompt):
        h, ck, cv = shard_map(
            prefill_local, mesh=mesh, in_specs=(layer_specs, act_spec),
            out_specs=(act_spec, cache_spec, cache_spec),
            check_vma=False)(params["layers"], embed(prompt))
        return h, (ck, cv)

    def tick(caches, tok, pos):
        h, ck, cv = shard_map(
            stages_local, mesh=mesh,
            in_specs=(layer_specs, cache_spec, cache_spec, act_spec, P()),
            out_specs=(act_spec, cache_spec, cache_spec),
            check_vma=False)(params["layers"], *caches, embed(tok[:, None]),
                             pos)
        return h, (ck, cv)

    return _sample_loop(
        prefill, tick, partial(_head, params, cfg=cfg,
                               dims=("batch", "vocab"), mesh=mesh),
        prompt, max_new_tokens, temperature, key)


def generate(params: dict, prompt: jax.Array, cfg: LlamaConfig, *,
             max_new_tokens: int, mesh: Optional[Mesh] = None,
             temperature: float = 0.0,
             key: Optional[jax.Array] = None) -> jax.Array:
    """Autoregressive decoding with a per-layer KV cache.

    ``prompt``: [B, P] int32.  Returns [B, P + max_new_tokens] — the
    prompt with the continuation appended.  ``temperature == 0`` (the
    default) decodes greedily; ``temperature > 0`` samples from
    ``softmax(logits / temperature)`` using ``key`` (required then).  Prefill runs the layer
    stack once over the prompt (causal, batched — MXU-shaped); decode is a
    ``lax.scan`` over new tokens, each step attending to the cache and
    appending its own K/V (O(T·L·cache) instead of re-running the full
    forward per token).  Works pure (mesh=None), under GSPMD meshes whose
    axes are automatic (dp/fsdp/tp — the KV cache is constrained to
    [batch over dp·fsdp, kv_heads over tp], never replicated), or on pp
    meshes via the stage-resident manual path (:func:`_generate_pp`).
    sp/ep stay training-path axes and MoE decode is out of scope
    (expert dispatch is built for training token volumes; rejected
    explicitly).
    """
    if cfg.use_moe:
        raise NotImplementedError("generate does not support MoE configs")
    if mesh is not None and any(
            mesh.shape.get(a, 1) > 1 for a in ("sp", "ep")):
        raise NotImplementedError(
            "generate supports dp/fsdp/tp/pp meshes; sp/ep are "
            "training-path axes")
    if temperature > 0.0 and key is None:
        raise ValueError("temperature > 0 requires a PRNG key")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got "
                         f"{max_new_tokens}")
    if key is None:
        key = jax.random.PRNGKey(0)  # unused when greedy
    if mesh is not None and mesh.shape.get("pp", 1) > 1:
        return _generate_pp(params, prompt, cfg, mesh, max_new_tokens,
                            temperature, key)
    B, P = prompt.shape
    T = P + max_new_tokens
    KV, Dh = cfg.n_kv_heads, cfg.head_dim
    pin = _pin_kv(cfg, mesh)
    renorm = lambda h: rmsnorm(h, params["final_norm"], cfg.rms_eps)

    def prefill(prompt):
        # Build the cache over the prompt.
        cache0 = jnp.zeros((cfg.cache_layers, B, T, KV, Dh), cfg.dtype)
        return _cache_stack(
            cfg, embed_lookup(params["embed"], prompt, cfg.dtype),
            params["layers"], cache0, cache0, 0, pin, renorm=renorm)

    def tick(caches, tok, pos):
        # One token per row, cache append.
        return _cache_stack(
            cfg, embed_lookup(params["embed"], tok[:, None], cfg.dtype),
            params["layers"], *caches, pos, pin, renorm=renorm)

    return _sample_loop(prefill, tick, partial(_head, params, cfg=cfg),
                        prompt, max_new_tokens, temperature, key)


# ---------------------------------------------------------------------------
# Serving entry points (horovod_tpu/serving: continuous batching over a
# block-paged KV cache).  The math mirrors the batch generate() paths op
# for op, so greedy decode through the engine reproduces generate()'s
# tokens; only cache PLACEMENT differs (the engine owns the page pool).
# ---------------------------------------------------------------------------

def model_of(cfg):
    """The module a served configuration belongs to, by its class: this
    one for a :class:`LlamaConfig`,
    :mod:`horovod_tpu.models.glm_moe_lite` for its own.  The serving
    steps ask it for what differs between models and nothing else:
    ``serve_embed``, ``serve_runs`` (the layer runs and their ``(mixer,
    mlp)`` pairs),
    ``prefill_attend`` and ``paged_attend`` (where a layer's cache
    entries go and what a query reads), ``cache_rows`` and ``pool_dims``
    (the pools' geometry), ``serve_stats``, ``check_servable``,
    ``paged_kernel_ok`` and ``PAGED_KERNEL``; where ``serve_stats``
    returns expert layers' counts, the engine also asks for
    ``moe_layer_names`` and ``moe_experts_scored``."""
    model = sys.modules[type(cfg).__module__]
    if not hasattr(model, "serve_runs"):
        raise NotImplementedError(
            f"{model.__name__} has no serving steps (serve_runs and the "
            "functions beside it)")
    return model


#: what :attr:`ServingEngine.attention_path` calls this model's kernel
PAGED_KERNEL = "pallas"


def check_servable(cfg: LlamaConfig, mesh: Optional[Mesh]) -> None:
    """Raise, by name, for what of a :class:`LlamaConfig` the serving
    steps cannot run."""
    if cfg.use_moe:
        raise NotImplementedError(
            "serving does not run the capacity-routed Switch expert layer "
            "(LlamaConfig.use_moe): it drops the tokens over an expert's "
            "capacity, so a request's answer would depend on its batch; "
            "the dropless expert layer is served (models/glm_moe_lite.py)")


def cache_rows(cfg: LlamaConfig) -> tuple:
    """Per-token shape of each pool of the paged cache: keys and values,
    grouped."""
    return ((cfg.n_kv_heads, cfg.head_dim),) * 2


def pool_dims(cfg: LlamaConfig) -> tuple:
    return POOL_DIMS


def serve_runs(params, cfg: LlamaConfig, positions, mesh) -> list:
    """``[(stacked leaves, attend -> mixer, mlp)]``: one run, the stack."""
    tables = rope_tables(positions, cfg.rope_theta, cfg.head_dim)
    return [(params["layers"],
             lambda attend: partial(gqa_mixer, tables=tables, attend=attend),
             dense_mlp)]


def serve_stats(cfg: LlamaConfig, extras: list) -> dict:
    return {}


#: the serving steps' embedding: the one-hot product of training, which
#: partitions under the vocab_rows sharding (a model served on one chip
#: with a large vocabulary brings a lookup: glm_moe_lite.serve_embed)
serve_embed = embed_lookup


def _serve_layers(params, tok, positions, cfg, mesh, attend, state=None):
    """The skeleton under the three serving steps, for every served
    model: embed ``tok [B, S]``, then each of the model's layer runs
    (:func:`model_of`'s ``serve_runs``: one for the Llama family, the
    dense layers and the expert layers for GLM-4.7-Flash) scanned through
    the frame over (layers, cache layer index), ``cfg.loops`` times over
    the same stacked weights (:func:`~horovod_tpu.models.layers.looped`).
    ``attend(..., li, state) -> (o, (state, out))`` is the step's own,
    handed what the run's mixer hands it (rotated q and grouped k, v; or
    q, the latent, the shared key and the up-projection): where the
    layer's cache entries go and what q attends over; ``li`` is the cache
    layer, ``t * n_layers + l`` in pass ``t``; ``state`` (the pools)
    rides the scan's carry, ``out`` (a layer's entries) is stacked,
    ``cache_layers`` deep.  Returns ``(h, state, outs, stats)``, ``stats``
    what the model makes of its mlps' extras (the expert layers' counts,
    or nothing)."""
    model = model_of(cfg)
    h = model.serve_embed(params["embed"], tok, cfg.dtype)
    if mesh is not None:
        h = shd.constrain(h, ("batch", None, None), mesh,
                          model.shard_rules(cfg, mesh))
    renorm = lambda h: rmsnorm(h, params["final_norm"], cfg.rms_eps)
    first, outs, extras = 0, [], []
    for stacked, mixer_of, mlp in model.serve_runs(params, cfg, positions,
                                                   mesh):
        def layer(carry, xs):    # traced here, with this run's pair
            h, state = carry
            lp, li = xs
            h, (state, out), extra = block(
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
    return h, state, outs, model.serve_stats(cfg, extras)


def prefill_attend(cfg: LlamaConfig, mesh, P: int):
    """``attend`` of the prompt prefill: causal attention of the prompt
    over its own keys; the layer's K and V are what it leaves."""
    scale = 1.0 / np.sqrt(cfg.head_dim)
    mask = jnp.tril(jnp.ones((P, P), bool))
    pin = _pin_kv(cfg, mesh)

    def attend(q, k, v, li, state):
        return cached_attend(q, k, v, mask, scale), (state, (pin(k), pin(v)))

    return attend


def prefill_step(params, tokens: jax.Array, cfg, *,
                 mesh: Optional[Mesh] = None,
                 last_pos: Optional[jax.Array] = None) -> tuple:
    """Prompt prefill for the serving engine.

    tokens [B, P] int32 → (next-token logits [B, V] fp32, the prompt's
    cache entries, one array ``[cache_layers, B, P, *row]`` for each pool
    of the model's cache (K and V for the Llama family, the latent rows
    for GLM-4.7-Flash), the step's stats).
    ``last_pos`` [B] selects the logits position per row (bucketed
    prompts are right-padded: the real last token sits at ``len-1``, not
    ``P-1``); None means ``P-1``.
    Causality makes the padded tail inert for every real position, so a
    bucketed prefill emits the same token as an exact-length one."""
    model = model_of(cfg)
    B, P = tokens.shape
    h, _, kept, stats = _serve_layers(
        params, tokens, jnp.broadcast_to(jnp.arange(P), (B, P)), cfg, mesh,
        model.prefill_attend(cfg, mesh, P))
    with region("head"):
        if last_pos is None:
            h_last = h[:, -1]
        else:
            h_last = jnp.take_along_axis(
                h, last_pos[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return (_head(params, h_last, cfg, ("batch", "vocab"), mesh,
                  model.shard_rules(cfg, mesh)), kept, stats)


def paged_kernel_ok(cfg: LlamaConfig, mesh: Optional[Mesh],
                    block_size: int, interpret: bool = False) -> bool:
    """Whether :func:`decode_step_paged` can take ``use_flash=True`` for
    this model, mesh and page size: tp must split q and kv heads alike
    (each chip runs the kernel on its own ``kv_heads`` shard), and the
    per-chip pool geometry must be one the kernel compiles for (any is,
    for the interpreter)."""
    tp = mesh.shape.get("tp", 1) if mesh is not None else 1
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        return False
    return interpret or FA.paged_supported(
        block_size, cfg.head_dim, cfg.n_kv_heads // tp, cfg.n_heads // tp,
        jnp.dtype(cfg.dtype).itemsize)


def _paged_kernel_attend(q, kp, vp, layer, tables, lengths, scale, mesh,
                         rules, interpret):
    """The Pallas paged-decode kernel on q [B, H, Dh] against layer
    ``layer`` of the whole pools.  Under a mesh it is shard_mapped —
    batch rows over dp·fsdp, q heads and the pool's kv_heads over tp —
    for the same reason as the training kernel in :func:`_attention`: a
    bare pallas_call has no GSPMD partitioning rule and would gather the
    pool onto every chip."""
    kernel = partial(FA.paged_attention, scale=scale, interpret=interpret)
    if mesh is None:
        return kernel(q, kp, vp, layer, tables, lengths)
    q_spec = shd.spec_for(("batch", "heads", None), rules)
    pool_spec = shd.spec_for(POOL_DIMS, rules)
    return shard_map(
        kernel, mesh=mesh,
        in_specs=(q_spec, pool_spec, pool_spec, P(),
                  shd.spec_for(("batch", None), rules),
                  shd.spec_for(("batch",), rules)),
        out_specs=q_spec, check_vma=False)(
            q, kp, vp, layer, tables, lengths)


def paged_attend(cfg: LlamaConfig, mesh, tables, blk, off, mask,
                 last=None, interpret: bool = False):
    """The ``attend`` of the two paged steps (see :func:`_serve_layers`):
    each layer writes its fresh K/V rows into page ``blk`` at offset
    ``off`` of its own pages first, then reads the table's logical window
    back: one query a row through the Pallas paged kernel where each
    stream's ``last`` position is given (a row whose table is all
    scratch has no stream, and the kernel skips it), else through the
    contiguous gather under ``mask`` (stale slots masked)."""
    scale = 1.0 / np.sqrt(cfg.head_dim)
    rules = shard_rules(cfg, mesh)

    def put(pool, li, new):
        # blk/off are [B] for one token a row, [B, S] for S.
        pool = pool.at[li, blk, off].set(
            new.reshape(blk.shape + new.shape[2:]))
        if mesh is None:
            return pool
        return shd.constrain(pool, POOL_DIMS, mesh, rules)

    # Block 0 is scratch and in no stream's table, and a row with no
    # stream is all block 0: it reaches the kernel as length 0, which the
    # kernel skips, where ``last + 1`` would read as a stream of one token.
    lengths = None if last is None else jnp.where(
        tables[:, 0] != 0, last + 1, 0)

    def attend(q, k1, v1, li, state):
        kp, vp = put(state[0], li, k1), put(state[1], li, v1)
        if lengths is not None:
            o = _paged_kernel_attend(q[:, 0], kp, vp, li, tables, lengths,
                                     scale, mesh, rules, interpret)[:, None]
        else:
            o = cached_attend(q, gather_blocks(kp[li], tables),
                              gather_blocks(vp[li], tables), mask, scale)
        return o, ((kp, vp), None)

    return attend


def decode_step_paged(params, tok: jax.Array, positions: jax.Array,
                      pools: tuple, tables: jax.Array, cfg, *,
                      mesh: Optional[Mesh] = None, use_flash: bool = False,
                      interpret: bool = False) -> tuple:
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
    (``use_flash``; callers check the model's ``paged_kernel_ok``).
    Returns (logits [B, V] fp32, pools, stats) — pass the pools donated
    so the writes land in place."""
    model = model_of(cfg)
    B = tok.shape[0]
    BS = pools[0].shape[2]
    T = tables.shape[1] * BS
    mask = (jnp.arange(T)[None, :] <= positions[:, None])[:, None, :]
    blk = tables[jnp.arange(B), positions // BS]                # [B]
    h, pools, _, stats = _serve_layers(
        params, tok[:, None], positions[:, None], cfg, mesh,
        model.paged_attend(cfg, mesh, tables, blk, positions % BS, mask,
                           positions if use_flash else None, interpret),
        tuple(pools))
    return (_head(params, h[:, 0], cfg, ("batch", "vocab"), mesh,
                  model.shard_rules(cfg, mesh)), pools, stats)


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
    (logits [B, S, V] fp32, pools, stats) — donate the pools."""
    model = model_of(cfg)
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
    return (_head(params, h, cfg, ("batch", None, "vocab"), mesh,
                  model.shard_rules(cfg, mesh)), pools, stats)


def _use_blockwise_ce(cfg: LlamaConfig, mesh: Optional[Mesh]) -> bool:
    if not cfg.blockwise_ce:
        return False
    if mesh is not None and (mesh.shape.get("tp", 1) > 1
                             or mesh.shape.get("sp", 1) > 1
                             or mesh.shape.get("pp", 1) > 1):
        # tp shards the vocab dim and pp/sp restructure the forward; the
        # blockwise scan currently assumes an unsharded lm_head column
        # space.  dp/fsdp compose fine.
        return False
    return True


def loss_fn(params: dict, batch: dict, cfg: LlamaConfig, *,
            mesh: Optional[Mesh] = None) -> jax.Array:
    """Causal LM loss: batch = {"tokens": [B,S+1] int32}."""
    loss, aux = causal_lm_loss(
        lambda inputs, hidden: forward(params, inputs, cfg, mesh=mesh,
                                       return_hidden=hidden),
        params["lm_head"], batch["tokens"], _use_blockwise_ce(cfg, mesh))
    return loss + cfg.moe_aux_weight * aux


def _opt_shardings(tx, cfg, mesh: Mesh, model=None):
    """Explicit shardings for the optimizer state: every param-shaped
    subtree (adam mu/nu, momentum, ...) mirrors the parameter shardings,
    anything else (step counters) replicates.

    jit with donated arguments needs these spelled out: leaving the opt
    state's shardings to inference lets the propagator pick layouts that
    disagree with the donated inputs on tp/sp meshes, and XLA aliasing
    fails at runtime with a sub-shape size mismatch."""
    model = model or _THIS
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


def _make_train_step_1f1b(cfg: LlamaConfig, mesh: Mesh, tx):
    """Training step for pp>1 meshes on the 1F1B schedule
    (:func:`horovod_tpu.parallel.pipeline.pipeline_train_local`).

    Unlike the GPipe path (autodiff through the forward tick loop, all M
    microbatch activations live at the fwd/bwd boundary), this computes
    gradients EXPLICITLY inside the manual region: the loss head (final
    norm + lm_head + CE over the tp-sharded vocab) runs on the last stage
    per microbatch, cotangents ride ``ppermute`` back up the pipeline, and
    at most 2*(pp-1) microbatch inputs are ever in flight.  The embedding
    sits outside the region; its gradient comes from the returned input
    cotangent via ``jax.vjp``.

    Gradient accounting inside the manual region (no shard_map AD here, so
    every reduction is explicit):
    - the CE seed is 1/(dp*fsdp*ep*sp) so per-shard local means sum to the
      global batch mean;
    - each parameter gradient is psummed over exactly the mesh axes its
      at-rest sharding does NOT mention (fsdp-sharded leaves already
      reduce-scatter through the all_gather transpose);
    - the input cotangent is psummed over tp (every tp rank's program
      contributes the gradient through its own head/vocab slice).
    """
    from ..parallel.pipeline import pipeline_train_local

    pp = mesh.shape["pp"]
    data_axes = ("dp", "fsdp", "ep", "sp")
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape.get(a, 1)
    pshard = param_shardings(cfg, mesh)
    repl = NamedSharding(mesh, P())
    batch_shard = NamedSharding(mesh, P(("dp", "fsdp")))
    head_dims = {"lm_head": param_logical_dims(cfg)["lm_head"],
                 "final_norm": param_logical_dims(cfg)["final_norm"]}
    head_specs = {k: shd.spec_for(d) for k, d in head_dims.items()}
    all_axes = ("dp", "fsdp", "ep", "sp", "tp")

    def reduce_grads(grads, specs):
        # psum each leaf over every axis its sharding does not mention.
        def red(g, spec):
            axes = tuple(a for a in all_axes
                         if a not in shd.spec_axes(spec))
            return lax.psum(g, axes) if axes else g
        return jax.tree.map(red, grads, specs,
                            is_leaf=lambda x: isinstance(x, P))

    def step(params, opt_state, batch):
        tokens = batch["tokens"]
        inputs = tokens[:, :-1]
        targets = tokens[:, 1:].astype(jnp.int32)
        B, S = inputs.shape
        D = cfg.d_model
        parts = _pp_machinery(cfg, mesh, True, S)
        make_stage_fn, S_loc = parts["make_stage_fn"], parts["S_loc"]
        M = _pick_microbatches(B, mesh, cfg.pp_microbatches)

        def embed_fn(emb):
            h = embed_lookup(emb, inputs, cfg.dtype)
            return shd.constrain(h, ("batch", "seq", None), mesh)

        h, embed_vjp = jax.vjp(embed_fn, params["embed"])
        head_in = {"lm_head": params["lm_head"],
                   "final_norm": params["final_norm"]}

        def local(layers_loc, head_loc, h_loc, tgt_loc):
            B_loc = h_loc.shape[0]
            mb_loc = B_loc // M
            mbs = h_loc.reshape(M, mb_loc, S_loc, D)
            tgts = tgt_loc.reshape(M, mb_loc, S_loc)

            # lm_head fsdp gather ONCE per step, outside the tick loop
            # (XLA does not hoist collectives out of while loops); its
            # grad reduce-scatters back once at the end.
            head_full = {
                "lm_head": lax.all_gather(head_loc["lm_head"], "fsdp",
                                          axis=0, tiled=True),  # [D, V/tp]
                "final_norm": head_loc["final_norm"],
            }

            def loss_head(head, y, m):
                with region("head"):
                    h2 = rmsnorm(y, head["final_norm"], cfg.rms_eps)
                    logits = jnp.einsum("bsd,dv->bsv", h2, head["lm_head"]
                                        ).astype(jnp.float32)
                with region("loss"):
                    # CE over the tp-sharded vocab.  The max shift is taken on
                    # stopped gradients (exact: the shift cancels in the lse
                    # derivative) and reduced with all_gather+max — pmax has
                    # no AD rule even on zero tangents.
                    mloc = jnp.max(jax.lax.stop_gradient(logits), axis=-1)
                    mx = jnp.max(lax.all_gather(
                        mloc, "tp", axis=0, tiled=False), axis=0)
                    lse = jnp.log(lax.psum(
                        jnp.sum(jnp.exp(logits - mx[..., None]), axis=-1),
                        "tp")) + mx
                    t = tgts[m]
                    vloc = logits.shape[-1]
                    vstart = lax.axis_index("tp") * vloc
                    within = (t >= vstart) & (t < vstart + vloc)
                    pl = jnp.take_along_axis(
                        logits, jnp.clip(t - vstart, 0, vloc - 1)[..., None],
                        axis=-1)[..., 0]
                    picked = lax.psum(jnp.where(within, pl, 0.0), "tp")
                    return (lse - picked).mean()

            loss, aux, dmbs, dlayers, dhead = pipeline_train_local(
                make_stage_fn(mb_loc), layers_loc, mbs, loss_head, head_full,
                axis_name="pp", aux_weight=cfg.moe_aux_weight,
                seed_scale=1.0 / n_data)
            loss = lax.pmean(loss, data_axes)
            dh = lax.psum(dmbs.reshape(B_loc, S_loc, D), "tp")
            dlayers = reduce_grads(dlayers, parts["layer_specs"])
            # Undo the step-level gather: reduce-scatter the full-embed
            # lm_head grad back to this rank's fsdp shard (the all_gather
            # transpose), then psum over the remaining unmentioned axes.
            dhead = {
                "lm_head": lax.psum_scatter(
                    dhead["lm_head"], "fsdp", scatter_dimension=0,
                    tiled=True),
                "final_norm": dhead["final_norm"],
            }
            dhead = reduce_grads(dhead, head_specs)
            return loss, aux, dh, dlayers, dhead

        fn = shard_map(
            local, mesh=mesh,
            in_specs=(parts["layer_specs"], head_specs, parts["act_spec"],
                      P(("dp", "fsdp", "ep"), "sp")),
            out_specs=(P(), P(), parts["act_spec"], parts["layer_specs"],
                       head_specs),
            check_vma=False)
        loss, aux, dh, dlayers, dhead = fn(params["layers"], head_in, h,
                                           targets)
        (d_embed,) = embed_vjp(dh.astype(h.dtype))
        grads = {"embed": d_embed, "layers": dlayers,
                 "lm_head": dhead["lm_head"],
                 "final_norm": dhead["final_norm"]}
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        with region("optim"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = jax.tree.map(jnp.add, params, updates)
        return params, opt_state, loss + cfg.moe_aux_weight * aux

    opt_shard = _opt_shardings(tx, cfg, mesh)
    return jax.jit(step, in_shardings=(pshard, opt_shard, batch_shard),
                   out_shardings=(pshard, opt_shard, repl),
                   donate_argnums=(0, 1))


def make_train_step(cfg, mesh: Mesh, tx, *,
                    pipeline_schedule: str = "1f1b", model=None):
    """Jitted full training step over the mesh (GSPMD collectives for
    dp/fsdp/tp, explicit shard_map blocks for sp/ep; layer stack over pp).

    ``model`` is the module the loss and the layout come from: it gives
    ``loss_fn(params, batch, cfg, mesh=)``, ``param_shardings(cfg, mesh)``
    and ``init_params(cfg, key)`` (for the optimizer state's shapes).
    The default is this module with a :class:`LlamaConfig`;
    :mod:`horovod_tpu.models.kimi_linear` is the other; a model whose
    objective is not written refuses by its ``check_trainable(cfg)``
    (:mod:`horovod_tpu.models.glm_moe_lite`).  Where the model
    sets ``LOSS_HAS_AUX`` its loss returns ``(loss, aux)`` and so does
    the step, as its third output.

    On pp>1 meshes ``pipeline_schedule`` selects "1f1b" (default: explicit
    interleaved fwd/bwd schedule, activation memory bounded by 2*(pp-1)
    microbatches) or "gpipe" (autodiff through the fill-drain forward);
    both are the Llama stack's."""
    model = model or _THIS
    if getattr(cfg, "loops", 1) > 1:
        raise NotImplementedError(
            f"loops={cfg.loops}: training a looped stack needs its own "
            "objective, each pass's loss weighed by a learned exit "
            "distribution with an entropy term, which is not here; plain "
            "cross-entropy on the last pass would train another model")
    if hasattr(model, "check_trainable"):
        model.check_trainable(cfg)       # raises, naming what is missing
    if mesh.shape.get("pp", 1) > 1 and model is not _THIS:
        raise NotImplementedError(
            f"{model.__name__} has no pipelined forward; use a pp=1 mesh")
    if mesh.shape.get("pp", 1) > 1 and pipeline_schedule == "1f1b":
        if cfg.blockwise_ce:
            raise NotImplementedError("blockwise CE requires a pp=1 mesh")
        return _make_train_step_1f1b(cfg, mesh, tx)
    has_aux = getattr(model, "LOSS_HAS_AUX", False)
    pshard = model.param_shardings(cfg, mesh)
    repl = NamedSharding(mesh, P())
    batch_shard = NamedSharding(mesh, P(("dp", "fsdp")))

    multi_device = any(s > 1 for s in mesh.shape.values())

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss_fn(p, batch, cfg, mesh=mesh),
            has_aux=has_aux)(params)
        # Pin gradients to the parameter shardings: the backward scan's
        # per-layer dynamic-update-slice accumulators otherwise get
        # propagation-derived shardings that force involuntary full
        # rematerialization on the way into the optimizer update.  (On a
        # single-device mesh the annotation is a no-op semantically and
        # only an XLA fusion barrier, so it is skipped.)
        if multi_device:
            grads = jax.lax.with_sharding_constraint(grads, pshard)
        with region("optim"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = jax.tree.map(jnp.add, params, updates)
        return params, opt_state, loss

    opt_shard = _opt_shardings(tx, cfg, mesh, model)
    return jax.jit(
        step,
        in_shardings=(pshard, opt_shard, batch_shard),
        out_shardings=(pshard, opt_shard, repl),
        donate_argnums=(0, 1))
