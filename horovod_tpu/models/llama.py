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
"""

from __future__ import annotations

import dataclasses
import sys
from functools import lru_cache, partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel import sharding as shd
from ..parallel.moe import moe_layer_local
from ..parallel.ring_attention import (
    ring_attention_local,
    ulysses_attention_local,
)
from ..utils import logging as hvd_logging

log = hvd_logging.get_logger()
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
    # (least memory), False = save everything (fastest at the bench shape
    # once trivial-mesh sharding constraints stopped fragmenting the
    # saved-buffer fusions: TPU v5 lite in-process A/B 92.1 ms/step vs
    # 93.7 "dots" vs ~98.8 full remat), or "dots" = jax.checkpoint with
    # the dots_with_no_batch_dims_saveable policy — the memory/speed
    # middle ground for configs that don't fit with remat=False.
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
    # (lax.scan's ``unroll``).  1 = compile one layer body (fastest
    # compile, depth-independent).  n_layers = fully unrolled: the
    # stacked-residual dynamic-update-slice copies the rolled scan pays
    # every layer (round-5 trace: 5.8 ms/step at the bench shape, pure
    # copy traffic) disappear and XLA fuses across layer boundaries, at
    # the cost of compile time linear in depth.  The bench config uses
    # full unroll; deep configs should stay rolled or pick a divisor.
    scan_unroll: int = 1
    # Blockwise (online-softmax) cross-entropy (ops/losses.py): trades
    # one extra lm_head matmul for never materializing the [B,S,V] fp32
    # logits.  Measured on TPU v5 lite (d1024/L8, B=8, S=1024, V=32000):
    # ~13% SLOWER than the dense path (XLA already streams the dense
    # softmax well) but saves the ~1 GB logits+grad residency — so it is
    # an opt-in memory lever for configs that don't otherwise fit, not a
    # default.
    blockwise_ce: bool = False
    # Fused tp matmul + reduce-scatter on the decode projection layers
    # (wo / w_down row-parallel psums in the stage-resident pp decode
    # path), chunked so chunk c's reduce-scatter can overlap chunk c+1's
    # partial matmul (ops/sched.matmul_reducescatter).  None = follow the
    # engine's HOROVOD_TPU_SCHED_MODE knob (on when "decomposed");
    # True/False force it.  Numerics: bit-identical at tp=2 (two-operand
    # sums commute; token parity asserted in tests/test_sched.py) and
    # within ~1 ulp beyond — psum and psum_scatter associate the tp-way
    # sum in different ring orders (the same caveat as the engine's
    # decomposed allreduce, docs/performance.md), so near-tie logits at
    # tp>=4 could in principle pick a different token.
    decode_tp_overlap: Optional[bool] = None

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

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


def _remat(body, mode):
    """Apply the configured rematerialization mode to a layer body."""
    if mode == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body) if mode else body


def _rmsnorm_impl(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms * w).astype(x.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with a hand-written VJP whose only residual is ``x``.

    Autodiff of the plain version makes XLA save the fp32 normalized
    activations for the backward — at the bench shape that is two
    f32[B,S,D] tensors per layer (≈512 MB/step at d1024/L8/B8/S1024)
    riding the layer-scan carry through HBM.  Recomputing the rsqrt from
    the already-saved bf16 ``x`` in the backward is a handful of VPU ops
    against ~2 ms/step of HBM traffic (round-5 trace: the fwd while
    carried 2x f32[8,8,1024,1024] purely as norm residuals)."""
    return _rmsnorm_impl(x, w, eps)


def _rmsnorm_fwd(x, w, eps):
    return _rmsnorm_impl(x, w, eps), (x, w)


def _rmsnorm_bwd(eps, res, dy):
    x, w = res
    x32 = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    u = x32 * r                                   # normalized activations
    du = dy.astype(jnp.float32) * w               # d(loss)/d(u)
    s = jnp.mean(du * u, axis=-1, keepdims=True)
    dx = (r * (du - u * s)).astype(x.dtype)
    dw = jnp.sum(dy.astype(jnp.float32) * u,
                 axis=tuple(range(x.ndim - 1))).astype(w.dtype)
    return dx, dw


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def _rope_tables(positions: jax.Array, theta: float, head_dim: int
                 ) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables [B, S, half] for these positions.  Computed once per
    forward and threaded through the layer scan as loop invariants rather
    than re-deriving the transcendentals per layer.  (Measured step-time
    effect on TPU v5 lite: none — XLA was already amortizing the
    recompute — but the hoist keeps the scanned body minimal.)"""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,half]
    return jnp.cos(angles), jnp.sin(angles)


def _rope(x: jax.Array, rope: tuple[jax.Array, jax.Array]) -> jax.Array:
    # x: [B, S, H, Dh]; rope: (cos, sin) each [B, S, Dh//2]
    half = x.shape[-1] // 2
    cos, sin = rope[0][:, :, None, :], rope[1][:, :, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _embed_lookup(embed: jax.Array, tokens: jax.Array, dtype) -> jax.Array:
    """Token embedding as a one-hot matmul rather than a gather: exact
    (each one-hot row has a single nonzero), and the backward becomes a
    transposed matmul on the MXU instead of a scatter-add.  In-process
    A/B at the bench shape measured the two forms equal on TPU v5 lite
    (XLA fuses the one-hot into the dot, and lowers the small-vocab
    gather well); the matmul form is kept because it partitions cleanly
    under the vocab_rows (tp, fsdp) sharding — a sharded gather lowers
    to per-shard lookup + select + psum anyway."""
    onehot = jax.nn.one_hot(tokens, embed.shape[0], dtype=dtype)
    return jnp.einsum("bsv,vd->bsd", onehot, embed.astype(dtype))


# One canonical expansion helper (shared with the dense oracle).
from ..ops.flash_attention import gqa_expand as _gqa_expand  # noqa: E402


def _attn_block(h, lp, rope, cfg: LlamaConfig, attention):
    """Shared attention sub-block: RMSNorm -> QKV -> RoPE -> ``attention``
    callable (handed GROUPED K/V — each path expands only if it must) ->
    output projection + residual."""
    x = _rmsnorm(h, lp["attn_norm"])
    q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"])
    q = _rope(q, rope)
    k = _rope(k, rope)
    return h + jnp.einsum("bshk,hkd->bsd", attention(q, k, v), lp["wo"])


def _swiglu_hidden(x2, lp):
    """SwiGLU gate/up half: ``silu(x@w_gate) * (x@w_up)`` — shared so the
    decode path's fused down-projection reuses the same hidden math."""
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x2, lp["w_gate"]))
    u = jnp.einsum("bsd,df->bsf", x2, lp["w_up"])
    return g * u


def _dense_mlp(x2, lp):
    """SwiGLU MLP shared by the scan and pipeline paths."""
    return jnp.einsum("bsf,fd->bsd", _swiglu_hidden(x2, lp), lp["w_down"])


# Test hook: route the TPU-gated flash branches through the Pallas
# interpreter so the CPU rig can exercise the exact structures the TPU
# path uses (the dp/fsdp/tp shard_map in `_attention` and the direct
# kernel call inside the fully-manual pipeline region).
_FORCE_FLASH_INTERPRET = False


def _flash_backend() -> bool:
    return jax.default_backend() == "tpu" or _FORCE_FLASH_INTERPRET


@lru_cache(maxsize=None)
def _log_attention_path(path: str, q_shape: tuple, mesh_shape) -> None:
    """INFO line naming the attention implementation a traced step uses,
    once per distinct (path, local shape, mesh)."""
    log.info("llama attention path: %s (local q %s, mesh %s)", path,
             q_shape, dict(mesh_shape) if mesh_shape else None)


def _sp_local_attention(sp_mode: str):
    """The mapped-context sequence-parallel attention for ``sp_mode``."""
    if sp_mode == "ulysses":
        return ulysses_attention_local
    if sp_mode == "ring":
        return ring_attention_local
    raise ValueError(f"unknown sp_attention {sp_mode!r} "
                     "(expected 'ring' or 'ulysses')")


def attention_path(q_shape: tuple, itemsize: int, mesh: Optional[Mesh],
                   sp_mode: str = "ring", v_dim: Optional[int] = None
                   ) -> str:
    """Which implementation :func:`_attention` runs for a global
    ``[B, S, H, D]`` query on ``mesh``: ``"ring"``/``"ulysses"`` when the
    sequence is sp-sharded, ``"flash"`` (the Pallas kernels) on TPU when
    the per-chip shard divides evenly and :func:`FA.supported` accepts
    it, ``"dense"`` (XLA) otherwise.  ``v_dim`` is the value width where
    it differs from the key width ``D``."""
    from ..ops import flash_attention as FA
    shape = dict(mesh.shape) if mesh is not None else {}
    if shape.get("sp", 1) > 1:
        _sp_local_attention(sp_mode)
        return sp_mode
    B, S, H, D = q_shape
    dpf = shape.get("dp", 1) * shape.get("fsdp", 1)
    tp = shape.get("tp", 1)
    if (_flash_backend() and B % dpf == 0 and H % tp == 0
            and FA.supported((B // dpf, S, H // tp, D), itemsize, v_dim)):
        return "flash"
    return "dense"


def _attention(q, k, v, mesh: Optional[Mesh], causal: bool,
               sp_mode: str = "ring") -> jax.Array:
    """Dispatch per :func:`attention_path`.  Under a mesh the sp paths and
    the flash kernel are shard_mapped so each chip works on its own
    batch/head shard (a bare pallas_call has no GSPMD partitioning rule
    and would be replicated)."""
    path = attention_path(q.shape, q.dtype.itemsize, mesh, sp_mode,
                          v.shape[-1])
    _log_attention_path(path, q.shape,
                        tuple(mesh.shape.items()) if mesh is not None
                        else None)
    if path in ("ring", "ulysses"):
        k, v = _gqa_expand(q, k, v)   # ring/Ulysses rotate full head sets
        # Manual over every mesh axis: the batch/head dims are explicitly
        # dp·fsdp / tp sliced instead of left to GSPMD, and the body only
        # communicates over sp.
        spec = P(("dp", "fsdp"), "sp", "tp", None)
        fn = shard_map(
            partial(_sp_local_attention(sp_mode), axis_name="sp",
                    causal=causal),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False)
        return fn(q, k, v)
    if path == "flash":
        from ..ops import flash_attention as FA
        flash = lambda q_, k_, v_: FA.flash_attention(
            q_, k_, v_, None, causal, None, None, _FORCE_FLASH_INTERPRET)
        if mesh is None:
            return flash(q, k, v)
        if k.shape[2] % mesh.shape.get("tp", 1):
            # tp divides H but not KV: the grouped cache cannot shard
            # over tp — expand K/V and keep the flash kernel (losing it
            # entirely would be a 2-5x regression for the sake of the
            # GQA memory win).
            k, v = _gqa_expand(q, k, v)
        spec = P(("dp", "fsdp"), None, "tp", None)
        return shard_map(flash, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
    from ..ops.flash_attention import dense_attention
    return dense_attention(q, k, v, 1.0 / np.sqrt(q.shape[-1]), causal)


def _moe_mlp(h2, lp, cfg: LlamaConfig, mesh: Optional[Mesh]):
    """Switch-MoE MLP: SwiGLU experts over the ep axis."""
    B, S, D = h2.shape
    flat = h2.reshape(B * S, D)

    def expert_fn(w, x):
        # w: dict leaves for ONE expert; x: [cap, D]
        g = jax.nn.silu(x @ w["w_gate"])
        u = x @ w["w_up"]
        return (g * u) @ w["w_down"]

    eparams = {"w_gate": lp["w_gate"], "w_up": lp["w_up"],
               "w_down": lp["w_down"]}
    ep = mesh.shape.get("ep", 1) if mesh is not None else 1
    if ep > 1:
        # Manual over every mesh axis: dp/fsdp/ep all count as token
        # axes so each ep rank dispatches distinct local tokens
        # (mirroring the pp path), and the expert hidden dim is
        # Megatron-sliced over tp with an explicit row-parallel psum.
        all_axes = tuple(mesh.axis_names)

        def expert_fn_tp(w, x):
            g = jax.nn.silu(x @ w["w_gate"])
            u = x @ w["w_up"]
            return lax.psum((g * u) @ w["w_down"], "tp")

        def local_moe(tok, rk, pr):
            out, aux = moe_layer_local(
                tok, rk, expert_fn_tp, pr, axis_name="ep",
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
        logits = flat.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
        dispatch, combine, aux, _drops = switch_route(logits, cap)
        einputs = jnp.einsum("tec,td->ecd", dispatch.astype(flat.dtype), flat)
        eouts = jax.vmap(expert_fn)(eparams, einputs)
        out = jnp.einsum("tec,ecd->td", combine.astype(flat.dtype), eouts)
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


def _pp_machinery(cfg: LlamaConfig, mesh: Mesh, causal: bool, S: int) -> dict:
    """Shared layer-stack machinery for the pipelined paths (GPipe forward
    and 1F1B training): the fully-manual layer body with Megatron-tp psums,
    ZeRO-3 fsdp gathers, ring attention over sp, MoE over ep — and the
    in/out specs matching the at-rest parameter shardings."""
    pp = mesh.shape["pp"]
    tp = mesh.shape.get("tp", 1)
    sp = mesh.shape.get("sp", 1)
    if cfg.n_layers % pp:
        raise ValueError(
            f"pp={pp} must divide n_layers={cfg.n_layers} evenly")
    if cfg.n_heads % tp or cfg.n_kv_heads % tp:
        raise ValueError(
            f"tp={tp} must divide n_heads={cfg.n_heads} and "
            f"n_kv_heads={cfg.n_kv_heads}")
    if S % sp:
        raise ValueError(f"sp={sp} must divide sequence length {S}")
    from ..ops import flash_attention as FA

    S_loc = S // sp
    scale = 1.0 / np.sqrt(cfg.head_dim)
    layer_dims = {k: d[1:]
                  for k, d in param_logical_dims(cfg)["layers"].items()}

    def gather_layer(lp):
        # ZeRO-3 gather: reassemble the embed dim of this layer's weights
        # from their fsdp shards; transpose = reduce-scatter of the grads.
        out = {}
        for k, leaf in lp.items():
            for i, dname in enumerate(layer_dims[k]):
                if dname == "embed":
                    leaf = lax.all_gather(leaf, "fsdp", axis=i, tiled=True)
            out[k] = leaf
        return out

    def attention(q, k, v):
        # q is this rank's shard already (the region is manual over
        # every axis), so the mesh-free dispatch applies to it as is.
        path = (cfg.sp_attention if sp > 1 else
                attention_path(q.shape, q.dtype.itemsize, None))
        _log_attention_path(path, q.shape, tuple(mesh.shape.items()))
        if sp > 1:
            k, v = _gqa_expand(q, k, v)
            return _sp_local_attention(cfg.sp_attention)(
                q, k, v, axis_name="sp", causal=causal)
        if path == "flash":
            return FA.flash_attention(q, k, v, None, causal, None, None,
                                      _FORCE_FLASH_INTERPRET)
        return FA.dense_attention(q, k, v, scale, causal)

    def moe_mlp_local(x2, lp):
        Bq, Sq, Dq = x2.shape
        flat = x2.reshape(Bq * Sq, Dq)

        def expert_fn(w, x):
            g = jax.nn.silu(x @ w["w_gate"])
            u = x @ w["w_up"]
            return lax.psum((g * u) @ w["w_down"], "tp")

        eparams = {"w_gate": lp["w_gate"], "w_up": lp["w_up"],
                   "w_down": lp["w_down"]}
        out, aux = moe_layer_local(
            flat, lp["router"].astype(jnp.float32), expert_fn, eparams,
            axis_name="ep", capacity_factor=cfg.capacity_factor)
        # pmean includes tp (a forward no-op — aux is tp-replicated) so the
        # aux gradient path is 1/tp-scaled per rank; the 1F1B step blanket-
        # psums replicated-param grads over tp, and without this the
        # routing-only aux path (which unlike the CE path has no tp-sharded
        # op on it) would count tp times.
        return (out.reshape(Bq, Sq, Dq),
                lax.pmean(aux, ("dp", "fsdp", "ep", "sp", "tp")))

    def layer_body(h, lp, rope):
        lp = gather_layer(lp)
        x = _rmsnorm(h, lp["attn_norm"])
        q = jnp.einsum("bsd,dhk->bshk", x, lp["wq"])     # heads local (tp)
        k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"])
        q = _rope(q, rope)
        k = _rope(k, rope)
        # K/V stay at kv_heads here; each attention path expands only if
        # it must (the flash kernels index kv heads natively).
        attn_out = jnp.einsum("bshk,hkd->bsd", attention(q, k, v), lp["wo"])
        h = h + lax.psum(attn_out, "tp")                  # row-parallel wo
        x2 = _rmsnorm(h, lp["mlp_norm"])
        if cfg.use_moe:
            mlp_out, aux = moe_mlp_local(x2, lp)
        else:
            mlp_out = lax.psum(_dense_mlp(x2, lp), "tp")  # row-parallel
            aux = jnp.zeros((), jnp.float32)
        return h + mlp_out, aux

    body = _remat(layer_body, cfg.remat)

    def make_stage_fn(rope):
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

    layer_specs = jax.tree.map(
        lambda dims: shd.spec_for(dims), param_logical_dims(cfg)["layers"],
        is_leaf=lambda x: isinstance(x, tuple))
    return {
        "make_stage_fn": make_stage_fn,
        "layer_specs": layer_specs,
        "layer_dims": layer_dims,
        "act_spec": P(("dp", "fsdp", "ep"), "sp", None),
        "S_loc": S_loc,
    }


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
    h = _embed_lookup(params["embed"], tokens, cfg.dtype)   # [B,S,D]
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
        # RoPE tables once per step (tick-invariant), not per tick.
        base = lax.axis_index("sp") * S_loc + jnp.arange(S_loc)
        positions = jnp.broadcast_to(base[None, :], (B_loc // M, S_loc))
        rope = _rope_tables(positions, cfg.rope_theta, cfg.head_dim)
        out, aux = pipeline_apply_local(make_stage_fn(rope), local_layers,
                                        mbs, axis_name="pp", with_aux=True)
        return out.reshape(B_loc, S_loc, D), aux

    layer_specs, act_spec = parts["layer_specs"], parts["act_spec"]
    fn = shard_map(local, mesh=mesh, in_specs=(layer_specs, act_spec),
                   out_specs=(act_spec, P()), check_vma=False)
    h, aux = fn(params["layers"], h)
    h = shd.constrain(h, ("batch", "seq", None), mesh)
    h = _rmsnorm(h, params["final_norm"])
    logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"])
    logits = shd.constrain(logits, ("batch", "seq", "vocab"), mesh)
    return logits.astype(jnp.float32), aux


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
    h = _embed_lookup(params["embed"], tokens, cfg.dtype)   # [B,S,D]
    h = shd.constrain(h, ("batch", "seq", None), mesh) if mesh else h
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    rope = _rope_tables(positions, cfg.rope_theta, cfg.head_dim)
    if mesh is not None:
        # Per-layer rule shardings for the scanned slices (leading "stage"
        # dim dropped).  Pinning the slices inside the body stops GSPMD's
        # propagator from deriving batch-flavored shardings for loop-body
        # weights — the source of "involuntary full rematerialization"
        # resharding on every layer (round-2 verdict finding).
        layer_dims = {k: d[1:]
                      for k, d in param_logical_dims(cfg)["layers"].items()}
        rules = shard_rules(cfg, mesh)

    def layer_body(carry, lp):
        h, aux = carry
        if mesh is not None:
            lp = {k: shd.constrain(v, layer_dims[k], mesh, rules)
                  for k, v in lp.items()}
        h = _attn_block(h, lp, rope, cfg,
                        lambda q, k, v: _attention(q, k, v, mesh, causal,
                                                   cfg.sp_attention))
        x2 = _rmsnorm(h, lp["mlp_norm"])
        if cfg.use_moe:
            mlp_out, moe_aux = _moe_mlp(x2, lp, cfg, mesh)
            aux = aux + moe_aux
        else:
            mlp_out = _dense_mlp(x2, lp)
        h = h + mlp_out
        if mesh is not None:
            h = shd.constrain(h, ("batch", "seq", None), mesh)
        return (h, aux), None

    body = _remat(layer_body, cfg.remat)
    (h, aux), _ = lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                           params["layers"], unroll=cfg.scan_unroll)
    h = _rmsnorm(h, params["final_norm"])
    if return_hidden:
        return h, aux
    logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"])
    if mesh is not None:
        logits = shd.constrain(logits, ("batch", "seq", "vocab"), mesh)
    return logits.astype(jnp.float32), aux


def _layer_kv(x, lp, rope):
    """Post-RoPE K/V for a normed input chunk (no GQA expand — the cache
    stores kv_heads and expands at attention time)."""
    k = jnp.einsum("bsd,dhk->bshk", x, lp["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, lp["wv"])
    return _rope(k, rope), v


def _cached_attend(q, keys, vals, mask, scale):
    """Decode-path attention against a KV cache, GQA-grouped.

    q [B,Sq,H,Dh]; keys/vals [B,T,KV,Dh]; mask [Sq,T] bool (shared across
    the batch) or [B,Sq,T] (per-request — the serving engine's slots sit
    at different context lengths).  The q heads are reshaped [KV, rep]
    and contracted against the grouped cache directly — the cache is
    never expanded to H heads (the repeat would rep x the dominant HBM
    traffic of decoding, which is exactly reading the cache)."""
    B, Sq, H, Dh = q.shape
    KV = keys.shape[2]
    rep = H // KV
    qg = q.reshape(B, Sq, KV, rep, Dh)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, keys
                   ).astype(jnp.float32) * scale
    m = mask[None, None, None] if mask.ndim == 2 else mask[:, None, None]
    s = jnp.where(m, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(vals.dtype), vals)
    return o.reshape(B, Sq, H, Dh)


def _pick_token(logits, step_key, temperature, dtype):
    """Greedy or temperature sampling from [B, V] fp32 logits."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(dtype)
    return jax.random.categorical(
        step_key, logits / temperature, axis=-1).astype(dtype)


def _decode_tp_overlap_chunks(cfg: LlamaConfig, tp: int) -> int:
    """Chunk count for the fused matmul+reduce-scatter decode projections
    (0 = plain ``psum``).  ``cfg.decode_tp_overlap`` wins when set;
    None follows the engine's schedule knob (``HOROVOD_TPU_SCHED_MODE``),
    so one switch turns on decomposed collectives engine-wide AND the
    decode-layer fusion."""
    if tp <= 1:
        return 0
    from .. import context as ctx_mod
    state = ctx_mod.global_state()
    gcfg = state.config if state.initialized else None
    enabled = cfg.decode_tp_overlap
    if enabled is None:
        enabled = gcfg is not None and gcfg.sched_mode == "decomposed"
    if not enabled:
        return 0
    return max(2, gcfg.sched_chunks if gcfg is not None else 2)


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
    L, D, H, KV, Dh = (cfg.n_layers, cfg.d_model, cfg.n_heads,
                       cfg.n_kv_heads, cfg.head_dim)
    dpf = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    if cfg.n_layers % pp:
        raise ValueError(f"pp={pp} must divide n_layers={L}")
    if H % tp or KV % tp:
        raise ValueError(f"tp={tp} must divide n_heads={H} and "
                         f"n_kv_heads={KV}")
    if B % dpf:
        raise ValueError(f"batch {B} must divide over dp*fsdp = {dpf}")
    scale = 1.0 / np.sqrt(Dh)
    tp_chunks = _decode_tp_overlap_chunks(cfg, tp)
    dims = param_logical_dims(cfg)
    layer_dims = {k: d[1:] for k, d in dims["layers"].items()}
    layer_specs = jax.tree.map(lambda d: shd.spec_for(d), dims["layers"],
                               is_leaf=lambda x: isinstance(x, tuple))
    cache_spec = P("pp", ("dp", "fsdp"), None, "tp", None)
    act_spec = P(("dp", "fsdp"), None, None)
    perm = [(i, i + 1) for i in range(pp - 1)]

    def gather_layer(lp):
        out = {}
        for k2, leaf in lp.items():
            for i, dname in enumerate(layer_dims[k2]):
                if dname == "embed":
                    leaf = lax.all_gather(leaf, "fsdp", axis=i, tiled=True)
            out[k2] = leaf
        return out

    def _row_parallel(x2, w2):
        """tp row-parallel projection: ``psum(x2 @ w2)``, or — behind the
        schedule knob — the fused chunked matmul + reduce-scatter
        (ops/sched), which lets chunk c's collective overlap chunk c+1's
        partial matmul on the decode critical path."""
        if tp_chunks:
            from ..ops.sched import matmul_reducescatter
            return matmul_reducescatter(x2, w2, "tp", chunks=tp_chunks)
        return lax.psum(jnp.matmul(x2, w2), "tp")

    def make_stage(rope, mask, write, attend_cache):
        def layer_step(h, inputs):
            lp, ck, cv = inputs
            lp = gather_layer(lp)
            x = _rmsnorm(h, lp["attn_norm"])
            q = _rope(jnp.einsum("bsd,dhk->bshk", x, lp["wq"]), rope)
            k1, v1 = _layer_kv(x, lp, rope)
            ck = write(ck, k1)
            cv = write(cv, v1)
            if attend_cache:                       # decode: q vs cache
                attn = _cached_attend(q, ck, cv, mask, scale)
            else:   # prefill: attend over the Plen prompt keys only —
                # scoring the zero-padded T-length cache would pay
                # T/Plen x the prefill attention FLOPs on masked slots
                # (same reasoning as the non-pp prefill_layer).
                attn = _cached_attend(q, k1, v1, mask, scale)
            # Row-parallel wo / w_down: the decode projection layers the
            # schedule IR fuses (matmul + reduce-scatter) when enabled.
            Bq, Sq = attn.shape[0], attn.shape[1]
            h = h + _row_parallel(
                attn.reshape(Bq, Sq, -1),
                lp["wo"].reshape(-1, lp["wo"].shape[-1]))
            x2 = _rmsnorm(h, lp["mlp_norm"])
            h = h + _row_parallel(_swiglu_hidden(x2, lp), lp["w_down"])
            return h, (ck, cv)

        def stage(h, layers_loc, ck_loc, cv_loc):
            h2, (ck2, cv2) = lax.scan(
                lambda c, i: layer_step(c, i), h,
                (layers_loc, ck_loc, cv_loc))
            return h2, ck2, cv2

        return stage

    def pp_chain(stage, h, layers_loc, ck_loc, cv_loc):
        idx = lax.axis_index("pp")
        ck, cv = ck_loc, cv_loc
        for s_ in range(pp):
            h, ck, cv = lax.cond(
                idx == s_,
                lambda op: stage(op[0], op[1], op[2], op[3]),
                lambda op: (op[0], op[2], op[3]),
                (h, layers_loc, ck, cv))
            if s_ < pp - 1:
                h = lax.ppermute(h, "pp", perm)
        # Replicate the last stage's output over pp (out_specs say so).
        return lax.psum(
            jnp.where(idx == pp - 1, h, jnp.zeros_like(h)), "pp"), ck, cv

    def prefill_local(layers_loc, h_loc):
        B_loc = h_loc.shape[0]
        L_loc = jax.tree.leaves(layers_loc)[0].shape[0]
        positions = jnp.broadcast_to(jnp.arange(Plen), (B_loc, Plen))
        rope = _rope_tables(positions, cfg.rope_theta, Dh)
        mask = jnp.tril(jnp.ones((Plen, Plen), bool))
        write = lambda c, new: lax.dynamic_update_slice(
            c, new, (0, 0, 0, 0))
        ck0 = jnp.zeros((L_loc, B_loc, T, KV // tp, Dh), cfg.dtype)
        stage = make_stage(rope, mask, write, attend_cache=False)
        return pp_chain(stage, h_loc, layers_loc, ck0, ck0)

    def decode_local(layers_loc, ck_loc, cv_loc, h_loc, pos):
        B_loc = h_loc.shape[0]
        rope = _rope_tables(
            jnp.broadcast_to(pos[None, None], (B_loc, 1)),
            cfg.rope_theta, Dh)
        mask = (jnp.arange(T) <= pos)[None, :]                   # [1, T]
        write = lambda c, new: lax.dynamic_update_slice(
            c, new, (0, pos, 0, 0))
        stage = make_stage(rope, mask, write, attend_cache=True)
        return pp_chain(stage, h_loc, layers_loc, ck_loc, cv_loc)

    def head_logits(h_last):
        h2 = _rmsnorm(h_last, params["final_norm"])
        logits = jnp.einsum("bd,dv->bv", h2, params["lm_head"]
                            ).astype(jnp.float32)
        return shd.constrain(logits, ("batch", "vocab"), mesh)

    # ---- prefill ------------------------------------------------------
    h = _embed_lookup(params["embed"], prompt, cfg.dtype)
    h = shd.constrain(h, ("batch", None, None), mesh)
    fn = shard_map(prefill_local, mesh=mesh,
                   in_specs=(layer_specs, act_spec),
                   out_specs=(act_spec, cache_spec, cache_spec),
                   check_vma=False)
    h, cache_k, cache_v = fn(params["layers"], h)
    key, k0 = jax.random.split(key)
    first_new = _pick_token(head_logits(h[:, -1]), k0, temperature,
                            prompt.dtype)

    # ---- decode -------------------------------------------------------
    def decode_step(carry, step_key):
        ck, cv, tok, pos = carry
        h = _embed_lookup(params["embed"], tok[:, None], cfg.dtype)
        h = shd.constrain(h, ("batch", None, None), mesh)
        fn = shard_map(decode_local, mesh=mesh,
                       in_specs=(layer_specs, cache_spec, cache_spec,
                                 act_spec, P()),
                       out_specs=(act_spec, cache_spec, cache_spec),
                       check_vma=False)
        h, ck, cv = fn(params["layers"], ck, cv, h, pos)
        nxt = _pick_token(head_logits(h[:, 0]), step_key, temperature,
                          prompt.dtype)
        return (ck, cv, nxt, pos + 1), nxt

    carry0 = (cache_k, cache_v, first_new, jnp.asarray(Plen, jnp.int32))
    _, toks = lax.scan(decode_step, carry0,
                       jax.random.split(key, max_new_tokens - 1))
    new_toks = jnp.concatenate([first_new[:, None], toks.swapaxes(0, 1)],
                               axis=1)
    return jnp.concatenate([prompt, new_toks], axis=1)


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
    scale = 1.0 / np.sqrt(Dh)

    def constrain_cache(c):
        # Heads over tp, batch over dp/fsdp: without the annotation the
        # propagator happily replicates the cache — the largest live
        # tensor of the whole decode — on every tp rank.
        if mesh is None:
            return c
        return shd.constrain(c, ("batch", None, "kv_heads", None), mesh,
                             shard_rules(cfg, mesh))

    # ---- prefill: build the cache over the prompt ----------------------
    h = _embed_lookup(params["embed"], prompt, cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(P), (B, P))
    rope_p = _rope_tables(positions, cfg.rope_theta, cfg.head_dim)
    prefill_mask = jnp.tril(jnp.ones((P, P), bool))

    def prefill_layer(h, lp):
        x = _rmsnorm(h, lp["attn_norm"])
        q = _rope(jnp.einsum("bsd,dhk->bshk", x, lp["wq"]), rope_p)
        k, v = _layer_kv(x, lp, rope_p)
        # Attention over the P prompt keys only; the T-length cache is
        # written separately (attending into the zero-padded cache would
        # pay T/P times the prefill score FLOPs on masked positions).
        attn = _cached_attend(q, k, v, prefill_mask, scale)
        ck = constrain_cache(
            jnp.zeros((B, T, KV, Dh), cfg.dtype).at[:, :P].set(k))
        cv = constrain_cache(
            jnp.zeros((B, T, KV, Dh), cfg.dtype).at[:, :P].set(v))
        h = h + jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
        h = h + _dense_mlp(_rmsnorm(h, lp["mlp_norm"]), lp)
        return h, (ck, cv)

    h, (cache_k, cache_v) = lax.scan(prefill_layer, h, params["layers"])
    key, k0 = jax.random.split(key)
    logits = jnp.einsum("bd,dv->bv",
                        _rmsnorm(h[:, -1], params["final_norm"]),
                        params["lm_head"]).astype(jnp.float32)
    first_new = _pick_token(logits, k0, temperature, prompt.dtype)  # [B]

    # ---- decode: one token per tick, cache append ----------------------
    def decode_step(carry, step_key):
        cache_k, cache_v, tok, pos = carry
        h = _embed_lookup(params["embed"], tok[:, None], cfg.dtype)
        rope_1 = _rope_tables(
            jnp.broadcast_to(pos[None, None], (B, 1)),
            cfg.rope_theta, cfg.head_dim)
        mask = (jnp.arange(T) <= pos)[None, :]          # [1, T]

        def layer(h, inputs):
            lp, ck, cv = inputs
            x = _rmsnorm(h, lp["attn_norm"])
            q = _rope(jnp.einsum("bsd,dhk->bshk", x, lp["wq"]), rope_1)
            k1, v1 = _layer_kv(x, lp, rope_1)
            ck = constrain_cache(
                lax.dynamic_update_slice(ck, k1, (0, pos, 0, 0)))
            cv = constrain_cache(
                lax.dynamic_update_slice(cv, v1, (0, pos, 0, 0)))
            attn = _cached_attend(q, ck, cv, mask, scale)
            h = h + jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
            h = h + _dense_mlp(_rmsnorm(h, lp["mlp_norm"]), lp)
            return h, (ck, cv)

        h, (cache_k, cache_v) = lax.scan(
            layer, h, (params["layers"], cache_k, cache_v))
        logits = jnp.einsum("bd,dv->bv",
                            _rmsnorm(h[:, 0], params["final_norm"]),
                            params["lm_head"]).astype(jnp.float32)
        nxt = _pick_token(logits, step_key, temperature, prompt.dtype)
        return (cache_k, cache_v, nxt, pos + 1), nxt

    # max_new_tokens - 1 decode steps: the first new token came from the
    # prefill logits, and collecting each step's OUTPUT token means no
    # trailing step whose result would be discarded.
    carry0 = (cache_k, cache_v, first_new, jnp.asarray(P, jnp.int32))
    _, toks = lax.scan(decode_step, carry0,
                       jax.random.split(key, max_new_tokens - 1))
    new_toks = jnp.concatenate([first_new[:, None], toks.swapaxes(0, 1)],
                               axis=1)
    return jnp.concatenate([prompt, new_toks], axis=1)


# ---------------------------------------------------------------------------
# Serving entry points (horovod_tpu/serving: continuous batching over a
# block-paged KV cache).  The math mirrors the batch generate() paths op
# for op, so greedy decode through the engine reproduces generate()'s
# tokens; only cache PLACEMENT differs (the engine owns the page pool).
# ---------------------------------------------------------------------------

def prefill_step(params, tokens: jax.Array, cfg: LlamaConfig, *,
                 mesh: Optional[Mesh] = None,
                 last_pos: Optional[jax.Array] = None
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Prompt prefill for the serving engine.

    tokens [B, P] int32 → (next-token greedy tokens' logits [B, V] fp32,
    per-layer K [L, B, P, KV, Dh], per-layer V).  ``last_pos`` [B] selects
    the logits position per row (bucketed prompts are right-padded: the
    real last token sits at ``len-1``, not ``P-1``); None means ``P-1``.
    Causality makes the padded tail inert for every real position, so a
    bucketed prefill emits the same token as an exact-length one."""
    B, P = tokens.shape
    scale = 1.0 / np.sqrt(cfg.head_dim)
    rules = shard_rules(cfg, mesh)
    h = _embed_lookup(params["embed"], tokens, cfg.dtype)
    if mesh is not None:
        h = shd.constrain(h, ("batch", None, None), mesh, rules)
    positions = jnp.broadcast_to(jnp.arange(P), (B, P))
    rope_p = _rope_tables(positions, cfg.rope_theta, cfg.head_dim)
    mask = jnp.tril(jnp.ones((P, P), bool))

    def layer(h, lp):
        x = _rmsnorm(h, lp["attn_norm"])
        q = _rope(jnp.einsum("bsd,dhk->bshk", x, lp["wq"]), rope_p)
        k, v = _layer_kv(x, lp, rope_p)
        attn = _cached_attend(q, k, v, mask, scale)
        h = h + jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
        h = h + _dense_mlp(_rmsnorm(h, lp["mlp_norm"]), lp)
        if mesh is not None:
            k = shd.constrain(k, ("batch", None, "kv_heads", None), mesh,
                              rules)
            v = shd.constrain(v, ("batch", None, "kv_heads", None), mesh,
                              rules)
        return h, (k, v)

    h, (ks, vs) = lax.scan(layer, h, params["layers"])
    if last_pos is None:
        h_last = h[:, -1]
    else:
        h_last = jnp.take_along_axis(
            h, last_pos[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = jnp.einsum("bd,dv->bv", _rmsnorm(h_last, params["final_norm"]),
                        params["lm_head"]).astype(jnp.float32)
    if mesh is not None:
        logits = shd.constrain(logits, ("batch", "vocab"), mesh, rules)
    return logits, ks, vs


# Logical dims of the serving page pool [L, NB, BS, KV, Dh].
_POOL_DIMS = (None, None, None, "kv_heads", None)


def paged_kernel_ok(cfg: LlamaConfig, mesh: Optional[Mesh],
                    block_size: int, interpret: bool = False) -> bool:
    """Whether :func:`decode_step_paged` can take ``use_flash=True`` for
    this model, mesh and page size: tp must split q and kv heads alike
    (each chip runs the kernel on its own ``kv_heads`` shard), and the
    per-chip pool geometry must be one the kernel compiles for (any is,
    for the interpreter)."""
    from ..ops import flash_attention as FA
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
    from ..ops import flash_attention as FA
    kernel = partial(FA.paged_attention, scale=scale, interpret=interpret)
    if mesh is None:
        return kernel(q, kp, vp, layer, tables, lengths)
    q_spec = shd.spec_for(("batch", "heads", None), rules)
    pool_spec = shd.spec_for(_POOL_DIMS, rules)
    return shard_map(
        kernel, mesh=mesh,
        in_specs=(q_spec, pool_spec, pool_spec, P(),
                  shd.spec_for(("batch", None), rules),
                  shd.spec_for(("batch",), rules)),
        out_specs=q_spec, check_vma=False)(
            q, kp, vp, layer, tables, lengths)


def decode_step_paged(params, tok: jax.Array, positions: jax.Array,
                      k_pool: jax.Array, v_pool: jax.Array,
                      tables: jax.Array, cfg: LlamaConfig, *,
                      mesh: Optional[Mesh] = None, use_flash: bool = False,
                      interpret: bool = False
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode tick for the serving engine against the paged pool.

    tok [B] int32 (this tick's input token per slot); positions [B] its
    absolute position; k_pool/v_pool [L, NB, BS, KV, Dh]; tables
    [B, n_cols] int32 block tables (inactive rows all-scratch).  Each
    layer writes its fresh K/V into ``tables[b][positions[b] // BS]`` at
    offset ``positions[b] % BS`` and attends over the table's logical
    window with a per-request ``<= position`` mask (stale slots masked).
    The attention reads the pool either through a contiguous gather (XLA
    path, GSPMD-shardable) or in the Pallas paged kernel, which copies
    each stream's live pages in by the scalar-prefetched table
    (``use_flash``; callers check :func:`paged_kernel_ok`).
    Returns (logits [B, V] fp32, k_pool, v_pool) — pass the pools donated
    so the writes land in place."""
    from ..serving.kv_pager import gather_blocks

    B = tok.shape[0]
    L, NB, BS, KV, Dh = k_pool.shape
    scale = 1.0 / np.sqrt(cfg.head_dim)
    rules = shard_rules(cfg, mesh)
    T = tables.shape[1] * BS
    h = _embed_lookup(params["embed"], tok[:, None], cfg.dtype)
    if mesh is not None:
        h = shd.constrain(h, ("batch", None, None), mesh, rules)
    rope_1 = _rope_tables(positions[:, None], cfg.rope_theta, cfg.head_dim)
    mask = (jnp.arange(T)[None, :] <= positions[:, None])[:, None, :]
    b_idx = jnp.arange(B)
    blk = tables[b_idx, positions // BS]                       # [B]
    off = positions % BS

    def constrain_pool(p):
        if mesh is None:
            return p
        return shd.constrain(p, _POOL_DIMS, mesh, rules)

    def layer(carry, xs):
        h, kp, vp = carry
        lp, li = xs
        x = _rmsnorm(h, lp["attn_norm"])
        q = _rope(jnp.einsum("bsd,dhk->bshk", x, lp["wq"]), rope_1)
        k1, v1 = _layer_kv(x, lp, rope_1)                  # [B, 1, KV, Dh]
        kp = constrain_pool(kp.at[li, blk, off].set(k1[:, 0]))
        vp = constrain_pool(vp.at[li, blk, off].set(v1[:, 0]))
        if use_flash:
            attn = _paged_kernel_attend(
                q[:, 0], kp, vp, li, tables, positions + 1, scale,
                mesh, rules, interpret)[:, None]
        else:
            keys = gather_blocks(kp[li], tables)           # [B, T, KV, Dh]
            vals = gather_blocks(vp[li], tables)
            attn = _cached_attend(q, keys, vals, mask, scale)
        h = h + jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
        h = h + _dense_mlp(_rmsnorm(h, lp["mlp_norm"]), lp)
        return (h, kp, vp), None

    (h, k_pool, v_pool), _ = lax.scan(
        layer, (h, k_pool, v_pool), (params["layers"], jnp.arange(L)))
    logits = jnp.einsum("bd,dv->bv",
                        _rmsnorm(h[:, 0], params["final_norm"]),
                        params["lm_head"]).astype(jnp.float32)
    if mesh is not None:
        logits = shd.constrain(logits, ("batch", "vocab"), mesh, rules)
    return logits, k_pool, v_pool


def extend_step_paged(params, tok: jax.Array, positions: jax.Array,
                      valid: jax.Array, k_pool: jax.Array,
                      v_pool: jax.Array, tables: jax.Array,
                      cfg: LlamaConfig, *, mesh: Optional[Mesh] = None
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Multi-token paged forward: S tokens per row in ONE dispatch.

    The serving front door's verify-forward entry — it serves both
    (a) **prefix-hit tail prefill**: a prompt whose head is already in
    the pool (radix prefix cache) prefills only its tail while attending
    over the cached prefix K/V, and (b) **speculative-decode verify**:
    the target model scores ``k + 1`` positions (last accepted token +
    k draft tokens) in one forward so the accepted prefix falls out of a
    single logits comparison.

    tok [B, S] int32; positions [B, S] absolute positions per token;
    valid [B, S] bool — False slots (right-padding, inactive verify
    rows) route their K/V writes to scratch block 0 so a padded slot
    repeating a real position can never double-write a live (block,
    offset); their logits are meaningless and must be ignored.
    k_pool/v_pool [L, NB, BS, KV, Dh]; tables [B, n_cols] int32.

    Each layer writes all S fresh K/V rows first, then attends over the
    table's logical window with the per-token causal mask ``pool_pos <=
    positions[b, s]`` — so token s sees the cached prefix AND the
    earlier tokens of this same call (their K/V just landed in the
    pool), exactly the visibility a monolithic prefill gives it.  Reads
    go through the contiguous-gather path (GSPMD-shardable); the Pallas
    decode kernel is single-query and does not apply here.  Returns
    (logits [B, S, V] fp32, k_pool, v_pool) — donate the pools."""
    from ..serving.kv_pager import gather_blocks

    B, S = tok.shape
    L, NB, BS, KV, Dh = k_pool.shape
    scale = 1.0 / np.sqrt(cfg.head_dim)
    rules = shard_rules(cfg, mesh)
    T = tables.shape[1] * BS
    h = _embed_lookup(params["embed"], tok, cfg.dtype)
    if mesh is not None:
        h = shd.constrain(h, ("batch", None, None), mesh, rules)
    rope_s = _rope_tables(positions, cfg.rope_theta, cfg.head_dim)
    mask = jnp.arange(T)[None, None, :] <= positions[:, :, None]  # [B,S,T]
    blk = jnp.where(valid,
                    jnp.take_along_axis(tables, positions // BS, axis=1),
                    0)                                             # [B,S]
    off = jnp.where(valid, positions % BS, 0)

    def constrain_pool(p):
        if mesh is None:
            return p
        return shd.constrain(p, _POOL_DIMS, mesh, rules)

    def layer(carry, xs):
        h, kp, vp = carry
        lp, li = xs
        x = _rmsnorm(h, lp["attn_norm"])
        q = _rope(jnp.einsum("bsd,dhk->bshk", x, lp["wq"]), rope_s)
        k1, v1 = _layer_kv(x, lp, rope_s)                  # [B, S, KV, Dh]
        kp = constrain_pool(kp.at[li, blk, off].set(k1))
        vp = constrain_pool(vp.at[li, blk, off].set(v1))
        keys = gather_blocks(kp[li], tables)               # [B, T, KV, Dh]
        vals = gather_blocks(vp[li], tables)
        attn = _cached_attend(q, keys, vals, mask, scale)
        h = h + jnp.einsum("bshk,hkd->bsd", attn, lp["wo"])
        h = h + _dense_mlp(_rmsnorm(h, lp["mlp_norm"]), lp)
        return (h, kp, vp), None

    (h, k_pool, v_pool), _ = lax.scan(
        layer, (h, k_pool, v_pool), (params["layers"], jnp.arange(L)))
    logits = jnp.einsum("bsd,dv->bsv",
                        _rmsnorm(h, params["final_norm"]),
                        params["lm_head"]).astype(jnp.float32)
    if mesh is not None:
        logits = shd.constrain(logits, ("batch", None, "vocab"), mesh,
                               rules)
    return logits, k_pool, v_pool


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
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    if _use_blockwise_ce(cfg, mesh):
        from ..ops.losses import blockwise_cross_entropy
        h, aux = forward(params, inputs, cfg, mesh=mesh,
                         return_hidden=True)
        B, S, D = h.shape
        nll = blockwise_cross_entropy(
            h.reshape(B * S, D), params["lm_head"],
            targets.reshape(-1).astype(jnp.int32))
        return nll.mean() + cfg.moe_aux_weight * aux
    logits, aux = forward(params, inputs, cfg, mesh=mesh)
    # logsumexp form of the CE — identical math to log_softmax + gather,
    # but the [B,S,V] fp32 log-prob tensor is never materialized, only
    # its row reduction (memory win; step time measured equal on TPU).
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return (lse - picked).mean() + cfg.moe_aux_weight * aux


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
            h = _embed_lookup(emb, inputs, cfg.dtype)
            return shd.constrain(h, ("batch", "seq", None), mesh)

        h, embed_vjp = jax.vjp(embed_fn, params["embed"])
        head_in = {"lm_head": params["lm_head"],
                   "final_norm": params["final_norm"]}

        def local(layers_loc, head_loc, h_loc, tgt_loc):
            B_loc = h_loc.shape[0]
            mb_loc = B_loc // M
            mbs = h_loc.reshape(M, mb_loc, S_loc, D)
            tgts = tgt_loc.reshape(M, mb_loc, S_loc)
            base = lax.axis_index("sp") * S_loc + jnp.arange(S_loc)
            positions = jnp.broadcast_to(base[None, :], (mb_loc, S_loc))
            rope = _rope_tables(positions, cfg.rope_theta, cfg.head_dim)

            # lm_head fsdp gather ONCE per step, outside the tick loop
            # (XLA does not hoist collectives out of while loops); its
            # grad reduce-scatters back once at the end.
            head_full = {
                "lm_head": lax.all_gather(head_loc["lm_head"], "fsdp",
                                          axis=0, tiled=True),  # [D, V/tp]
                "final_norm": head_loc["final_norm"],
            }

            def loss_head(head, y, m):
                h2 = _rmsnorm(y, head["final_norm"])
                logits = jnp.einsum("bsd,dv->bsv", h2, head["lm_head"]
                                    ).astype(jnp.float32)
                # CE over the tp-sharded vocab.  The max shift is taken on
                # stopped gradients (exact: the shift cancels in the lse
                # derivative) and reduced with all_gather+max — pmax has
                # no AD rule even on zero tangents.
                mloc = jnp.max(jax.lax.stop_gradient(logits), axis=-1)
                mx = jnp.max(
                    lax.all_gather(mloc, "tp", axis=0, tiled=False), axis=0)
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
                make_stage_fn(rope), layers_loc, mbs, loss_head, head_full,
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
    :mod:`horovod_tpu.models.kimi_linear` is the other.  Where the model
    sets ``LOSS_HAS_AUX`` its loss returns ``(loss, aux)`` and so does
    the step, as its third output.

    On pp>1 meshes ``pipeline_schedule`` selects "1f1b" (default: explicit
    interleaved fwd/bwd schedule, activation memory bounded by 2*(pp-1)
    microbatches) or "gpipe" (autodiff through the fill-drain forward);
    both are the Llama stack's."""
    model = model or _THIS
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
        updates, opt_state = tx.update(grads, opt_state, params)
        params = jax.tree.map(jnp.add, params, updates)
        return params, opt_state, loss

    opt_shard = _opt_shardings(tx, cfg, mesh, model)
    return jax.jit(
        step,
        in_shardings=(pshard, opt_shard, batch_shard),
        out_shardings=(pshard, opt_shard, repl),
        donate_argnums=(0, 1))
