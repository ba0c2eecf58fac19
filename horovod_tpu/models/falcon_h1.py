"""Falcon-H1 (tiiuae, ``model_type: falcon_h1``): attention and a Mamba-2
state-space mixer side by side in every layer, a dense SwiGLU after them.

Pre-norm residual stack (:func:`horovod_tpu.models.layers.block`),
RMSNorm, untied head, every layer alike (``attn_layer_indices`` null).
With ``u`` the layer's normed input:

- **mixer** (:func:`parallel_mixer`): attention and the SSM read the same
  ``u`` and their outputs go into one residual add.  Attention is GQA with
  the keys times ``key_multiplier``, half-split RoPE at ``rope_theta``,
  the output times ``attention_out_multiplier``.  The SSM (region
  ``hvd.ssm``): ``(u ssm_in_multiplier) W_in``, each of its segments z, x,
  B, C, dt times its ``ssm_multipliers`` entry; ``xBC = SiLU(causal
  depthwise conv(xBC) + b)``; ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; the scan of :mod:`horovod_tpu.ops.ssd`; ``y + D x``;
  ``GroupRMSNorm(y SiLU(z)) w`` over ``ssm_groups`` groups; ``W_out``
  times ``ssm_out_multiplier``.
- **mlp**: ``W_down(SiLU((v W_gate) m0) (v W_up)) m1``, ``(m0, m1)`` the
  ``mlp_multipliers``.
- the embedding times ``embedding_multiplier``, the head times
  ``lm_head_multiplier``.  The serving steps' logits leave the head's
  multiplier out: a positive scale moves no greedy pick.

Served by :class:`horovod_tpu.serving.ServingEngine` through the steps of
:mod:`horovod_tpu.models.steps`, which find this module by the
configuration's class; its attention's cache facts are the GQA mixer's
(``layers.gqa_*``).  Beside the paged K/V pools each engine row holds a
**state slot** (:func:`state_rows`): the SSM state ``[H, N, P]`` and the
convolution's last ``k - 1`` inputs, one array of each ``[layers,
max_active, ...]``.  A prompt's prefill runs the chunked scan and leaves
the state at its last real token (a bucket's padding takes ``dt = 0``,
which leaves the state unchanged) for the engine to write into the
request's row; every decode tick reads and writes each row's state in
place through ``hvd_ssd_decode``.  What the slot cannot give is refused
(:func:`check_servable`): the prefix cache, speculative decoding, the
prefix-hit extend path and migration would need the state at a prefix
boundary, which is neither kept nor shipped.  Not trained here
(:func:`check_trainable`).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from ..obs.trace import region
from ..ops import ssd
from .layers import (  # noqa: F401  (the GQA cache facts, the model's own)
    attention,
    attention_path,
    block,
    causal_conv,
    conv_tail,
    gqa_cache_rows as cache_rows,
    gqa_paged_attend,
    gqa_paged_kernel_ok as paged_kernel_ok,
    gqa_pool_dims as pool_dims,
    rmsnorm,
    rope,
    rope_tables,
)

#: what :attr:`ServingEngine.attention_path` calls this model's kernel
PAGED_KERNEL = "pallas"

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    d_model: int = 5120
    n_layers: int = 72
    n_heads: int = 20
    n_kv_heads: int = 4
    head_dim: int = 128
    d_ff: int = 21504
    ssm_heads: int = 32
    ssm_head_dim: int = 128
    ssm_groups: int = 2
    ssm_state: int = 256
    conv_kernel: int = 4
    ssm_chunk: int = 128
    rope_theta: float = 1e11
    rms_eps: float = 1e-5
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    key_multiplier: float = 0.011048543456039804
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    #: the factors of W_in's segments z, x, B, C, dt
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    #: the gate's factor and the MLP output's
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    dtype: Any = jnp.bfloat16

    #: a plain stack: one pass, a cache layer a layer
    loops = 1

    @property
    def cache_layers(self) -> int:
        return self.n_layers

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: x, B and C."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def in_dim(self) -> int:
        """Width of ``W_in``'s output: z, then x, B, C, then dt."""
        return self.d_ssm + self.conv_dim + self.ssm_heads

    @staticmethod
    def from_published(c: dict, **kw) -> "FalconH1Config":
        """From the keys of the published ``config.json``."""
        for key, want in (("attn_layer_indices", None),
                          ("mamba_rms_norm", True),
                          ("mamba_norm_before_gate", False),
                          ("mamba_use_mlp", True), ("mamba_conv_bias", True),
                          ("mamba_proj_bias", False),
                          ("attention_bias", False), ("mlp_bias", False),
                          ("projectors_bias", False), ("rope_scaling", None),
                          ("hidden_act", "silu"),
                          ("tie_word_embeddings", False)):
            if c[key] != want:
                raise NotImplementedError(
                    f"falcon_h1: {key}={c[key]!r} is not written here "
                    f"(only {want!r})")
        if c["mamba_n_heads"] * c["mamba_d_head"] != c["mamba_d_ssm"]:
            raise NotImplementedError(
                "falcon_h1: mamba_d_ssm must be mamba_n_heads x mamba_d_head")
        base = dict(
            vocab_size=c["vocab_size"], d_model=c["hidden_size"],
            n_layers=c["num_hidden_layers"],
            n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            d_ff=c["intermediate_size"], ssm_heads=c["mamba_n_heads"],
            ssm_head_dim=c["mamba_d_head"], ssm_groups=c["mamba_n_groups"],
            ssm_state=c["mamba_d_state"], conv_kernel=c["mamba_d_conv"],
            ssm_chunk=c["mamba_chunk_size"],
            rope_theta=float(c["rope_theta"]), rms_eps=c["rms_norm_eps"],
            embedding_multiplier=c["embedding_multiplier"],
            lm_head_multiplier=c["lm_head_multiplier"],
            key_multiplier=c["key_multiplier"],
            attention_in_multiplier=c["attention_in_multiplier"],
            attention_out_multiplier=c["attention_out_multiplier"],
            ssm_in_multiplier=c["ssm_in_multiplier"],
            ssm_out_multiplier=c["ssm_out_multiplier"],
            ssm_multipliers=tuple(c["ssm_multipliers"]),
            mlp_multipliers=tuple(c["mlp_multipliers"]))
        base.update(kw)
        return FalconH1Config(**base)

    @staticmethod
    def tiny(**kw) -> "FalconH1Config":
        """Test-scale config (fast CPU compile): 4 SSM heads of 16 in 2
        groups, a state of 8, chunks of 8."""
        base = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, head_dim=16, d_ff=128, ssm_heads=4,
                    ssm_head_dim=16, ssm_groups=2, ssm_state=8, ssm_chunk=8,
                    rope_theta=1e4, dtype=jnp.float32)
        base.update(kw)
        return FalconH1Config(**base)


# -- parameters ---------------------------------------------------------------

def leaf_shapes(cfg: FalconH1Config) -> dict:
    """One layer's leaves: ``name -> (shape, fan_in)``; fan_in None marks
    a float32 leaf with an initialisation of its own (gains, ``A_log``,
    ``dt_bias``, ``ssm_D``).  The convolution's kernel and bias are drawn
    with the kernel's width as their fan-in."""
    D, H, KV, Dh, F = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                       cfg.head_dim, cfg.d_ff)
    Hs, k = cfg.ssm_heads, cfg.conv_kernel
    return {
        "attn_norm": ((D,), None), "mlp_norm": ((D,), None),
        "wq": ((D, H, Dh), D), "wk": ((D, KV, Dh), D),
        "wv": ((D, KV, Dh), D), "wo": ((H, Dh, D), H * Dh),
        "ssm_in": ((D, cfg.in_dim), D),
        "conv_w": ((k, cfg.conv_dim), k), "conv_b": ((cfg.conv_dim,), k),
        "A_log": ((Hs,), None), "dt_bias": ((Hs,), None),
        "ssm_D": ((Hs,), None), "ssm_norm": ((cfg.d_ssm,), None),
        "ssm_out": ((cfg.d_ssm, D), cfg.d_ssm),
        "w_gate": ((D, F), D), "w_up": ((D, F), D), "w_down": ((F, D), F)}


def _special_leaf(name: str, shape: tuple, key):
    """Mamba-2's own initialisation: ``A = -exp(A_log)`` with ``exp(A_log)``
    uniform in [1, 16]; ``dt_bias`` the softplus inverse of a ``dt`` whose
    log is uniform in [log 1e-3, log 1e-1]; ``D`` 1; gains 1."""
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, np.log(1e-3),
                                        np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    return jnp.ones(shape, F32)


def init_params(cfg: FalconH1Config, key: jax.Array) -> dict:
    """The program's own seeded leaves, whole on one chip."""
    D, V, L = cfg.d_model, cfg.vocab_size, cfg.n_layers
    rnd = lambda k, shape, fan: (jax.random.normal(
        k, shape, F32) / np.sqrt(fan)).astype(cfg.dtype)
    shapes = leaf_shapes(cfg)
    ks = jax.random.split(jax.random.fold_in(key, 0), len(shapes))
    layers = {
        name: (rnd(k, (L,) + shape, fan) if fan is not None else
               jax.vmap(lambda kk: _special_leaf(name, shape, kk))(
                   jax.random.split(k, L)))
        for k, (name, (shape, fan)) in zip(ks, sorted(shapes.items()))}
    ke, kh = jax.random.split(jax.random.fold_in(key, 1 << 20))
    return {"embed": rnd(ke, (V, D), D), "layers": layers,
            "final_norm": jnp.ones((D,), F32), "lm_head": rnd(kh, (D, V), D)}


# -- the layer ----------------------------------------------------------------

def _segments(cfg: FalconH1Config, zxbcdt):
    """``W_in``'s output, each segment times its multiplier: ``(z, xBC,
    dt)``."""
    m = cfg.ssm_multipliers
    z, xbc, dt = jnp.split(zxbcdt, [cfg.d_ssm, cfg.d_ssm + cfg.conv_dim], -1)
    GN = cfg.ssm_groups * cfg.ssm_state
    x, b, c = jnp.split(xbc, [cfg.d_ssm, cfg.d_ssm + GN], -1)
    xbc = jnp.concatenate([x * m[1], b * m[2], c * m[3]], -1)
    return z * m[0], xbc, dt * m[4]


def _ssm_inputs(cfg: FalconH1Config, lp, xbc, dt):
    """After the convolution: ``x [B, S, H, P]``, ``B, C [B, S, G, N]``,
    ``dt`` through its softplus ``[B, S, H]`` and ``A [H]``, float32."""
    Bsz, S = xbc.shape[:2]
    H, P, G, N = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
                  cfg.ssm_state)
    x, b, c = jnp.split(xbc.astype(F32), [cfg.d_ssm, cfg.d_ssm + G * N], -1)
    dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"])
    return (x.reshape(Bsz, S, H, P), b.reshape(Bsz, S, G, N),
            c.reshape(Bsz, S, G, N), dt, -jnp.exp(lp["A_log"]))


def ssm_prompt(cfg: FalconH1Config, lp, xbc, dt, last=None):
    """The SSM of a whole prompt from its start: ``(y [B, S, d_ssm], the
    state at its last real token [B, H, N, P], the convolution's tail [B,
    k-1, conv_dim])``; ``last [B]`` the last real position of a
    right-padded prompt (None: every position is real).  The padding
    takes ``dt = 0``, which leaves the state as it is."""
    with region("ssm"):
        conv = causal_conv(xbc, lp["conv_w"], lp["conv_b"])
        x, b, c, dtp, A = _ssm_inputs(cfg, lp, conv, dt)
        if last is not None:
            real = jnp.arange(xbc.shape[1])[None, :] <= last[:, None]
            dtp = jnp.where(real[..., None], dtp, 0.0)
        y, state = ssd.ssd_chunked(x, dtp, A, b, c, cfg.ssm_chunk)
        y = y + lp["ssm_D"][:, None] * x
        tail = conv_tail(xbc, cfg.conv_kernel, last=last)
        return y.reshape(xbc.shape[:2] + (cfg.d_ssm,)), state, tail


def ssm_tick(cfg: FalconH1Config, lp, xbc, dt, slots, li, live,
             kernel: bool, interpret=False):
    """One decode token of every row against the state slots of cache
    layer ``li``: ``slots`` ``(ssm [L, R, H, N, P], conv [L, R, k-1,
    conv_dim])``, ``live [R]`` the rows with a stream.  Through
    ``hvd_ssd_decode`` (``kernel``), which writes each live row's state in
    place, else :func:`horovod_tpu.ops.ssd.ssd_step` on the layer.
    Returns ``(y [R, 1, d_ssm], slots)``."""
    with region("ssm"):
        ssm_slots, conv_slots = slots
        tail = conv_slots[li]
        conv = causal_conv(xbc, lp["conv_w"], lp["conv_b"], tail=tail)
        conv_slots = conv_slots.at[li].set(
            conv_tail(xbc, cfg.conv_kernel, tail=tail).astype(
                conv_slots.dtype))
        x, b, c, dtp, A = _ssm_inputs(cfg, lp, conv, dt)
        x, b, c, dtp = x[:, 0], b[:, 0], c[:, 0], dtp[:, 0]   # one token
        xdt, decay = x * dtp[..., None], jnp.exp(dtp * A)
        if kernel:
            y, ssm_slots = ssd.ssd_decode(ssm_slots, li, live, xdt, decay, b,
                                          c, interpret=interpret)
        else:
            y, new = ssd.ssd_step(ssm_slots[li], xdt, decay, b, c)
            ssm_slots = ssm_slots.at[li].set(new.astype(ssm_slots.dtype))
        y = y + lp["ssm_D"][:, None] * x
        return y.reshape(y.shape[0], 1, cfg.d_ssm), (ssm_slots, conv_slots)


def gated_norm(y, z, w, groups: int, eps: float):
    """``GroupRMSNorm(y SiLU(z)) w``: the gate before the norm, each of
    ``groups`` equal slices of the last axis normed alone, float32."""
    g = y.astype(F32) * jax.nn.silu(z.astype(F32))
    g = g.reshape(g.shape[:-1] + (groups, -1))
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return g.reshape(y.shape) * w


def parallel_mixer(x, lp, cfg: FalconH1Config, tables, attend):
    """Attention and the SSM on the same normed ``x``, summed: the frame's
    mixer.  ``attend(q, k, v, (xBC, dt, lp)) -> ((o, y), kept)`` is the
    step's own: where the layer's keys, values and state go, what q
    attends over, and the SSM's scan from ``xBC`` (before its
    convolution) and ``dt`` (before its softplus) to ``y`` (with its
    ``D`` term)."""
    xa = x if cfg.attention_in_multiplier == 1 else \
        x * cfg.attention_in_multiplier
    q = jnp.einsum("bsd,dhk->bshk", xa, lp["wq"])
    k = jnp.einsum("bsd,dhk->bshk", xa, lp["wk"]) * cfg.key_multiplier
    v = jnp.einsum("bsd,dhk->bshk", xa, lp["wv"])
    q, k = rope(q, tables), rope(k, tables)
    with region("ssm"):
        z, xbc, dt = _segments(cfg, jnp.einsum(
            "bsd,de->bse", x * cfg.ssm_in_multiplier, lp["ssm_in"]))
    (o, y), kept = attend(q, k, v, (xbc, dt, lp))
    out = jnp.einsum("bshk,hkd->bsd", o, lp["wo"]) * \
        cfg.attention_out_multiplier
    with region("ssm"):
        y = gated_norm(y, z, lp["ssm_norm"], cfg.ssm_groups, cfg.rms_eps)
        out = out + jnp.einsum("bse,ed->bsd", y.astype(x.dtype),
                               lp["ssm_out"]) * cfg.ssm_out_multiplier
    return out, kept


def falcon_mlp(x2, lp, cfg: FalconH1Config):
    """The SwiGLU with its two multipliers, in the frame's mlp form."""
    m0, m1 = cfg.mlp_multipliers
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x2, lp["w_gate"]) * m0)
    u = jnp.einsum("bsd,df->bsf", x2, lp["w_up"])
    return jnp.einsum("bsf,fd->bsd", g * u, lp["w_down"]) * m1, None


def _mixer(cfg: FalconH1Config, tables, attend):
    return partial(parallel_mixer, cfg=cfg, tables=tables, attend=attend)


def forward(params: dict, tokens: jax.Array, cfg: FalconH1Config, *,
            mesh: Optional[Mesh] = None, return_hidden: bool = False):
    """Logits ``[B, S, V]`` (float32, the head's multiplier in; with
    ``return_hidden`` the final normed hidden states).  The whole
    sequence at once from an empty state, no cache: the tests' path."""
    B, S = tokens.shape
    h = serve_embed(params["embed"], tokens, cfg)
    tables = rope_tables(jnp.broadcast_to(jnp.arange(S), (B, S)),
                         cfg.rope_theta, cfg.head_dim)

    def attend(q, k, v, s):
        y, _, _ = ssm_prompt(cfg, s[2], s[0], s[1])
        return (attention(q, k, v, mesh, True), y), None

    mixer = _mixer(cfg, tables, attend)
    mlp = partial(falcon_mlp, cfg=cfg)

    def body(h, lp):
        h, _, _ = block(h, lp, mixer, mlp, cfg.rms_eps)
        return h, None

    h, _ = lax.scan(body, h, params["layers"])
    with region("head"):
        h = rmsnorm(h, params["final_norm"], cfg.rms_eps)
        if return_hidden:
            return h
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"])
        return logits.astype(F32) * cfg.lm_head_multiplier


def check_trainable(cfg: FalconH1Config) -> None:
    """What :func:`horovod_tpu.models.steps.make_train_step` asks first."""
    raise NotImplementedError(
        "falcon_h1 is served here, not trained: no training path (its "
        "shardings, its optimizer state) is written for it, and four of "
        "its layers at 16 bytes a parameter are 27.5 GB")


# -- serving: what models.steps asks a model for ----------------------------

def state_rows(cfg: FalconH1Config) -> tuple:
    """Per-row shape of each state slot array ``[L, max_active, ...]``:
    the SSM state ``[H, N, P]`` and the convolution's last ``k - 1``
    inputs."""
    return ((cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim),
            (cfg.conv_kernel - 1, cfg.conv_dim))


#: what the state slot cannot give, by the name the refusal gives it
_NO_PREFIX_STATE = ("a request's state slot holds its state after its last "
                    "token only: the state at a prefix boundary is neither "
                    "kept nor shipped")


def check_servable(cfg: FalconH1Config, mesh: Optional[Mesh],
                   engine_cfg=None, path: Optional[str] = None) -> None:
    """Raise, by name, for what the serving steps cannot run for this
    model: a mesh, the prefix cache, speculative decoding (``spec_k``),
    the prefix-hit extend path and migration (``path``: ``"extend"`` or
    ``"migration"``, asked where the engine takes one)."""
    if mesh is not None and any(s > 1 for s in mesh.shape.values()):
        raise NotImplementedError(
            "falcon_h1 is served whole on one chip: its leaves and state "
            "slots carry no logical dims to split over a mesh")
    if engine_cfg is not None and engine_cfg.prefix_cache:
        raise NotImplementedError(f"falcon_h1 with prefix_cache: "
                                  f"{_NO_PREFIX_STATE}")
    if engine_cfg is not None and engine_cfg.spec_k:
        raise NotImplementedError(
            f"falcon_h1 with spec_k: a rejected draft token would have "
            f"moved the state; {_NO_PREFIX_STATE}")
    if path == "extend":
        raise NotImplementedError(
            f"falcon_h1 has no prefix-hit extend path: {_NO_PREFIX_STATE}")
    if path == "migration":
        raise NotImplementedError(
            f"falcon_h1 requests are not migrated: the payload carries K/V "
            f"pages and no state slot; {_NO_PREFIX_STATE}")


def serve_embed(embed, tokens, cfg: FalconH1Config):
    """A lookup (over 261,120 rows the one-hot product would be as much
    work as the layers it feeds), times ``embedding_multiplier``."""
    with region("embed"):
        return (jnp.take(embed, tokens, axis=0).astype(F32)
                * cfg.embedding_multiplier).astype(cfg.dtype)


def serve_runs(params: dict, cfg: FalconH1Config, step) -> list:
    """``[(stacked leaves, attend -> mixer, mlp)]``: one run, the stack
    (the tick's kernels are its attend's)."""
    tables = rope_tables(step.positions, cfg.rope_theta, cfg.head_dim)
    return [(params["layers"], partial(_mixer, cfg, tables),
             partial(falcon_mlp, cfg=cfg))]


def prefill_attend(cfg: FalconH1Config, mesh, P: int, last_pos=None):
    """``attend`` of the prompt prefill: causal attention of the prompt
    through the attention dispatch (the flash forward kernel on the chip)
    and the chunked scan from an empty state; the layer leaves its keys,
    values, the state at ``last_pos`` and the convolution's tail there."""
    def attend(q, k, v, s, li, state):
        y, final, tail = ssm_prompt(cfg, s[2], s[0], s[1], last_pos)
        return (attention(q, k, v, mesh, True), y), (state, (
            k, v, final.astype(cfg.dtype), tail.astype(cfg.dtype)))
    return attend


def prefill_path(cfg: FalconH1Config, P: int) -> str:
    """The attention a ``P``-token prefill runs (``"flash"`` on the chip
    at the buckets a configuration should list)."""
    return attention_path((1, P, cfg.n_heads, cfg.head_dim),
                          jnp.dtype(cfg.dtype).itemsize, None)


def paged_attend(cfg: FalconH1Config, mesh, tables, blk, off, mask,
                 last=None, interpret: bool = False):
    """``attend`` of a decode tick: ``state`` is the K and V pools, then
    the two state slot arrays.  Keys and values as the GQA mixer's
    (:func:`horovod_tpu.models.layers.gqa_paged_attend`: the paged kernel
    where each stream's ``last`` position is given, else the gather); the
    SSM through ``hvd_ssd_decode`` on the same condition, a row whose
    table starts with scratch block 0 holding no stream."""
    if blk.ndim != 1:
        check_servable(cfg, mesh, path="extend")
    kv_attend = gqa_paged_attend(cfg, mesh, tables, blk, off, mask, last,
                                 interpret)
    live = tables[:, 0] != 0

    def attend(q, k1, v1, s, li, state):
        o, ((kp, vp), _) = kv_attend(q, k1, v1, li, state[:2])
        y, slots = ssm_tick(cfg, s[2], s[0], s[1], state[2:], li, live,
                            last is not None, interpret)
        return (o, y), ((kp, vp) + slots, None)

    return attend
