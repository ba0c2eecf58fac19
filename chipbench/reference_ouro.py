"""The plain float32 reference of a looped decoder (Ouro), and the
comparison that decides a serving cell's ``correct``.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, a request at a time, nothing imported from the program.
Weights come from :mod:`chipbench.weights_ouro` again, from the seed, one
layer at a time, each layer used once in every pass.

The model, as ``config.json`` of ByteDance/Ouro-2.6B and the family's
paper (arXiv:2510.25741) give it; what the file does not itself state is
listed under ``assumed`` in the configuration:

    h = E[x]
    for pass t = 1..T, for layer l = 1..L, the same weights in every pass:
        h = h + RMSNorm(Attn_l(RMSNorm(h; g1_l)); g2_l)
        h = h + RMSNorm(SwiGLU_l(RMSNorm(h; g3_l)); g4_l)
    after layer L of every pass:  h = RMSNorm(h; g_f),  z_t = h,
        lambda_t = sigmoid(w_g . z_t + b_g)
    logits = W_head z_T

``Attn_l`` is causal softmax attention at scale ``head_dim ** -0.5`` with
rotary embedding over the whole head in the half-split convention; a query
of pass ``t`` sees the keys and values of pass ``t``, which a forward
without a cache gives by itself.  A token leaves at the first pass whose
cumulative exit probability (:func:`exit_distribution`) reaches the
threshold, else at the last; at the published threshold of 1 none leaves
early, which :func:`served_gaps` checks on what it is given
(``exit_sum_max``) instead of assuming it.

``quant="fp8"`` is the control of :mod:`chipbench.reference`: every matmul
operand rounded through float8_e4m3.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_ouro as weights
from .reference import F32, _frozen, _mm, _rmsnorm, _rope


# -- the arithmetic ---------------------------------------------------------

def layer_fn(w: dict, h, dims: dict, quant=None):
    """One sandwich-norm decoder layer on one sequence h [S, D] (float32)."""
    w = jax.tree.map(lambda a: a.astype(F32), w)
    eps, theta = dims["rms_norm_eps"], dims["rope_theta"]
    H, KV, Dh = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    S = h.shape[0]
    x = _rmsnorm(h, w["attn_norm"], eps)
    q = _rope(_mm("sd,dhk->shk", x, w["wq"], quant), theta)
    k = _rope(_mm("sd,dhk->shk", x, w["wk"], quant), theta)
    v = _mm("sd,dhk->shk", x, w["wv"], quant)
    qg = q.reshape(S, KV, H // KV, Dh)
    s = _mm("sgrk,tgk->grst", qg, k, quant) / np.sqrt(Dh)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = _mm("grst,tgk->sgrk", p, v, quant).reshape(S, H, Dh)
    a = _mm("shk,hkd->sd", a, w["wo"], quant)
    h = h + _rmsnorm(a, w["attn_post_norm"], eps)
    x = _rmsnorm(h, w["mlp_norm"], eps)
    g = jax.nn.silu(_mm("sd,df->sf", x, w["w_gate"], quant))
    u = _mm("sd,df->sf", x, w["w_up"], quant)
    m = _mm("sf,fd->sd", g * u, w["w_down"], quant)
    return h + _rmsnorm(m, w["mlp_post_norm"], eps)


def close_pass(outer: dict, h, dims: dict):
    """What ends every pass: the final norm, and the exit gate on the
    normed state.  Returns ``(z [S, D], lambda [S])``."""
    z = _rmsnorm(h, outer["final_norm"].astype(F32), dims["rms_norm_eps"])
    lam = jax.nn.sigmoid(
        jnp.einsum("sd,d->s", z, outer["exit_gate_w"].astype(F32),
                   precision="highest") + outer["exit_gate_b"].astype(F32))
    return z, lam


def forward(layers: list, outer: dict, tokens, dims: dict, quant=None):
    """The whole model on one sequence of token ids [S]: ``(logits [S, V],
    lambda [loops, S])``.  ``layers`` is the list of the stack's layers,
    used once in every pass."""
    h = outer["embed"].astype(F32)[jnp.asarray(tokens)]
    lams = []
    for _ in range(dims["loops"]):
        for w in layers:
            h = layer_fn(w, h, dims, quant)
        h, lam = close_pass(outer, h, dims)
        lams.append(lam)
    logits = _mm("sd,dv->sv", h, outer["lm_head"].astype(F32), quant)
    return logits, jnp.stack(lams)


# -- the exit rule ------------------------------------------------------------

def exit_distribution(lams, threshold: float) -> dict:
    """From the gates ``lams [T, ...]`` of the T passes (the last pass's is
    not read): ``p [T, ...]``, the probability of leaving at each pass,
    ``p_t = lambda_t prod_{s<t} (1 - lambda_s)`` and the rest at the last;
    ``before_last``, the running sum of ``p`` before the last pass; and
    ``exit_pass`` (0-based), the first pass at which the running sum
    reaches ``threshold``, else the last.  On the host in float64: three
    gates near 1 leave a rest that float32 rounds away, and the sum would
    read as having reached 1."""
    lams = np.asarray(lams, np.float64)
    T = lams.shape[0]
    stay = np.cumprod(1.0 - lams[:-1], axis=0)           # prod_{s<=t}
    before = np.concatenate([np.ones_like(lams[:1]), stay[:-1]], 0)
    p = np.concatenate([lams[:-1] * before, stay[-1:]], 0)
    run = np.cumsum(p[:-1], axis=0)
    reached = run >= threshold
    exit_pass = np.where(reached.any(0), np.argmax(reached, 0), T - 1)
    return {"p": p, "before_last": run[-1], "exit_pass": exit_pass}


# -- serving: the gap of each served token under the reference ---------------

@functools.lru_cache(maxsize=None)
def _programs(fdims, dtype_name: str, quant):
    """The jitted blocks for one model shape (built once per process)."""
    dims, dtype = dict(fdims), jnp.dtype(dtype_name)
    make_layer = jax.jit(lambda key, i: weights.layer(key, i, dims, dtype))
    make_outer = jax.jit(lambda key: weights.outer(key, dims, dtype))

    @jax.jit
    def embed(w_embed, tokens):
        return w_embed.astype(F32)[tokens]

    @jax.jit
    def fwd(w, rows):
        """One layer on rows [K, S, D], a row at a time."""
        return jax.lax.map(lambda h: layer_fn(w, h, dims, quant), rows)

    @jax.jit
    def close(outer, rows):
        return jax.lax.map(lambda h: close_pass(outer, h, dims), rows)

    @jax.jit
    def logits_at(outer, rows, pos):
        """Head logits at the listed positions of each row [K, n, V]."""
        head = outer["lm_head"].astype(F32)
        return jax.lax.map(
            lambda hp: _mm("sd,dv->sv", hp[0][hp[1]], head, quant),
            (rows, pos))

    return dict(make_layer=make_layer, make_outer=make_outer, embed=embed,
                fwd=fwd, close=close, logits_at=logits_at)


def served_gaps(seed: int, dims: dict, dtype_name: str, samples: list,
                control: bool = False) -> dict:
    """``samples``: (prompt ids, served ids) pairs.  One teacher-forced
    pass over prompt + served tokens per sample; at every served position
    the gap by which the served token's reference logit lies below the
    reference's best (``served_gap``), and the largest running sum of the
    exit distribution before the last pass at those positions
    (``exit_sum_max``: under ``exit_threshold`` means no served token
    would have left early).  With ``control`` also the gap of the token
    the fp8 reference puts first at the same positions."""
    key = weights.root_key(seed)
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(t, np.int32)]) for p, t in samples]
    n_out = max(len(t) for _, t in samples)
    S = -(-max(len(s) for s in seqs) // 256) * 256
    toks = np.zeros((len(seqs), S), np.int32)
    pos = np.zeros((len(seqs), n_out), np.int32)
    served = np.zeros((len(seqs), n_out), np.int32)
    valid = np.zeros((len(seqs), n_out), bool)
    for i, (p, t) in enumerate(samples):
        toks[i, :len(seqs[i])] = seqs[i]
        # served token j was picked from the logits at position P-1+j
        pos[i, :len(t)] = len(p) - 1 + np.arange(len(t))
        served[i, :len(t)] = t
        valid[i, :len(t)] = True

    def logits(quant):
        pr = _programs(_frozen(dims), dtype_name, quant)
        outer = pr["make_outer"](key)
        rows = pr["embed"](outer["embed"], toks)
        lams = []
        for _ in range(dims["loops"]):
            for i in range(dims["n_layers"]):
                rows = pr["fwd"](pr["make_layer"](key, i), rows)
            rows, lam = pr["close"](outer, rows)
            lams.append(jnp.take_along_axis(lam, jnp.asarray(pos), axis=1))
        return pr["logits_at"](outer, rows, pos), jnp.stack(lams)

    ref, lams = logits(None)
    best = ref.max(-1)
    gap = best - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    exits = exit_distribution(lams, dims["exit_threshold"])
    out = {"served_gap": np.asarray(gap)[valid],
           "n_tokens": int(valid.sum()),
           "exit_sum_max": float(exits["before_last"][valid].max()),
           "left_early": int((exits["exit_pass"][valid]
                              < dims["loops"] - 1).sum())}
    if control:
        low = jnp.argmax(logits("fp8")[0], -1)
        cgap = best - jnp.take_along_axis(ref, low[..., None], -1)[..., 0]
        out["control_gap"] = np.asarray(cgap)[valid]
    return out
