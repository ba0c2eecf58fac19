"""The plain float32 reference of Falcon-H1 (``falcon_h1``), and the
comparison that decides its serving cell's ``correct``.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no cache, no chunked scan, a request at a time, nothing imported
from the program.  Weights come from :mod:`chipbench.weights_falcon_h1`
again, from the seed, one layer at a time.

The model, from the published ``config.json`` (what a key does not
itself state is listed under ``assumed`` in the configuration file):

    h = E[x] embedding_multiplier
    for layer l, with u = RMSNorm(h; g1):
      Attn: q = u W_q, k = (u W_k) key_multiplier, v = u W_v; RoPE at
            positions 0.. (half-split, rope_theta); causal softmax at
            1/sqrt(head_dim), GQA; (o W_o) attention_out_multiplier
      SSM:  [z, x, B, C, dt] = ((u ssm_in_multiplier) W_in), each segment
            times its ssm_multipliers entry;
            [x, B, C] = SiLU(causal depthwise conv_k([x, B, C]) + b);
            dt = softplus(dt + dt_bias); A = -exp(A_log);
            for head h (group g = h // (H / G)), S in R^{P x N}, from 0:
              S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D x_t
            y = GroupRMSNorm_G(y SiLU(z)) w;  (y W_out) ssm_out_multiplier
      h = h + Attn + SSM
      h = h + W_down(SiLU((v W_gate) m0) (v W_up)) m1,  v = RMSNorm(h; g2)
    logits = (RMSNorm(h; g_f) W_head) lm_head_multiplier

The SSM runs token by token (``lax.scan``), attention in blocks of
queries, the head in blocks of vocabulary rows: the float32 weights of
the served cut would be 21 GB, so nothing but one layer and one block of
the head is held at once, beside the server's freed memory.

**The gap is read before the head's multiplier.**  ``lm_head_multiplier``
(1/128) scales every logit alike and moves no pick; a served token's gap
is ``(best - served) / lm_head_multiplier``, the logits' own unit (the
normed state times a head of variance 1/fan_in), where bf16 rounding and
the fp8 control stand apart.

``quant="fp8"`` is the control of :mod:`chipbench.reference`: every
matmul operand rounded through float8_e4m3.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights_falcon_h1 as weights
from .reference import F32, _frozen, _mm, _rmsnorm, _rope

Q_BLOCK = 512
V_BLOCK = 16384


def vocab_block(V: int) -> int:
    """Rows of the head a block: the most, up to :data:`V_BLOCK`, that
    divide the vocabulary (16,320 for 261,120)."""
    return next(b for b in range(min(V, V_BLOCK), 0, -1) if V % b == 0)


# -- the arithmetic ---------------------------------------------------------

def attention(w: dict, u, d: dict, quant=None):
    """GQA over one sequence u [S, D]: ``(o W_o) attention_out_multiplier``."""
    S = u.shape[0]
    H, KV, Dh = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    x = u * d["attention_in_multiplier"]
    q = _rope(_mm("sd,dhk->shk", x, w["wq"], quant), d["rope_theta"])
    k = _rope(_mm("sd,dhk->shk", x, w["wk"], quant) * d["key_multiplier"],
              d["rope_theta"])
    v = _mm("sd,dhk->shk", x, w["wv"], quant)
    blk = min(Q_BLOCK, S)
    assert S % blk == 0, (S, blk)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk)
        qg = qb.reshape(blk, KV, H // KV, Dh)
        s = _mm("qgrk,tgk->grqt", qg, k, quant) / np.sqrt(Dh)
        seen = (i * blk + jnp.arange(blk))[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm("grqt,tgk->qgrk", p, v, quant).reshape(blk, H, Dh)

    o = jax.lax.map(block, jnp.arange(S // blk)).reshape(S, H, Dh)
    return _mm("shk,hkd->sd", o, w["wo"], quant) * \
        d["attention_out_multiplier"]


def ssm(w: dict, u, d: dict, quant=None):
    """The Mamba-2 mixer on one sequence u [S, D], the state from zero and
    carried a token at a time."""
    S = u.shape[0]
    H, P, G, N = (d["ssm_heads"], d["ssm_head_dim"], d["ssm_groups"],
                  d["ssm_state"])
    Ds, GN, k = d["d_ssm"], G * N, d["conv_kernel"]
    m = d["ssm_multipliers"]
    zxbcdt = _mm("sd,de->se", u * d["ssm_in_multiplier"], w["ssm_in"], quant)
    cuts = np.cumsum([Ds, Ds, GN, GN])
    z, x, B, C, dt = (a * f for a, f in zip(jnp.split(zxbcdt, cuts, -1), m))
    xbc = jnp.concatenate([x, B, C], -1)
    pad = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    conv = sum(w["conv_w"][j] * pad[j:j + S] for j in range(k)) + w["conv_b"]
    x, B, C = jnp.split(jax.nn.silu(conv), np.cumsum([Ds, GN]), -1)
    x = x.reshape(S, H, P)
    B, C = B.reshape(S, G, N), C.reshape(S, G, N)
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # [S, H]
    A = -jnp.exp(w["A_log"])
    g = np.arange(H) // (H // G)

    def step(state, t):                                      # state [H, P, N]
        x_t, B_t, C_t, dt_t = t
        state = jnp.exp(dt_t * A)[:, None, None] * state + \
            (dt_t[:, None] * x_t)[:, :, None] * B_t[g][:, None, :]
        y = _mm("hpn,hn->hp", state, C_t[g], quant)
        return state, y

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, B, C, dt))
    y = (y + w["ssm_D"][:, None] * x).reshape(S, Ds)
    gated = (y * jax.nn.silu(z)).reshape(S, G, Ds // G)
    y = _rmsnorm(gated, 1.0, d["rms_norm_eps"]).reshape(S, Ds) * w["ssm_norm"]
    return _mm("se,ed->sd", y, w["ssm_out"], quant) * d["ssm_out_multiplier"]


def layer_fn(w: dict, h, d: dict, quant=None):
    """One layer on one sequence h [S, D] (float32)."""
    w = jax.tree.map(lambda a: a.astype(F32), w)
    eps = d["rms_norm_eps"]
    u = _rmsnorm(h, w["attn_norm"], eps)
    h = h + attention(w, u, d, quant) + ssm(w, u, d, quant)
    v = _rmsnorm(h, w["mlp_norm"], eps)
    m0, m1 = d["mlp_multipliers"]
    g = jax.nn.silu(_mm("sd,df->sf", v, w["w_gate"], quant) * m0)
    up = _mm("sd,df->sf", v, w["w_up"], quant)
    return h + _mm("sf,fd->sd", g * up, w["w_down"], quant) * m1


def embed(w_embed, tokens, d: dict):
    return w_embed[tokens].astype(F32) * d["embedding_multiplier"]


def forward(seed_key, tokens, dims: dict, dtype, quant=None):
    """The whole model on one sequence of token ids [S] (a multiple of
    :data:`Q_BLOCK` or shorter than it): logits ``[S, V]``, the head's
    multiplier in.  For the CPU tests; the chip's path is
    :func:`served_gaps`, which never holds all the logits."""
    outer = weights.outer(seed_key, dims, dtype)
    h = embed(outer["embed"], jnp.asarray(tokens), dims)
    for i in range(dims["n_layers"]):
        h = layer_fn(weights.layer(seed_key, i, dims, dtype), h, dims, quant)
    x = _rmsnorm(h, outer["final_norm"].astype(F32), dims["rms_norm_eps"])
    return _mm("sd,dv->sv", x, outer["lm_head"].astype(F32), quant) * \
        dims["lm_head_multiplier"]


# -- serving: the gap of each served token under the reference ---------------

@functools.lru_cache(maxsize=None)
def _programs(fdims, dtype_name: str, quant):
    """The jitted blocks for one model shape (built once per process)."""
    dims, dtype = dict(fdims), jnp.dtype(dtype_name)
    make_layer = jax.jit(lambda key, i: weights.layer(key, i, dims, dtype))
    make_outer = jax.jit(lambda key: weights.outer(key, dims, dtype))
    fwd = jax.jit(lambda w, rows: jax.lax.map(
        lambda h: layer_fn(w, h, dims, quant), rows))

    @jax.jit
    def normed(outer, rows, pos):
        """The final norm at the listed positions of each row [K, n, D]."""
        norm = outer["final_norm"].astype(F32)
        return jax.vmap(lambda h, p: _rmsnorm(h[p], norm,
                                              dims["rms_norm_eps"]))(rows, pos)

    vb = vocab_block(dims["vocab_size"])

    @jax.jit
    def head_block(outer, x, lo):
        """Logits of vocabulary rows ``lo .. lo + vb``, before the head's
        multiplier: ``[K, n, vb]``."""
        cols = jax.lax.dynamic_slice_in_dim(outer["lm_head"], lo, vb, 1)
        return _mm("knd,dv->knv", x, cols.astype(F32), quant)

    embed_rows = jax.jit(lambda w_embed, tokens: embed(w_embed, tokens, dims))
    return dict(make_layer=make_layer, make_outer=make_outer, fwd=fwd,
                normed=normed, head_block=head_block, embed=embed_rows,
                vb=vb)


def served_gaps(seed: int, dims: dict, dtype_name: str, samples: list,
                control: bool = False, picks=None) -> dict:
    """``samples``: (prompt ids, served ids) pairs.  One teacher-forced
    pass over prompt + served tokens per sample; at every served position
    the gap by which the served token's reference logit lies below the
    reference's best, in the logits' unit before the head's multiplier
    (``served_gap``).  With ``control`` also the gap of the token the fp8
    reference puts first (``control_gap``).  ``picks`` is for models with
    routing and is not used."""
    key = weights.root_key(seed)
    seqs = [np.concatenate([np.asarray(p, np.int32),
                            np.asarray(t, np.int32)]) for p, t in samples]
    n_out = max(len(t) for _, t in samples)
    S = -(-max(len(s) for s in seqs) // Q_BLOCK) * Q_BLOCK
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

    def hidden(quant):
        """The final normed state at the served positions [K, n, D]."""
        pr = _programs(_frozen(dims), dtype_name, quant)
        outer = pr["make_outer"](key)
        rows = pr["embed"](outer["embed"], toks)
        for i in range(dims["n_layers"]):
            rows = pr["fwd"](pr["make_layer"](key, i), rows)
        return pr, outer, pr["normed"](outer, rows, pos)

    def best(pr, outer, x, picks=()):
        """The largest logit and its id, over the vocabulary in blocks,
        and the logits of each of ``picks`` (ids ``[K, n]``) read from the
        same blocks, so that a pick that is the best reads a gap of 0."""
        top = jnp.full(x.shape[:2], -jnp.inf, F32)
        arg = jnp.zeros(x.shape[:2], jnp.int32)
        at = [jnp.zeros(x.shape[:2], F32) for _ in picks]
        for lo in range(0, dims["vocab_size"], pr["vb"]):
            blk = pr["head_block"](outer, x, lo)
            m = blk.max(-1)
            arg = jnp.where(m > top, lo + blk.argmax(-1), arg)
            top = jnp.maximum(top, m)
            for i, ids in enumerate(picks):
                inside = (ids >= lo) & (ids < lo + pr["vb"])
                got = jnp.take_along_axis(
                    blk, jnp.clip(ids - lo, 0, pr["vb"] - 1)[..., None],
                    -1)[..., 0]
                at[i] = jnp.where(inside, got, at[i])
        return top, arg, at

    picks = [jnp.asarray(served)]
    if control:
        lpr, louter, lx = hidden("fp8")
        picks.append(best(lpr, louter, lx)[1])
    pr, outer, x = hidden(None)
    top, _, at = best(pr, outer, x, picks)
    out = {"served_gap": np.asarray(top - at[0])[valid],
           "n_tokens": int(valid.sum()), "checks": []}
    if control:
        out["control_gap"] = np.asarray(top - at[1])[valid]
    return out
