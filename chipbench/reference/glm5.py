"""Plain reference of the GLM-5 decoder (`model_type: glm_moe_dsa`): the
full forward pass over one token sequence in `jax.numpy` float32 under
`jax.default_matmul_precision("highest")`. No cache, no kernels, no
batching, nothing imported from the program: it is handed the weights as
arrays (`{name: array}` per layer, stored (in, out), upcast where used so
that bf16 weights cost no float32 copy) and the published keys. A layer
is one jitted function (the layers of a kind share it), queries are
taken a block at a time and experts one after the other, so that a
6k-token sequence at full width fits beside the served weights.

The equations (one token t, layer input x, u = RMSNorm(x), eps 1e-5, no
bias anywhere):

  layer   h = x + Attn(u);  y = h + FFN(RMSNorm(h));  final RMSNorm, head
  MLA     c_q = RMSNorm(W_qa u); q_h = W_qb c_q -> q_h^n (nope), q_h^r (rope)
          [c; k^r] = W_kva u; c <- RMSNorm(c); RoPE(q_h^r), RoPE(k^r)
          k_h,s = [W_h^K c_s; k_s^r], v_h,s = W_h^V c_s   (EXPANDED heads)
          a = q_h . k_h,s / sqrt(nope + rope), softmax over s in S_t,
          o_h = sum_s p v_h,s, output W_o [o_1 .. o_H]
  indexer q_j^I = W_qI c_q (heads x dim), k_s^I = LayerNorm(W_kI u_s),
          RoPE on the first `rope` dims of both, w_t = W_w u_t scaled by
          heads^-1/2 dim^-1/2; I_t,s = sum_j w_t,j relu(q_t,j^I . k_s^I);
          S_t = the `index_topk` positions s <= t of largest I (all of
          them while t < index_topk; equal scores: the earlier position)
  experts sigma = sigmoid(W_r v) (float32); the k largest of sigma + b are
          chosen; g_e = scale sigma_e / sum_chosen sigma;
          y = sum_{e chosen and given} g_e E_e(v) + E_shared(v), E a SwiGLU

Assumed where the config's keys do not settle it (the program's docstring,
`mxnet_tpu/models/glm.py`, says the same): the indexer follows
DeepSeek-V3.2's published lightning indexer as written above; RoPE turns
interleaved pairs (x[2i], x[2i+1]) by pos * theta^(-2i/d); no Hadamard
rotation and no fp8 in the indexer; the multi-token-prediction layer is
not part of the main model's logits and is left out.

The share of the experts: `first_expert` and the stack of experts given
say which experts this pass holds; what absent experts would add is left
out and the partial sum goes on, as in the program. Given all experts
(`first_expert=0`, the whole stack) it is the uncut layer.

`mantissa_bits` rounds both operands of every product to that many
explicit mantissa bits (23: float32, nothing rounded; 7: what bf16 with
float32 accumulation computes; 3: an fp8-e4m3 mantissa with the exponent
left wide, the nearest precision below): the readings a tolerance is set
between. `flip_boundary` drops each query's `index_topk`-th pick for its
next best: an upper bound on what a selection that flips at the last
score's rounding can do.
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rounder(mantissa_bits):
    if mantissa_bits >= 23:
        return lambda x: x.astype(F32)
    return lambda x: jax.lax.reduce_precision(
        x.astype(F32), exponent_bits=8, mantissa_bits=mantissa_bits)


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma.astype(F32)


def layer_norm(x, gamma, beta, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gamma.astype(F32) \
        + beta.astype(F32)


def rope(x, pos, theta):
    """x (L, ..., d), pos (L,): pairs (x[2i], x[2i+1]) turned by
    pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    pair = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pair[..., 0], pair[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)], -1) \
        .reshape(x.shape)


def rope_first(x, n, pos, theta):
    return jnp.concatenate([rope(x[..., :n], pos, theta), x[..., n:]], -1)


def attention(u, w, cfg, pos, rnd, block, flip_boundary):
    """MLA with expanded heads over the positions the indexer selects.
    u (L, E) float32, the normed layer input, L a multiple of `block`:
    queries are taken `block` at a time, one after the other, so that the
    (heads, block, L) scores are all that is ever held. Returns (L, E)."""
    L = u.shape[0]
    H = cfg["num_attention_heads"]
    R, nope, rp, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                       cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    ih, idim, topk = (cfg["index_n_heads"], cfg["index_head_dim"],
                      cfg["index_topk"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]

    def mm(x, name):
        return jnp.matmul(rnd(x), rnd(w[name]))

    c_q = rms_norm(mm(u, "q_a_proj"), w["q_a_norm_weight"], eps)
    q = mm(c_q, "q_b_proj").reshape(L, H, nope + rp)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, theta)], -1)
    kv = mm(u, "kv_a_proj")
    c = rms_norm(kv[:, :R], w["kv_a_norm_weight"], eps)
    k_r = rope(kv[:, R:], pos, theta)
    kb = mm(c, "kv_b_proj").reshape(L, H, nope + vd)
    k = jnp.concatenate(
        [kb[..., :nope], jnp.broadcast_to(k_r[:, None, :], (L, H, rp))], -1)
    v = kb[..., nope:]

    q_i = mm(c_q, "indexer_q_proj").reshape(L, ih, idim)
    q_i = rope_first(q_i, rp, pos, theta)
    k_i = layer_norm(mm(u, "indexer_k_proj"), w["indexer_k_norm_weight"],
                     w["indexer_k_norm_shift"], eps)
    k_i = rope_first(k_i, rp, pos, theta)
    w_i = mm(u, "indexer_weights_proj") * (ih ** -0.5 * idim ** -0.5)

    def queries(blk):
        q_b, qi_b, wi_b, pos_b = blk
        causal = jnp.arange(L)[None, :] <= pos_b[:, None]       # (q, s)
        score = jnp.einsum(
            "qjs,qj->qs", rnd(jax.nn.relu(jnp.einsum(
                "qjd,sd->qjs", rnd(qi_b), rnd(k_i)))), rnd(wi_b))
        score = jnp.where(causal, score, -jnp.inf)
        keep = causal
        if L > topk:
            # ties go to the earlier position, as `lax.top_k` breaks them
            picks = jax.lax.top_k(score, topk + int(flip_boundary))[1]
            if flip_boundary:       # the last pick out, the next best in
                picks = jnp.concatenate(
                    [picks[:, :topk - 1], picks[:, topk:]], 1)
            keep = causal & jnp.zeros_like(causal).at[
                jnp.arange(block)[:, None], picks].set(True)
        s = jnp.einsum("qhd,shd->hqs", rnd(q_b), rnd(k)) \
            * (nope + rp) ** -0.5
        p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shv->qhv", rnd(p), rnd(v)) \
            .reshape(block, H * vd)

    def blocks(x):
        return x.reshape((L // block, block) + x.shape[1:])

    o = jax.lax.map(queries, (blocks(q), blocks(q_i), blocks(w_i),
                              blocks(pos)))
    return mm(o.reshape(L, H * vd), "o_proj")


def swiglu(x, w_gate, w_up, w_down, rnd):
    h = jax.nn.silu(jnp.matmul(rnd(x), rnd(w_gate))) \
        * jnp.matmul(rnd(x), rnd(w_up))
    return jnp.matmul(rnd(h), rnd(w_down))


def experts(v, w, cfg, first_expert, rnd, shared=True):
    """The expert layer's part that the experts GIVEN (`w['experts_*']`,
    global ids from `first_expert` on) add, plus the shared expert."""
    sigma = jax.nn.sigmoid(jnp.matmul(v, w["router"].astype(F32)))
    _, chosen = jax.lax.top_k(
        sigma + w["router_select_offset"].astype(F32),
        cfg["num_experts_per_tok"])
    gate = jnp.take_along_axis(sigma, chosen, -1)
    if cfg["norm_topk_prob"]:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * cfg["routed_scaling_factor"]

    def one(y, given):              # one expert after the other
        e, w_gate, w_up, w_down = given
        g_e = jnp.where(chosen == first_expert + e, gate, 0.0).sum(-1)
        return y + g_e[:, None] * swiglu(v, w_gate, w_up, w_down, rnd), None

    n_given = w["experts_gate_proj"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(v), (
        jnp.arange(n_given), w["experts_gate_proj"], w["experts_up_proj"],
        w["experts_down_proj"]))
    if shared:
        y = y + swiglu(v, w["shared_gate_proj"], w["shared_up_proj"],
                       w["shared_down_proj"], rnd)
    return y


@functools.lru_cache(maxsize=None)
def _layer_fns(cfg_items, first_expert, block, mantissa_bits, flip_boundary):
    """The two kinds of layer, jitted: layers of a kind share shapes, so
    each compiles once a sequence length, with the weights as arguments
    (upcast where used, inside)."""
    cfg, rnd = dict(cfg_items), _rounder(mantissa_bits)
    eps = cfg["rms_norm_eps"]

    def attend(x, w, pos):
        u = rms_norm(x, w["attn_norm_weight"], eps)
        x = x + attention(u, w, cfg, pos, rnd, block, flip_boundary)
        return x, rms_norm(x, w["ffn_norm_weight"], eps)

    def dense(x, w, pos):
        x, v = attend(x, w, pos)
        return x + swiglu(v, w["gate_proj"], w["up_proj"], w["down_proj"],
                          rnd)

    def sparse(x, w, pos):
        x, v = attend(x, w, pos)
        return x + experts(v, w, cfg, first_expert, rnd)

    return jax.jit(dense), jax.jit(sparse)


def forward(tokens, layers, model, cfg, first_expert=0, logits_from=0,
            block=512, mantissa_bits=23, flip_boundary=False, pad_to=None):
    """Float32 logits (L - logits_from, V) of positions `logits_from`..
    of the token sequence `tokens` (L,). `layers`: one {name: array} per
    layer, a layer with `gate_proj` dense, else the expert layer; `model`:
    `embed_tokens` (V, E), `final_norm_weight`, `lm_head` (E, V); `cfg`:
    the published keys (`rope_theta` flat). The sequence is padded (token
    0) to `pad_to`, or the next multiple of `block`: a padded position
    lies after every real one, and causality keeps it out of their sight."""
    rnd = _rounder(mantissa_bits)
    n = len(tokens)
    n_pad = -(-max(n, pad_to or 0) // block) * block
    scalars = tuple(sorted((k, v) for k, v in cfg.items()
                           if isinstance(v, (int, float, bool, str))))
    with jax.default_matmul_precision("highest"):
        dense, sparse = _layer_fns(scalars, first_expert, block,
                                   mantissa_bits, flip_boundary)
        tokens = jnp.zeros((n_pad,), jnp.int32).at[:n].set(
            jnp.asarray(tokens, jnp.int32))
        pos = jnp.arange(n_pad, dtype=jnp.int32)
        x = model["embed_tokens"][tokens].astype(F32)
        for w in layers:
            x = (dense if "gate_proj" in w else sparse)(x, w, pos)
        x = rms_norm(x[logits_from:n], model["final_norm_weight"],
                     cfg["rms_norm_eps"])
        return jnp.matmul(rnd(x), rnd(model["lm_head"]))


def relative_errors(got, want):
    """Per position, the root-mean-square difference of two (T, V) logit
    arrays over the root-mean-square spread of `want`'s row about its
    mean: (T,) float64. A rounding error shows in every row; a flipped
    discrete choice (a pick at the selection's boundary, an expert at the
    router's) in the rows it touched."""
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    spread = np.sqrt(((want - want.mean(-1, keepdims=True)) ** 2).mean(-1))
    return np.sqrt(((got - want) ** 2).mean(-1)) / spread
