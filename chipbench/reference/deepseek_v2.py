"""Plain reference of the DeepSeek-V2 decoder (`model_type: deepseek_v2`,
arXiv:2405.04434): the full forward pass over one token sequence in
`jax.numpy` float32 under `jax.default_matmul_precision("highest")`. No
cache, no kernels, no batching, nothing imported from the program: it is
handed the weights as arrays (`{name: array}` per layer, stored (in, out),
upcast where used so that bf16 weights cost no float32 copy) and the
published keys. A layer is one jitted function (the layers of a kind share
it), queries are taken a block at a time and experts one after the other,
each upcast as its turn comes, so that a 9k-token sequence at full width
fits beside the served weights.

The equations (T tokens, layer input x, eps `rms_norm_eps`, no bias
anywhere):

  layer   h = x + Attn(RMSNorm(x)); y = h + FFN(RMSNorm(h)); after the
          last layer RMSNorm, untied head
  Attn    u = RMSNorm(x); c_q = RMSNorm(u W_qa); q_h = (c_q W_qb)_h ->
          [q_nope (qk_nope_head_dim), q_pe (qk_rope_head_dim)]
          u W_kva -> [c (kv_lora_rank), k_pe]; c <- RMSNorm(c); k_pe is
          ONE vector for all heads; RoPE on q_pe and k_pe
          (c W_kvb)_h -> [k_nope, v] (EXPANDED heads, no absorption)
          scores (q_nope . k_nope + q_pe . k_pe) * s over keys j <= i,
          softmax in float32, o = concat_h(sum_j p v_h) W_o
          s = (nope + rope)^-0.5 * m^2, m = 0.1 * mscale_all_dim *
          ln(factor) + 1
  RoPE    YaRN: pair i of d/2 turns by pos * f_i, f_i the public blend of
          theta^(-2i/d) and the same over `factor`, by a linear ramp over
          the pairs between those at which
          `original_max_position_embeddings` positions make `beta_fast`
          and `beta_slow` rotations; cos and sin times mscale(factor,
          mscale) / mscale(factor, mscale_all_dim)
  FFN     the first `first_k_dense_replace` layers: SwiGLU of
          `intermediate_size`. The others: p = softmax(RMSNorm(h) W_r)
          (float32) over `n_routed_experts`; a group's score is the
          largest p among its experts (`n_group` groups of equal size, in
          order); the `topk_group` best groups stay; the
          `num_experts_per_tok` largest p among their experts are chosen;
          g_e = p_e * routed_scaling_factor (`norm_topk_prob` false: not
          normalised); y = sum_chosen g_e E_e(v) + S(v), E a SwiGLU of
          `moe_intermediate_size`, S ONE SwiGLU of `n_shared_experts`
          times that width

Departures from the published code (the configuration file lists the same
under `assumed`):
  * RoPE is written as the public code has it: the last dimension's
    interleaved pairs (x[2i], x[2i+1]) are first permuted to halves
    ([x0, x2, ..; x1, x3, ..]) and then halves are rotated, on q_pe and
    k_pe alike. The program turns the interleaved pairs in place; the
    scores are the same, which this reference is there to show;
  * equal scores at the router go to the expert of the lower index, as
    `lax.top_k` breaks them (the public `torch.topk` leaves it open);
  * the two shared experts are the public code's single MLP of twice the
    width (they are that there too);
  * a sequence is padded to a multiple of `block` with token 0: padding
    lies after every real position and causality hides it.

The share of the experts: `first_expert` and the stack of experts given
say which experts this pass holds; the router is as wide as published and
the group limit runs over all groups; what absent experts would add is
left out and the partial sum goes on, as in the program. Given all experts
(`first_expert=0`, the whole stack) it is the uncut layer.

`mantissa_bits` rounds both operands of every product to that many
explicit mantissa bits (23: float32, nothing rounded; 7: what bf16 with
float32 accumulation computes; 3: an fp8-e4m3 mantissa with the exponent
left wide, the nearest precision below): the readings a tolerance is set
between. The controls that have to fail are keys and two flags:
`softmax_mscale=False` leaves m^2 out of the scale, `yarn=False` turns by
the plain frequencies, `n_group` 1 / `topk_group` 1 ignores the group
limit, `routed_scaling_factor` 1 leaves the gates unscaled.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rounder(mantissa_bits):
    if mantissa_bits >= 23:
        return lambda x: x.astype(F32)
    return lambda x: jax.lax.reduce_precision(
        x.astype(F32), exponent_bits=8, mantissa_bits=mantissa_bits)


def rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gamma.astype(F32)


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_table(cfg, yarn=True):
    """(the frequency of each of the d/2 pairs as a tuple, the factor on
    cos and sin) from `rope_theta` and `rope_scaling`."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    plain = np.array([theta ** (-2.0 * i / d) for i in range(d // 2)])
    rs = cfg.get("rope_scaling")
    if rs is None or not yarn:
        return tuple(plain.tolist()), 1.0
    assert rs["type"] == "yarn", rs["type"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def pair_of(rotations):     # the pair that turns `rotations` times
        return d * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(rs["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    # 0 below `low` (plain frequency kept), 1 above `high` (divided)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    blend = plain / factor * ramp + plain * (1.0 - ramp)
    scale = yarn_mscale(factor, rs.get("mscale", 1)) \
        / yarn_mscale(factor, rs.get("mscale_all_dim", 0))
    return tuple(blend.tolist()), float(scale)


def softmax_scale(cfg, with_mscale=True):
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs is not None and with_mscale and rs.get("mscale_all_dim", 0):
        m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
        s = s * m * m
    return s


def rope(x, pos, freqs, scale):
    """The public `apply_rotary_pos_emb`: x (L, ..., d) is permuted from
    interleaved pairs to halves, then x * cos + rotate_half(x) * sin with
    cos and sin of pos * [f; f]. pos (L,)."""
    d = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (d,))
    f = jnp.asarray(freqs, F32)
    ang = pos.astype(F32)[:, None] * jnp.concatenate([f, f])[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d,))
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * (jnp.cos(ang) * scale) + turned * (jnp.sin(ang) * scale)


def attention(u, w, sig, pos, rnd):
    """MLA with expanded heads over every earlier position. u (L, E)
    float32, the normed layer input, L a multiple of `sig.block`: queries
    are taken `block` at a time, one after the other, so that the (heads,
    block, L) scores are all that is ever held. Returns (L, E)."""
    L, H, block = u.shape[0], sig.heads, sig.block
    R, nope, rp, vd = sig.kv_lora_rank, sig.nope, sig.rope, sig.v_dim

    def mm(x, name):
        return jnp.matmul(rnd(x), rnd(w[name]))

    c_q = rms_norm(mm(u, "q_a_proj"), w["q_a_norm_weight"], sig.eps)
    q = mm(c_q, "q_b_proj").reshape(L, H, nope + rp)
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], pos, sig.freqs, sig.rope_scale)],
        -1)
    kv = mm(u, "kv_a_proj")
    c = rms_norm(kv[:, :R], w["kv_a_norm_weight"], sig.eps)
    k_pe = rope(kv[:, R:], pos, sig.freqs, sig.rope_scale)
    kb = mm(c, "kv_b_proj").reshape(L, H, nope + vd)
    k = jnp.concatenate(
        [kb[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (L, H, rp))], -1)
    v = kb[..., nope:]

    def queries(blk):
        q_b, pos_b = blk
        seen = jnp.arange(L)[None, :] <= pos_b[:, None]         # (q, s)
        s = jnp.einsum("qhd,shd->hqs", rnd(q_b), rnd(k)) * sig.scale
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqs,shv->qhv", rnd(p), rnd(v)) \
            .reshape(block, H * vd)

    o = jax.lax.map(queries, (
        q.reshape((L // block, block) + q.shape[1:]),
        pos.reshape(L // block, block)))
    return mm(o.reshape(L, H * vd), "o_proj")


def swiglu(x, w_gate, w_up, w_down, rnd):
    h = jax.nn.silu(jnp.matmul(rnd(x), rnd(w_gate))) \
        * jnp.matmul(rnd(x), rnd(w_up))
    return jnp.matmul(rnd(h), rnd(w_down))


def route(v, router, sig):
    """(chosen (L, k) expert ids, gate (L, k)) by group-limited greedy
    choice over softmax scores."""
    p = jax.nn.softmax(jnp.matmul(v, router.astype(F32)), axis=-1)
    L, n_e = p.shape
    allowed = p
    if sig.n_group > 1:
        size = n_e // sig.n_group
        group_score = p.reshape(L, sig.n_group, size).max(-1)
        _, best = jax.lax.top_k(group_score, sig.topk_group)
        stays = jnp.zeros((L, sig.n_group), bool).at[
            jnp.arange(L)[:, None], best].set(True)
        allowed = jnp.where(jnp.repeat(stays, size, axis=1), p, 0.0)
    _, chosen = jax.lax.top_k(allowed, sig.top_k)
    gate = jnp.take_along_axis(p, chosen, -1)
    if sig.norm_topk:
        return chosen, gate / (gate.sum(-1, keepdims=True) + 1e-20)
    return chosen, gate * sig.routed_scale


def experts(v, w, sig, rnd, shared=True):
    """The expert layer's part that the experts GIVEN (`w['experts_*']`,
    global ids from `sig.first_expert` on) add, plus the shared experts."""
    chosen, gate = route(v, w["router"], sig)

    def one(y, given):              # one expert after the other
        e, w_gate, w_up, w_down = given
        g_e = jnp.where(chosen == sig.first_expert + e, gate, 0.0).sum(-1)
        return y + g_e[:, None] * swiglu(v, w_gate, w_up, w_down, rnd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(v), (
        jnp.arange(w["experts_gate_proj"].shape[0]), w["experts_gate_proj"],
        w["experts_up_proj"], w["experts_down_proj"]))
    if shared:
        y = y + swiglu(v, w["shared_gate_proj"], w["shared_up_proj"],
                       w["shared_down_proj"], rnd)
    return y


class Signature(tuple):
    """What a layer's jitted function is specialised on, hashable."""
    _fields = ("heads", "kv_lora_rank", "nope", "rope", "v_dim", "freqs",
               "rope_scale", "scale", "eps", "n_group", "topk_group",
               "top_k", "norm_topk", "routed_scale", "first_expert", "block",
               "mantissa_bits")

    def __new__(cls, **kw):
        return super().__new__(cls, (kw[f] for f in cls._fields))

    def __getattr__(self, name):
        try:
            return self[self._fields.index(name)]
        except ValueError:
            raise AttributeError(name) from None


def signature(cfg, first_expert=0, block=256, mantissa_bits=23, yarn=True,
              softmax_mscale=True):
    freqs, rope_scale = rope_table(cfg, yarn)
    return Signature(
        heads=cfg["num_attention_heads"], kv_lora_rank=cfg["kv_lora_rank"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], freqs=freqs, rope_scale=rope_scale,
        scale=softmax_scale(cfg, softmax_mscale), eps=cfg["rms_norm_eps"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        top_k=cfg["num_experts_per_tok"], norm_topk=cfg["norm_topk_prob"],
        routed_scale=cfg["routed_scaling_factor"],
        first_expert=first_expert, block=block, mantissa_bits=mantissa_bits)


@functools.lru_cache(maxsize=None)
def _layer_fns(sig):
    """The two kinds of layer, jitted: layers of a kind share shapes, so
    each compiles once a sequence length, with the weights as arguments
    (upcast where used, inside)."""
    rnd = _rounder(sig.mantissa_bits)

    def attend(x, w, pos):
        u = rms_norm(x, w["attn_norm_weight"], sig.eps)
        x = x + attention(u, w, sig, pos, rnd)
        return x, rms_norm(x, w["ffn_norm_weight"], sig.eps)

    def dense(x, w, pos):
        x, v = attend(x, w, pos)
        return x + swiglu(v, w["gate_proj"], w["up_proj"], w["down_proj"],
                          rnd)

    def sparse(x, w, pos):
        x, v = attend(x, w, pos)
        return x + experts(v, w, sig, rnd)

    return jax.jit(dense), jax.jit(sparse)


def forward(tokens, layers, model, cfg, first_expert=0, logits_from=0,
            block=256, mantissa_bits=23, pad_to=None, yarn=True,
            softmax_mscale=True):
    """Float32 logits (L - logits_from, V) of positions `logits_from`..
    of the token sequence `tokens` (L,). `layers`: one {name: array} per
    layer, a layer with `gate_proj` dense, else the expert layer; `model`:
    `embed_tokens` (V, E), `final_norm_weight`, `lm_head` (E, V); `cfg`:
    the published keys. The sequence is padded (token 0) to `pad_to`, or
    the next multiple of `block`."""
    rnd = _rounder(mantissa_bits)
    n = len(tokens)
    n_pad = -(-max(n, pad_to or 0) // block) * block
    sig = signature(cfg, first_expert, block, mantissa_bits, yarn,
                    softmax_mscale)
    with jax.default_matmul_precision("highest"):
        dense, sparse = _layer_fns(sig)
        tokens = jnp.zeros((n_pad,), jnp.int32).at[:n].set(
            jnp.asarray(tokens, jnp.int32))
        pos = jnp.arange(n_pad, dtype=jnp.int32)
        x = model["embed_tokens"][tokens].astype(F32)
        for w in layers:
            x = (dense if "gate_proj" in w else sparse)(x, w, pos)
        x = rms_norm(x[logits_from:n], model["final_norm_weight"],
                     cfg["rms_norm_eps"])
        return jnp.matmul(rnd(x), rnd(model["lm_head"]))
