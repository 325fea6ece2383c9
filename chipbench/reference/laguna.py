"""Plain reference of the Laguna decoder (`model_type: laguna`): the full
forward pass over one token sequence in `jax.numpy` float32 under
`jax.default_matmul_precision("highest")`. No cache, no kernels, no
batching, nothing imported from the program: it is handed the weights as
arrays (`{name: array}` per layer, stored (in, out), upcast where used so
that bf16 weights cost no float32 copy) and the published keys. A layer is
one jitted function (layers of one kind and head count share it), queries
are taken a block at a time and experts one after the other, each upcast
as its turn comes, so that a 12.7k-token sequence at full width fits
beside the served weights.

The equations (T tokens, layer l, input x, no bias anywhere):

  layer   a = RMSNorm(x); x = x + Attn_l(a); b = RMSNorm(x);
          x = x + FFN_l(b); after the last layer RMSNorm, head
  Attn_l  q = a Wq as H_l heads of D (H_l = num_attention_heads_per_layer
          [l]); k = a Wk, v = a Wv as Hkv heads of D; query head h reads
          KV head h // (H_l / Hkv)
          RoPE on q and k by `rope_parameters[layer_types[l]]`:
          sliding_attention: default, theta^(-2i/D), all D dims;
          full_attention: YaRN on the first D * partial_rotary_factor
          dims, cos and sin times `attention_factor`, the rest of the head
          not rotated
          scores q.k / sqrt(D) over keys j <= i, on a sliding layer over
          i - sliding_window < j <= i; softmax in float32
          o = concat_h(g_h o_h) Wo with g = sigmoid(a Wg), a gate a head
  FFN_l   mlp_layer_types[l] dense: SwiGLU of `intermediate_size`;
          sparse: sigma = sigmoid(b Wr) (float32), the
          `num_experts_per_tok` largest chosen, gate_e = sigma_e /
          sum_chosen sigma, y = scale * sum_e gate_e E_e(b) + E_shared(b),
          every E a SwiGLU, the gate on the expert's OUTPUT
          (`moe_apply_router_weight_on_input: false`)

Departures from the published description, and what it does not settle
(the configuration file lists the same under `assumed`):
  * `gating: true` is read as the per-head sigmoid gate above (Wg hidden x
    H_l), the reading whose parameter count gives the published 33.4 B;
  * router: sigmoid scores, gates normalised over the chosen then times
    `moe_routed_scaling_factor`, no selection bias (no `topk_method`);
  * no q/k norm (no key for one); RoPE pairs dimension i with i + rot/2
    (rotate-half); the YaRN blend as in the public
    `_compute_yarn_parameters`: a linear ramp, over the rotated
    dimensions' pairs, between the pairs at which
    `original_max_position_embeddings` positions make `beta_fast` and
    `beta_slow` full rotations;
  * a sequence is padded to a multiple of `block` with token 0: padding
    lies after every real position and causality hides it.

`mantissa_bits` rounds both operands of every product to that many explicit
mantissa bits (23: float32, nothing rounded; 7: what bf16 with float32
accumulation computes; 3: an fp8-e4m3 mantissa with the exponent left wide,
the nearest precision below): the readings a tolerance is set between.
`group_interleaved` makes query head h read KV head h % Hkv, a WRONG
grouping, for the control that has to fail. The other controls are keys:
`sliding_window` past the sequence (window ignored), `rope_parameters`
with the full layers' `partial_rotary_factor` 1.
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


def rope_table(params, head_dim):
    """(inverse frequency of each rotated pair as a tuple, the factor on
    cos and sin) of one `rope_parameters` entry."""
    rot = int(round(head_dim * params.get("partial_rotary_factor", 1)))
    theta = float(params["rope_theta"])
    plain = np.array([theta ** (-2.0 * i / rot) for i in range(rot // 2)])
    if params["rope_type"] == "default":
        return tuple(plain.tolist()), 1.0
    assert params["rope_type"] == "yarn", params["rope_type"]
    factor = params["factor"]
    orig = params["original_max_position_embeddings"]

    def pair_of(rotations):     # the pair that turns `rotations` times
        return rot * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(params["beta_fast"])), 0)
    high = min(math.ceil(pair_of(params["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    # 0 below `low` (plain frequency kept), 1 above `high` (divided)
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    blend = plain / factor * ramp + plain * (1.0 - ramp)
    scale = params.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return tuple(blend.tolist()), float(scale)


def rope(x, pos, inv_freq, scale):
    """x (L, heads, D): the first 2 * len(inv_freq) dims turned, dim i
    with dim i + len(inv_freq); pos (L,)."""
    half = len(inv_freq)
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv_freq, F32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :] * scale, \
        jnp.sin(ang)[:, None, :] * scale
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def attention(a, w, sig, pos, rnd):
    """Grouped-query attention of one layer. a (L, E) float32, the normed
    layer input, L a multiple of `sig.block`: queries are taken a block at
    a time, one after the other, so that the (heads, block, L) scores are
    all that is ever held. Returns (L, E)."""
    L = a.shape[0]
    H, Hkv, D, block = sig.heads, sig.kv_heads, sig.head_dim, sig.block
    group = H // Hkv

    def mm(x, name):
        return jnp.matmul(rnd(x), rnd(w[name]))

    q = rope(mm(a, "q_proj").reshape(L, H, D), pos, sig.inv_freq,
             sig.rope_scale)
    k = rope(mm(a, "k_proj").reshape(L, Hkv, D), pos, sig.inv_freq,
             sig.rope_scale)
    v = mm(a, "v_proj").reshape(L, Hkv, D)
    gate = jax.nn.sigmoid(mm(a, "g_proj"))                      # (L, H)
    # query heads by the KV head they read: (L, Hkv, group, D)
    if sig.group_interleaved:       # the wrong grouping, h % Hkv
        q = q.reshape(L, group, Hkv, D).transpose(0, 2, 1, 3)
    else:
        q = q.reshape(L, Hkv, group, D)

    def queries(blk):
        q_b, pos_b = blk
        seen = jnp.arange(L)[None, :] <= pos_b[:, None]         # (q, s)
        if sig.window is not None:
            seen &= jnp.arange(L)[None, :] > pos_b[:, None] - sig.window
        s = jnp.einsum("qhgd,shd->hgqs", rnd(q_b), rnd(k)) * D ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return jnp.einsum("hgqs,shd->qhgd", rnd(p), rnd(v))

    o = jax.lax.map(queries, (
        q.reshape((L // block, block) + q.shape[1:]),
        pos.reshape(L // block, block))).reshape(L, Hkv, group, D)
    if sig.group_interleaved:
        o = o.transpose(0, 2, 1, 3)
    o = o.reshape(L, H, D) * gate[..., None]
    return mm(o.reshape(L, H * D), "o_proj")


def swiglu(x, w_gate, w_up, w_down, rnd):
    h = jax.nn.silu(jnp.matmul(rnd(x), rnd(w_gate))) \
        * jnp.matmul(rnd(x), rnd(w_up))
    return jnp.matmul(rnd(h), rnd(w_down))


def experts(b, w, sig, rnd):
    """The expert layer: every routed expert in turn (its weights upcast
    as its turn comes), then the shared one."""
    sigma = jax.nn.sigmoid(jnp.matmul(b, w["router"].astype(F32)))
    top, chosen = jax.lax.top_k(sigma, sig.top_k)
    gate = top / top.sum(-1, keepdims=True) * sig.routed_scale

    def one(y, given):
        e, w_gate, w_up, w_down = given
        g_e = jnp.where(chosen == e, gate, 0.0).sum(-1)
        return y + g_e[:, None] * swiglu(b, w_gate, w_up, w_down, rnd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(b), (
        jnp.arange(w["experts_gate_proj"].shape[0]), w["experts_gate_proj"],
        w["experts_up_proj"], w["experts_down_proj"]))
    return y + swiglu(b, w["shared_gate_proj"], w["shared_up_proj"],
                      w["shared_down_proj"], rnd)


class _Signature(tuple):
    """What a layer's jitted function is specialised on, hashable."""
    _fields = ("heads", "kv_heads", "head_dim", "window", "inv_freq",
               "rope_scale", "dense", "top_k", "routed_scale", "eps",
               "block", "mantissa_bits", "group_interleaved")

    def __new__(cls, **kw):
        return super().__new__(cls, (kw[f] for f in cls._fields))

    def __getattr__(self, name):
        try:
            return self[self._fields.index(name)]
        except ValueError:
            raise AttributeError(name) from None


@functools.lru_cache(maxsize=None)
def _layer_fn(sig):
    rnd = _rounder(sig.mantissa_bits)

    def layer(x, w, pos):
        x = x + attention(rms_norm(x, w["attn_norm_weight"], sig.eps), w,
                          sig, pos, rnd)
        b = rms_norm(x, w["ffn_norm_weight"], sig.eps)
        if sig.dense:
            return x + swiglu(b, w["gate_proj"], w["up_proj"],
                              w["down_proj"], rnd)
        return x + experts(b, w, sig, rnd)

    return jax.jit(layer)


def forward(tokens, layers, model, cfg, logits_from=0, block=128,
            mantissa_bits=23, pad_to=None, group_interleaved=False):
    """Float32 logits (L - logits_from, V) of positions `logits_from`.. of
    the token sequence `tokens` (L,). `layers`: one {name: array} per
    layer; `model`: `embed_tokens` (V, E), `final_norm_weight`, `lm_head`
    (E, V); `cfg`: the published keys (layer l of the lists is layer l
    here). The sequence is padded (token 0) to `pad_to`, or the next
    multiple of `block`."""
    rnd = _rounder(mantissa_bits)
    n = len(tokens)
    n_pad = -(-max(n, pad_to or 0) // block) * block
    with jax.default_matmul_precision("highest"):
        tokens = jnp.zeros((n_pad,), jnp.int32).at[:n].set(
            jnp.asarray(tokens, jnp.int32))
        pos = jnp.arange(n_pad, dtype=jnp.int32)
        x = model["embed_tokens"][tokens].astype(F32)
        for l, w in enumerate(layers):
            kind = cfg["layer_types"][l]
            inv_freq, rope_scale = rope_table(
                cfg["rope_parameters"][kind], cfg["head_dim"])
            sig = _Signature(
                heads=cfg["num_attention_heads_per_layer"][l],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"],
                window=cfg["sliding_window"]
                if kind == "sliding_attention" else None,
                inv_freq=inv_freq, rope_scale=rope_scale,
                dense=cfg["mlp_layer_types"][l] == "dense",
                top_k=cfg["num_experts_per_tok"],
                routed_scale=cfg["moe_routed_scaling_factor"],
                eps=cfg["rms_norm_eps"], block=block,
                mantissa_bits=mantissa_bits,
                group_interleaved=group_interleaved)
            x = _layer_fn(sig)(x, w, pos)
        x = rms_norm(x[logits_from:n], model["final_norm_weight"],
                     cfg["rms_norm_eps"])
        return jnp.matmul(rnd(x), rnd(model["lm_head"]))
