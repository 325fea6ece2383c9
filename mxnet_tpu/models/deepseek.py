"""DeepSeek-V2 decoder (`model_type: deepseek_v2`), for serving.

Source of the keys: huggingface.co/deepseek-ai/DeepSeek-V2 `config.json`
(arXiv:2405.04434). What sets it apart from the decoders beside it:

  * **dense latent attention (MLA)** — keys and values are up-projections
    of ONE cached latent row per token, `[c (kv_lora_rank); k_pe
    (qk_rope_head_dim)]`, no head axis, and every one of the
    `num_attention_heads` (128) heads attends EVERY cached row: there is
    no indexer (`models/glm.py` attends the rows a learned indexer
    selects). The served step runs the absorbed form: the per-head key
    up-projection is folded into the query (`q~_h = W_uk,h^T q_nope,h`,
    kv_lora_rank wide), `pallas_ops.paged_latent_attention` walks the
    row's pages and reduces each against all heads at once, and the value
    up-projection is applied to the weighted sum of latents.
  * **YaRN that also changes the softmax scale** (`rope_scaling`) — the
    frequencies of the `qk_rope_head_dim` rotated dims are the public
    blend (`models/laguna.rope_frequencies`), cos and sin times
    `mscale(factor, mscale) / mscale(factor, mscale_all_dim)` (1 as
    published), and the scores times `(qk_nope_head_dim +
    qk_rope_head_dim)^-0.5 * mscale(factor, mscale_all_dim)^2`,
    `mscale(s, m) = 0.1 m ln s + 1`. RoPE turns interleaved pairs
    (x[2i], x[2i+1]); the public code permutes them to halves and rotates
    halves, on queries and keys alike, which gives the same scores.
  * **group-limited softmax routing** (`topk_method:
    group_limited_greedy`) — `n_routed_experts` (160) SwiGLU experts in
    `n_group` (8) groups, a group a device of the deployment the router
    is built for; scores are a softmax over all experts, only experts of
    the `topk_group` (3) groups with the largest best score can be
    chosen, `num_experts_per_tok` (6) a token; the gates are the chosen
    scores times `routed_scaling_factor`, not normalised
    (`norm_topk_prob` false), no selection bias; `n_shared_experts` (2)
    shared experts are one SwiGLU of twice the width:
    `parallel.moe.moe_topk_route` + `moe_share_ffn`. The model is told
    which experts it holds (`experts_held`, `first_expert`): one chip's
    share of an expert-parallel deployment computes its own experts' part
    and passes the partial sum on.

The first `first_k_dense_replace` layers are a dense SwiGLU. No bias, no
q/k norm beyond the two latents' RMSNorms, untied head.
`chipbench/reference/deepseek_v2.py` is the plain float32 reference of
the same equations (expanded heads, no cache).

Serving only, paged only: `decode_paged_chunk` has the contract of
`GPTForCausalLM.decode_paged_chunk` (one pass over the step's tokens as
virtual rows; prefill is the same pass), against ONE arena a layer:
latents `(pages, page_size, kv_lora_rank + qk_rope_head_dim)`. It shares
`rms_norm`, `rope_pairs`, `swiglu` and the parameter helpers with
`models/glm.py`. Parameters carry no gradient buffers; matrices are
stored (in, out), `y = x @ W`.
"""
import math

from ..gluon import HybridBlock
from ..ndarray import NDArray
from ..parallel import moe as _moe
from ._decode import ServingSpec, virtual_rows
from .glm import _Gauss, _dot, _param, _w, rms_norm, rope_pairs, swiglu
from .laguna import rope_frequencies

# the published config.json, without the keys that say nothing of shape
DEEPSEEK_V2_PUBLISHED = dict(
    vocab_size=102400, hidden_size=5120, num_hidden_layers=60,
    first_k_dense_replace=1, intermediate_size=12288,
    moe_intermediate_size=1536, n_routed_experts=160, n_shared_experts=2,
    num_experts_per_tok=6, n_group=8, topk_group=3, norm_topk_prob=False,
    routed_scaling_factor=16.0, num_attention_heads=128, q_lora_rank=1536,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling={"type": "yarn", "factor": 40,
                  "original_max_position_embeddings": 4096,
                  "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                  "mscale_all_dim": 0.707},
    max_position_embeddings=163840)


def deepseek_v2_config(**overrides):
    """The published keys plus this chip's share: `experts_held` of the
    `n_routed_experts` (from `first_expert` on; the router stays
    `n_routed_experts` wide and the group limit runs over all `n_group`
    groups) and `vocab_size` rows of the vocabulary."""
    cfg = dict(DEEPSEEK_V2_PUBLISHED, experts_held=None, first_expert=0,
               dtype="bfloat16")
    cfg.update(overrides)
    if cfg["experts_held"] is None:
        cfg["experts_held"] = cfg["n_routed_experts"]
    if cfg["n_routed_experts"] % cfg["n_group"]:
        raise ValueError(f"{cfg['n_routed_experts']} experts in "
                         f"{cfg['n_group']} groups")
    return cfg


def deepseek_tiny_config(**overrides):
    """Test scale: every mechanism present. The 8 groups are kept, 2
    experts a group, 4 a token from the best 3 groups; YaRN at work from
    position 16 on."""
    cfg = deepseek_v2_config(
        vocab_size=96, hidden_size=64, num_hidden_layers=3,
        first_k_dense_replace=1, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=16, experts_held=2,
        num_experts_per_tok=4, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8,
        v_head_dim=16,
        rope_scaling=dict(DEEPSEEK_V2_PUBLISHED["rope_scaling"], factor=4,
                          original_max_position_embeddings=16),
        max_position_embeddings=256, dtype="float32")
    cfg.update(overrides)
    return cfg


def yarn_mscale(factor, mscale):
    """The public `yarn_get_mscale`."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_and_scale(cfg):
    """(inverse frequencies (qk_rope_head_dim / 2,) float32, factor on cos
    and sin, softmax scale) from `rope_theta` and `rope_scaling` (None:
    default RoPE, scale (nope + rope)^-0.5)."""
    d = cfg["qk_rope_head_dim"]
    scale = (cfg["qk_nope_head_dim"] + d) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs is None:
        inv, _ = rope_frequencies(
            {"rope_type": "default", "rope_theta": cfg["rope_theta"]}, d)
        return inv, 1.0, scale
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r}")
    every = yarn_mscale(rs["factor"], rs.get("mscale_all_dim", 0))
    on_rope = yarn_mscale(rs["factor"], rs.get("mscale", 1)) / every
    inv, _ = rope_frequencies(
        {"rope_type": "yarn", "rope_theta": cfg["rope_theta"],
         "factor": rs["factor"], "beta_fast": rs["beta_fast"],
         "beta_slow": rs["beta_slow"], "attention_factor": on_rope,
         "original_max_position_embeddings":
         rs["original_max_position_embeddings"]}, d)
    return inv, on_rope, scale * every * every


class DeepseekLayer(HybridBlock):
    """One decoder layer: MLA, then a dense SwiGLU (`dense`) or the expert
    layer. Holds parameters only; `DeepseekForCausalLM` runs it."""

    def __init__(self, cfg, dense, **kwargs):
        super().__init__(**kwargs)
        self.cfg, self.dense = cfg, dense
        E, dt = cfg["hidden_size"], cfg["dtype"]
        H = cfg["num_attention_heads"]
        qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
        nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"])

        def mat(name, n_in, n_out, dtype=dt, lead=()):
            return _param(name, lead + (n_in, n_out), dtype,
                          _Gauss(n_in ** -0.5))

        def gain(name, n):
            return _param(name, (n,), "float32", _Gauss(0.1, 1.0))

        self.attn_norm = gain("attn_norm_weight", E)
        self.w_qa = mat("q_a_proj", E, qr)
        self.q_norm = gain("q_a_norm_weight", qr)
        self.w_qb = mat("q_b_proj", qr, H * (nope + rope))
        self.w_kva = mat("kv_a_proj", E, kr + rope)
        self.kv_norm = gain("kv_a_norm_weight", kr)
        self.w_kb = mat("kv_b_proj", kr, H * (nope + vd))
        self.w_o = mat("o_proj", H * vd, E)
        self.ffn_norm = gain("ffn_norm_weight", E)
        if dense:
            F = cfg["intermediate_size"]
            self.w_gate, self.w_up = mat("gate_proj", E, F), \
                mat("up_proj", E, F)
            self.w_down = mat("down_proj", F, E)
            return
        F = cfg["moe_intermediate_size"]
        n_e, held = cfg["n_routed_experts"], cfg["experts_held"]
        self.router = mat("router", E, n_e, dtype="float32")
        self.e_gate = mat("experts_gate_proj", E, F, lead=(held,))
        self.e_up = mat("experts_up_proj", E, F, lead=(held,))
        self.e_down = mat("experts_down_proj", F, E, lead=(held,))
        Fs = F * cfg["n_shared_experts"]
        self.s_gate, self.s_up = mat("shared_gate_proj", E, Fs), \
            mat("shared_up_proj", E, Fs)
        self.s_down = mat("shared_down_proj", Fs, E)

    def weights(self):
        """{name: raw array} of this layer, as the reference takes them."""
        return {p.name: _w(p) for _, p in self._iter_params()}


class DeepseekForCausalLM(HybridBlock):
    """Token ids -> logits over this chip's slice of the vocabulary,
    through `serve.Server`."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        self.inv_freq, self.rope_mscale, self.softmax_scale = \
            rope_and_scale(cfg)
        E, V, dt = cfg["hidden_size"], cfg["vocab_size"], cfg["dtype"]
        self.embed = _param("embed_tokens", (V, E), dt, _Gauss(1.0))
        self.layers = []
        for i in range(cfg["num_hidden_layers"]):
            layer = DeepseekLayer(cfg,
                                  dense=i < cfg["first_k_dense_replace"])
            self.register_child(layer, f"layer{i}")
            self.layers.append(layer)
        self.final_norm = _param("final_norm_weight", (E,), "float32",
                                 _Gauss(0.1, 1.0))
        self.head = _param("lm_head", (E, V), dt, _Gauss(E ** -0.5))

    def forward(self, *args):
        raise NotImplementedError(
            "DeepseekForCausalLM is served through serve.Server; "
            "chipbench/reference/deepseek_v2.py is the full forward pass")

    # -- what serve.Server asks ------------------------------------------
    def serving_spec(self):
        import jax.numpy as jnp
        cfg = self.cfg
        lat = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
               jnp.dtype(cfg["dtype"]))
        return ServingSpec(
            vocab_size=cfg["vocab_size"],
            max_length=cfg["max_position_embeddings"],
            streams=[lat] * len(self.layers), index_topk=None,
            chunk_step=self.decode_paged_chunk, draft_step=None)

    def layer_weights(self):
        """([{name: raw array} per layer], {embedding, final norm, head}):
        what the reference is handed."""
        return [layer.weights() for layer in self.layers], {
            "embed_tokens": _w(self.embed),
            "final_norm_weight": _w(self.final_norm),
            "lm_head": _w(self.head)}

    # -- one token pass --------------------------------------------------
    def _attention(self, layer, u, pos, tables, wp, wo, lat):
        """MLA in absorbed form over every cached row of the request. u
        (B, E) the normed layer input; lat this layer's arena; tables
        (B, n_pg), wp/wo (B,) the rows' table rows and write targets.
        Returns (attention output (B, E), lat)."""
        import jax
        import jax.numpy as jnp
        from ..pallas_ops import paged_latent_attention
        cfg = self.cfg
        f32 = jnp.float32
        B = u.shape[0]
        H = cfg["num_attention_heads"]
        R, nope, rope, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                             cfg["qk_rope_head_dim"], cfg["v_head_dim"])
        eps = cfg["rms_norm_eps"]
        inv_freq = jnp.asarray(self.inv_freq)

        def turned(x):
            x = rope_pairs(x, pos, inv_freq)
            return x if self.rope_mscale == 1.0 \
                else (x * self.rope_mscale).astype(x.dtype)

        with jax.named_scope("latent_attention"):
            c_q = rms_norm(_dot(u, _w(layer.w_qa)), _w(layer.q_norm), eps)
            q = _dot(c_q, _w(layer.w_qb)).reshape(B, H, nope + rope)
            kv = _dot(u, _w(layer.w_kva))
            row = jnp.concatenate(
                [rms_norm(kv[:, :R], _w(layer.kv_norm), eps),
                 turned(kv[:, R:])], -1)                        # (B, R+rope)
        with jax.named_scope("kv_arena_update"):
            # one row a token; the arena's last dimension may be wider
            # (lane padding where the paged kernels run)
            lat = lat.at[wp, wo, :R + rope].set(row.astype(lat.dtype))
        with jax.named_scope("latent_attention"):
            w_kb = _w(layer.w_kb).reshape(R, H, nope + vd)
            q_abs = jnp.einsum("bhn,rhn->bhr", q[..., :nope],
                               w_kb[..., :nope],
                               preferred_element_type=f32).astype(u.dtype)
            o_lat = paged_latent_attention(
                jnp.concatenate([q_abs, turned(q[..., nope:])], -1), lat,
                tables, pos, self.softmax_scale, R)             # (B, H, R)
            o = jnp.einsum("bhr,rhv->bhv", o_lat, w_kb[..., nope:],
                           preferred_element_type=f32).astype(u.dtype)
            return _dot(o.reshape(B, H * vd), _w(layer.w_o)), lat

    def _ffn(self, layer, v):
        import jax
        cfg = self.cfg
        if layer.dense:
            return swiglu(v, _w(layer.w_gate), _w(layer.w_up),
                          _w(layer.w_down))
        with jax.named_scope("moe_experts"):
            # the public code scales the gates it does not normalise
            norm = cfg["norm_topk_prob"]
            expert, gate = _moe.moe_topk_route(
                v, _w(layer.router), None, cfg["num_experts_per_tok"],
                1.0 if norm else cfg["routed_scaling_factor"], norm,
                scoring="softmax", n_group=cfg["n_group"],
                topk_group=cfg["topk_group"])
            return _moe.moe_share_ffn(
                v, expert, gate, _w(layer.e_gate), _w(layer.e_up),
                _w(layer.e_down), cfg["first_expert"]) \
                + swiglu(v, _w(layer.s_gate), _w(layer.s_up),
                         _w(layer.s_down))

    def decode_paged_chunk(self, toks, pos, slot, last, tables, flat,
                           page_size, full=False):
        """The serving step `serve.Server` runs, with the contract of
        `GPTForCausalLM.decode_paged_chunk`: ONE pass over the step's W
        virtual rows (token toks[w] at position pos[w] of the request in
        slot slot[w]; pos = -1 pads). Within a layer every row's latent is
        written (page `wp`, offset `wo`; scratch for padding) before any
        row attends the positions <= its own. flat = the latent arena of
        each layer. Returns (float32 logits of row last[s] for each slot s
        (slots, V), or of all rows (W, V) when `full`; the new arenas)."""
        import jax
        import jax.numpy as jnp

        eps = self.cfg["rms_norm_eps"]
        pos_d = pos._data.astype(jnp.int32)
        lats = [f._data for f in flat]
        rows, wp, wo = virtual_rows(
            pos_d, slot._data.astype(jnp.int32),
            tables._data.astype(jnp.int32), page_size)
        x = _w(self.embed)[toks._data.astype(jnp.int32)]        # (W, E)
        for i, layer in enumerate(self.layers):
            a, lats[i] = self._attention(
                layer, rms_norm(x, _w(layer.attn_norm), eps), pos_d, rows,
                wp, wo, lats[i])
            x = x + a
            x = x + self._ffn(layer, rms_norm(x, _w(layer.ffn_norm), eps))
        if not full:
            x = x[last._data.astype(jnp.int32)]
        x = rms_norm(x, _w(self.final_norm), eps)
        with jax.named_scope("lm_head"):
            lg = jnp.matmul(x, _w(self.head),
                            preferred_element_type=jnp.float32)
        return NDArray(lg), [NDArray(a) for a in lats]


def param_count(cfg):
    """Parameters of the model as built from `cfg` (this chip's share)."""
    E, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    attn = E * qr + qr + qr * H * (nope + rope) + E * (kr + rope) + kr \
        + kr * H * (nope + vd) + H * vd * E + 2 * E
    dense = 3 * E * cfg["intermediate_size"]
    moe = E * cfg["n_routed_experts"] + 3 * E * cfg["moe_intermediate_size"] \
        * (cfg["experts_held"] + cfg["n_shared_experts"])
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    return n_dense * (attn + dense) + n_moe * (attn + moe) \
        + 2 * E * cfg["vocab_size"] + E
