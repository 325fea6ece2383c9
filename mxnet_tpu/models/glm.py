"""GLM-5-style decoder (`model_type: glm_moe_dsa`), for serving.

Source of the keys: huggingface.co/zai-org/GLM-5 `config.json`. Three
mechanisms beside a plain pre-norm decoder:

  * **latent attention (MLA)** — keys and values are up-projections of ONE
    cached latent row per token, `[c (kv_lora_rank); k_rope
    (qk_rope_head_dim)]`, no head axis. The served step runs the absorbed
    form: the per-head key up-projection is folded into the query
    (`q~_h = W_h^K^T q_h^nope`, kv_lora_rank wide) and the value
    up-projection applied after the weighted sum of latents, so attention
    reads the cache as it lies.
  * **learned sparse attention (DSA)** — an indexer of `index_n_heads`
    small heads scores every cached token from a second cached row (the
    indexer key, `index_head_dim` wide): `I = sum_j w_j relu(q_j . k_s)`,
    and attention runs over the `index_topk` tokens of largest score
    (all of them while the context is shorter).
  * **sparse experts** — `n_routed_experts` SwiGLU experts,
    `num_experts_per_tok` a token by sigmoid score plus a selection bias,
    one shared expert: `parallel.moe.moe_topk_route` + `moe_share_ffn`.
    The model is told which experts it holds (`experts_held`,
    `first_expert`): one chip's share of an expert-parallel deployment
    computes its own experts' part and passes the partial sum on.

What the config's keys do not settle follows DeepSeek-V3.2's published
lightning indexer: indexer key = LayerNorm of a projection of the layer's
normed input, queries from the query latent, head weights from the layer
input scaled by heads^-1/2 * dim^-1/2, RoPE on the first
`qk_rope_head_dim` dims of both; no Hadamard rotation, no fp8. The
multi-token-prediction layer is not built (it does not enter the main
model's logits). `chipbench/reference/glm5.py` is the plain float32
reference of the same equations.

Serving only, paged only: `decode_paged_chunk` has the contract of
`GPTForCausalLM.decode_paged_chunk` (one pass over the step's tokens as
virtual rows; prefill is the same pass), against two arenas a layer: latents
`(pages, page_size, kv_lora_rank + qk_rope_head_dim)` and indexer keys
`(pages, page_size, index_head_dim)`. Parameters carry no gradient
buffers; matrices are stored (in, out), `y = x @ W`.
"""
from .. import initializer as _init
from ..gluon import HybridBlock
from ..gluon.parameter import Parameter
from ..ndarray import NDArray
from ..parallel import moe as _moe
from ._decode import ServingSpec, virtual_rows

# the published config.json, without the keys that say nothing of shape
GLM5_PUBLISHED = dict(
    vocab_size=154880, hidden_size=6144, num_hidden_layers=78,
    first_k_dense_replace=3, intermediate_size=12288,
    moe_intermediate_size=2048, n_routed_experts=256, n_shared_experts=1,
    num_experts_per_tok=8, norm_topk_prob=True, routed_scaling_factor=2.5,
    num_attention_heads=64, q_lora_rank=2048, kv_lora_rank=512,
    qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
    index_n_heads=32, index_head_dim=128, index_topk=2048,
    rms_norm_eps=1e-5, rope_theta=1e6, max_position_embeddings=202752)


def glm5_config(**overrides):
    """The published keys plus this chip's share: `experts_held` of the
    `n_routed_experts` (from `first_expert` on; the router stays
    `n_routed_experts` wide) and `vocab_size` rows of the vocabulary."""
    cfg = dict(GLM5_PUBLISHED, experts_held=None, first_expert=0,
               dtype="bfloat16")
    cfg.update(overrides)
    if cfg["experts_held"] is None:
        cfg["experts_held"] = cfg["n_routed_experts"]
    return cfg


def glm_tiny_config(**overrides):
    """Test scale: every mechanism present, selection at work from a
    context of 9 on."""
    cfg = glm5_config(
        vocab_size=96, hidden_size=64, num_hidden_layers=3,
        first_k_dense_replace=1, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=8, experts_held=2,
        num_experts_per_tok=3, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8,
        v_head_dim=16, index_n_heads=2, index_head_dim=16, index_topk=8,
        max_position_embeddings=256, dtype="float32")
    cfg.update(overrides)
    return cfg


class _Gauss(_init.Initializer):
    """Normal(mean, sigma), drawn on the device in the parameter's own
    dtype, in one fused pass: no float32 copy of a 200 M-element expert
    stack."""

    def __init__(self, sigma, mean=0.0):
        self.sigma, self.mean = sigma, mean

    def _init(self, key, shape, dtype):
        import jax
        return jax.jit(
            lambda k: self.mean + self.sigma * jax.random.normal(
                k, shape, dtype))(key)


def rms_norm(x, gamma, eps):
    import jax
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, gamma, beta, eps):
    import jax
    import jax.numpy as jnp
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, -1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32)).astype(x.dtype)


def rope_pairs(x, pos, inv_freq):
    """Rotary embedding over the last dimension of x (..., d), pairs
    (x[2i], x[2i+1]) turned by pos * inv_freq[i]. x (B, ..., d) with
    pos (B,) broadcast over the middle dimensions; inv_freq (d/2,)."""
    import jax.numpy as jnp
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]  # (B, d/2)
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pair = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pair[..., 0], pair[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


def rope_interleaved(x, pos, theta):
    """`rope_pairs` at the default frequencies theta^(-2i/d)."""
    import jax.numpy as jnp
    d = x.shape[-1]
    return rope_pairs(
        x, pos, theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))


def rope_first(x, n, pos, theta):
    """RoPE on the first `n` dims of the last axis, the rest as it is."""
    import jax.numpy as jnp
    return jnp.concatenate(
        [rope_interleaved(x[..., :n], pos, theta), x[..., n:]], -1)


def _w(param):
    """A parameter's raw array (the traced one inside a jitted step)."""
    return param.data()._data


def _param(name, shape, dtype, init):
    # no gradient buffer: 4.7 B served parameters have no room for one
    return Parameter(name, shape=shape, dtype=dtype, init=init,
                     grad_req="null")


def _dot(x, w):
    """x @ w with float32 accumulation, result in x's dtype."""
    import jax.numpy as jnp
    return jnp.matmul(x, w, preferred_element_type=jnp.float32) \
        .astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    g = jnp.matmul(x, w_gate, preferred_element_type=f32)
    u = jnp.matmul(x, w_up, preferred_element_type=f32)
    return _dot((jax.nn.silu(g) * u).astype(x.dtype), w_down)


class GLMLayer(HybridBlock):
    """One decoder layer: MLA + indexer, then a dense SwiGLU (`dense`) or
    the expert layer. Holds parameters only; `GLMForCausalLM` runs it."""

    def __init__(self, cfg, dense, **kwargs):
        super().__init__(**kwargs)
        self.cfg, self.dense = cfg, dense
        E, dt = cfg["hidden_size"], cfg["dtype"]
        H = cfg["num_attention_heads"]
        qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
        nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                          cfg["v_head_dim"])
        ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]

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
        self.w_qi = mat("indexer_q_proj", qr, ih * idim)
        self.w_ki = mat("indexer_k_proj", E, idim)
        self.ki_norm = gain("indexer_k_norm_weight", idim)
        self.ki_shift = _param("indexer_k_norm_shift", (idim,),
                                    "float32", _Gauss(0.1))
        self.w_wi = mat("indexer_weights_proj", E, ih)
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
        # small and non-zero, so that the choice it steers is not the
        # choice by score alone
        self.select_bias = _param("router_select_offset", (n_e,),
                                       "float32", _Gauss(0.05))
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


class GLMForCausalLM(HybridBlock):
    """Token ids -> logits over this chip's slice of the vocabulary,
    through `serve.Server`."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        E, V, dt = cfg["hidden_size"], cfg["vocab_size"], cfg["dtype"]
        self.embed = _param("embed_tokens", (V, E), dt, _Gauss(1.0))
        self.layers = []
        for i in range(cfg["num_hidden_layers"]):
            layer = GLMLayer(cfg, dense=i < cfg["first_k_dense_replace"])
            self.register_child(layer, f"layer{i}")
            self.layers.append(layer)
        self.final_norm = _param("final_norm_weight", (E,),
                                          "float32", _Gauss(0.1, 1.0))
        self.head = _param("lm_head", (E, V), dt, _Gauss(E ** -0.5))

    def forward(self, *args):
        raise NotImplementedError(
            "GLMForCausalLM is served through serve.Server; "
            "chipbench/reference/glm5.py is the full forward pass")

    # -- what serve.Server asks ------------------------------------------
    def serving_spec(self):
        import jax.numpy as jnp
        cfg = self.cfg
        dt = jnp.dtype(cfg["dtype"])
        n = len(self.layers)
        lat = (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], dt)
        return ServingSpec(
            vocab_size=cfg["vocab_size"],
            max_length=cfg["max_position_embeddings"],
            streams=[lat] * n + [(cfg["index_head_dim"], dt)] * n,
            index_topk=cfg["index_topk"],
            chunk_step=self.decode_paged_chunk, draft_step=None)

    def layer_weights(self):
        """([{name: raw array} per layer], {embedding, final norm, head}):
        what the reference is handed."""
        return [layer.weights() for layer in self.layers], {
            "embed_tokens": _w(self.embed),
            "final_norm_weight": _w(self.final_norm),
            "lm_head": _w(self.head)}

    # -- one token pass --------------------------------------------------
    def _attention(self, layer, u, pos, tables, wp, wo, lat, idx,
                   page_size):
        """MLA in absorbed form over the rows the indexer selects. u
        (B, E) the normed layer input; lat/idx this layer's arenas.
        Returns (attention output (B, E), lat, idx)."""
        import jax
        import jax.numpy as jnp
        cfg = self.cfg
        f32 = jnp.float32
        B = u.shape[0]
        H = cfg["num_attention_heads"]
        R, nope, rope, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                             cfg["qk_rope_head_dim"], cfg["v_head_dim"])
        ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
        eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]

        c_q = rms_norm(_dot(u, _w(layer.w_qa)), _w(layer.q_norm), eps)
        q = _dot(c_q, _w(layer.w_qb)).reshape(B, H, nope + rope)
        q_n, q_r = q[..., :nope], rope_interleaved(q[..., nope:], pos, theta)
        kv = _dot(u, _w(layer.w_kva))
        row = jnp.concatenate(
            [rms_norm(kv[:, :R], _w(layer.kv_norm), eps),
             rope_interleaved(kv[:, R:], pos, theta)], -1)      # (B, R+rope)
        k_i = layer_norm(_dot(u, _w(layer.w_ki)), _w(layer.ki_norm),
                         _w(layer.ki_shift), eps)
        k_i = rope_first(k_i, rope, pos, theta)
        with jax.named_scope("kv_arena_update"):
            # one row a token and arena; the arenas' last dimension may be
            # wider (lane padding where the paged kernels run)
            lat = lat.at[wp, wo, :R + rope].set(row.astype(lat.dtype))
            idx = idx.at[wp, wo, :idim].set(k_i.astype(idx.dtype))

        ps = page_size
        L = tables.shape[1] * ps
        with jax.named_scope("sparse_index"):
            q_i = _dot(c_q, _w(layer.w_qi)).reshape(B, ih, idim)
            q_i = rope_first(q_i, rope, pos, theta)
            w_i = jnp.matmul(u, _w(layer.w_wi), preferred_element_type=f32) \
                * (ih ** -0.5 * idim ** -0.5)                   # (B, ih)
            keys = idx[tables].reshape(B, L, -1)[..., :idim]    # (B, L, idim)
            s = jnp.einsum("bjd,bld->bjl", q_i, keys,
                           preferred_element_type=f32)
            score = jnp.einsum("bjl,bj->bl", jax.nn.relu(s), w_i)
            score = jnp.where(jnp.arange(L)[None, :] <= pos[:, None],
                              score, -jnp.inf)
            top, sel = jax.lax.top_k(score, min(cfg["index_topk"], L))
            valid = top > -jnp.inf                              # (B, k)
        with jax.named_scope("latent_attention"):
            page = jnp.take_along_axis(tables, sel // ps, axis=1)
            rows = lat.reshape(-1, lat.shape[-1])[page * ps + sel % ps]
            c_s, kr_s = rows[..., :R], rows[..., R:R + rope]    # (B, k, .)
            w_kb = _w(layer.w_kb).reshape(R, H, nope + vd)
            q_abs = jnp.einsum("bhn,rhn->bhr", q_n, w_kb[..., :nope],
                               preferred_element_type=f32).astype(u.dtype)
            a = (jnp.einsum("bhr,bkr->bhk", q_abs, c_s,
                            preferred_element_type=f32)
                 + jnp.einsum("bhe,bke->bhk", q_r, kr_s,
                              preferred_element_type=f32)) \
                * (nope + rope) ** -0.5
            a = jnp.where(valid[:, None, :], a, -1e30)
            p = jax.nn.softmax(a, axis=-1).astype(u.dtype)
            o_lat = jnp.einsum("bhk,bkr->bhr", p, c_s,
                               preferred_element_type=f32).astype(u.dtype)
            o = jnp.einsum("bhr,rhv->bhv", o_lat, w_kb[..., nope:],
                           preferred_element_type=f32).astype(u.dtype)
            out = _dot(o.reshape(B, H * vd), _w(layer.w_o))
        return out, lat, idx

    def _ffn(self, layer, v):
        import jax
        cfg = self.cfg

        if layer.dense:
            return swiglu(v, _w(layer.w_gate), _w(layer.w_up), _w(layer.w_down))
        with jax.named_scope("moe_experts"):
            expert, gate = _moe.moe_topk_route(
                v, _w(layer.router), _w(layer.select_bias),
                cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
                cfg["norm_topk_prob"])
            return _moe.moe_share_ffn(
                v, expert, gate, _w(layer.e_gate), _w(layer.e_up),
                _w(layer.e_down), cfg["first_expert"]) \
                + swiglu(v, _w(layer.s_gate), _w(layer.s_up), _w(layer.s_down))

    def _paged_token_step(self, tok, pos, tables, wp, wo, lats, idxs,
                          page_size, head_rows=None):
        """One token a row through every layer (raw arrays; the whole of
        the chunk step). A row is one fed token and `tables[b]` the
        page-table row it goes through. `head_rows` (R,): the rows whose
        logits are wanted (all B where None). Returns (float32 logits
        (R|B, V), lats, idxs)."""
        import jax
        import jax.numpy as jnp
        eps = self.cfg["rms_norm_eps"]
        x = _w(self.embed)[tok]                        # (B, E)
        new_lat, new_idx = [], []
        for i, layer in enumerate(self.layers):
            u = rms_norm(x, _w(layer.attn_norm), eps)
            a, lat, idx = self._attention(layer, u, pos, tables, wp, wo,
                                          lats[i], idxs[i], page_size)
            new_lat.append(lat)
            new_idx.append(idx)
            x = x + a
            x = x + self._ffn(layer, rms_norm(x, _w(layer.ffn_norm), eps))
        if head_rows is not None:
            x = x[head_rows]
        x = rms_norm(x, _w(self.final_norm), eps)
        with jax.named_scope("lm_head"):
            lg = jnp.matmul(x, _w(self.head),
                            preferred_element_type=jnp.float32)
        return lg, tuple(new_lat), tuple(new_idx)

    def decode_paged_chunk(self, toks, pos, slot, last, tables, flat,
                           page_size, full=False):
        """The serving step `serve.Server` runs, with the contract of
        `GPTForCausalLM.decode_paged_chunk`: ONE pass over the step's W
        virtual rows (token toks[w] at position pos[w] of the request in
        slot slot[w]; pos = -1 pads). Within a layer every row's latent
        and indexer key is written (page `wp`, offset `wo`; scratch for
        padding) before any row scores its pages, selects, gathers and
        attends positions <= its own. flat = latent arenas per layer, then
        indexer-key arenas. Returns (float32 logits of row last[s] for
        each slot s (slots, V), or of all rows (W, V) when `full`; the
        new arenas)."""
        import jax.numpy as jnp

        n_l = len(self.layers)
        pos_d = pos._data.astype(jnp.int32)
        slot_d = slot._data.astype(jnp.int32)
        flat_d = [f._data for f in flat]
        rows, wp, wo = virtual_rows(
            pos_d, slot_d, tables._data.astype(jnp.int32), page_size)
        lg, lats, idxs = self._paged_token_step(
            toks._data.astype(jnp.int32), pos_d, rows, wp, wo,
            tuple(flat_d[:n_l]), tuple(flat_d[n_l:]), page_size,
            head_rows=None if full else last._data.astype(jnp.int32))
        return NDArray(lg), [NDArray(a) for a in list(lats) + list(idxs)]


def param_count(cfg):
    """Parameters of the model as built from `cfg` (this chip's share)."""
    E, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    attn = E * qr + qr + qr * H * (nope + rope) + E * (kr + rope) + kr \
        + kr * H * (nope + vd) + H * vd * E + 2 * E
    index = qr * ih * idim + E * idim + 2 * idim + E * ih
    dense = 3 * E * cfg["intermediate_size"]
    F = cfg["moe_intermediate_size"]
    n_e = cfg["n_routed_experts"]
    moe = E * n_e + n_e + 3 * E * F * (cfg["experts_held"]
                                       + cfg["n_shared_experts"])
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    return n_dense * (attn + index + dense) + n_moe * (attn + index + moe) \
        + 2 * E * cfg["vocab_size"] + E

