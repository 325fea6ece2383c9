"""Laguna-style decoder (`model_type: laguna`), for serving.

Source of the keys: huggingface.co/poolside/Laguna-XS.2 `config.json`.
What sets it apart from a plain pre-norm decoder, layer by layer
(`layer_types`, `mlp_layer_types`, `num_attention_heads_per_layer`):

  * **window layers beside full ones** — a `sliding_attention` layer sees
    the last `sliding_window` positions only (key j for query i iff
    i - window < j <= i), a `full_attention` layer everything. In serving
    these are two kinds of cache: the full layers' rows stay for the
    request's life, a window layer's are dead once `window` behind its
    position. `serving_spec().windows` puts each layer's K and V arenas
    in the page class of its window, and `mx.serve` returns a window
    class's pages as a request moves on.
  * **head counts by layer, grouped-query KV** — layer l has
    `num_attention_heads_per_layer[l]` query heads (48 on a full layer of
    the published model, 64 on a sliding one) over `num_key_value_heads`
    KV heads of `head_dim`; query head h reads KV head h // (H_l / Hkv).
    `pallas_ops.paged_attention` brings a KV page once for its group.
  * **RoPE by layer type** (`rope_parameters`) — sliding: default, all of
    the head; full: YaRN on the first `partial_rotary_factor` of it, cos
    and sin times `attention_factor`, the rest of the head not rotated.
    Rotate-half pairing (dimension i with i + rot/2).
  * **a gate on the attention output** (`gating`) — read as one sigmoid
    gate a head from the layer's normed input, `g = sigmoid(a Wg)`, `Wg`
    hidden x H_l, applied to the head's output before the output
    projection (the reading the published parameter count bears out).
  * **sparse experts** — layer 0 a dense SwiGLU, the others `num_experts`
    small experts, `num_experts_per_tok` a token by sigmoid score (gates
    normalised over the chosen, times `moe_routed_scaling_factor`), one
    shared expert: `parallel.moe.moe_topk_route` + `moe_share_ffn` with
    every expert held.

No q/k norm, no bias, untied head. `chipbench/reference/laguna.py` is the
plain float32 reference of the same equations.

Serving only, paged only: `decode_paged_chunk` has the contract of
`GPTForCausalLM.decode_paged_chunk` (one pass over the step's tokens as
virtual rows; prefill is the same pass), against K and V arenas
`(pages, Hkv, page_size, head_dim)` a layer in two page classes, so the
step's `tables` are (2, slots, n_pg): the full class's, then the window
class's. Parameters carry no gradient buffers; matrices are stored (in,
out), `y = x @ W`.
"""
import math

import numpy as np

from ..gluon import HybridBlock
from ..ndarray import NDArray
from ..parallel import moe as _moe
from ._decode import ServingSpec, virtual_rows
from .glm import _Gauss, _dot, _param, _w, rms_norm, swiglu

_PERIOD = ["full_attention"] + ["sliding_attention"] * 3

# the published config.json, without the keys that say nothing of shape
LAGUNA_XS2_PUBLISHED = dict(
    vocab_size=100352, hidden_size=2048, intermediate_size=8192,
    num_hidden_layers=40, num_attention_heads=48, num_key_value_heads=8,
    head_dim=128, max_position_embeddings=262144, rms_norm_eps=1e-6,
    num_experts=256, num_experts_per_tok=8, moe_intermediate_size=512,
    shared_expert_intermediate_size=512, moe_routed_scaling_factor=2.5,
    sliding_window=512,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1}},
    layer_types=_PERIOD * 10,
    mlp_layer_types=["dense"] + ["sparse"] * 39,
    num_attention_heads_per_layer=[48, 64, 64, 64] * 10)


def laguna_config(**keys):
    """The published keys under their own names, those given changed.
    The three per-layer lists may be longer than `num_hidden_layers`: the
    model is built from their first `num_hidden_layers` entries (a cut in
    depth keeps the lists as published)."""
    cfg = dict(LAGUNA_XS2_PUBLISHED, dtype="bfloat16")
    cfg.update(keys)
    n = cfg["num_hidden_layers"]
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        if len(cfg[key]) < n:
            raise ValueError(f"{key} has {len(cfg[key])} entries for "
                             f"{n} layers")
    return cfg


def laguna_tiny_config(**keys):
    """Test scale: both kinds of layer and both head counts, a window of
    a few pages, more experts than a token takes."""
    cfg = laguna_config(
        vocab_size=96, hidden_size=64, intermediate_size=128,
        num_hidden_layers=5, num_attention_heads=6, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=512, num_experts=8,
        num_experts_per_tok=3, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, sliding_window=12,
        rope_parameters={
            "full_attention": dict(
                LAGUNA_XS2_PUBLISHED["rope_parameters"]["full_attention"],
                factor=4, original_max_position_embeddings=16,
                attention_factor=0.1 * math.log(4) + 1),
            "sliding_attention":
                LAGUNA_XS2_PUBLISHED["rope_parameters"]["sliding_attention"]},
        num_attention_heads_per_layer=[6, 8, 8, 8] * 10, dtype="float32")
    cfg.update(keys)
    return cfg


def rope_frequencies(params, head_dim):
    """(inverse frequencies (rot/2,) float32, factor on cos and sin) of a
    layer type's `rope_parameters` entry; `rot = head_dim *
    partial_rotary_factor` dimensions are rotated. `default`: theta^(-2i
    /rot). `yarn`: the public blend of that with the same divided by
    `factor`, by a linear ramp between the dimensions where
    `original_max_position_embeddings` positions make `beta_fast` and
    `beta_slow` rotations."""
    rot = int(head_dim * params.get("partial_rotary_factor", 1))
    base = float(params["rope_theta"])
    pos_freqs = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if params["rope_type"] == "default":
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    if params["rope_type"] != "yarn":
        raise ValueError(f"rope_type {params['rope_type']!r}")
    factor, orig = params["factor"], \
        params["original_max_position_embeddings"]

    def correction_dim(rotations):
        return rot * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(params["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(params["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)
    scale = params.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), float(scale)


def rope_half(x, pos, inv_freq, scale):
    """Rotate-half RoPE on the first 2 * len(inv_freq) dims of the last
    axis of x (B, heads, d), dimension i paired with i + rot/2, cos and
    sin times `scale`; the rest of the head as it is. pos (B,)."""
    import jax.numpy as jnp
    half = inv_freq.shape[0]
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos, sin = (jnp.cos(ang) * scale)[:, None, :], \
        (jnp.sin(ang) * scale)[:, None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:2 * half]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x32[..., 2 * half:]],
        -1).astype(x.dtype)


class LagunaLayer(HybridBlock):
    """One decoder layer: grouped-query attention of its own kind and head
    count, then a dense SwiGLU or the expert layer. Holds parameters
    only; `LagunaForCausalLM` runs it."""

    def __init__(self, cfg, index, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        self.kind = cfg["layer_types"][index]
        self.heads = cfg["num_attention_heads_per_layer"][index]
        self.dense = cfg["mlp_layer_types"][index] == "dense"
        self.window = cfg["sliding_window"] \
            if self.kind == "sliding_attention" else None
        self.inv_freq, self.rope_scale = rope_frequencies(
            cfg["rope_parameters"][self.kind], cfg["head_dim"])
        E, dt = cfg["hidden_size"], cfg["dtype"]
        D, Hkv = cfg["head_dim"], cfg["num_key_value_heads"]
        if self.heads % Hkv:
            raise ValueError(f"layer {index}: {self.heads} query heads over "
                             f"{Hkv} KV heads")

        def mat(name, n_in, n_out, dtype=dt, lead=()):
            return _param(name, lead + (n_in, n_out), dtype,
                          _Gauss(n_in ** -0.5))

        def gain(name, n):
            return _param(name, (n,), "float32", _Gauss(0.1, 1.0))

        self.attn_norm = gain("attn_norm_weight", E)
        self.w_q = mat("q_proj", E, self.heads * D)
        self.w_k = mat("k_proj", E, Hkv * D)
        self.w_v = mat("v_proj", E, Hkv * D)
        self.w_g = mat("g_proj", E, self.heads)
        self.w_o = mat("o_proj", self.heads * D, E)
        self.ffn_norm = gain("ffn_norm_weight", E)
        if self.dense:
            F = cfg["intermediate_size"]
            self.w_gate, self.w_up = mat("gate_proj", E, F), \
                mat("up_proj", E, F)
            self.w_down = mat("down_proj", F, E)
            return
        F, n_e = cfg["moe_intermediate_size"], cfg["num_experts"]
        self.router = mat("router", E, n_e, dtype="float32")
        self.e_gate = mat("experts_gate_proj", E, F, lead=(n_e,))
        self.e_up = mat("experts_up_proj", E, F, lead=(n_e,))
        self.e_down = mat("experts_down_proj", F, E, lead=(n_e,))
        Fs = cfg["shared_expert_intermediate_size"]
        self.s_gate, self.s_up = mat("shared_gate_proj", E, Fs), \
            mat("shared_up_proj", E, Fs)
        self.s_down = mat("shared_down_proj", Fs, E)

    def weights(self):
        """{name: raw array} of this layer, as the reference takes them."""
        return {p.name: _w(p) for _, p in self._iter_params()}


class LagunaForCausalLM(HybridBlock):
    """Token ids -> logits, through `serve.Server`."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        E, V, dt = cfg["hidden_size"], cfg["vocab_size"], cfg["dtype"]
        self.embed = _param("embed_tokens", (V, E), dt, _Gauss(1.0))
        self.layers = []
        for i in range(cfg["num_hidden_layers"]):
            layer = LagunaLayer(cfg, i)
            self.register_child(layer, f"layer{i}")
            self.layers.append(layer)
        self.final_norm = _param("final_norm_weight", (E,), "float32",
                                 _Gauss(0.1, 1.0))
        self.head = _param("lm_head", (E, V), dt, _Gauss(E ** -0.5))

    def forward(self, *args):
        raise NotImplementedError(
            "LagunaForCausalLM is served through serve.Server; "
            "chipbench/reference/laguna.py is the full forward pass")

    # -- what serve.Server asks ------------------------------------------
    def serving_spec(self):
        import jax.numpy as jnp
        cfg = self.cfg
        kv = (cfg["num_key_value_heads"], cfg["head_dim"],
              jnp.dtype(cfg["dtype"]))
        windows = [layer.window for layer in self.layers]
        return ServingSpec(
            vocab_size=cfg["vocab_size"],
            max_length=cfg["max_position_embeddings"],
            streams=[kv] * (2 * len(self.layers)), index_topk=None,
            chunk_step=self.decode_paged_chunk, draft_step=None,
            windows=windows * 2)        # K a layer, then V

    def layer_weights(self):
        """([{name: raw array} per layer], {embedding, final norm, head}):
        what the reference is handed."""
        return [layer.weights() for layer in self.layers], {
            "embed_tokens": _w(self.embed),
            "final_norm_weight": _w(self.final_norm),
            "lm_head": _w(self.head)}

    # -- one token pass --------------------------------------------------
    def _attention(self, layer, u, pos, tables, wp, wo, kp, vp):
        """Grouped-query attention of one layer over its page class. u
        (B, E) the normed layer input; kp/vp the layer's arenas; tables
        (B, n_pg), wp/wo (B,) of the layer's class. Returns (attention
        output (B, E), kp, vp)."""
        import jax
        import jax.numpy as jnp
        from ..pallas_ops import kv_page_write, paged_attention
        cfg = self.cfg
        B = u.shape[0]
        H, Hkv, D = layer.heads, cfg["num_key_value_heads"], cfg["head_dim"]
        scope = "full_attention" if layer.window is None \
            else "window_attention"

        def turned(x):
            return rope_half(x, pos, layer.inv_freq, layer.rope_scale)

        with jax.named_scope(scope):
            q = turned(_dot(u, _w(layer.w_q)).reshape(B, H, D))
            k = turned(_dot(u, _w(layer.w_k)).reshape(B, Hkv, D))
            v = _dot(u, _w(layer.w_v)).reshape(B, Hkv, D)
        with jax.named_scope("kv_arena_update"):
            kp, vp = kv_page_write(kp, vp, k[:, :, None, :], v[:, :, None, :],
                                   wp, wo)
        with jax.named_scope(scope):
            o = paged_attention(q[:, :, None, :], kp, vp, tables, pos,
                                layer.window)[:, :, 0, :]       # (B, H, D)
            gate = jax.nn.sigmoid(jnp.matmul(
                u, _w(layer.w_g), preferred_element_type=jnp.float32))
            o = (o * gate[..., None].astype(o.dtype)).reshape(B, H * D)
            return _dot(o, _w(layer.w_o)), kp, vp

    def _ffn(self, layer, v):
        import jax
        import jax.numpy as jnp
        cfg = self.cfg
        if layer.dense:
            return swiglu(v, _w(layer.w_gate), _w(layer.w_up),
                          _w(layer.w_down))
        with jax.named_scope("moe_experts"):
            # no selection bias: the config has no `topk_method`
            expert, gate = _moe.moe_topk_route(
                v, _w(layer.router),
                jnp.zeros((cfg["num_experts"],), jnp.float32),
                cfg["num_experts_per_tok"],
                cfg["moe_routed_scaling_factor"], True)
            return _moe.moe_share_ffn(
                v, expert, gate, _w(layer.e_gate), _w(layer.e_up),
                _w(layer.e_down)) \
                + swiglu(v, _w(layer.s_gate), _w(layer.s_up),
                         _w(layer.s_down))

    def decode_paged_chunk(self, toks, pos, slot, last, tables, flat,
                           page_size, full=False):
        """The serving step `serve.Server` runs, with the contract of
        `GPTForCausalLM.decode_paged_chunk`: ONE pass over the step's W
        virtual rows (token toks[w] at position pos[w] of the request in
        slot slot[w]; pos = -1 pads). Within a layer every row's key and
        value is written (into the layer's page class: through the full
        class's table on a full layer, the window class's on a sliding
        one) before any row attends positions <= its own, on a sliding
        layer the last `sliding_window` of them. tables (2, slots, n_pg):
        the full class's page tables, then the window class's; flat = K
        arenas per layer, then V arenas. Returns (float32 logits of row
        last[s] for each slot s (slots, V), or of all rows (W, V) when
        `full`; the new arenas)."""
        import jax
        import jax.numpy as jnp

        n_l = len(self.layers)
        eps = self.cfg["rms_norm_eps"]
        pos_d = pos._data.astype(jnp.int32)
        slot_d = slot._data.astype(jnp.int32)
        tables_d = tables._data.astype(jnp.int32)
        ks = [f._data for f in flat[:n_l]]
        vs = [f._data for f in flat[n_l:]]
        # rows' table rows and write targets, a set for each page class
        by_class = {None: virtual_rows(pos_d, slot_d, tables_d[0], page_size),
                    self.cfg["sliding_window"]:
                    virtual_rows(pos_d, slot_d, tables_d[1], page_size)}
        x = _w(self.embed)[toks._data.astype(jnp.int32)]        # (W, E)
        for i, layer in enumerate(self.layers):
            rows, wp, wo = by_class[layer.window]
            a, ks[i], vs[i] = self._attention(
                layer, rms_norm(x, _w(layer.attn_norm), eps), pos_d, rows,
                wp, wo, ks[i], vs[i])
            x = x + a
            x = x + self._ffn(layer, rms_norm(x, _w(layer.ffn_norm), eps))
        if not full:
            x = x[last._data.astype(jnp.int32)]
        x = rms_norm(x, _w(self.final_norm), eps)
        with jax.named_scope("lm_head"):
            lg = jnp.matmul(x, _w(self.head),
                            preferred_element_type=jnp.float32)
        return NDArray(lg), [NDArray(a) for a in ks + vs]


def param_count(cfg):
    """Parameters of the model as built from `cfg`."""
    E, D = cfg["hidden_size"], cfg["head_dim"]
    Hkv = cfg["num_key_value_heads"]
    n = cfg["num_hidden_layers"]
    total = 2 * E * cfg["vocab_size"] + E
    for heads, mlp in zip(cfg["num_attention_heads_per_layer"][:n],
                          cfg["mlp_layer_types"][:n]):
        total += 2 * E * heads * D + 2 * E * Hkv * D + E * heads + 2 * E
        if mlp == "dense":
            total += 3 * E * cfg["intermediate_size"]
        else:
            total += E * cfg["num_experts"] \
                + 3 * E * cfg["moe_intermediate_size"] * cfg["num_experts"] \
                + 3 * E * cfg["shared_expert_intermediate_size"]
    return total
