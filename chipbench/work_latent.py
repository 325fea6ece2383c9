"""Operations and bytes of sparse attention over a latent cache (an
indexer that scores every cached token, then attention in absorbed MLA
form over the rows it selects), from what the traced stretch was fed.

A floor that no implementation of this algorithm over this cache can
beat, so a share of it cannot pass 100 %: it reads the same work
whatever implements it (XLA gathers today, a kernel later).

`shapes["traced"]` holds the stretch's counts, from the server's own
position counters (`Server.stats()`: `attn_ctx_tokens`, `attn_sel_tokens`;
no device read) and the harness's per-step count of cached rows.
Returns (flops, bytes) for ONE step on ONE chip, as `work.py` does.
"""


def sparse_attention(shapes):
    """Operations: per fed token with a context of L cached tokens, the
    indexer's scores `2 * heads_i * dim_i * L` and, over the min(L, topk)
    selected rows, scores against the latent row (`latent_width`) and the
    weighted sum of latents (`kv_lora_rank`) for every head:
    `2 * heads * (latent_width + kv_lora_rank) * min(L, topk)`; times
    layers. `ctx_tokens` is the sum of L over the fed tokens, `sel_tokens`
    of min(L, topk).

    Bytes: every pass has to read each cached indexer key once, however
    many rows share it (`row_passes`: cached rows summed over the
    stretch's token passes), and at least the `topk` latent rows one query
    selects (`sel_row_passes`: min(cached rows, topk) summed likewise; the
    union over the batch's queries is larger, and not known without the
    device); times layers. Pages allocated for tokens not generated yet
    are not counted: nothing has to read them."""
    t = shapes["traced"]
    flops = shapes["layers"] * (
        2 * shapes["index_heads"] * shapes["index_dim"] * t["ctx_tokens"]
        + 2 * shapes["heads"]
        * (shapes["latent_width"] + shapes["kv_lora_rank"]) * t["sel_tokens"])
    nbytes = shapes["layers"] * shapes["itemsize"] * (
        shapes["index_dim"] * t["row_passes"]
        + shapes["latent_width"] * t["sel_row_passes"])
    return flops / t["steps"], nbytes / t["steps"]


def serve_step(shapes):
    """The operations of one serving step on this chip's share of the
    model, for the whole step's share of the chip's peak (`step_mfu`), from
    what the traced stretch was fed. Per fed token the matrix products of
    every layer: the MLA projections in absorbed form and the indexer's,
    then the dense feed-forward (the `dense_layers` leading layers) or the
    router, the shared experts and as many routed experts as a token's
    `experts_per_token` picks find on this chip on average (`experts_held`
    of `router_width`); per emitted token the head over this chip's slice
    of the vocabulary; and `sparse_attention`'s operations. Multiply-adds
    count twice. Bytes: `sparse_attention`'s alone (the weights' bytes are
    a floor of their own, not part of this share)."""
    t, e = shapes["traced"], shapes["hidden"]
    heads, kr = shapes["heads"], shapes["kv_lora_rank"]
    nope, rope = shapes["qk_nope_head_dim"], shapes["latent_width"] - kr
    qr, vd = shapes["q_lora_rank"], shapes["v_head_dim"]
    attention = e * qr + qr * heads * (nope + rope) + e * (kr + rope) \
        + heads * kr * (nope + vd) + heads * vd * e
    indexer = qr * shapes["index_heads"] * shapes["index_dim"] \
        + e * shapes["index_dim"] + e * shapes["index_heads"]
    experts = shapes["shared_experts"] + shapes["experts_per_token"] \
        * shapes["experts_held"] / shapes["router_width"]
    expert_layer = e * shapes["router_width"] \
        + 3 * e * shapes["expert_width"] * experts
    dense = shapes["dense_layers"]
    per_token = shapes["layers"] * (attention + indexer) \
        + dense * 3 * e * shapes["dense_width"] \
        + (shapes["layers"] - dense) * expert_layer
    attn_flops, attn_bytes = sparse_attention(shapes)
    flops = 2 * (per_token * t["tokens"] + e * shapes["vocab"] * t["emitted"])
    return flops / t["steps"] + attn_flops, attn_bytes
