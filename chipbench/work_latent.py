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
