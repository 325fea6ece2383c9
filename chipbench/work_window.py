"""Operations and bytes of grouped-query attention over a paged KV cache
in two page classes (full layers that keep every row, window layers that
keep the last `window`), and of a whole serving step of such a model, from
what the traced stretch was fed.

Floors that no implementation of this algorithm over this cache can beat,
so a share of them cannot pass 100 %: they read the same work whatever
implements it (one program a row today; a kernel that shares a request's
page trips between its rows later).

`shapes["traced"]` holds the stretch's counts, from the server's own
position counters (`Server.stats()`: `attn_tokens`, `attn_ctx_tokens`,
`attn_window_tokens`; no device read) and the harness's per-step sums over
the decoding rows. Returns (flops, bytes) for ONE step on ONE chip, as
`work.py` does.
"""


def paged_attention(shapes):
    """Operations: a fed row at position q sees q + 1 keys on a full layer
    and min(q + 1, window) on a window layer; for each visible key and
    query head the score and the weighted value, `4 * head_dim`
    operations: `4 * H_l * head_dim` a row and key, summed over the
    layers at their own head counts. `ctx_tokens` is the sum of q + 1 over
    the fed rows, `window_tokens` of min(q + 1, window).

    Bytes: a pass has to bring, for each request it feeds, the KV rows the
    LAST of the request's rows sees, once a layer (its earlier rows see a
    subset on a full layer; on a window layer the rows of one pass see
    at most `window + rows - 1` between them, of which this counts the
    last row's `window`). A decoding request feeds one row, whose context
    the harness knows from lengths (`decode_ctx_tokens`,
    `decode_window_tokens`). The other rows are prompt rows, at most
    `prefill_chunk` of a request in a pass, and the last of them sees no
    fewer keys than their mean: their contexts' sum over `prefill_chunk`
    is a floor under what their passes bring. K and V, `kv_heads *
    head_dim` wide each, `itemsize` bytes."""
    t = shapes["traced"]
    row_ops = 4 * shapes["head_dim"]
    flops = row_ops * (sum(shapes["full_layer_heads"]) * t["ctx_tokens"]
                       + sum(shapes["window_layer_heads"])
                       * t["window_tokens"])
    chunk = shapes["prefill_chunk"]
    full_rows = t["decode_ctx_tokens"] \
        + (t["ctx_tokens"] - t["decode_ctx_tokens"]) / chunk
    window_rows = t["decode_window_tokens"] \
        + (t["window_tokens"] - t["decode_window_tokens"]) / chunk
    row_bytes = 2 * shapes["kv_heads"] * shapes["head_dim"] \
        * shapes["itemsize"]
    nbytes = row_bytes * (len(shapes["full_layer_heads"]) * full_rows
                          + len(shapes["window_layer_heads"]) * window_rows)
    return flops / t["steps"], nbytes / t["steps"]


def serve_step(shapes):
    """The operations of one serving step, for the whole step's share of
    the chip's peak (`step_mfu`), from what the traced stretch was fed. Per
    fed token the matrix products of every layer: the query, gate and
    output projections at the layer's own head count, the key and value
    projections, then the dense feed-forward (the `dense_layers` leading
    layers) or the router, the shared expert and the `experts_per_token`
    routed experts a token is sent to (not the experts it is not sent to,
    whatever an implementation runs); per emitted token the head; and
    `paged_attention`'s operations. Multiply-adds count twice. Bytes:
    `paged_attention`'s alone."""
    t, e, d = shapes["traced"], shapes["hidden"], shapes["head_dim"]
    heads = shapes["full_layer_heads"] + shapes["window_layer_heads"]
    attention = sum(2 * e * h * d + e * h for h in heads) \
        + len(heads) * 2 * e * shapes["kv_heads"] * d
    expert_layer = e * shapes["experts"] + 3 * e * (
        shapes["shared_width"]
        + shapes["experts_per_token"] * shapes["expert_width"])
    dense = shapes["dense_layers"]
    per_token = attention + dense * 3 * e * shapes["dense_width"] \
        + (shapes["layers"] - dense) * expert_layer
    attn_flops, attn_bytes = paged_attention(shapes)
    flops = 2 * (per_token * t["tokens"] + e * shapes["vocab"] * t["emitted"])
    return flops / t["steps"] + attn_flops, attn_bytes
