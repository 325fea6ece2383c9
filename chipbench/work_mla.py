"""Operations and bytes of attention over a paged latent cache in absorbed
form with NO indexer (every fed row attends its whole context:
`mxnet_tpu.pallas_ops.paged_latent_attention`), from what the traced
stretch was fed.

A floor that no implementation of this algorithm over this cache can
beat, so a share of it cannot pass 100 %: it reads the same work whatever
implements it (one program a virtual row today; a kernel that shares a
request's page trips between its chunk's rows later).

`shapes["traced"]` holds the stretch's counts, from the server's own
position counters (`Server.stats()`: `attn_ctx_tokens`; no device read)
and the harness's per-step sums over the decoding rows. Returns (flops,
bytes) for ONE step on ONE chip, as `work.py` does.
"""


def paged_latent_attention(shapes):
    """Operations: a fed row at position q sees q + 1 cached rows; for
    each visible row and head the score against the whole latent row
    (`latent_width`) and the weighted sum of its first `kv_lora_rank`
    lanes: `2 * heads * (latent_width + kv_lora_rank)` a row and key,
    times layers. `ctx_tokens` is the sum of q + 1 over the fed rows.

    Bytes: a pass has to bring, for each request it feeds, the cached rows
    the LAST of the request's rows sees, once a layer (its earlier rows
    see a subset), `latent_width * itemsize` each (the arena's lane
    padding is not counted: nothing has to read it). A decoding request
    feeds one row, whose context the harness knows from lengths
    (`decode_ctx_tokens`). The other rows are prompt rows, at most
    `prefill_chunk` of a request in a pass, and the last of them sees no
    fewer rows than their mean: their contexts' sum over `prefill_chunk`
    is a floor under what their passes bring."""
    t = shapes["traced"]
    flops = shapes["layers"] * 2 * shapes["heads"] \
        * (shapes["latent_width"] + shapes["kv_lora_rank"]) * t["ctx_tokens"]
    rows = t["decode_ctx_tokens"] \
        + (t["ctx_tokens"] - t["decode_ctx_tokens"]) / shapes["prefill_chunk"]
    nbytes = shapes["layers"] * shapes["latent_width"] * shapes["itemsize"] \
        * rows
    return flops / t["steps"], nbytes / t["steps"]
