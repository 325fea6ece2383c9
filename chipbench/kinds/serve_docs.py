"""kind `serve_docs`: the closed loop of kind `serve` for questions over
long documents, against a model served from a latent cache with no
indexer (`mxnet_tpu.models.deepseek`): every row attends its whole
context.

A request is a document, which several clients read and the prefix tree
holds, and a short question of its own. Documents sit in `seats`; client c
reads seat c mod seats. Once a seat's document has been asked
`asks_per_document` times the seat's next request brings a new document
(the next serial number), which that request prefills in chunks beside
the decoding rows; until it has its first token the seat's other readers
go on asking about the seat's previous document, and those asks are not
counted. The harness uses what a client sees (its own replies, and that
first token), nothing of the server's inside. Lengths are data: request k
(in order of submission) takes `cycle[k % len(cycle)]` = (question, new
tokens), document n has `doc_cycle[n % len(doc_cycle)]` tokens; `--seed`
makes the weights and the token ids only (a document's ids from the seed
and its serial number), so the composition of scheduler step k is a
function of the cell's files, as in kind `serve` (whose `Client`,
`ClosedLoop` and window protocol, closed by counts, this imports). Set-up
is by counts too: one primer request a seat (the seat's first document and
a few tokens) driven to their end, which leaves the documents' pages in
the tree; the clients; the warm-up steps.

`correct` is decided at the timed sizes from what the timed path itself
produced: the server keeps, for the audited requests (one whose document
comes from the tree, one that brings its own), the float32 logits row
behind every token it emitted (`submit(keep_logits=True)`), and after the
window they are compared with the plain reference's full forward pass
(`chipbench/reference/deepseek_v2.py`) over document + question +
generated tokens. Logits, not tokens. The invariants of kind `serve`
(every request DONE, exact length, ids inside the slice, nothing shed)
hold beside it.
"""
import collections
import functools
import gc
import time

import numpy as np

from chipbench.kinds import serve
from chipbench.kinds.serve import Client, ClosedLoop

FED = ("attn_tokens", "attn_ctx_tokens", "chunk_steps", "token_steps",
       "prompt_tokens", "prefix_tokens")


def model_config(config):
    """The constructor's arguments from the configuration file's own keys:
    the published names as run, the share, the router's full width."""
    from mxnet_tpu.models import deepseek
    keys = {k: config[k] for k in deepseek.DEEPSEEK_V2_PUBLISHED
            if k in config}
    keys.update(n_routed_experts=config["share"]["router_width"],
                experts_held=config["n_routed_experts"],
                first_expert=config["share"]["first_expert"],
                dtype=config["model"]["dtype"])
    return deepseek.deepseek_v2_config(**keys)


def build(ctx):
    """(server, model, model config) on a one-device mesh."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel, serve
    from mxnet_tpu.models import deepseek

    cfg = model_config(ctx.config)
    parallel.make_mesh(devices=ctx.devices[:1])
    model = deepseek.DeepseekForCausalLM(cfg)
    mx.random.seed(ctx.seed)
    model.initialize()
    return serve.Server(model, **ctx.config["server"]), model, cfg


class Reader(Client):
    """A `Client` that knows who sent it and which document it reads."""
    __slots__ = ("client", "serial", "brings")


class Seat:
    """A document and its readers' count of asks; `pending` is the request
    that is bringing the seat's next document, until its first token."""
    __slots__ = ("serial", "asks", "pending")

    def __init__(self, serial, asks):
        self.serial, self.asks, self.pending = serial, asks, None


@functools.lru_cache(maxsize=32)     # a document is read many times
def document(seed, serial, n_tokens, vocab):
    """Document `serial`'s ids: a function of the seed and the serial."""
    return np.random.RandomState([seed, serial]).randint(
        0, vocab, (n_tokens,)).astype(np.int32)


class DocsLoop(ClosedLoop):
    """`ClosedLoop` whose prompts are a seat's document and a question;
    keeps, per step, the server's position counters and what the decoding
    rows read."""

    def __init__(self, srv, traffic, vocab, seed, rng):
        self.doc_cycle = traffic["doc_cycle"]
        self.asks_per_document = traffic["asks_per_document"]
        self.audited, self.seed = set(traffic["audited"]), seed
        n_seats = traffic["seats"]
        self.seats = [Seat(s, 2 * s) for s in range(n_seats)]
        self.next_serial = n_seats
        self.free = collections.deque()     # clients whose request ended
        self.fed = []           # per step: the server's counters, summed
        self.decode_ctx = []    # per step: sum of the decoding rows' contexts
        super().__init__(srv, [tuple(p) for p in traffic["cycle"]],
                         traffic["clients"], vocab, rng)

    def doc(self, serial):
        return document(self.seed, serial,
                        self.doc_cycle[serial % len(self.doc_cycle)],
                        self.vocab)

    def reads(self, seat):
        """(serial the seat's next request reads, whether it brings it)."""
        if seat.pending is not None:
            if not seat.pending.stamps:
                return seat.serial, False       # not there yet, not counted
            seat.serial, seat.pending = seat.pending.serial, None
        if seat.asks >= self.asks_per_document:
            serial, self.next_serial = self.next_serial, self.next_serial + 1
            seat.asks = 1       # the count starts again with the new one
            return serial, True
        seat.asks += 1
        return seat.serial, False

    def submit(self):
        k = len(self.requests)
        client = self.free.popleft() if self.free else k
        seat = self.seats[client % len(self.seats)]
        serial, brings = self.reads(seat)
        n_question, n_new = self.cycle[k % len(self.cycle)]
        question = self.rng.randint(0, self.vocab, (n_question,))
        prompt = np.concatenate([self.doc(serial), question]) \
            .astype(np.int32)
        req = self.srv.submit(prompt, max_new_tokens=n_new, eos=None,
                              temperature=0.0, keep_logits=k in self.audited)
        reader = Reader(req, prompt.size, n_new)
        reader.client, reader.serial, reader.brings = client, serial, brings
        if brings:
            seat.pending = reader
        self.requests.append(reader)
        self.live.append(reader)

    def check(self, client):
        self.free.append(client.client)
        super().check(client)

    def step(self):
        # a request with a token feeds one row this step, its last token:
        # the context that row attends is known from lengths alone
        self.decode_ctx.append(sum(
            c.n_prompt + len(c.req.tokens) for c in self.live
            if c.req.tokens))
        super().step()
        st = self.srv.stats()
        self.fed.append(tuple(st[k] for k in FED))

    def fed_over(self, steps):
        """{counter: its growth over the step range `steps`}."""
        first = self.fed[steps.start - 1] if steps.start else (0,) * len(FED)
        return {k: b - a for k, a, b
                in zip(FED, first, self.fed[steps.stop - 1])}


def prime(srv, prompts, n_new):
    """Drive one request a prompt to its end, all together: the prompts'
    whole pages stay in the prefix tree."""
    from mxnet_tpu import serve
    reqs = [srv.submit(p.astype(np.int32), max_new_tokens=n_new, eos=None,
                       temperature=0.0) for p in prompts]
    while not all(r.done for r in reqs):
        srv.step()
    if any(r.state != serve.DONE for r in reqs):
        raise RuntimeError(f"a primer request did not finish: {reqs!r}")


CONTROLS = (
    ("operands at bf16's mantissa", {"mantissa_bits": 7}),
    ("operands at an fp8 mantissa (3 bits)", {"mantissa_bits": 3}),
    ("mscale^2 left out of the softmax scale", {"softmax_mscale": False}),
    ("the group limit ignored (plain top-k of all experts)",
     {"keys": {"n_group": 1, "topk_group": 1}}),
    ("YaRN off (plain RoPE frequencies)", {"yarn": False}),
    ("the gates times 1", {"keys": {"routed_scaling_factor": 1.0}}))


def audit(ctx, model, cfg, clients):
    """Compare the kept logits of the audited requests with the plain
    reference's forward pass. Returns (ok, lines to say, {name: [number
    compared, its limit]})."""
    from chipbench.reference import deepseek_v2 as reference
    from chipbench.reference.glm5 import relative_errors

    spec, limits = ctx.config["audit"], ctx.config["audit"]["limits"]
    layers, top = model.layer_weights()
    ok, lines, checks = True, [], {}
    # one padded length for all: the reference compiles a layer once
    longest = max(c.req.prompt.size + len(c.req.tokens) for c in clients)
    for n, client in enumerate(clients):
        req = client.req
        seq = np.concatenate([req.prompt, req.tokens[:-1]])
        got = np.stack(req.logits)

        def against(keys=None, **kw):
            return np.asarray(reference.forward(
                seq, layers, top, dict(cfg, **(keys or {})),
                cfg["first_expert"], logits_from=req.prompt.size - 1,
                block=spec["block"], pad_to=longest, **kw))

        t = time.perf_counter()
        want = against()
        err = relative_errors(got, want)
        seconds = time.perf_counter() - t
        med, worst = float(np.median(err)), float(err.max())
        passed = got.shape == want.shape and bool(np.isfinite(got).all()) \
            and med <= limits["median_relative_error"] \
            and worst <= limits["max_relative_error"]
        ok = ok and passed
        checks[f"audit{n}_median_relative_error"] = [
            med, limits["median_relative_error"]]
        checks[f"audit{n}_max_relative_error"] = [
            worst, limits["max_relative_error"]]
        lines.append(
            f"audit request {req.id} (document {client.serial}, "
            f"{'brought' if client.brings else 'from the tree'}): "
            f"{got.shape[0]} positions x {got.shape[1]} logits at contexts "
            f"{req.prompt.size}-{seq.size}; relative error median {med:.6f} "
            f"(limit {limits['median_relative_error']}), 90th percentile "
            f"{float(np.percentile(err, 90)):.6f}, largest {worst:.6f} "
            f"(limit {limits['max_relative_error']}); greedy token equal "
            f"at {float((got.argmax(-1) == want.argmax(-1)).mean()):.4f} "
            f"of positions; reference {seconds:.1f}s: "
            f"{'ok' if passed else 'NOT CORRECT'}")
        if n == 0 and ctx.trace:
            # the readings the limits were set between (PERF.md), against
            # the same float32 reference; the traced run alone pays them
            for label, kw in CONTROLS:
                e = relative_errors(against(**kw), want)
                lines.append(
                    f"  reference with {label}: median "
                    f"{float(np.median(e)):.6f}, 90th percentile "
                    f"{float(np.percentile(e, 90)):.6f}, largest "
                    f"{float(e.max()):.6f}")
    return ok, lines, checks


def stretch_counts(step_fed, decode_ctx, emitted, prefill_chunk):
    """What the traced stretch fed, for `work_mla` and
    `work_latent.serve_step`: the growth of the server's position counters
    over it, the decoding rows' contexts, the tokens it gave back. With no
    indexer every fed row attends its whole context (`sel_tokens` =
    `ctx_tokens`) and no pass reads an indexer key (`row_passes` 0);
    `sel_row_passes` is the cached rows a pass has to bring at least: the
    context of the LAST row of each request and pass, a decoding row's own
    and, of a request's prompt rows (at most `prefill_chunk` a pass), no
    fewer than their mean."""
    def over(key):
        return sum(f[key] for f in step_fed)

    ctx, decode = over("attn_ctx_tokens"), sum(decode_ctx)
    return {
        "steps": len(step_fed), "tokens": over("attn_tokens"),
        "ctx_tokens": ctx, "sel_tokens": ctx, "decode_ctx_tokens": decode,
        "emitted": emitted,
        "passes": over("chunk_steps") + over("token_steps"),
        "row_passes": 0,
        "sel_row_passes": decode + (ctx - decode) / prefill_chunk}


def run(ctx):
    from chipbench import window

    traffic, server_args = ctx.traffic, ctx.config["server"]
    t_build = time.perf_counter()
    srv, model, cfg = build(ctx)
    t_model = time.perf_counter()
    slots, page = server_args["slots"], server_args["page_size"]
    doc_cycle = traffic["doc_cycle"]
    ctx.say(f"{ctx.cell['name']}: DeepSeek-V2 {cfg['num_hidden_layers']}L/"
            f"{cfg['hidden_size']} {cfg['dtype']}, "
            f"{cfg['num_attention_heads']} heads over a latent of "
            f"{cfg['kv_lora_rank']}+{cfg['qk_rope_head_dim']}, experts "
            f"{cfg['first_expert']}..{cfg['first_expert'] + cfg['experts_held']}"
            f" of {cfg['n_routed_experts']} in {cfg['n_group']} groups "
            f"(top {cfg['num_experts_per_tok']} of the best "
            f"{cfg['topk_group']}), vocabulary {cfg['vocab_size']}; "
            f"Server({server_args}); {traffic['clients']} clients in a "
            f"closed loop over {traffic['seats']} documents of "
            f"{min(doc_cycle)}-{max(doc_cycle)} tokens, "
            f"{traffic['asks_per_document']} asks a document")

    rng = np.random.RandomState(ctx.seed)
    n_primer, n_primer_new = traffic["primer"]
    prime(srv, [np.concatenate([
        document(ctx.seed, s, doc_cycle[s % len(doc_cycle)],
                 cfg["vocab_size"]),
        rng.randint(0, cfg["vocab_size"], (n_primer,))])
        for s in range(traffic["seats"])], n_primer_new)
    t_primed = time.perf_counter()

    loop = DocsLoop(srv, traffic, cfg["vocab_size"], ctx.seed, rng)
    win = window.measure(ctx, loop.step, lambda: None,
                         traffic["warmup_steps"], traffic["trace_steps"],
                         serve.by_count(ctx, loop))
    audited = [loop.requests[k] for k in traffic["audited"]]
    drained = 0     # outside every measurement: the audit needs its ends
    while not all(c.req.done for c in audited):
        if drained >= traffic["audit_drain_steps"]:
            raise RuntimeError("the audited requests did not finish")
        loop.step()
        drained += 1
    st = srv.stats()        # before stop() cancels what is in flight
    srv.stop()
    ctx.say(f"set-up {win.setup_s:.1f}s: imports and device "
            f"{t_build - ctx.t_start:.1f}, model and server "
            f"{t_model - t_build:.1f}, the primers' "
            f"{sum(doc_cycle[s % len(doc_cycle)] for s in range(traffic['seats']))}"
            f" document tokens {t_primed - t_model:.1f}, warm-up steps "
            f"{win.t0 - t_primed:.1f}; {drained} steps after the "
            "measurements until the audited requests ended")
    brought = [c for c in loop.requests if c.brings]
    end_to_end, in_window, held = serve.window_numbers(
        ctx, loop, win, f"; executables {st['executables']}; tree hits "
        f"{st['prefix_hits']}, copies on write {st['cow_copies']}; "
        f"documents brought {len(brought)} (serials "
        f"{[c.serial for c in brought][:12]}), tree pages evicted "
        f"{st.get('tree_evicted_pages', 'n/a')}")
    steps = win.steps
    traced = slice(in_window.stop, in_window.stop + win.traced_steps)

    # the pool's arenas go before the reference's temporaries come
    loop.srv = srv = None
    gc.collect()
    agrees, lines, checks = audit(ctx, model, cfg, audited)
    for line in lines:
        ctx.say(line)

    fed = loop.fed_over(in_window)
    shapes = {"layers": cfg["num_hidden_layers"], "slots": slots,
              "page_size": page, "heads": cfg["num_attention_heads"],
              "kv_lora_rank": cfg["kv_lora_rank"],
              "latent_width": cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"],
              "prefill_chunk": server_args["prefill_chunk"], "itemsize": 2,
              # no indexer: what `work_latent` reads of one is nothing
              "index_heads": 0, "index_dim": 0,
              # the rest of what `work_latent.serve_step` reads
              "hidden": cfg["hidden_size"], "q_lora_rank": cfg["q_lora_rank"],
              "qk_nope_head_dim": cfg["qk_nope_head_dim"],
              "v_head_dim": cfg["v_head_dim"],
              "dense_layers": cfg["first_k_dense_replace"],
              "dense_width": cfg["intermediate_size"],
              "expert_width": cfg["moe_intermediate_size"],
              "shared_experts": cfg["n_shared_experts"],
              "experts_per_token": cfg["num_experts_per_tok"],
              "experts_held": cfg["experts_held"],
              "router_width": cfg["n_routed_experts"],
              "vocab": cfg["vocab_size"]}
    if win.traced_steps:
        step_fed = [loop.fed_over(slice(k, k + 1))
                    for k in range(traced.start, traced.stop)]
        shapes["traced"] = stretch_counts(
            step_fed, loop.decode_ctx[traced], sum(loop.tokens_out[traced]),
            server_args["prefill_chunk"])
        ctx.say(f"traced stretch: {shapes['traced']}; per step (wide "
                "passes, narrow passes, rows fed): " + ", ".join(
                    f"({f['chunk_steps']}, {f['token_steps']}, "
                    f"{f['attn_tokens']})" for f in step_fed))
    lost = st["rejected"] + st["shed"] + st["failed"] + st["expired"]
    return {
        "correct": agrees and not loop.failures and lost == 0
        and loop.checked > 0 and not win.overran,
        "attempted": loop.checked,
        "failed": len(loop.failures) + lost + win.overran,
        "checks": dict(checks,
                       requests_not_as_asked=[len(loop.failures), 0],
                       requests_lost=[lost, 0],
                       window_overran=[int(win.overran), 0]),
        "end_to_end": end_to_end,
        "window": held,
        "spans": {"bench.step": loop.step_s[in_window]},
        "counters": {
            "steps": steps,
            "prefill_steps": sum(loop.prefill_steps[in_window]),
            "running_sum": sum(loop.running[in_window]),
            "slot_steps": slots * steps,
            "pages_in_use_sum": sum(loop.pages_in_use[in_window]),
            "page_steps": st["pool_pages_total"] * steps,
            "prompt_tokens": fed["prompt_tokens"],
            "prefix_tokens": fed["prefix_tokens"],
            "attn_tokens": fed["attn_tokens"],
            "documents_brought": len(brought)},
        "composition": loop.composition[in_window],
        "documents": [(c.client, c.serial, c.brings) for c in loop.requests],
        "shapes": shapes,
        "peaks": ctx.peaks,
        **window.trace_result(win),
    }
