"""kind `serve_agent`: the closed loop of kind `serve` for agent traffic
over a model served from a latent cache (`mxnet_tpu.models.glm`).

Every request's prompt is one prefix shared by the whole run, which the
prefix tree holds, and a short suffix of its own; the (suffix, new
tokens) pairs are the traffic file's cycle in order of submission and
`--seed` makes the weights and the token ids only, so the composition of
scheduler step k is a function of the cell's files, as in kind `serve`
(whose `Client`, `ClosedLoop` and window protocol, closed by counts, this
imports). Set-up is by counts too: one primer request (prefix + a few
tokens) driven to its end, which leaves the prefix's pages in the tree;
the clients; the warm-up steps.

`correct` is decided at the timed sizes from what the timed path itself
produced: the server keeps, for the audited requests, the float32 logits
row behind every token it emitted (`submit(keep_logits=True)`), and after
the window they are compared with the plain reference's full forward
pass (`chipbench/reference/glm5.py`) over prefix + suffix + generated
tokens. Logits, not tokens. The invariants of kind `serve` (every request
DONE, exact length, ids inside the slice, nothing shed) hold beside it.
"""
import gc
import time

import numpy as np

from chipbench.kinds import serve
from chipbench.kinds.serve import Client, ClosedLoop

FED = ("attn_tokens", "attn_ctx_tokens", "attn_sel_tokens", "sparse_tokens",
       "chunk_steps", "token_steps", "prompt_tokens", "prefix_tokens")


def model_config(config):
    """The constructor's arguments from the configuration file's own keys:
    the published names as run, the share, the router's full width."""
    from mxnet_tpu.models import glm
    keys = {k: config[k] for k in glm.GLM5_PUBLISHED if k in config}
    keys.update(rope_theta=config["rope_parameters"]["rope_theta"],
                n_routed_experts=config["share"]["router_width"],
                experts_held=config["n_routed_experts"],
                first_expert=config["share"]["first_expert"],
                dtype=config["model"]["dtype"])
    return glm.glm5_config(**keys)


def build(ctx):
    """(server, model, model config) on a one-device mesh."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel, serve
    from mxnet_tpu.models import glm

    cfg = model_config(ctx.config)
    parallel.make_mesh(devices=ctx.devices[:1])
    model = glm.GLMForCausalLM(cfg)
    mx.random.seed(ctx.seed)
    model.initialize()
    return serve.Server(model, **ctx.config["server"]), model, cfg


class AgentLoop(ClosedLoop):
    """`ClosedLoop` whose prompts are the shared prefix and a suffix of
    the request's own; keeps, per step, the server's position counters
    and the rows the cache holds."""

    def __init__(self, srv, cycle, clients, vocab, rng, prefix, audited):
        self.prefix, self.audited = prefix, set(audited)
        self.fed = []           # per step: the server's counters, summed
        self.cached_rows = []   # per step: distinct rows in the cache
        super().__init__(srv, cycle, clients, vocab, rng)

    def submit(self):
        k = len(self.requests)
        n_suffix, n_new = self.cycle[k % len(self.cycle)]
        suffix = self.rng.randint(0, self.vocab, (n_suffix,))
        prompt = np.concatenate([self.prefix, suffix]).astype(np.int32)
        req = self.srv.submit(prompt, max_new_tokens=n_new, eos=None,
                              temperature=0.0, keep_logits=k in self.audited)
        client = Client(req, prompt.size, n_new)
        self.requests.append(client)
        self.live.append(client)

    def step(self):
        # rows cached when the step begins: the prefix once, and what each
        # live request has written of its own
        own = sum(c.n_prompt - self.prefix.size + len(c.req.tokens)
                  for c in self.live if c.req.queue_wait_s is not None)
        self.cached_rows.append(self.prefix.size + own)
        super().step()
        st = self.srv.stats()
        self.fed.append(tuple(st[k] for k in FED))

    def fed_over(self, steps):
        """{counter: its growth over the step range `steps`}."""
        first = self.fed[steps.start - 1] if steps.start else (0,) * len(FED)
        return {k: b - a for k, a, b
                in zip(FED, first, self.fed[steps.stop - 1])}


def prime(srv, prompt, n_new):
    """Drive one request to its end: its prompt's whole pages stay in the
    prefix tree."""
    from mxnet_tpu import serve
    req = srv.submit(prompt.astype(np.int32), max_new_tokens=n_new, eos=None,
                     temperature=0.0)
    while not req.done:
        srv.step()
    if req.state != serve.DONE:
        raise RuntimeError(f"the primer request did not finish: {req!r}")


def audit(ctx, model, cfg, clients):
    """Compare the kept logits of the audited requests with the plain
    reference's forward pass. Returns (ok, lines to say, {name: [number
    compared, its limit]})."""
    from chipbench.reference import glm5 as reference

    spec, limits = ctx.config["audit"], ctx.config["audit"]["limits"]
    layers, top = model.layer_weights()
    ok, lines, checks = True, [], {}
    # one padded length for all: the reference compiles a layer once
    longest = max(c.req.prompt.size + len(c.req.tokens) for c in clients)
    for n, client in enumerate(clients):
        req = client.req
        seq = np.concatenate([req.prompt, req.tokens[:-1]])
        got = np.stack(req.logits)

        def against(**kw):
            want = reference.forward(
                seq, layers, top, dict(cfg, **kw.pop("keys", {})),
                cfg["first_expert"], logits_from=req.prompt.size - 1,
                block=spec["block"], pad_to=longest, **kw)
            return np.asarray(want)

        t = time.perf_counter()
        want = against()
        err = reference.relative_errors(got, want)
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
            f"audit request {req.id}: {got.shape[0]} positions x "
            f"{got.shape[1]} logits at contexts {req.prompt.size}-"
            f"{seq.size}; relative error median {med:.6f} (limit "
            f"{limits['median_relative_error']}), 90th percentile "
            f"{float(np.percentile(err, 90)):.6f}, largest {worst:.6f} "
            f"(limit {limits['max_relative_error']}); greedy token equal "
            f"at {float((got.argmax(-1) == want.argmax(-1)).mean()):.4f} "
            f"of positions; reference {seconds:.1f}s: "
            f"{'ok' if passed else 'NOT CORRECT'}")
        if n == 0 and ctx.trace:
            # the readings the limits were set between (PERF.md), against
            # the same float32 reference; the traced run alone pays them
            for label, kw in (
                    ("operands at bf16's mantissa", {"mantissa_bits": 7}),
                    ("operands at an fp8 mantissa (3 bits)",
                     {"mantissa_bits": 3}),
                    ("index_topk halved",
                     {"keys": {"index_topk": cfg["index_topk"] // 2}}),
                    ("every query's last pick flipped",
                     {"flip_boundary": True})):
                e = reference.relative_errors(against(**kw), want)
                lines.append(
                    f"  reference with {label}: median "
                    f"{float(np.median(e)):.6f}, largest {float(e.max()):.6f}")
    return ok, lines, checks


def stretch_counts(step_fed, cached_rows, index_topk, emitted):
    """What the traced stretch read and fed, for `work_latent`: per step
    the growth of the server's counters (`step_fed`) and the rows the cache
    held when the step began. A pass is one dispatch of either executable,
    the wide one or the `slots`-wide one: each reads every cached indexer
    key once and at least the `index_topk` latent rows one query selects.
    `emitted` is the tokens the stretch gave back (one head row each)."""
    passes = [f["chunk_steps"] + f["token_steps"] for f in step_fed]

    def over(key):
        return sum(f[key] for f in step_fed)

    return {
        "steps": len(step_fed), "tokens": over("attn_tokens"),
        "ctx_tokens": over("attn_ctx_tokens"),
        "sel_tokens": over("attn_sel_tokens"),
        "sparse_tokens": over("sparse_tokens"), "emitted": emitted,
        "passes": sum(passes),
        "row_passes": sum(p * r for p, r in zip(passes, cached_rows)),
        "sel_row_passes": sum(p * min(r, index_topk)
                              for p, r in zip(passes, cached_rows))}


def run(ctx):
    from chipbench import window

    traffic, server_args = ctx.traffic, ctx.config["server"]
    t_build = time.perf_counter()
    srv, model, cfg = build(ctx)
    t_model = time.perf_counter()
    slots, page = server_args["slots"], server_args["page_size"]
    cycle = [tuple(pair) for pair in traffic["cycle"]]
    n_prefix = traffic["prefix_tokens"]
    ctx.say(f"{ctx.cell['name']}: GLM {cfg['num_hidden_layers']}L/"
            f"{cfg['hidden_size']} {cfg['dtype']}, experts "
            f"{cfg['first_expert']}..{cfg['first_expert'] + cfg['experts_held']}"
            f" of {cfg['n_routed_experts']}, vocabulary {cfg['vocab_size']}; "
            f"Server({server_args}); {traffic['clients']} clients in a "
            f"closed loop over a cycle of {len(cycle)} (suffix, new) pairs "
            f"behind a shared prefix of {n_prefix} tokens")

    rng = np.random.RandomState(ctx.seed)
    prefix = rng.randint(0, cfg["vocab_size"], (n_prefix,))
    n_primer, n_primer_new = traffic["primer"]
    prime(srv, np.concatenate(
        [prefix, rng.randint(0, cfg["vocab_size"], (n_primer,))]),
        n_primer_new)
    t_primed = time.perf_counter()

    loop = AgentLoop(srv, cycle, traffic["clients"], cfg["vocab_size"], rng,
                     prefix, traffic["audited"])
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
            f"{t_model - t_build:.1f}, the primer's "
            f"{n_prefix + n_primer} prompt tokens {t_primed - t_model:.1f}, "
            f"warm-up steps {win.t0 - t_primed:.1f}; {drained} steps "
            "after the measurements until the audited requests ended")
    end_to_end, in_window, held = serve.window_numbers(
        ctx, loop, win, f"; executables {st['executables']}; tree hits "
        f"{st['prefix_hits']}, copies on write {st['cow_copies']}")
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
              "index_heads": cfg["index_n_heads"],
              "index_dim": cfg["index_head_dim"],
              "index_topk": cfg["index_topk"], "itemsize": 2,
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
            step_fed, loop.cached_rows[traced], cfg["index_topk"],
            sum(loop.tokens_out[traced]))
        ctx.say(f"traced stretch: {shapes['traced']}; per step (wide "
                "passes, token passes, cached rows): " + ", ".join(
                    f"({f['chunk_steps']}, {f['token_steps']}, {r})"
                    for f, r in zip(step_fed, loop.cached_rows[traced])))
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
            "sparse_tokens": fed["sparse_tokens"],
            "attn_tokens": fed["attn_tokens"]},
        "composition": loop.composition[in_window],
        "shapes": shapes,
        "peaks": ctx.peaks,
        **window.trace_result(win),
    }
