"""kind `serve`: a closed loop of clients against serve.Server.

The harness drives the scheduler itself: `srv.step()` in a plain loop in
this thread, and after each step one new request for each that finished.
Nothing here draws a length, keeps a rate, sleeps or starts a thread: the
(prompt, new tokens) pairs are the traffic file's cycle, taken in order of
submission, and `--seed` makes the weights and the token ids only. So the
composition of scheduler step k is a pure function of the cell's files, the
same in every run, and the clock decides nothing about what is measured
(`window.ByCount`): the warm-up is a count of steps of this very loop and
ends once every request of the opening burst has its first token, the
window closes when `window_tokens_per_s x --seconds` tokens have been
emitted since it opened, and the traced stretch begins at step
`trace_from_step` of the sequence. The clock is read at the window's two
ends and at each step's end, for the token stamps.
"""
import json
import time

import numpy as np


def build(ctx):
    """(server, model config) on a one-device mesh: the server places
    nothing itself and the paged kernel reads the installed mesh."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel, serve
    from mxnet_tpu.models import gpt

    model_cfg = dict(ctx.config["model"])
    family = model_cfg.pop("family")
    if family != "gpt":
        raise ValueError(f"kind serve knows the family 'gpt', not {family!r}")
    cfg = gpt.gpt2_117m_config(**model_cfg)
    parallel.make_mesh(devices=ctx.devices[:1])
    model = gpt.GPTForCausalLM(cfg)
    mx.random.seed(ctx.seed)
    model.initialize()
    return serve.Server(model, **ctx.config["server"]), cfg


class Client:
    """One request from the client's side: what was asked, and when each
    of its tokens was seen (the end of the step that emitted it)."""
    __slots__ = ("req", "n_prompt", "n_new", "stamps")

    def __init__(self, req, n_prompt, n_new):
        self.req, self.n_prompt, self.n_new = req, n_prompt, n_new
        self.stamps = []


class ClosedLoop:
    """`clients` requests in flight; what each step did, as counts."""

    def __init__(self, srv, cycle, clients, vocab, rng):
        self.srv, self.cycle, self.vocab, self.rng = srv, cycle, vocab, rng
        self.opening = clients  # the burst submitted before the first step
        self.emitted = 0        # tokens seen so far, over all requests
        self.tokens_out = []    # per step: tokens it emitted
        self.wide = []          # per step: dispatches of the wide executable
        self.wide_so_far = srv.stats()["chunk_steps"]
        self.requests = []      # every Client ever submitted, in order
        self.live = []          # those not finished
        self.checked = 0
        self.failures = []
        self.composition = []   # per step (admitted, finished, running)
        self.prefill_steps = []  # per step: entered with a tokenless request
        self.step_s = []
        self.step_end = []      # per step: the clock when it returned
        self.running = []
        self.pages_in_use = []
        for _ in range(clients):
            self.submit()

    def submit(self):
        n_prompt, n_new = self.cycle[len(self.requests) % len(self.cycle)]
        prompt = self.rng.randint(0, self.vocab, (n_prompt,))
        req = self.srv.submit(prompt.astype(np.int32), max_new_tokens=n_new,
                              eos=None, temperature=0.0)
        client = Client(req, n_prompt, n_new)
        self.requests.append(client)
        self.live.append(client)

    def opening_has_first_tokens(self):
        """Whether the warm-up may end: a first token counts from `submit`,
        so one of the opening burst that fell inside the window would carry
        set-up and compiles into the window's TTFT."""
        return all(c.stamps for c in self.requests[:self.opening])

    def check(self, client):
        from mxnet_tpu import serve
        self.checked += 1
        req = client.req
        if req.state != serve.DONE or len(req.tokens) != client.n_new \
                or not all(0 <= t < self.vocab for t in req.tokens):
            self.failures.append(repr(req))

    def step(self):
        """One scheduler step, then the clients' side of it. The one call
        site of `srv.step()`: warm-up, window and traced stretch all go
        through here (a Mosaic kernel's cache key holds the Python stack
        that traced it)."""
        from jax.profiler import TraceAnnotation
        tokenless = [c for c in self.live if not c.stamps]
        queued = sum(c.req.queue_wait_s is None for c in tokenless)
        self.prefill_steps.append(bool(tokenless))
        t = time.perf_counter()
        with TraceAnnotation("bench.step"):
            self.srv.step()
        now = time.perf_counter()
        self.step_s.append(now - t)
        self.step_end.append(now)
        with TraceAnnotation("bench.refill"):
            before = self.emitted
            for c in self.live:
                new = len(c.req.tokens) - len(c.stamps)
                c.stamps.extend([now] * new)
                self.emitted += new
            self.tokens_out.append(self.emitted - before)
            finished = [c for c in self.live if c.req.done]
            self.live = [c for c in self.live if not c.req.done]
            for c in finished:
                self.check(c)
            st = self.srv.stats()
            self.running.append(st["running"])
            self.wide.append(st["chunk_steps"] - self.wide_so_far)
            self.wide_so_far = st["chunk_steps"]
            self.pages_in_use.append(
                st["pool_pages_total"] - st["pool_pages_free"])
            self.composition.append(
                (queued - st["queued"], len(finished), st["running"]))
            for _ in finished:
                self.submit()


def by_count(ctx, loop):
    """What closes this cell's window, from its traffic file."""
    from chipbench import window
    traffic = ctx.traffic
    return window.ByCount(
        work=lambda: loop.emitted,
        target=round(traffic["window_tokens_per_s"] * ctx.seconds),
        ready=loop.opening_has_first_tokens,
        trace_from=traffic["trace_from_step"])


def window_numbers(ctx, loop, win, more=""):
    """(end-to-end metrics, the window's slice of the loop's per-step lists,
    what the window held as counts), said as they are found. Everything is
    taken over the whole window: every token, gap and first token whose
    stamp lies inside it."""
    from chipbench import stats
    steps, t0, t1 = win.steps, win.t0, win.t1

    def inside(when):
        return t0 < when <= t1

    emitted = sum(inside(s) for c in loop.requests for s in c.stamps)
    gaps = [gap for c in loop.requests for when, gap
            in zip(c.stamps[1:], stats.token_gaps(c.stamps)) if inside(when)]
    ttfts = [(c.req.ttft_s, c.n_prompt) for c in loop.requests
             if c.stamps and inside(c.stamps[0])]
    in_window = slice(win.warmup_steps, win.warmup_steps + steps)
    comp = loop.composition[in_window]
    wide = loop.wide[in_window]
    held = {"warmup_steps": win.warmup_steps, "steps": steps,
            "tokens": emitted, "first_tokens": len(ttfts),
            "gaps": len(gaps), "wide_steps": sum(w > 0 for w in wide),
            "hash": stats.composition_hash(
                row + (w,) for row, w in zip(comp, wide))}
    if win.traced_steps:
        traced = loop.wide[in_window.stop:in_window.stop + win.traced_steps]
        held["stretch"] = {
            "from_step": in_window.stop,
            "wide_steps": sum(w > 0 for w in traced),
            "token_steps": sum(w == 0 for w in traced)}
        ctx.say(f"traced stretch: {held['stretch']}")
    first = ctx.traffic["hash_steps"]
    ctx.say(f"warm-up took {win.warmup_steps} steps (the file's "
            f"{ctx.traffic['warmup_steps']}, then until the opening burst "
            "had its first tokens)")
    ctx.say(f"composition hash over the whole window: {held['hash']}; over "
            f"the first {first} measured steps: "
            f"{stats.composition_hash(comp[:first])}")
    ctx.say(f"{steps} steps ({held['wide_steps']} wide), {emitted} tokens, "
            f"{len(ttfts)} first tokens, {len(gaps)} gaps in {t1 - t0:.3f}s; "
            f"{loop.checked} requests finished and checked, "
            f"{len(loop.failures)} failed{more}")
    if win.overran:
        ctx.say(f"NOT CORRECT: the window was closed by the clock after "
                f"{t1 - t0:.1f}s, over twice --seconds, with "
                f"{emitted} tokens emitted")
    ms = [1e3 * s for s in loop.step_s[in_window]]
    median = stats.percentile(ms, 50)
    for label, flag in (("wide", True), ("token", False)):
        of_kind = [m for m, w in zip(ms, wide) if (w > 0) is flag]
        if of_kind:
            ctx.say(f"{label} steps: {len(of_kind)}, median "
                    f"{stats.percentile(of_kind, 50):.3f} ms")
    long = [(k, m) for k, m in enumerate(ms) if m > 3 * median]
    ctx.say(f"steps over three times the median ({median:.3f} ms): "
            f"{len(long)}, {sum(m for _, m in long):.1f} ms of the window; "
            "the longest (step: ms): " + ", ".join(
                f"{k}: {m:.1f}" for k, m
                in sorted(long, key=lambda e: -e[1])[:6]))
    for failure in loop.failures[:5]:
        ctx.say(f"  FAILED {failure}")
    end_to_end = {
        "serve_tokens_per_s": emitted / (t1 - t0),
        "token_gap_p95_ms": 1e3 * stats.percentile(gaps, 95),
        "ttft_ms_per_prompt_token": stats.ttft_ms_per_prompt_token(
            [s for s, _ in ttfts], [n for _, n in ttfts]),
        "setup_s": win.setup_s}
    if ctx.dump_steps:
        dump_steps(ctx.dump_steps, loop, win, end_to_end)
    return end_to_end, in_window, held


def dump_steps(path, loop, win, end_to_end):
    """Write what every step of the run did and when (`--dump-steps`): the
    raw material for asking where a spread between runs comes from. Times
    are seconds since the window opened; a token is the index of the step
    that emitted it."""
    index = {when: k for k, when in enumerate(loop.step_end)}
    with open(path, "w") as f:
        json.dump({
            "warmup_steps": win.warmup_steps, "window_steps": win.steps,
            "window_s": win.t1 - win.t0, "end_to_end": end_to_end,
            "step_s": loop.step_s,
            "step_end_s": [when - win.t0 for when in loop.step_end],
            "wide_dispatches": loop.wide,
            "requests": [
                {"n_prompt": c.n_prompt, "n_new": c.n_new,
                 "ttft_s": c.req.ttft_s if c.stamps else None,
                 "token_steps": [index[when] for when in c.stamps]}
                for c in loop.requests]}, f)


def run(ctx):
    import jax.numpy as jnp

    from chipbench import window
    from tools import tpu_validate

    traffic, server_args = ctx.traffic, ctx.config["server"]
    t_build = time.perf_counter()
    srv, cfg = build(ctx)
    t_model = time.perf_counter()
    slots, page = server_args["slots"], server_args["page_size"]
    heads = cfg["num_heads"]
    head_dim = cfg["units"] // heads
    cycle = [tuple(pair) for pair in traffic["cycle"]]
    bucket = min(b for b in server_args["buckets"]
                 if b >= max(p + n for p, n in cycle))
    ctx.say(f"{ctx.cell['name']}: GPT {cfg['num_layers']}L/{cfg['units']} "
            f"{cfg['dtype']}; Server({server_args}); {traffic['clients']} "
            f"clients in a closed loop over a cycle of {len(cycle)} "
            f"(prompt, new) pairs; reachable bucket {bucket}")
    tpu_validate.paged_parity(
        B=slots, H=heads, D=head_dim, page_size=page, n_pg=bucket // page,
        dtype=jnp.dtype(cfg["dtype"]), expect_kernel=not ctx.rehearsal)

    t_parity = time.perf_counter()
    loop = ClosedLoop(srv, cycle, traffic["clients"], cfg["vocab_size"],
                      np.random.RandomState(ctx.seed))
    win = window.measure(ctx, loop.step, lambda: None,
                         traffic["warmup_steps"], traffic["trace_steps"],
                         by_count(ctx, loop))
    st = srv.stats()        # before stop() cancels what is in flight
    srv.stop()
    ctx.say(f"set-up {win.setup_s:.1f}s: imports and device "
            f"{t_build - ctx.t_start:.1f}, model and server "
            f"{t_model - t_build:.1f}, kernel parity "
            f"{t_parity - t_model:.1f}, warm-up steps "
            f"{win.t0 - t_parity:.1f}")
    end_to_end, in_window, held = window_numbers(
        ctx, loop, win, f"; executables {st['executables']}")
    lost = st["rejected"] + st["shed"] + st["failed"] + st["expired"]
    steps = win.steps
    return {
        "correct": not loop.failures and lost == 0 and loop.checked > 0
        and not win.overran,
        "attempted": loop.checked,
        "failed": len(loop.failures) + lost + win.overran,
        "checks": {"requests_not_as_asked": [len(loop.failures), 0],
                   "requests_lost": [lost, 0],
                   "window_overran": [int(win.overran), 0]},
        "end_to_end": end_to_end,
        "window": held,
        "spans": {"bench.step": loop.step_s[in_window]},
        "counters": {
            "steps": steps,
            "prefill_steps": sum(loop.prefill_steps[in_window]),
            "running_sum": sum(loop.running[in_window]),
            "slot_steps": slots * steps,
            "pages_in_use_sum": sum(loop.pages_in_use[in_window]),
            "page_steps": st["pool_pages_total"] * steps},
        "composition": loop.composition[in_window],
        "shapes": {"heads": heads, "head_dim": head_dim,
                   "layers": cfg["num_layers"], "slots": slots},
        "peaks": ctx.peaks,
        **window.trace_result(win),
    }
