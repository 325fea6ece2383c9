"""kind `serve`: a closed loop of clients against serve.Server.

The harness drives the scheduler itself: `srv.step()` in a plain loop in
this thread, and after each step one new request for each that finished.
Nothing here draws a length, keeps a rate, sleeps or starts a thread: the
(prompt, new tokens) pairs are the traffic file's cycle, taken in order of
submission, and `--seed` makes the weights and the token ids only. So the
composition of scheduler step k is a pure function of the cell's files, the
same in every run, and the clock only says when the window closes. Warm-up
is a count of steps of this very loop, so the window opens at the same
point of the sequence every time.
"""
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

    def __init__(self, srv, cycle, clients, vocab, seed):
        self.srv, self.cycle, self.vocab = srv, cycle, vocab
        self.rng = np.random.RandomState(seed)
        self.requests = []      # every Client ever submitted, in order
        self.live = []          # those not finished
        self.checked = 0
        self.failures = []
        self.composition = []   # per step (admitted, finished, running)
        self.prefill_steps = []  # per step: entered with a tokenless request
        self.step_s = []
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
        with TraceAnnotation("bench.refill"):
            for c in self.live:
                c.stamps.extend([now] * (len(c.req.tokens) - len(c.stamps)))
            finished = [c for c in self.live if c.req.done]
            self.live = [c for c in self.live if not c.req.done]
            for c in finished:
                self.check(c)
            st = self.srv.stats()
            self.running.append(st["running"])
            self.pages_in_use.append(
                st["pool_pages_total"] - st["pool_pages_free"])
            self.composition.append(
                (queued - st["queued"], len(finished), st["running"]))
            for _ in finished:
                self.submit()


def run(ctx):
    import jax.numpy as jnp

    from chipbench import stats, window
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
                      ctx.seed)
    win = window.measure(ctx, loop.step, lambda: None,
                         traffic["warmup_steps"], traffic["trace_steps"])
    st = srv.stats()        # before stop() cancels what is in flight
    srv.stop()
    n_warm, steps, t0, t1 = traffic["warmup_steps"], win.steps, win.t0, win.t1
    ctx.say(f"set-up {win.setup_s:.1f}s: imports and device "
            f"{t_build - ctx.t_start:.1f}, model and server "
            f"{t_model - t_build:.1f}, kernel parity "
            f"{t_parity - t_model:.1f}, {n_warm} warm-up steps "
            f"{t0 - t_parity:.1f}")

    def inside(when):
        return t0 < when <= t1

    emitted = sum(inside(s) for c in loop.requests for s in c.stamps)
    gaps = [gap for c in loop.requests for when, gap
            in zip(c.stamps[1:], stats.token_gaps(c.stamps)) if inside(when)]
    ttfts = [(c.req.ttft_s, c.n_prompt) for c in loop.requests
             if c.stamps and inside(c.stamps[0])]
    in_window = slice(n_warm, n_warm + steps)
    comp = loop.composition[in_window]
    ctx.say(f"composition hash over the first {traffic['hash_steps']} "
            f"measured steps: "
            f"{stats.composition_hash(comp[:traffic['hash_steps']])}")
    ctx.say(f"{steps} steps, {emitted} tokens, {len(ttfts)} first tokens, "
            f"{len(gaps)} gaps in {t1 - t0:.3f}s; {loop.checked} requests "
            f"finished and checked, {len(loop.failures)} failed; "
            f"executables {st['executables']}")
    kinds = list(zip(loop.prefill_steps[in_window], loop.step_s[in_window]))
    for label, flag in (("entered with a prefilling request", True),
                        ("decode only", False)):
        ms = [1e3 * s for f, s in kinds if f is flag]
        if ms:
            ctx.say(f"steps {label}: {len(ms)}, median "
                    f"{stats.percentile(ms, 50):.1f} ms")
    for failure in loop.failures[:5]:
        ctx.say(f"  FAILED {failure}")
    lost = st["rejected"] + st["shed"] + st["failed"] + st["expired"]
    return {
        "correct": not loop.failures and lost == 0 and loop.checked > 0,
        "attempted": loop.checked, "failed": len(loop.failures) + lost,
        "end_to_end": {
            "serve_tokens_per_s": emitted / (t1 - t0),
            "token_gap_p95_ms": 1e3 * stats.percentile(gaps, 95),
            "ttft_ms_per_prompt_token": stats.ttft_ms_per_prompt_token(
                [s for s, _ in ttfts], [n for _, n in ttfts]),
            "setup_s": win.setup_s},
        "spans": {"bench.step": loop.step_s[in_window]},
        "counters": {
            "steps": steps,
            "prefill_steps": sum(loop.prefill_steps[in_window]),
            "running_sum": sum(loop.running[in_window]),
            "slot_steps": slots * steps,
            "pages_in_use_sum": sum(loop.pages_in_use[in_window]),
            "page_steps": st["pool_pages_total"] * steps},
        "composition": comp,
        "shapes": {"heads": heads, "head_dim": head_dim,
                   "layers": cfg["num_layers"], "slots": slots},
        "peaks": ctx.peaks,
        **window.trace_result(win),
    }
