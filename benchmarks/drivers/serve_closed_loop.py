"""Driver kind ``serve_closed_loop``: ``deferred_init`` ->
``materialize_module`` -> ``ServeEngine`` driven through ``submit`` /
``step`` by as many clients as the mix says, each submitting its next
request the moment its last one finishes.

Every time is the benchmark's own clock, read when ``step()`` returns:
that is when a caller can first see what the step produced.  Tokens one
step delivers share its time."""

from __future__ import annotations

import gc
import time

import numpy as np

from harness import counts, reference, traffic


class Client:
    __slots__ = ("handle", "request", "submitted", "seen", "last_seen_at",
                 "in_window")


class Driver:
    kind = "serve_closed_loop"

    def __init__(self, ctx):
        self.ctx = ctx
        self.mix = ctx.cell.traffic
        self.cfg = ctx.cell.config
        self.arch = reference.Arch.from_config(self.cfg)
        self.stream = traffic.RequestStream(self.mix, self.arch.vocab_size,
                                            ctx.seed)
        self.finished = []      # (prompt, served tokens) of the window
        self.ttft, self.gaps = [], []
        self.tokens = 0
        self.attempted = self.failed = 0
        self.measuring = False
        self.prompt_lens, self.decode_rows = [], []

    # -- the loop -------------------------------------------------------------

    def _submit(self, client: Client, now: float):
        req = self.stream.next()
        client.request = req
        client.handle = self.engine.submit(
            req["prompt"], max_new_tokens=req["max_new_tokens"],
            temperature=req["temperature"])
        client.submitted = now
        client.seen = 0
        client.last_seen_at = now
        client.in_window = self.measuring
        if self.measuring:
            self.attempted += 1

    def _observe(self, now: float, resubmit: bool):
        """After a step: what each client can now see."""
        for c in self.clients:
            if c.handle is None:
                continue
            # RequestHandle has no public progress accessor: its request
            # object's ``generated`` list is read here and nowhere else
            produced = c.handle._request.generated
            n = len(produced)
            new = n - c.seen
            if new and self.measuring:
                first = c.seen == 0
                if first:
                    self.prompt_lens.append(c.request["prompt"].size)
                    if c.in_window:
                        self.ttft.append(now - c.submitted)
                decoded = new - 1 if first else new
                for j in range(decoded):
                    # tokens one step delivers share its time
                    gap = 0.0 if (first or j) else now - c.last_seen_at
                    self.gaps.append(gap)
                    self.decode_rows.append(
                        c.request["prompt"].size + n - decoded + j)
                self.tokens += new
            if new:
                c.seen, c.last_seen_at = n, now
            if c.handle.done():
                res = c.handle.result()
                bad = res.truncated or res.tokens.size != c.request["max_new_tokens"]
                if c.in_window:
                    self.failed += int(bad)
                    if not bad:
                        self.finished.append((c.request["prompt"], res.tokens))
                c.handle = None
                if resubmit:
                    self._submit(c, now)

    def _loop(self, until, resubmit_until=None):
        """Step until ``until()``; returns the time of the last step's end."""
        now = time.monotonic()
        while not until(now):
            self.engine.step()
            now = time.monotonic()
            self.ctx.tick(now)
            self._observe(now, resubmit_until is None or now < resubmit_until)
        return now

    # -- set-up ---------------------------------------------------------------

    def setup(self):
        ctx = self.ctx
        with ctx.span("import"):
            import jax

            import torchdistx_tpu as tdx
            from torchdistx_tpu.serve import ServeEngine

            family = ctx.family()
        with ctx.span("materialize"):
            tdx.manual_seed(reference.seed31(ctx.seed))
            model = tdx.deferred_init(family.constructor(self.cfg))
            tdx.materialize_module(model)
            jax.block_until_ready([p for _, p in model.named_parameters()])
        opts = dict(self.mix["engine"])
        opts["prefill_buckets"] = tuple(opts["prefill_buckets"])
        with ctx.span("build_engine"):
            self.engine = ServeEngine(model, **opts)
        self.model = model
        n_clients = int(self.mix["clients"])
        with ctx.span("warm_up"):
            # every program the window can reach, each past its second
            # call (a donated cache comes back in the executable's layout)
            rs = np.random.RandomState(reference.seed31(ctx.seed) ^ 0x5EED)
            for _ in range(2):
                for b in opts["prefill_buckets"]:
                    n = min(b, opts["max_len"] - 3)
                    self.engine.submit(
                        rs.randint(0, self.arch.vocab_size, n).astype(np.int32),
                        max_new_tokens=3)
                while self.engine.step():
                    pass
        with ctx.span("slot_fill"):
            # until every slot is busy and one mean request time has
            # passed: the window opens on slots at mixed depths
            self.clients = [Client() for _ in range(n_clients)]
            now = time.monotonic()
            for c in self.clients:
                c.handle = None
                self._submit(c, now)
            done_before = len(self.engine.finished_requests())
            self._loop(lambda now: (
                len(self.engine.finished_requests()) - done_before >= n_clients))
        self.engine.reset_metrics()

    # -- the measured window ------------------------------------------------

    def window(self, seconds: float):
        eng = self.engine
        t0 = time.monotonic()
        self.measuring = True
        for c in self.clients:   # carried over: tokens count, their TTFT does not
            c.last_seen_at = t0
        self.ctx.window_opened(t0)
        t_end = t0 + seconds
        t1 = self._loop(lambda now: now >= t_end, resubmit_until=t_end)
        self.measuring = False
        self.window_s = t1 - t0
        m = eng.metrics
        cnt = dict(m.counters)
        self.ctx.counters.update({
            "serve.window_s": self.window_s,
            "serve.tokens": self.tokens,
            "serve.host_syncs": cnt.get("host_syncs", 0),
            "serve.tokens_generated": cnt.get("tokens_generated", 0),
            "serve.decode_s_p50": m.decode_s.quantile(0.5),
            "serve.prefill_s_p50": m.prefill_s.quantile(0.5),
            "serve.decode_dispatches": cnt.get("decode_dispatches", 0),
            "serve.prefill_calls": cnt.get("prefill_calls", 0),
            "serve.flops": counts.serve_flops(self.cfg, self.prompt_lens,
                                              self.decode_rows),
            "serve.prompt_lens": list(map(int, self.prompt_lens)),
            "serve.decode_rows_sum": int(sum(self.decode_rows)),
            "serve.requests_finished": len(self.finished),
            "serve.ttft_p95_s": _pct(self.ttft, 95),
        })
        return {
            "attempted": self.attempted, "failed": self.failed,
            "end_to_end": {
                "serve_tokens_per_s": self.tokens / self.window_s,
                "ttft_p50_ms": 1e3 * _pct(self.ttft, 50),
                "gap_p95_ms": 1e3 * _pct(self.gaps, 95),
            },
        }

    def after_window(self):
        """Readings that need the live program, taken once the window has
        closed and the memory peak has been read."""
        self.weights_differ = reference.weights_differ(
            self.arch, self.ctx.seed, dict(self.model.named_parameters()))

    def free(self):
        self.engine = self.model = self.clients = None
        gc.collect()

    # -- correct ----------------------------------------------------------------

    def sample(self):
        """The finished requests the reference follows: the longest and
        others drawn from the seed, as padded whole sequences."""
        k = int(self.mix["check_requests"])
        if not self.finished:
            return None
        order = np.random.RandomState(
            reference.seed31(self.ctx.seed) ^ 0xC0DE).permutation(len(self.finished))
        longest = max(range(len(self.finished)),
                      key=lambda i: self.finished[i][0].size + self.finished[i][1].size)
        picks = [longest] + [int(i) for i in order if i != longest][: k - 1]
        width = int(self.mix["check_width"])
        seqs = np.zeros((k, width), np.int32)
        lens = []
        for row, i in enumerate(picks):
            prompt, served = self.finished[i]
            total = prompt.size + served.size
            seqs[row, : prompt.size] = prompt
            seqs[row, prompt.size:total] = served
            lens.append((prompt.size, total))
        for row in range(len(picks), k):   # fewer finished than asked: repeat
            seqs[row] = seqs[0]
            lens.append(lens[0])
        return seqs, lens

    def check(self, verdict):
        lim = self.ctx.cell.limits
        verdict.add("weights_differ", self.weights_differ, 0,
                    "leaves not bit for bit what the seed's rule makes")
        picked = self.sample()
        if picked is None:
            verdict.add("requests_finished", float("inf"), 0, "none finished")
            return
        seqs, lens = picked
        ref = reference.ServeReference(self.arch, self.ctx.seed, "f32")
        gaps, _ = reference.served_gaps(ref, seqs, lens)
        served = sum(gaps["tokens"])
        note = f"{len(lens)} requests, {served} served tokens"
        verdict.add("logit_gap", max(gaps["max"]), lim["logit_gap"], note)
        verdict.add("logit_gap_mean", sum(gaps["sum"]) / served,
                    lim["logit_gap_mean"], note)


def _pct(values, q):
    if not len(values):
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))
