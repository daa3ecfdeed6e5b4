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

from harness import reference, traffic


class Client:
    __slots__ = ("handle", "request", "submitted", "seen", "last_seen_at",
                 "in_window")


class Driver:
    kind = "serve_closed_loop"

    def __init__(self, ctx):
        self.ctx = ctx
        self.mix = ctx.cell.traffic
        self.cfg = ctx.cell.config
        # everything that depends on the architecture comes from the
        # configuration's family, never from a module named here
        self.family = ctx.family("reference.ServeReference", "counts.serve_flops")
        self.arch = self.family.reference.Arch.from_config(self.cfg)
        self.stream = traffic.RequestStream(self.mix, self.arch.vocab_size,
                                            ctx.seed)
        self.finished = []      # (prompt, served tokens) of the window
        self.ttft, self.gaps = [], []
        self.tokens = 0
        self.attempted = self.failed = 0
        self.measuring = False
        self.prompt_lens, self.decode_rows = [], []
        self.step_ends = []     # the window's steps, each at its end

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
            if self.measuring:
                self.step_ends.append(
                    (now, self.engine.metrics.counters.get("prefill_calls", 0)))
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
        with ctx.span("materialize"):
            tdx.manual_seed(reference.seed31(ctx.seed))
            model = tdx.deferred_init(self.family.constructor(self.cfg))
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
            # the host's part of a step, by the host's clock (the trace's
            # two clocks disagree by 1-2 ms): None where nothing was recorded
            "serve.decode_args_s_p50": m.decode_args_s.quantile(0.5),
            "serve.harvest_s_p50": m.harvest_s.quantile(0.5),
            "serve.schedule_s_total": m.schedule_s.total,
            "serve.prefill_s_total": m.prefill_s.total,
            "serve.flops": self.family.counts.serve_flops(
                self.cfg, self.prompt_lens, self.decode_rows),
            "serve.prompt_lens": list(map(int, self.prompt_lens)),
            "serve.decode_rows_sum": int(sum(self.decode_rows)),
            "serve.requests_finished": len(self.finished),
            "serve.ttft_p95_s": _pct(self.ttft, 95),
        })
        return {
            "attempted": self.attempted, "failed": self.failed,
            "look": self._look(t0, m),
            "end_to_end": {
                "serve_tokens_per_s": self.tokens / self.window_s,
                "ttft_p50_ms": 1e3 * _pct(self.ttft, 50),
                "gap_p95_ms": 1e3 * _pct(self.gaps, 95),
            },
        }

    def _look(self, t0, m, slices=8):
        """Where a run's time went, for whoever has to say why one run read
        apart from the rest: the cycle (end of one ``step()`` to the end of
        the next) of the steps that admitted nothing, by eighth of the
        window, the longest cycles, and the engine's own split of a step.
        Shown in the result line (``window_look``), never compared."""
        if len(self.step_ends) < slices:
            return None
        ends = np.asarray([t0] + [t for t, _ in self.step_ends], np.float64)
        cycles = 1e3 * np.diff(ends)
        at = ends[1:] - t0
        plain = np.diff([0] + [n for _, n in self.step_ends]) == 0
        eighth = np.minimum((at * slices / at[-1]).astype(int), slices - 1)

        def p50_ms(values):
            return round(float(np.median(values)), 3) if len(values) else None

        def hist_ms(h):
            q = h.quantile(0.5)
            return None if q is None else round(1e3 * q, 4)

        return {
            "steps": int(cycles.size), "decode_only_steps": int(plain.sum()),
            "decode_only_cycle_ms_p50_by_eighth": [
                p50_ms(cycles[plain & (eighth == i)]) for i in range(slices)],
            "decode_only_cycle_ms_p50": p50_ms(cycles[plain]),
            "longest_cycles_at_s_ms": [
                [round(float(at[i]), 2), round(float(cycles[i]), 1)]
                for i in np.argsort(cycles)[::-1][:5]],
            "decode_ms_p50": hist_ms(m.decode_s),
            "prefill_ms_p50": hist_ms(m.prefill_s),
            "decode_args_ms_p50": hist_ms(m.decode_args_s),
            "harvest_ms_p50": hist_ms(m.harvest_s),
            "ttft_ms_p25_p50_p75": [round(1e3 * _pct(self.ttft, q), 3)
                                    for q in (25, 50, 75)],
            "requests_submitted": len(self.ttft),
        }

    def after_window(self):
        """Readings that need the live program, taken once the window has
        closed and the memory peak has been read."""
        self.weights_differ = reference.weights_differ(
            self.arch, self.family.reference.leaf_plan(self.arch),
            self.ctx.seed, dict(self.model.named_parameters()))

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
        ref = self.family.reference.ServeReference(self.arch, self.ctx.seed, "f32")
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
