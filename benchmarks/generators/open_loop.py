"""Open loop: requests fall due on a schedule, whatever the system does.

``arrivals.rate_per_s`` is fixed in the mix file. Over ``span`` seconds
n = round(rate x span) requests fall due; their gaps are the
exponential distribution's quantiles (a Poisson process with its count
fixed), scaled to fill the span. The warm phase (``warm_s``, part of
set-up) and the window each get a schedule of their own.

Every seed offers the window the same work in the same order: the
order of gaps and lengths is drawn once from the mix's ``order_seed``.
The run's seed draws the tokens (and the weights), and turns the warm
phase's cycle to another starting point. A tail then measures the
system, not how one seed's bursts and long prompts happen to coincide;
what a run's seed changes is what the correctness check looks at.
"""
import numpy as np

from benchmarks.distributions import quantiles, shuffled


class Generator:
    closed = False

    def __init__(self, mix, seed, seconds, vocab):
        rng = np.random.default_rng([int(seed), 0x0B5E])
        order = np.random.default_rng([int(mix["order_seed"]), 0x0B5E])
        rate = float(mix["arrivals"]["rate_per_s"])
        self.rate = rate
        self.warm_s = float(mix["warm_s"])
        self.seconds = float(seconds)
        self.requests = []
        for phase, start, span in (("warm", 0.0, self.warm_s),
                                   ("window", self.warm_s, self.seconds)):
            n = int(round(rate * span))
            if n == 0:
                continue
            turn = int(seed) % n if phase == "warm" else 0
            gaps, plen, olen = (
                np.roll(shuffled(quantiles(spec, n), order), -turn)
                for spec in ({"dist": "exponential", "mean": 1.0},
                             mix["prompt_len"], mix["output_len"]))
            due = start + (np.cumsum(gaps) - gaps) * (span / gaps.sum())
            for i in range(n):
                self.requests.append({
                    "due": float(due[i]), "phase": phase,
                    "prompt": rng.integers(0, vocab, int(plen[i]),
                                           dtype=np.int32),
                    "max_new_tokens": int(olen[i])})
        self._next = 0

    def due(self, now):
        """Requests due at ``now`` (seconds since the generator began)."""
        out = []
        while (self._next < len(self.requests)
               and self.requests[self._next]["due"] <= now):
            out.append(self.requests[self._next])
            self._next += 1
        return out

    def next_due(self):
        return (self.requests[self._next]["due"]
                if self._next < len(self.requests) else None)

    def finished(self, request):
        pass

    def offered(self):
        win = [r for r in self.requests if r["phase"] == "window"]
        return {"rate_per_s": self.rate, "window_requests": len(win),
                "window_prompt_tokens": int(sum(r["prompt"].size
                                                for r in win)),
                "window_output_tokens": int(sum(r["max_new_tokens"]
                                                for r in win))}
