"""Open loop whose arrivals come in bursts: the rate switches on and off
in a fixed cycle, whatever the system does.

``arrivals`` in the mix file: ``cycle_s`` seconds of which the first
``on_s`` run at ``on_rate_per_s`` and the rest at ``off_rate_per_s``.
The warm phase (``warm_s``, part of set-up) and the window each start a
cycle of their own at its "on" part; a span that ends inside a cycle
cuts it there. Within each part of a cycle n = round(rate x its
seconds) requests fall due; their gaps are the exponential
distribution's quantiles (a Poisson process with its count fixed),
scaled to fill that part, so no request falls due outside the part it
was counted in.

Every seed offers the window the same work in the same order, as
``open_loop`` does: the order of gaps and lengths is drawn once from
the mix's ``order_seed``; the lengths are one quantile grid over the
whole span, so bursts and lulls draw from the same distribution. The
run's seed draws the tokens (and the weights), and turns the warm
phase's lengths to another starting point.
"""
import numpy as np

from benchmarks.distributions import quantiles, shuffled


def phases(arrivals, span):
    """[(start, seconds, rate)] of the on and off parts that cover
    ``span`` seconds, in order."""
    cycle, on = float(arrivals["cycle_s"]), float(arrivals["on_s"])
    rates = (float(arrivals["on_rate_per_s"]),
             float(arrivals["off_rate_per_s"]))
    out, t = [], 0.0
    while t < span:
        for length, rate in ((on, rates[0]), (cycle - on, rates[1])):
            length = min(length, span - t)
            if length > 0:
                out.append((t, length, rate))
            t += length
    return out


class Generator:
    closed = False

    def __init__(self, mix, seed, seconds, vocab):
        rng = np.random.default_rng([int(seed), 0x0B5E])
        order = np.random.default_rng([int(mix["order_seed"]), 0x0B5E])
        arrivals = mix["arrivals"]
        self.warm_s = float(mix["warm_s"])
        self.seconds = float(seconds)
        self.requests = []
        for phase, start, span in (("warm", 0.0, self.warm_s),
                                   ("window", self.warm_s, self.seconds)):
            parts = [(t, length, int(round(rate * length)))
                     for t, length, rate in phases(arrivals, span)]
            n = sum(k for _, _, k in parts)
            if n == 0:
                continue
            turn = int(seed) % n if phase == "warm" else 0
            plen, olen = (np.roll(shuffled(quantiles(spec, n), order),
                                  -turn)
                          for spec in (mix["prompt_len"],
                                       mix["output_len"]))
            i = 0
            for t, length, k in parts:
                if k == 0:
                    continue
                gaps = shuffled(quantiles(
                    {"dist": "exponential", "mean": 1.0}, k), order)
                due = start + t + (np.cumsum(gaps) - gaps) * (
                    length / gaps.sum())
                for d in due:
                    self.requests.append({
                        "due": float(d), "phase": phase,
                        "part": (start + t, start + t + length),
                        "prompt": rng.integers(0, vocab, int(plen[i]),
                                               dtype=np.int32),
                        "max_new_tokens": int(olen[i])})
                    i += 1
        cycle, on = float(arrivals["cycle_s"]), float(arrivals["on_s"])
        self.rate = (on * float(arrivals["on_rate_per_s"])
                     + (cycle - on) * float(arrivals["off_rate_per_s"])
                     ) / cycle
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
