"""Closed loop: ``clients`` callers, each sending its next request when
its last has finished. No rate: the system sets its own pace, and what
is judged is the work it completes.

The requests are one list of ``pool`` entries: the length
distributions' quantiles in an order drawn once from the mix's
``order_seed``, turned to another starting point by the run's seed (so
every seed does the same work); clients take the next entry in turn. All are due at once, so every one is "late" by
construction and lateness is not reported.
"""
import numpy as np

from benchmarks.distributions import quantiles, shuffled


class Generator:
    closed = True

    def __init__(self, mix, seed, seconds, vocab):
        rng = np.random.default_rng([int(seed), 0xC105ED])
        self.clients = int(mix["clients"])
        self.warm_s = float(mix["warm_s"])
        self.seconds = float(seconds)
        order = np.random.default_rng([int(mix["order_seed"]), 0xC105ED])
        n = int(mix["pool"])
        plen, olen = (np.roll(shuffled(quantiles(mix[k], n), order),
                              -(int(seed) % n))
                      for k in ("prompt_len", "output_len"))
        self.requests = [{
            "due": None, "phase": None,
            "prompt": rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
            "max_new_tokens": int(olen[i])} for i in range(n)]
        self._next = 0
        self._out = 0

    def due(self, now):
        out = []
        while self._out < self.clients:
            # the list is a cycle: a faster system starts it over
            r = dict(self.requests[self._next % len(self.requests)],
                     due=now,
                     phase="warm" if now < self.warm_s else "window")
            out.append(r)
            self._next += 1
            self._out += 1
        return out

    def next_due(self):
        return None

    def finished(self, request):
        self._out -= 1

    def offered(self):
        return {"clients": self.clients, "pool": len(self.requests)}
