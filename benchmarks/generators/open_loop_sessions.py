"""Open loop of chat SESSIONS: sessions start on a schedule, whatever
the system does; inside a session the turns follow each other.

``arrivals.rate_per_s`` is the rate of session starts, fixed in the mix
file; over ``span`` seconds n = round(rate x span) sessions start, their
gaps the exponential distribution's quantiles scaled to fill the span
(as ``open_loop``). A session draws one of the mix's ``system_prompts``
(lengths; each is one fixed token sequence of the run, shared by every
session that draws it) and runs ``turns`` turns. Turn k's prompt is

    system prompt + [user part + served answer] of turns 1..k-1 + a new user part

and falls due ``think_s`` seconds after turn k-1's last token was
served (the first turn at the session's start). A turn that would fall
due after the window's end is not offered: the window cuts the session.

Every seed offers the same sessions in the same order: which system
prompt, every user part's and every answer's length and the gaps are
drawn once from the mix's ``order_seed``; the run's seed draws the
tokens and turns the warm phase's cycle to another starting point.

The served answer is part of the next prompt, as a chat client sends
its history back. The driver tells a generator that a request has
finished by handing back the request's spec (``finished(spec)``), not
its tokens; they are on the driver's record of the request, which the
calling frame holds as ``r``, and are read from there. Where no such
record is found (another driver) the answer's place is filled with
seeded tokens of the same length, and ``answers_unseen`` counts it.
"""
import sys
import time

import numpy as np

from benchmarks.distributions import quantiles, shuffled


class Generator:
    closed = False

    def __init__(self, mix, seed, seconds, vocab):
        self.rng = np.random.default_rng([int(seed), 0x5E55])
        order = np.random.default_rng([int(mix["order_seed"]), 0x5E55])
        self.rate = float(mix["arrivals"]["rate_per_s"])
        self.warm_s = float(mix["warm_s"])
        self.seconds = float(seconds)
        self.end = self.warm_s + self.seconds
        self.think_s = float(mix["think_s"])
        self.turns = int(mix["turns"])
        self.vocab = int(vocab)
        self.system = [self.rng.integers(0, vocab, int(n), dtype=np.int32)
                       for n in mix["system_prompts"]]
        self.sessions = []
        self.pending = []              # turns not yet handed out, by due
        self.answers_unseen = 0
        self._offset = None            # perf_counter() - generator time
        T = self.turns
        for phase, start, span in (("warm", 0.0, self.warm_s),
                                   ("window", self.warm_s, self.seconds)):
            n = int(round(self.rate * span))
            if n == 0:
                continue
            turn = int(seed) % n if phase == "warm" else 0
            gaps = np.roll(shuffled(quantiles(
                {"dist": "exponential", "mean": 1.0}, n), order), -turn)
            which = np.roll(shuffled(np.arange(n) % len(self.system),
                                     order), -turn)
            ulen, olen = (
                np.roll(shuffled(quantiles(mix[k], n * T), order)
                        .reshape(n, T), -turn, axis=0)
                for k in ("user_len", "output_len"))
            due = start + (np.cumsum(gaps) - gaps) * (span / gaps.sum())
            for i in range(n):
                s = {"id": len(self.sessions), "phase": phase,
                     "system": int(which[i]),
                     "user_len": [int(v) for v in ulen[i]],
                     "output_len": [int(v) for v in olen[i]],
                     # drawn now, so that a turn's tokens do not depend
                     # on the order in which the answers come back
                     "user": [self.rng.integers(0, vocab, int(v),
                                                dtype=np.int32)
                              for v in ulen[i]],
                     "history": self.system[int(which[i])], "turn": 0}
                self.sessions.append(s)
                self._offer(s, float(due[i]))

    # -- turns ---------------------------------------------------------
    def _offer(self, s, due):
        """Queue session ``s``'s next turn to fall due at ``due``."""
        k = s["turn"]
        if k >= self.turns or due > self.end:
            return
        prompt = np.concatenate([s["history"], s["user"][k]])
        self.pending.append({
            "due": due, "phase": "warm" if due < self.warm_s else "window",
            "prompt": prompt, "max_new_tokens": s["output_len"][k],
            "session": s["id"], "turn": k,
            "shared_tokens": int(s["history"].size)})
        self.pending.sort(key=lambda r: r["due"])

    def due(self, now):
        if self._offset is None:
            self._offset = time.perf_counter() - now
        out = []
        while self.pending and self.pending[0]["due"] <= now:
            out.append(self.pending.pop(0))
        return out

    def next_due(self):
        return self.pending[0]["due"] if self.pending else None

    def _served(self, request):
        """The tokens the system served for ``request``: on the
        driver's record ``r`` in the frame that called ``finished``."""
        record = sys._getframe(2).f_locals.get("r")
        if record is not None and getattr(record, "spec", None) is request:
            tokens = getattr(getattr(record, "req", None), "tokens", None)
            if tokens is not None and len(tokens):
                return np.asarray(tokens, np.int32)
        self.answers_unseen += 1
        fill = np.random.default_rng([request["session"], request["turn"]])
        return fill.integers(0, self.vocab, request["max_new_tokens"],
                             dtype=np.int32)

    def finished(self, request):
        now = (time.perf_counter() - self._offset
               if self._offset is not None else request["due"])
        s = self.sessions[request["session"]]
        s["history"] = np.concatenate([request["prompt"],
                                       self._served(request)])
        s["turn"] = request["turn"] + 1
        self._offer(s, now + self.think_s)

    def offered(self):
        win = [s for s in self.sessions if s["phase"] == "window"]
        return {"rate_per_s": self.rate, "sessions": len(win),
                "turns_a_session": self.turns,
                "window_requests": len(win) * self.turns,
                "window_prompt_tokens": int(sum(
                    self.system[s["system"]].size * self.turns
                    + sum((self.turns - k) * s["user_len"][k]
                          + (self.turns - 1 - k) * s["output_len"][k]
                          for k in range(self.turns)) for s in win)),
                "window_output_tokens": int(sum(sum(s["output_len"])
                                                for s in win))}
