"""Training batches: a seeded stream of [batch, seq] token blocks, a new
one every step, labels the tokens shifted by one. Every row differs."""
import numpy as np


class Generator:
    def __init__(self, mix, seed, seconds, vocab):
        self.batch, self.seq = int(mix["batch"]), int(mix["seq"])
        self.vocab = vocab
        self.seed = int(seed)

    def batches(self):
        """Endless (tokens, labels) int32 pairs; the n-th batch depends
        on the seed and n alone."""
        n = 0
        while True:
            rng = np.random.default_rng([self.seed, 0xBA7C4, n])
            block = rng.integers(0, self.vocab, (self.batch, self.seq + 1),
                                 dtype=np.int32)
            yield block[:, :-1].copy(), block[:, 1:].copy()
            n += 1

    def offered(self):
        return {"batch": self.batch, "seq": self.seq,
                "tokens_per_step": self.batch * self.seq}
