"""The one traffic generator: it reads a mix's parameters from its file.

A mix file (``traffic/<name>.json``) holds:

    loop        "closed_batches": a client sends ``slots`` requests at once,
                waits until all of them have their last token, then sends
                the next batch (the program has no front end that admits a
                request while others decode)
    prompt_len  tokens in every prompt
    gen_len     tokens generated for every request, greedily

Token ids are uniform over the vocabulary, drawn from the seed and the
batch's index, so the same seed gives the same prompts however many batches
a window holds, and every seed gives the same sizes.
"""

from __future__ import annotations

import numpy as np

LOOPS = ("closed_batches",)


class ClosedBatches:
    def __init__(self, mix: dict, *, slots: int, vocab: int, seed: int):
        if mix.get("loop") not in LOOPS:
            raise ValueError(f"traffic loop {mix.get('loop')!r}: the "
                             f"generator knows {LOOPS}")
        self.prompt_len = int(mix["prompt_len"])
        self.gen_len = int(mix["gen_len"])
        if self.prompt_len < 1 or self.gen_len < 1:
            raise ValueError("prompt_len and gen_len must be at least 1")
        self.slots = slots
        self.vocab = vocab
        self.seed = seed

    def prompts(self, batch: int) -> np.ndarray:
        """(slots, prompt_len) int32 token ids of batch number ``batch``."""
        rng = np.random.default_rng([self.seed, batch])
        return rng.integers(0, self.vocab, (self.slots, self.prompt_len),
                            dtype=np.int32)
