"""Which experts a program's router chose, read through ``models.moe.PROBE``
(set WHILE the program is traced): by layer, position and input token id.
``tests/test_deltanet_gqa_moe.py`` and ``chip_smoke_deepseek.py --config
qwen3-next-80b-a3b-int8`` run the plain reference on these choices (ten of 512
near-tied probabilities flip under bfloat16, so a comparison by logits is
tight only where both sides sum the same experts) and hold the choices
themselves to the reference's probabilities.

A record has no sequence's name: the model code sees tokens and positions.
Two sequences that hold the same token at the same position are told apart by
nothing, so a caller keeps its sequences' (position, token) pairs disjoint,
and ``conflicts`` counts the keys that arrived with two different choices."""

import numpy as np


class Probe:
    def __init__(self):
        # {tag: {(layer, position, token): experts [k], sorted}}; ``tag`` is
        # whatever ``mark`` last said ("step" until then)
        self.seen = {}
        self.tag = "step"
        self.conflicts = 0

    def mark(self, tag):
        self.tag = tag

    def __call__(self, layer, tokens, positions, valid, experts):
        layer = int(layer)
        book = self.seen.setdefault(self.tag, {})
        tokens, positions = tokens.reshape(-1), positions.reshape(-1)
        for t in np.nonzero(valid)[0]:
            at = (layer, int(positions[t]), int(tokens[t]))
            got = np.sort(experts[t])
            if at in book and not np.array_equal(book[at], got):
                self.conflicts += 1
            book[at] = got

    def choices(self, tokens, layers: int, k: int, prefer=("peek", "step")):
        """``[layers, len(tokens), k]``: the experts chosen where sequence
        ``tokens`` was the input, a row of -1 where no record is (a token
        that was never an input: the last one out).  ``prefer``: the tags in
        the order they are looked up (a position whose logits were peeked
        takes the peek's choices)."""
        out = np.full((layers, len(tokens), k), -1, np.int32)
        for l in range(layers):
            for p, t in enumerate(tokens):
                for tag in prefer:
                    got = self.seen.get(tag, {}).get((l, p, int(t)))
                    if got is not None:
                        out[l, p] = got
                        break
        return out
