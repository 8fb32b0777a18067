"""What a program with a sparse-attention indexer scored and chose, read
through ``ops.dsa.PROBE`` (set WHILE the program is traced): by sequence (the
first page of its table), layer and position.  ``tests/test_mla_dsa_moe.py``
and ``chip_smoke_deepseek.py --config glm-5-int8`` compare it with the plain
reference's scores and sets, and run the reference on these sets."""

import numpy as np


class Probe:
    def __init__(self):
        # {(first page, layer, position): ...}
        self.scores, self.sets, self.kinds = {}, {}, {}

    def __call__(self, kind, layer, first, *a):
        layer = int(layer)
        if kind == "decode":
            hist, q_len, sc, sel, keep = a
            for b in np.nonzero(q_len > 0)[0]:
                p = int(hist[b])
                at = (int(first[b]), layer, p)
                self.scores[at] = np.array(sc[b, :p + 1])
                self.sets[at] = np.sort(sel[b][keep[b]])
                self.kinds[at] = "decode"
            return
        t0, q_len, hist, scores, chosen = a
        S = scores.shape[1] - scores.shape[0]
        for r in np.nonzero(q_len > 0)[0]:
            h, lo = int(hist[r]), int(t0[r])
            for j in range(int(q_len[r])):
                at = (int(first[r]), layer, h + j)
                own = slice(S + lo, S + lo + j + 1)
                self.scores[at] = np.concatenate(
                    [scores[lo + j, :h], scores[lo + j, own]])
                self.sets[at] = np.nonzero(np.concatenate(
                    [chosen[lo + j, :h], chosen[lo + j, own]]))[0]
                self.kinds[at] = "chunk"

    def selection(self, first, layers: int, n: int, topk: int):
        """``[n, n]`` boolean sets a layer: the program's where it chose,
        every causal key where it attended all it had (no probe fires: a
        cold row of no more than ``topk`` tokens on the latent kernel)."""
        out = []
        for l in range(layers):
            m = np.tril(np.ones((n, n), bool))
            for p in range(n):
                got = self.sets.get((first, l, p))
                if got is not None:
                    m[p] = False
                    m[p, got] = True
                elif p + 1 > topk and p != n - 1:
                    # (the last token out was never a query)
                    raise AssertionError(
                        f"layer {l} position {p}: past {topk} keys and "
                        "no choice was probed")
            out.append(m)
        return out
