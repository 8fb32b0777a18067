"""The plain reference of ``mellum2-12b-a2.5b-int8``: the window / full
attention decoder with a softmax router renormalised over the chosen, every
expert held, of ``benchmark/lib/reference_window_softmax_moe_decoder.py`` at
the sizes of ``benchmark/configs/mellum2-12b-a2.5b-int8.json`` (the file's
Hugging Face keys are the reference's ``cfg``).  Loaded by path, not imported
by name.

Departures from the published description and the ASSUMED readings of the
published config, each a line of the file's ``assumed`` and of the
reference's docstring:
- no q/k norm: no key names one (``max_window_layers`` and
  ``use_sliding_window`` are keys of a lineage whose q/k norm has no key;
  ``qk_norm: true`` is one field away);
- rope over all 128 dims in both kinds at theta 500,000, YaRN on the full
  layers alone, its ``attention_factor`` on cos and sin and nothing further
  on the scores;
- the window counts the query itself (1,024 keys at most);
- the router is a softmax over all 64 experts, the top 8 by probability,
  renormalised over themselves, with no scale, the weight on the expert's
  output; no shared expert, no dense layer;
- the multi-token-prediction head the family's description names has no key
  in the config and is not built."""

import json
import os

from benchmark.lib.reference_window_softmax_moe_decoder import (  # noqa: F401
    forward,
)

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "mellum2-12b-a2.5b-int8.json")) as _f:
    CONFIG = json.load(_f)
