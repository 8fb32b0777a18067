"""The plain reference of ``laguna-xs2-int8``: the window / full attention,
gated, held-experts decoder of
``benchmark/lib/reference_window_moe_decoder.py`` at the sizes of
``benchmark/configs/laguna-xs2-int8.json`` (the file's Hugging Face keys are
the reference's ``cfg``).  Loaded by path, not imported by name.

The ASSUMED readings of the published config, each a line of the file's
``assumed`` and of the reference's docstring:
- the gate is ONE value a head, from the branch's normed input through a
  sigmoid (the sibling states "per-head"; the parameter count bears it out);
- the router scores by sigmoid and renormalises over the chosen eight, times
  2.5, with no selection bias;
- no q/k norm;
- YaRN's ``attention_factor`` multiplies cos and sin: the rotated dims only;
- the window counts the query itself (512 keys at most)."""

import json
import os

from benchmark.lib.reference_window_moe_decoder import forward  # noqa: F401

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "laguna-xs2-int8.json")) as _f:
    CONFIG = json.load(_f)
