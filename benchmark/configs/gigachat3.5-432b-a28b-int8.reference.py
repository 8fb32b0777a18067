"""The plain reference of ``gigachat3.5-432b-a28b-int8``: the delta-rule /
latent-attention / held-experts decoder of
``benchmark/lib/reference_deltanet_mla_moe_decoder.py`` at the sizes of
``benchmark/configs/gigachat3.5-432b-a28b-int8.json`` (the file's Hugging
Face keys are the reference's ``cfg``).  Loaded by path, not imported by
name."""

import json
import os

from benchmark.lib.reference_deltanet_mla_moe_decoder import (  # noqa: F401
    forward,
)

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "gigachat3.5-432b-a28b-int8.json")) as _f:
    CONFIG = json.load(_f)
