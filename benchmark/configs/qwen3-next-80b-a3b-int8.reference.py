"""The plain reference of ``qwen3-next-80b-a3b-int8``: the delta-rule /
gated-GQA / held-experts decoder of
``benchmark/lib/reference_deltanet_gqa_moe_decoder.py`` at the sizes of
``benchmark/configs/qwen3-next-80b-a3b-int8.json`` (the file's Hugging Face
keys are the reference's ``cfg``).  Loaded by path, not imported by name."""

import json
import os

from benchmark.lib.reference_deltanet_gqa_moe_decoder import (  # noqa: F401
    forward,
)

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "qwen3-next-80b-a3b-int8.json")) as _f:
    CONFIG = json.load(_f)
