"""The plain reference of ``deepseek-v2-lite-int8``: the latent-attention,
shared-and-routed-experts decoder of
``benchmark/lib/reference_mla_moe_decoder.py`` at the sizes of
``benchmark/configs/deepseek-v2-lite-int8.json`` (the file's Hugging Face keys
are the reference's ``cfg``).  Loaded by path, not imported by name."""

import json
import os

from benchmark.lib.reference_mla_moe_decoder import forward  # noqa: F401

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "deepseek-v2-lite-int8.json")) as _f:
    CONFIG = json.load(_f)
