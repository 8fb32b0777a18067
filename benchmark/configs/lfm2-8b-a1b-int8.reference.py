"""The plain reference of ``lfm2-8b-a1b-int8``: the hybrid decoder of gated
short convolutions, GQA layers and sigmoid-routed experts of
``benchmark/lib/reference_hybrid_conv_moe_decoder.py`` at the sizes of
``benchmark/configs/lfm2-8b-a1b-int8.json`` (the file's Hugging Face keys are
the reference's ``cfg``).  Loaded by path, not imported by name."""

import json
import os

from benchmark.lib.reference_hybrid_conv_moe_decoder import forward  # noqa: F401

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "lfm2-8b-a1b-int8.json")) as _f:
    CONFIG = json.load(_f)
