"""The plain reference of ``glm-5-int8``: the sparse-latent-attention /
held-experts decoder of ``benchmark/lib/reference_mla_dsa_moe_decoder.py``
at the sizes of ``benchmark/configs/glm-5-int8.json`` (the file's Hugging
Face keys are the reference's ``cfg``).  Loaded by path, not imported by
name."""

import json
import os

from benchmark.lib.reference_mla_dsa_moe_decoder import (  # noqa: F401
    forward,
)

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "glm-5-int8.json")) as _f:
    CONFIG = json.load(_f)
