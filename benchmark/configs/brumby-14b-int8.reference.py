"""The plain reference of ``brumby-14b-int8``: the power-retention decoder of
``benchmark/lib/reference_retention_decoder.py`` at the sizes of
``benchmark/configs/brumby-14b-int8.json`` (the file's Hugging Face keys are
the reference's ``cfg``).  Loaded by path, not imported by name."""

import json
import os

from benchmark.lib.reference_retention_decoder import forward  # noqa: F401

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "brumby-14b-int8.json")) as _f:
    CONFIG = json.load(_f)
