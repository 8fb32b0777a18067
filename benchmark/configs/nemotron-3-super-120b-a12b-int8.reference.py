"""The plain reference of ``nemotron-3-super-120b-a12b-int8``: the Mamba-2 /
attention / latent-expert decoder of one-branch layers of
``benchmark/lib/reference_ssd_latent_moe_decoder.py`` at the sizes of
``benchmark/configs/nemotron-3-super-120b-a12b-int8.json`` (the file's Hugging
Face keys are the reference's ``cfg``).  Loaded by path, not imported by name.

The ASSUMED readings of the published config, each a line of the file's
``assumed`` and of the reference's docstring:
- attention applies no rotary embedding;
- the Mamba-2 gate ``silu(z)`` multiplies ``y`` BEFORE the grouped norm, which
  runs over each of the 8 groups' 1,024 channels apart;
- the in-projection's order is z | x | B | C | dt, and ``B`` and ``C`` are
  shared by the 16 heads of a group;
- the router scores by sigmoid, selects on score + bias, weighs without the
  bias, renormalised over the chosen 22 (``1e-6`` in the divisor), times 5;
- the experts, the shared one too, are ungated ``W_down relu(W_up x) ** 2``;
  the routed ones live in the 1,024-wide latent between ``W_fc1`` and
  ``W_fc2``, the router and the shared expert read the un-projected input;
- multi-token prediction is not run."""

import json
import os

from benchmark.lib.reference_ssd_latent_moe_decoder import forward  # noqa: F401

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "nemotron-3-super-120b-a12b-int8.json")) as _f:
    CONFIG = json.load(_f)
