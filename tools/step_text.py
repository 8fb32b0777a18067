#!/usr/bin/env python3
"""sha256 of the lowered text of a tiny model's step programs, one model a
kind of page and of per-sequence state (``helix_tpu/models/mixers.py``):

    python tools/step_text.py [model ...]

A PR that MOVES code runs it at its parent and on its own tree: the same
digests mean the device runs the same programs.  The text carries no source
locations; it does carry the matmul precision, set here as
``tests/conftest.py`` sets it, so ``tests/test_state_mixers.py`` pins what
this prints.
"""

import hashlib
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")

# (token bucket, prefill rows, the rows have history) of
# ``tests/joint_pass.py::step_program``
PROGRAMS = {
    "decode": (0, 0, False),
    "wave": (16, 2, False),
    "chunk_with_history": (16, 1, True),
}

_LATENT = dict(num_kv_heads=4, kv_lora_rank=32, q_lora_rank=48,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
_STATE = dict(num_layers=2, sliding_window=8, conv_kernel=4, mamba_heads=4,
              mamba_head_dim=32, mamba_groups=1, mamba_state_size=8,
              linear_key_heads=2, linear_value_heads=4, linear_key_dim=16,
              linear_value_dim=16)
# ``ModelConfig.tiny``'s overrides: the three kinds of page, then each state
# kind beside a K/V layer, then a model with no paged layer at all
MODELS = {
    "kv": {},
    "kv_int8": {},
    "latent": _LATENT,
    # a wave of 16 cold tokens attends all it has, a row with history chooses
    "latent_indexed": dict(_LATENT, index_heads=4, index_head_dim=16,
                           index_topk=32),
    **{kind: dict(_STATE, layer_types=(kind, "attn"))
       for kind in ("conv", "retention", "deltanet", "window", "mamba2")},
    "retention_alone": dict(_STATE, layer_types=("retention",) * 2),
}
# ... and ``EngineConfig``'s
ENGINES = {"kv_int8": dict(kv_cache_dtype="int8")}


def digest(model: str, program: str) -> str:
    import joint_pass
    from helix_tpu.engine.engine import Engine, EngineConfig
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params

    cfg = ModelConfig.tiny(
        vocab_size=512, dtype="float32", **MODELS[model])
    served_with_prefix_cache = cfg.state_kind is None or (
        cfg.state_kind.snapshots)
    eng = Engine(cfg, init_params(cfg, jax.random.PRNGKey(3)), EngineConfig(
        max_decode_batch=3, page_size=8, num_pages=96, max_pages_per_seq=16,
        max_prefill_len=16, attn_backend="reference",
        decode_steps_per_sync=4,
        enable_prefix_cache=served_with_prefix_cache,
        **ENGINES.get(model, {})))
    fn, args = joint_pass.step_program(eng, *PROGRAMS[program])
    return hashlib.sha256(fn.lower(*args).as_text().encode()).hexdigest()


if __name__ == "__main__":
    for model in sys.argv[1:] or MODELS:
        for program in PROGRAMS:
            print(f"{model:16} {program:19} {digest(model, program)}")
