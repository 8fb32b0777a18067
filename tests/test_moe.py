"""Mixture-of-experts (Mixtral family): GShard-style dispatch algebra,
expert-parallel sharding over the mesh's ep axis, and end-to-end engine
parity with a naive per-token reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helix_tpu.models.common import ModelConfig
from helix_tpu.models.llama import forward, init_params, param_logical_axes
from helix_tpu.models.moe import moe_ffn


def tiny_moe_cfg(**over):
    base = dict(num_experts=4, num_experts_per_tok=2,
                expert_capacity_factor=2.0, dtype="float32")
    base.update(over)
    return ModelConfig.tiny(**base)


def naive_moe(x, router_w, mats, cfg, act):
    """Per-token loop oracle: exact top-k mixture, no capacity limit."""
    B, S, E = x.shape
    out = np.zeros((B, S, E), np.float32)
    for b in range(B):
        for s in range(S):
            t = np.asarray(x[b, s], np.float32)
            logits = t @ np.asarray(router_w, np.float32)
            k = cfg.num_experts_per_tok
            idx = np.argsort(-logits)[:k]
            w = np.exp(logits[idx] - logits[idx].max())
            w = w / w.sum()
            acc = np.zeros(E, np.float32)
            for wi, xi in zip(w, idx):
                g = t @ np.asarray(mats["w_gate"][xi], np.float32)
                u = t @ np.asarray(mats["w_up"][xi], np.float32)
                h = (np.asarray(act(jnp.asarray(g))) * u) @ np.asarray(
                    mats["w_down"][xi], np.float32
                )
                acc += wi * h
            out[b, s] = acc
    return out


class TestMoELayer:
    def test_dispatch_matches_naive_reference(self):
        cfg = tiny_moe_cfg()
        key = jax.random.PRNGKey(0)
        B, S, E, F, X = 2, 5, cfg.hidden_size, cfg.intermediate_size, 4
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (B, S, E), jnp.float32) * 0.5
        router_w = jax.random.normal(ks[1], (E, X), jnp.float32) * 0.2
        mats = {
            "w_gate": jax.random.normal(ks[2], (X, E, F)) * 0.05,
            "w_up": jax.random.normal(ks[3], (X, E, F)) * 0.05,
            "w_down": jax.random.normal(ks[4], (X, F, E)) * 0.05,
        }
        wrapped = {k2: {"weight": v} for k2, v in mats.items()}
        got = moe_ffn(x, router_w, wrapped, cfg, jax.nn.silu)
        want = naive_moe(x, router_w, mats, cfg, jax.nn.silu)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)

    def test_capacity_overflow_drops_weakest(self):
        """With capacity 1 and all tokens preferring one expert, only one
        token's first choice survives; the rest contribute less (second
        choice only) instead of erroring."""
        cfg = tiny_moe_cfg(expert_capacity_factor=0.01)  # C = 1
        E, X = cfg.hidden_size, 4
        x = jnp.ones((1, 6, E), jnp.float32) * 0.3       # identical tokens
        router_w = jnp.zeros((E, X), jnp.float32).at[:, 0].set(0.1)
        mats = {
            "w_gate": {"weight": jnp.ones((X, E, cfg.intermediate_size)) * 0.01},
            "w_up": {"weight": jnp.ones((X, E, cfg.intermediate_size)) * 0.01},
            "w_down": {"weight": jnp.ones((X, cfg.intermediate_size, E)) * 0.01},
        }
        out = moe_ffn(x, router_w, mats, cfg, jax.nn.silu)
        assert np.isfinite(np.asarray(out)).all()
        # token 0 keeps its top choice; later identical tokens lost it to
        # capacity, so their outputs are strictly smaller mixtures
        n0 = float(jnp.abs(out[0, 0]).sum())
        n5 = float(jnp.abs(out[0, 5]).sum())
        assert n5 < n0

    def test_forward_with_moe_layers(self):
        cfg = tiny_moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(1))
        assert "experts" in params["layers"]
        assert "w_gate" not in params["layers"]
        toks = jnp.array([[1, 2, 3, 4]])
        pos = jnp.arange(4)[None]
        from helix_tpu.models.llama import prefill_attn_fn

        logits, _ = forward(
            params, cfg, toks, pos,
            attn_fn=lambda q, k, v, c, p: prefill_attn_fn(
                q, k, v, c, p, backend="reference"
            ),
        )
        assert logits.shape == (1, 4, cfg.vocab_size)
        assert np.isfinite(np.asarray(logits)).all()

    def test_int8_expert_weights(self):
        from helix_tpu.ops.quant import quantize_params

        cfg = tiny_moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(2))
        q = quantize_params(params)
        assert q["layers"]["experts"]["w_gate"]["weight"].dtype == jnp.int8
        toks = jnp.array([[5, 6, 7]])
        pos = jnp.arange(3)[None]
        from helix_tpu.models.llama import prefill_attn_fn

        lg_q, _ = forward(
            q, cfg, toks, pos,
            attn_fn=lambda qq, k, v, c, p: prefill_attn_fn(
                qq, k, v, c, p, backend="reference"
            ),
        )
        lg_f, _ = forward(
            params, cfg, toks, pos,
            attn_fn=lambda qq, k, v, c, p: prefill_attn_fn(
                qq, k, v, c, p, backend="reference"
            ),
        )
        # int8 weight-only stays close to fp32
        np.testing.assert_allclose(
            np.asarray(lg_q), np.asarray(lg_f), atol=0.35
        )

    def test_hf_config_mapping(self):
        cfg = ModelConfig.from_hf_config({
            "vocab_size": 32000, "hidden_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "num_key_value_heads": 2, "intermediate_size": 256,
            "model_type": "mixtral", "num_local_experts": 8,
            "num_experts_per_tok": 2,
        }, name="mixtral-tiny")
        assert cfg.num_experts == 8 and cfg.num_experts_per_tok == 2


class TestExpertParallel:
    def test_ep_sharded_forward_matches_unsharded(self, cpu_devices):
        """Expert weights sharded over an ep=4 mesh produce the same
        logits as the unsharded forward (XLA inserts the collectives)."""
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        cfg = tiny_moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(3))
        toks = jnp.array([[1, 2, 3, 4, 5, 6]])
        pos = jnp.arange(6)[None]
        from helix_tpu.models.llama import prefill_attn_fn

        def fwd(p):
            lg, _ = forward(
                p, cfg, toks, pos,
                attn_fn=lambda q, k, v, c, pp: prefill_attn_fn(
                    q, k, v, c, pp, backend="reference"
                ),
            )
            return lg

        want = np.asarray(fwd(params))

        mesh = Mesh(
            np.array(cpu_devices[:4]).reshape(4), axis_names=("ep",)
        )
        axes = param_logical_axes(cfg)

        def to_sharded(p, ax):
            # the ep mesh only has the ep axis: shard specs that mention
            # the expert logical axis, replicate everything else
            if isinstance(ax, tuple) and "expert" in ax:
                spec = P(*[
                    "ep" if a == "expert" else None for a in ax
                ])
            else:
                spec = P()
            return jax.device_put(p, NamedSharding(mesh, spec))

        sharded = jax.tree.map(
            to_sharded, params, axes,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(a, (str, type(None))) for a in x
            ),
        )
        with mesh:
            got = np.asarray(jax.jit(fwd)(sharded))
        np.testing.assert_allclose(got, want, atol=2e-4)


class TestMoEEngine:
    def test_engine_greedy_decode_moe(self):
        """The full serving engine (packed prefill + paged decode) runs a
        MoE model and matches the growing-sequence oracle."""
        from helix_tpu.engine.engine import Engine, EngineConfig
        from helix_tpu.engine.sampling import SamplingParams
        from helix_tpu.models.llama import prefill_attn_fn

        cfg = tiny_moe_cfg()
        params = init_params(cfg, jax.random.PRNGKey(4))
        eng = Engine(
            cfg, params,
            EngineConfig(
                max_decode_batch=2, page_size=4, num_pages=64,
                max_pages_per_seq=16, max_prefill_len=64,
                attn_backend="reference", enable_prefix_cache=False,
            ),
        )
        prompt = [3, 1, 4, 1, 5]
        got = eng.generate(
            [prompt], SamplingParams(temperature=0.0, max_tokens=6)
        )[0]

        toks = list(prompt)
        want = []
        for _ in range(6):
            lg, _ = forward(
                params, cfg, jnp.asarray(toks)[None],
                jnp.arange(len(toks))[None],
                attn_fn=lambda q, k, v, c, p: prefill_attn_fn(
                    q, k, v, c, p, backend="reference"
                ),
            )
            nxt = int(jnp.argmax(lg[0, -1]))
            want.append(nxt)
            toks.append(nxt)
        assert got == want


class TestMoEDeterminism:
    def test_prefill_independent_of_batch_mates(self):
        """The same prompt admitted alone vs in a burst produces the same
        tokens (MoE requests never co-pack, so no shared capacity field;
        decode is dropless)."""
        from helix_tpu.engine.engine import Engine, EngineConfig, Request
        from helix_tpu.engine.sampling import SamplingParams

        cfg = tiny_moe_cfg(expert_capacity_factor=1.0)
        params = init_params(cfg, jax.random.PRNGKey(7))

        def make():
            return Engine(
                cfg, params,
                EngineConfig(
                    max_decode_batch=4, page_size=4, num_pages=64,
                    max_pages_per_seq=16, max_prefill_len=64,
                    attn_backend="reference", enable_prefix_cache=False,
                ),
            )

        target = [9, 8, 7, 6, 5]
        alone = make().generate(
            [target], SamplingParams(temperature=0.0, max_tokens=5)
        )[0]
        # same prompt in a burst with expert-hungry batch-mates
        burst = make().generate(
            [[1] * 12, target, [2] * 12],
            SamplingParams(temperature=0.0, max_tokens=5),
        )[1]
        assert alone == burst

    def test_lora_all_targets_on_moe(self):
        """ALL_TARGETS works on MoE configs: FFN targets are skipped
        with attention-only adapters, not KeyError'd."""
        from helix_tpu.training.lora import (
            ALL_TARGETS,
            LoraConfig,
            init_lora_params,
            merge_lora_into_params,
        )

        cfg = tiny_moe_cfg()
        lp = init_lora_params(
            cfg, LoraConfig(rank=4, targets=ALL_TARGETS),
            jax.random.PRNGKey(0),
        )
        assert "wq" in lp and "w_gate" not in lp
        params = init_params(cfg, jax.random.PRNGKey(1))
        merged = merge_lora_into_params(params, lp, scaling=1.0)
        assert "experts" in merged["layers"]


class TestMoEDropCounter:
    def test_moe_ffn_reports_capacity_drops(self):
        """return_dropped counts exactly the valid (token, choice)
        assignments that overflowed expert capacity."""
        cfg = tiny_moe_cfg(expert_capacity_factor=0.01)  # C = 1
        E, X = cfg.hidden_size, 4
        x = jnp.ones((1, 6, E), jnp.float32) * 0.3       # identical tokens
        router_w = jnp.zeros((E, X), jnp.float32).at[:, 0].set(0.1)
        mats = {
            "w_gate": {"weight": jnp.ones((X, E, cfg.intermediate_size)) * 0.01},
            "w_up": {"weight": jnp.ones((X, E, cfg.intermediate_size)) * 0.01},
            "w_down": {"weight": jnp.ones((X, cfg.intermediate_size, E)) * 0.01},
        }
        out, dropped = moe_ffn(
            x, router_w, mats, cfg, jax.nn.silu, return_dropped=True
        )
        # identical tokens all route to the same two experts (top-1 and
        # the tied top-2 pick): 6 tokens x 2 choices = 12 assignments
        # into 2 capacity-1 experts -> exactly 2 survive, 10 drop
        assert int(dropped) == 10
        # padding/masked tokens never count as drops
        mask = jnp.zeros((1, 6), bool).at[0, 0].set(True)
        _, dropped_masked = moe_ffn(
            x, router_w, mats, cfg, jax.nn.silu, token_mask=mask,
            return_dropped=True,
        )
        assert int(dropped_masked) == 0   # 1 token, 2 choices, both fit

    @pytest.mark.parametrize("factor", [0.3, 2.0])
    def test_decode_rows_on_a_prefills_axis_route_as_each_alone(self, factor):
        """A step's one pass: ``decode_rows`` decode rows ride behind a
        prefill's tokens.  The prefill tokens keep and drop what they keep
        and drop alone (their own capacity), and the decode rows stay
        dropless, as a decode call's are."""
        cfg = tiny_moe_cfg(expert_capacity_factor=factor)
        E, X, F = cfg.hidden_size, 4, cfg.intermediate_size
        ks = jax.random.split(jax.random.PRNGKey(5), 5)
        x = jax.random.normal(ks[0], (1, 19, E), jnp.float32)
        # decode rows that all want the experts the prefill overflows
        x = x.at[0, 12:].set(x[0, 3] + 0.01 * x[0, 12:])
        router_w = jax.random.normal(ks[1], (E, X), jnp.float32)
        mats = {
            n: {"weight": 0.1 * jax.random.normal(k, shp, jnp.float32)}
            for n, k, shp in (("w_gate", ks[2], (X, E, F)),
                              ("w_up", ks[3], (X, E, F)),
                              ("w_down", ks[4], (X, F, E)))}
        mask = jnp.ones((1, 19), bool).at[0, 5].set(False).at[0, 15].set(
            False)
        both, d_both = moe_ffn(x, router_w, mats, cfg, jax.nn.silu,
                               token_mask=mask, return_dropped=True,
                               decode_rows=7)
        pre, d_pre = moe_ffn(x[:, :12], router_w, mats, cfg, jax.nn.silu,
                             token_mask=mask[:, :12], return_dropped=True)
        dec, d_dec = moe_ffn(x[0, 12:, None], router_w, mats, cfg,
                             jax.nn.silu, token_mask=mask[0, 12:, None],
                             return_dropped=True)
        assert int(d_dec) == 0 and int(d_both) == int(d_pre)
        assert (int(d_pre) > 0) == (factor < 1)
        np.testing.assert_allclose(both[0, :12], pre[0], atol=1e-6)
        np.testing.assert_allclose(both[0, 12:], dec[:, 0], atol=1e-6)

    def test_engine_counts_prefill_drops(self):
        """The serving engine surfaces prefill capacity overflow in its
        per-engine counter instead of dropping silently (ADVICE r5)."""
        from helix_tpu.engine.engine import Engine, EngineConfig
        from helix_tpu.engine.sampling import SamplingParams

        cfg = tiny_moe_cfg(expert_capacity_factor=0.01)
        params = init_params(cfg, jax.random.PRNGKey(4))
        eng = Engine(
            cfg, params,
            EngineConfig(
                max_decode_batch=2, page_size=4, num_pages=64,
                max_pages_per_seq=16, max_prefill_len=64,
                attn_backend="reference", enable_prefix_cache=False,
            ),
        )
        from helix_tpu.engine.engine import Request

        req = Request(
            id="moe-drops", prompt_tokens=[3, 1, 4, 1, 5, 9, 2, 6],
            sampling=SamplingParams(temperature=0.0, max_tokens=5),
        )
        eng.add_request(req)
        eng.step()   # prefill + first token
        # capacity 1 with an 8-token prompt must overflow during prefill
        after_prefill = eng.moe_dropped_tokens
        assert after_prefill > 0
        # decode is dropless (C = T): the counter must not move while the
        # remaining 4 tokens drain
        while eng.has_work():
            eng.step()
        assert len(req.output_tokens) == 5
        eng._drain_moe_drops()   # fold anything decode might have queued
        assert eng.moe_dropped_tokens == after_prefill
