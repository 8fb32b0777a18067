"""Adapter serving, both paths (ISSUE 15):

- the **batched multi-LoRA pool** (``engine/adapters.py``): many
  adapters serve concurrently against ONE resident base model —
  requests address ``model@adapter``, mixed-adapter waves pack a single
  device call, residency tiers HBM -> host -> filestore with async
  prefetch, and train -> publish -> serve needs no restart;
- the **merge-at-apply fallback** (``adapter:``/``adapter_scale:``
  profile fields, slow lane): one adapter baked into the served tree at
  profile-apply time — the numerical reference the batched path is
  pinned against at scale = alpha/rank.
"""

import asyncio
import json
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from helix_tpu.control.node_agent import NodeAgent
from helix_tpu.control.profile import ServingProfile
from helix_tpu.engine.adapters import (
    AdapterStore,
    adapter_residency_summary,
    pack_lora_tree,
    sanitize_adapter_id,
    split_model_adapter,
    validate_adapter_block,
)
from helix_tpu.engine.engine import Engine, EngineConfig, Request
from helix_tpu.engine.sampling import SamplingParams
from helix_tpu.models.common import ModelConfig
from helix_tpu.models.llama import init_params
from helix_tpu.training.checkpoint import save_checkpoint
from helix_tpu.training.lora import (
    LoraConfig,
    _target_dims,
    export_merged_weights,
    init_lora_params,
    merge_lora_into_params,
)

ECFG = dict(
    max_decode_batch=2, page_size=16, num_pages=64,
    max_pages_per_seq=8, max_prefill_len=32, attn_backend="reference",
)
# the batched-pool engine config: 3 slots = identity + 2 usable, so two
# tenants' adapters + adapter-free rows share one device call while
# eviction pressure is reachable with a third adapter
POOL_ECFG = dict(
    max_decode_batch=3, page_size=16, num_pages=64,
    max_pages_per_seq=8, max_prefill_len=64, attn_backend="reference",
    adapter_pool_slots=3, adapter_rank=4,
)

GREEDY = dict(temperature=0.0, max_tokens=6)


def _fake_trained_adapter(cfg, rank=4, seed=9):
    """An adapter with NON-zero B so it visibly changes the logits (a
    freshly initialised adapter is an identity)."""
    lp = init_lora_params(
        cfg, LoraConfig(rank=rank), jax.random.PRNGKey(seed)
    )
    for t in lp:
        lp[t]["lora_b"] = (
            jax.random.normal(
                jax.random.fold_in(jax.random.PRNGKey(seed), hash(t) % 97),
                lp[t]["lora_b"].shape, jnp.float32,
            )
            * 0.05
        )
    return lp


# ---------------------------------------------------------------------------
# addressing + sanitisation (hostile ids never mint labels or paths)
# ---------------------------------------------------------------------------


class TestAdapterAddressing:
    def test_sanitize_bounds_hostile_ids(self):
        assert sanitize_adapter_id("tenant-7.v2") == "tenant-7.v2"
        assert sanitize_adapter_id("A1_b") == "A1_b"
        # path escapes, metric-label injection, the __other__ fold
        # bucket, unbounded length: all rejected
        for hostile in (
            "../../etc/passwd", "a/b", ".hidden", "a b",
            'x"} evil', "__other__", "", None, 42, "a" * 65,
        ):
            assert sanitize_adapter_id(hostile) == ""

    def test_split_model_adapter(self):
        assert split_model_adapter("m") == ("m", "", True)
        assert split_model_adapter("m@a1") == ("m", "a1", True)
        base, adapter, ok = split_model_adapter("m@../x")
        assert not ok and adapter == ""

    def test_validate_adapter_block_clamps(self):
        hostile = [
            "m@good", "m@../bad", 17, {"x": 1}, "noseparator",
            "m@" + "a" * 80, "m@also-good",
        ] + [f"m@bulk{i}" for i in range(500)]
        out = validate_adapter_block(hostile)
        assert "m@good" in out and "m@also-good" in out
        assert all("@" in e for e in out)
        assert len(out) <= 128
        assert validate_adapter_block("nope") == []
        assert validate_adapter_block(None) == []


# ---------------------------------------------------------------------------
# the batched pool: one engine, many adapters, one device call
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_base():
    cfg = ModelConfig.tiny(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def pool_rig(tiny_base):
    """One pool-enabled engine with two published adapters, plus the
    adapter trees for merged-reference comparisons."""
    cfg, params = tiny_base
    eng = Engine(cfg, params, EngineConfig(**POOL_ECFG))
    a1 = _fake_trained_adapter(cfg, seed=9)
    a2 = _fake_trained_adapter(cfg, seed=23)
    eng.publish_adapter("a1", a1, 2.0)
    eng.publish_adapter("a2", a2, 2.0)
    return eng, {"a1": (a1, 2.0), "a2": (a2, 2.0)}


class TestBatchedAdapters:
    def test_adapter_free_bit_identical_with_pool_on(self, tiny_base):
        """The identity slot: greedy outputs of adapter-free traffic
        through the pool-ENABLED program are bit-identical to the
        pool-less engine, and the compiled step-shape count is
        unchanged (no new trace families)."""
        cfg, params = tiny_base
        prompts = [[5, 6, 7, 8], [9, 10, 11]]
        base_cfg = dict(POOL_ECFG)
        base_cfg["adapter_pool_slots"] = 0
        plain = Engine(cfg, params, EngineConfig(**base_cfg))
        ref = plain.generate(
            [list(p) for p in prompts], SamplingParams(**GREEDY)
        )
        pooled = Engine(cfg, params, EngineConfig(**POOL_ECFG))
        got = pooled.generate(
            [list(p) for p in prompts], SamplingParams(**GREEDY)
        )
        assert got == ref
        assert (
            pooled.compiled_step_shapes == plain.compiled_step_shapes
        )

    def test_mixed_wave_matches_merged_reference(
        self, tiny_base, pool_rig
    ):
        """Two adapters + an adapter-free row admitted in ONE wave and
        decoded in ONE device call per step match the per-request
        merged-weights references (scale = the published scale)."""
        cfg, params = tiny_base
        eng, adapters = pool_rig
        prompts = {
            "a1": [5, 6, 7, 8], "a2": [9, 10, 11, 12], "": [3, 4, 5],
        }
        reqs = []
        for aid, prompt in prompts.items():
            r = Request(
                id=f"mix-{aid or 'base'}",
                prompt_tokens=list(prompt),
                sampling=SamplingParams(**GREEDY),
                adapter=aid,
            )
            eng.add_request(r)
            reqs.append(r)
        calls0 = eng.num_device_calls
        eng.step()
        # all three rows packed the SAME admission wave: every request
        # holds a slot and emitted its first token after one step
        assert all(r.slot is not None or r.finished for r in reqs)
        assert all(len(r.output_tokens) >= 1 for r in reqs)
        while eng.has_work():
            eng.step()
        # mixed-adapter decode shares the device call: steps consumed
        # far fewer calls than 3 sequential requests would have
        assert eng.num_device_calls - calls0 <= 8
        for r in reqs:
            aid = r.adapter
            if not aid:
                base_cfg = dict(ECFG)
                ref_eng = Engine(cfg, params, EngineConfig(**base_cfg))
            else:
                lp, scale = adapters[aid]
                ref_eng = Engine(
                    cfg, merge_lora_into_params(params, lp, scale),
                    EngineConfig(**ECFG),
                )
            ref = ref_eng.generate(
                [list(prompts[aid])], SamplingParams(**GREEDY)
            )[0]
            assert r.output_tokens == ref, (
                f"adapter {aid or '(none)'} diverged from the merged "
                f"reference: {r.output_tokens} vs {ref}"
            )
        # per-adapter activity accounting is bounded + populated
        rows = eng.adapter_pool.rows_applied()
        assert rows.get("a1", 0) >= 1 and rows.get("a2", 0) >= 1

    def test_pool_matches_merge_and_export_at_alpha(self, tiny_base):
        """Satellite: ``merge_lora_into_params`` and
        ``export_merged_weights`` pin the batched path numerically at
        scale = alpha/rank — forward-level, no engines."""
        from helix_tpu.models.llama import forward, prefill_attn_fn

        cfg, params = tiny_base
        lora_cfg = LoraConfig(rank=4, alpha=8.0)
        lp = _fake_trained_adapter(cfg, rank=4, seed=31)
        scaling = lora_cfg.scaling   # alpha / rank
        toks = jnp.arange(8)[None]

        def fwd(p, adapter_ids=None):
            pos = jnp.broadcast_to(
                jnp.arange(toks.shape[1])[None], toks.shape
            )
            return forward(
                p, cfg, toks, pos,
                attn_fn=lambda q, k, v, c, pp: prefill_attn_fn(
                    q, k, v, c, pp, backend="reference"
                ),
                adapter_ids=adapter_ids,
            )[0]

        # batched-pool layout: stack the adapter at slot 1, identity 0
        from helix_tpu.engine.adapters import AdapterPool

        pool = AdapterPool(cfg, tuple(lp), 4, 2, dtype=jnp.float32)
        pool.acquire(
            "x", lambda _id: pack_lora_tree("x", lp, scaling)
        )
        grafted = dict(params)
        layers = dict(grafted["layers"])
        for t, entry in pool.entries().items():
            layers[t] = {**layers[t], **entry}
        grafted["layers"] = layers
        ids = jnp.ones(toks.shape, jnp.int32)
        got = np.asarray(fwd(grafted, adapter_ids=ids))
        merged = np.asarray(
            fwd(merge_lora_into_params(params, lp, scaling))
        )
        baked = np.asarray(
            fwd(export_merged_weights(params, lp, scaling))
        )
        np.testing.assert_allclose(got, merged, atol=1e-4)
        np.testing.assert_allclose(got, baked, atol=1e-4)
        # and the identity slot is an exact zero delta
        got0 = np.asarray(
            fwd(grafted, adapter_ids=jnp.zeros(toks.shape, jnp.int32))
        )
        np.testing.assert_array_equal(got0, np.asarray(fwd(params)))

    def test_cold_adapter_prefetch_never_blocks(
        self, tiny_base, tmp_path, monkeypatch
    ):
        """A cold adapter (filestore rung only) defers its request
        while everything else keeps admitting and decoding; the async
        prefetch overlaps the queue wait and the request completes with
        the right weights — no engine step ever waits on the load."""
        cfg, params = tiny_base
        lp = _fake_trained_adapter(cfg, seed=41)
        dims = _target_dims(cfg)
        root = str(tmp_path / "adapters")
        warm = AdapterStore(
            "tiny", {t: dims[t] for t in ("wq", "wk", "wv", "wo")},
            cfg.num_layers, 4, root_dir=root,
        )
        warm.publish(pack_lora_tree("cold1", lp, 2.0))
        eng = Engine(cfg, params, EngineConfig(**POOL_ECFG))
        # a FRESH store over the same filestore root: host tier empty,
        # so the adapter is genuinely cold
        eng.adapter_store = AdapterStore(
            "tiny", {t: dims[t] for t in ("wq", "wk", "wv", "wo")},
            cfg.num_layers, 4, root_dir=root,
        )
        free = Request(
            id="free", prompt_tokens=[3, 4, 5],
            sampling=SamplingParams(**GREEDY),
        )
        cold = Request(
            id="cold", prompt_tokens=[5, 6, 7, 8],
            sampling=SamplingParams(**GREEDY), adapter="cold1",
        )
        eng.add_request(cold)   # cold adapter at the QUEUE HEAD
        eng.add_request(free)
        deadline = time.monotonic() + 60
        while eng.has_work() and time.monotonic() < deadline:
            eng.step()
        assert free.finished and cold.finished
        assert eng.adapter_store.prefetches >= 1
        # the cold request decoded through the REAL adapter weights
        ref = Engine(
            cfg, merge_lora_into_params(params, lp, 2.0),
            EngineConfig(**ECFG),
        ).generate([[5, 6, 7, 8]], SamplingParams(**GREEDY))[0]
        assert cold.output_tokens == ref

    def test_eviction_and_refcount_churn(self, tiny_base, pool_rig):
        """LRU eviction recycles refcount-0 slots for new adapters; a
        slot pinned by a live request is never evicted."""
        cfg, params = tiny_base
        eng, _adapters = pool_rig
        pool = eng.adapter_pool
        # pin a1 as a live request would
        assert pool.acquire("a1", eng.adapter_store.get) is not None
        # publish a third adapter: with 2 usable slots and a1 pinned,
        # loading a3 must evict a2 (refcount 0), never a1
        eng.publish_adapter("a3", _fake_trained_adapter(cfg, seed=55), 2.0)
        slot3 = pool.acquire("a3", eng.adapter_store.get)
        assert slot3 is not None
        assert pool.resident("a1") and pool.resident("a3")
        assert not pool.resident("a2")
        assert pool.stats()["evictions"] >= 1
        # a fourth adapter cannot load while both slots are pinned
        eng.publish_adapter("a4", _fake_trained_adapter(cfg, seed=56), 2.0)
        assert pool.acquire("a4", eng.adapter_store.get) is None
        # releasing the pins frees capacity again
        pool.release("a1")
        pool.release("a3")
        assert pool.acquire("a4", eng.adapter_store.get) is not None
        pool.release("a4")

    def test_republish_reloads_weights(self, tiny_base):
        """Re-publishing an adapter must serve the NEW weights on the
        next admission — a resident slot loaded from an older publish
        generation reloads in place (refcount-0) instead of pinning
        stale weights forever."""
        cfg, params = tiny_base
        eng = Engine(cfg, params, EngineConfig(**POOL_ECFG))
        v1 = _fake_trained_adapter(cfg, seed=71)
        v2 = _fake_trained_adapter(cfg, seed=72)
        prompt = [5, 6, 7, 8]

        def serve():
            r = Request(
                id=f"rp-{time.monotonic_ns()}",
                prompt_tokens=list(prompt),
                sampling=SamplingParams(**GREEDY), adapter="t",
            )
            eng.add_request(r)
            while eng.has_work():
                eng.step()
            return r.output_tokens

        eng.publish_adapter("t", v1, 2.0)
        out1 = serve()
        eng.publish_adapter("t", v2, 2.0)
        out2 = serve()
        ref2 = Engine(
            cfg, merge_lora_into_params(params, v2, 2.0),
            EngineConfig(**ECFG),
        ).generate([list(prompt)], SamplingParams(**GREEDY))[0]
        assert out2 == ref2, "re-publish served stale weights"
        assert out1 != out2

    def test_one_slot_pool_degrades_to_off(self, tiny_base):
        """adapter_pool_slots=1 has no usable slot (0 is the identity):
        the engine warns and serves WITHOUT a pool instead of failing
        the whole model's profile apply."""
        cfg, params = tiny_base
        one = dict(POOL_ECFG)
        one["adapter_pool_slots"] = 1
        eng = Engine(cfg, params, EngineConfig(**one))
        assert eng.adapter_pool is None
        assert eng.generate(
            [[5, 6, 7]], SamplingParams(**GREEDY)
        )[0]

    def test_pool_bytes_are_low_rank_factors_not_model_copies(
        self, tiny_base, pool_rig
    ):
        """What the resident adapters cost in device memory: the pool's
        A and B factors for every slot and target, a small share of ONE
        copy of the base weights, where a merged model an adapter would
        cost a whole copy each."""
        cfg, params = tiny_base
        eng, adapters = pool_rig
        pool = eng.adapter_pool
        base_bytes = sum(int(x.nbytes) for x in jax.tree.leaves(params))
        want = 0
        for t, (d_in, d_out) in _target_dims(cfg).items():
            if t in pool.targets:
                want += (
                    cfg.num_layers * POOL_ECFG["adapter_pool_slots"]
                    * POOL_ECFG["adapter_rank"] * (d_in + d_out) * 4
                )
        assert pool.hbm_bytes() == want
        assert pool.hbm_bytes() < base_bytes / 4 < len(adapters) * base_bytes

    def test_residency_summary_bounded(self, pool_rig):
        eng, _ = pool_rig

        class _M:
            name = "tiny"
            loop = type("L", (), {"engine": eng})()

        entries = adapter_residency_summary([_M()])
        assert entries and all(e.startswith("tiny@") for e in entries)
        assert len(entries) <= 128


# ---------------------------------------------------------------------------
# train -> publish -> serve over HTTP, no restart (the tentpole loop)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def adapter_server(tiny_base, tmp_path_factory):
    from helix_tpu.serving.engine_loop import EngineLoop
    from helix_tpu.serving.openai_api import OpenAIServer
    from helix_tpu.serving.registry import ModelRegistry, ServedModel
    from helix_tpu.serving.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    cfg = ModelConfig.tiny(vocab_size=512, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(3))
    eng = Engine(
        cfg, params,
        EngineConfig(
            max_decode_batch=2, page_size=4, num_pages=256,
            max_pages_per_seq=32, max_prefill_len=128,
            attn_backend="reference", eos_token_ids=tok.eos_ids,
            adapter_pool_slots=3, adapter_rank=4,
        ),
    )
    loop = EngineLoop(eng, "tiny-ad").start()
    registry = ModelRegistry()
    registry.register(
        ServedModel(name="tiny-ad", loop=loop, tokenizer=tok,
                    context_length=128)
    )
    srv = OpenAIServer(registry)
    app = srv.build_app()
    started = threading.Event()
    holder = {}

    def run():
        aloop = asyncio.new_event_loop()
        asyncio.set_event_loop(aloop)
        runner = __import__("aiohttp").web.AppRunner(app)
        aloop.run_until_complete(runner.setup())
        site = __import__("aiohttp").web.TCPSite(
            runner, "127.0.0.1", 18341
        )
        aloop.run_until_complete(site.start())
        holder["loop"] = aloop
        started.set()
        aloop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(10)
    yield "http://127.0.0.1:18341", cfg, params, eng
    holder["loop"].call_soon_threadsafe(holder["loop"].stop)
    loop.stop(join=False)


class TestAdapterHTTP:
    def test_train_publish_serve_no_restart(
        self, adapter_server, tmp_path
    ):
        """The restartless loop: a LoRA checkpoint written by the
        training checkpointer publishes through POST /v1/adapters and
        serves as ``model@adapter`` over the SAME live engine — no
        restart, no hot-swap, no profile re-apply; /v1/models lists the
        published adapter."""
        import requests

        url, cfg, _params, eng = adapter_server
        lora = _fake_trained_adapter(cfg)
        ckpt_dir = str(tmp_path / "adapter")
        save_checkpoint(
            ckpt_dir, 3, lora, opt_state={"dummy": jnp.zeros(1)},
            lora_scaling=2.0,
        )
        body = {
            "model": "tiny-ad",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 8, "temperature": 0,
        }
        base = requests.post(
            f"{url}/v1/chat/completions", json=body, timeout=120
        )
        assert base.status_code == 200, base.text
        base_text = base.json()["choices"][0]["message"]["content"]
        # publish (registry surface) — servable immediately
        pub = requests.post(
            f"{url}/v1/adapters",
            json={"model": "tiny-ad", "name": "sft-1",
                  "checkpoint": ckpt_dir},
            timeout=120,
        )
        assert pub.status_code == 200, pub.text
        assert pub.json()["id"] == "tiny-ad@sft-1"
        models = requests.get(f"{url}/v1/models", timeout=10).json()
        ids = [m["id"] for m in models["data"]]
        assert "tiny-ad" in ids and "tiny-ad@sft-1" in ids
        adapted = requests.post(
            f"{url}/v1/chat/completions",
            json={**body, "model": "tiny-ad@sft-1"}, timeout=120,
        )
        assert adapted.status_code == 200, adapted.text
        adapted_text = (
            adapted.json()["choices"][0]["message"]["content"]
        )
        assert adapted_text != base_text, (
            "adapter had no effect on generation"
        )
        # adapter-free traffic through the same engine is untouched
        again = requests.post(
            f"{url}/v1/chat/completions", json=body, timeout=120
        )
        assert again.json()["choices"][0]["message"]["content"] == (
            base_text
        )
        # the pool is resident + metrics render from the one owner
        metrics = requests.get(f"{url}/metrics", timeout=10).text
        assert "helix_adapter_resident" in metrics
        assert "helix_adapter_rows_applied_total" in metrics

    def test_unknown_and_hostile_adapters_404(self, adapter_server):
        import requests

        url = adapter_server[0]
        body = {
            "model": "tiny-ad@does-not-exist",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 4,
        }
        r = requests.post(
            f"{url}/v1/chat/completions", json=body, timeout=30
        )
        assert r.status_code == 404
        r = requests.post(
            f"{url}/v1/chat/completions",
            json={**body, "model": "tiny-ad@../../etc/passwd"},
            timeout=30,
        )
        assert r.status_code == 404
        # hostile publish names are rejected before touching disk
        r = requests.post(
            f"{url}/v1/adapters",
            json={"model": "tiny-ad", "name": "../evil",
                  "checkpoint": "/nope"},
            timeout=30,
        )
        assert r.status_code == 400


# ---------------------------------------------------------------------------
# control plane: federation + adapter-affinity routing
# ---------------------------------------------------------------------------


class TestAdapterRouting:
    def test_rr_pick_prefers_resident_adapter(self):
        from helix_tpu.control.router import InferenceRouter

        router = InferenceRouter(ttl_seconds=60)
        for rid, adapters in (
            ("r1", []), ("r2", ["m@tenant-a"]), ("r3", []),
        ):
            router.upsert_from_heartbeat(
                rid, models=["m"], profile_status="running",
                adapters=adapters,
            )
        # the adapter-affinity hint wins among equally loaded runners,
        # repeatedly (no RR rotation away from the warm runner)
        for _ in range(4):
            st = router.pick_runner("m", adapter="tenant-a")
            assert st is not None and st.id == "r2"
        assert router.route_adapter_affinity_hits >= 4
        # no resident runner: ordinary pick still lands somewhere
        assert router.pick_runner("m", adapter="tenant-b") is not None
        # federation surfaces the bounded union for cp /v1/models
        assert router.available_adapters() == ["m@tenant-a"]

    def test_scored_pick_adapter_yields_to_saturation(self):
        from helix_tpu.control.router import (
            InferenceRouter,
            RouterPolicy,
        )

        router = InferenceRouter(
            ttl_seconds=60,
            policy=RouterPolicy(policy="scored"),
        )
        full_sat = {"kv_occupancy": 0.99}
        idle_sat = {"kv_occupancy": 0.1}
        router.upsert_from_heartbeat(
            "warm-but-full", models=["m"], profile_status="running",
            adapters=["m@t1"], saturation=full_sat,
        )
        router.upsert_from_heartbeat(
            "cold-but-idle", models=["m"], profile_status="running",
            adapters=[], saturation=idle_sat,
        )
        st = router.pick_runner("m", adapter="t1")
        # the resident runner is past the FULL threshold: affinity
        # yields, the idle runner takes the request
        assert st is not None and st.id == "cold-but-idle"


# ---------------------------------------------------------------------------
# lint contract 11: one helix_adapter_* owner
# ---------------------------------------------------------------------------


class TestLintContract11:
    def _run_lint(self, root):
        import importlib.util
        import pathlib

        spec = importlib.util.spec_from_file_location(
            "lint_metrics_adapter_test",
            pathlib.Path(__file__).resolve().parent.parent
            / "tools" / "lint_metrics.py",
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.run(str(root))

    def test_repo_is_clean(self):
        import pathlib

        root = pathlib.Path(__file__).resolve().parent.parent
        assert self._run_lint(root) == []

    def test_fixture_violations(self, tmp_path):
        import pathlib
        import shutil

        root = pathlib.Path(__file__).resolve().parent.parent
        fix = tmp_path / "fixture"
        (fix / "helix_tpu" / "engine").mkdir(parents=True)
        (fix / "helix_tpu" / "serving").mkdir(parents=True)
        (fix / "helix_tpu" / "control").mkdir(parents=True)
        (fix / "helix_tpu" / "obs").mkdir(parents=True)
        (fix / "tools").mkdir(parents=True)
        for rel in (
            "helix_tpu/engine/adapters.py",
            "helix_tpu/obs/flight.py",
            "helix_tpu/obs/slo.py",
            "helix_tpu/serving/sched.py",
            "helix_tpu/serving/migration.py",
            "helix_tpu/serving/kv_filestore.py",
            "helix_tpu/serving/engine_loop.py",
            "helix_tpu/serving/openai_api.py",
            "helix_tpu/control/node_agent.py",
            "helix_tpu/control/server.py",
            "helix_tpu/control/router.py",
            "helix_tpu/control/compute.py",
        ):
            shutil.copy(root / rel, fix / rel)
        # violation 1: the family named outside the owner module
        (fix / "helix_tpu" / "serving" / "rogue.py").write_text(
            'NAME = "helix_adapter_rogue_total"\n'
        )
        out = self._run_lint(fix)
        assert any(
            "helix_adapter_" in v and "rogue.py" in v for v in out
        ), out
        # violation 2: a scrape surface that dropped the importer
        api = fix / "helix_tpu" / "serving" / "openai_api.py"
        api.write_text(
            api.read_text().replace("collect_adapter_metrics", "c_a_m")
        )
        out = self._run_lint(fix)
        assert any(
            "collect_adapter_metrics" in v for v in out
        ), out


# ---------------------------------------------------------------------------
# legacy merged path (the single-adapter fallback) — unchanged contract
# ---------------------------------------------------------------------------


@pytest.mark.slow  # full profile-apply + LoRA e2e, ~90 s; adapter math covered in test_training
def test_profile_adapter_changes_generation(tmp_path):
    cfg = ModelConfig.tiny(dtype="float32")
    lora = _fake_trained_adapter(cfg)
    ckpt_dir = str(tmp_path / "adapter")
    save_checkpoint(ckpt_dir, 3, lora, opt_state={"dummy": jnp.zeros(1)})

    prompt = [5, 6, 7, 8]

    def serve(model_block):
        agent = NodeAgent(f"n-{model_block.get('adapter') is not None}")
        profile = ServingProfile.from_dict({
            "name": "adapter-test",
            "requirement": {"chips": 1},
            "models": [model_block],
        })
        try:
            state = agent.apply_profile(profile)
            assert state.status == "running", state.error
            loop = agent.registry.get(model_block["name"]).loop
            loop.stop(join=True)
            return loop.engine.generate(
                [list(prompt)],
                SamplingParams(temperature=0.0, max_tokens=6),
            )[0]
        finally:
            agent.stop()

    base = serve({"name": "tiny-base", "engine": dict(ECFG)})
    adapted = serve({
        "name": "tiny-base", "engine": dict(ECFG),
        "adapter": ckpt_dir, "adapter_scale": 4.0,
    })
    assert len(adapted) == 6
    assert adapted != base, "adapter had no effect on generation"


def test_missing_adapter_is_loud(tmp_path):
    agent = NodeAgent("n-missing")
    profile = ServingProfile.from_dict({
        "name": "bad-adapter",
        "requirement": {"chips": 1},
        "models": [{
            "name": "tiny-base", "engine": dict(ECFG),
            "adapter": str(tmp_path / "nope"),
        }],
    })
    try:
        state = agent.apply_profile(profile)
        assert state.status == "failed"
        assert "adapter checkpoint not found" in (state.error or "")
    finally:
        agent.stop()
