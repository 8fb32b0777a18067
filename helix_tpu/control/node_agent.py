"""TPU node agent: applies serving profiles and heartbeats to the control plane.

The single-process TPU replacement for the reference's on-node stack
(``SURVEY.md`` §2.2/§3.3): compose-manager (``composemgr/manager.go:161``
``Apply``: pull -> down old -> up -> poll health), inference-proxy (model ->
container port routing) and sandbox-heartbeat (30s POST with GPU inventory).
Here "apply" means: diff the assigned profile against running Engines, tear
down removed models, build added ones (load weights -> HBM, optionally
int8), register them in the ModelRegistry the OpenAI surface routes by, and
publish state through the same lifecycle strings the router gates on
(assigning | loading | starting | running | failed).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
import traceback
from typing import Callable, Optional

log = logging.getLogger("helix.node_agent")

from helix_tpu.control.profile import ProfileModel, ServingProfile
from helix_tpu.device.detect import detect_accelerators
from helix_tpu.obs import trace as obs_trace
from helix_tpu.obs.canary import CanaryProber, canary_enabled
from helix_tpu.obs.flight import SATURATION_KEYS
from helix_tpu.serving.registry import ModelRegistry, ServedModel


@dataclasses.dataclass
class ApplyState:
    status: str = "assigning"       # assigning|loading|starting|running|failed
    profile_name: str = ""
    models: list = dataclasses.field(default_factory=list)
    error: str = ""
    progress: dict = dataclasses.field(default_factory=dict)  # model -> phase

    def to_dict(self):
        return dataclasses.asdict(self)


def seeded_params(model_cfg, seed: int, int8: bool, mesh=None):
    """A checkpoint-less model's weights from ``seed``.  int8 trees are
    built tensor by tensor, born quantized and (under a mesh) sharded; a
    float tree is returned unplaced, for the caller to shard."""
    import jax

    from helix_tpu.models.llama import init_params, param_logical_axes

    shardings = None
    if int8 and mesh is not None:
        from helix_tpu.ops.quant import quantized_logical_axes
        from helix_tpu.parallel.sharding import sharding_tree

        shardings = sharding_tree(
            mesh, quantized_logical_axes(param_logical_axes(model_cfg))
        )
    return init_params(
        model_cfg, jax.random.PRNGKey(seed), int8=int8, shardings=shardings
    )


def _build_served_model(pm: ProfileModel, mesh=None) -> ServedModel:
    """Realise one ProfileModel as a ServedModel (engine or embedder).

    The profile's ``mesh:`` block is realised here: a multi-chip or
    offset MeshSpec becomes a ``jax.sharding.Mesh`` over its device slice,
    weights load sharded (shard-wise host->HBM), and the Engine's KV pool +
    forward shard over it — the TPU analogue of compose pinning a vLLM
    service to ``device_ids`` with ``--tensor-parallel-size``
    (``design/sample-profiles/8xH100-vllm.yaml``,
    ``api/pkg/runner/composeparse/parse.go:49-102``).
    """
    import jax

    from helix_tpu.serving.tokenizer import load_tokenizer

    tokenizer = load_tokenizer(pm.checkpoint, pm.name)

    if mesh is None and (pm.mesh.num_devices > 1 or pm.mesh.device_offset > 0):
        from helix_tpu.device.mesh import build_mesh

        mesh = build_mesh(pm.mesh)

    if pm.kind == "vision-embedding":
        # vision-RAG pooling worker (reference: Qwen3-VL-Embedding as a
        # vLLM --runner pooling service, 8xH100-vllm.yaml:15-43)
        from helix_tpu.models.vision_embed import VisionEmbeddingRunner

        vembedder = VisionEmbeddingRunner.build(pm, tokenizer)
        if mesh is not None:
            dev = mesh.devices.flat[0]
            vembedder.params = jax.device_put(vembedder.params, dev)
            vembedder.vparams = jax.device_put(vembedder.vparams, dev)
        return ServedModel(
            name=pm.name, loop=None, tokenizer=tokenizer,
            kind="vision-embedding", embedder=vembedder,
            context_length=pm.context_length,
        )

    if pm.kind == "embedding":
        from helix_tpu.models.bge import EmbeddingRunner

        embedder = EmbeddingRunner.build(pm, tokenizer)
        if mesh is not None:
            # encoders are small: no intra-model sharding, but commit the
            # weights to the slice's first device so embed traffic stays
            # off other models' chips (computation follows committed data)
            dev = mesh.devices.flat[0]
            embedder.params = jax.device_put(embedder.params, dev)
        return ServedModel(
            name=pm.name, loop=None, tokenizer=tokenizer,
            kind="embedding", embedder=embedder,
            context_length=pm.context_length,
        )

    from helix_tpu.engine.engine import Engine, EngineConfig
    from helix_tpu.models.common import CATALOG, ModelConfig
    from helix_tpu.models.llama import init_params
    from helix_tpu.ops.quant import quantize_params
    from helix_tpu.serving.engine_loop import EngineLoop
    from helix_tpu.serving.sched import SchedConfig

    t_load = time.monotonic()
    vision_runner = None
    # int8 on the way to the device: a 7-8B model's bf16 weights do not
    # fit a 16 GB chip even transiently, so both text-model sources
    # below quantize tensor by tensor (``streamed``); only the vision
    # branch still quantizes a resident bf16 tree
    want_int8 = pm.quantization == "int8"
    streamed = want_int8 and pm.kind != "vision"
    if pm.kind == "vision":
        from helix_tpu.models.qwen2_vl import (
            VisionConfig,
            init_vision_params,
            load_qwen2_vl,
        )
        from helix_tpu.serving.vision import VisionRunner

        if pm.checkpoint:
            # mesh-aware load: text tower placed shard-wise, vision tower
            # committed whole to the slice's first device (see
            # ``models.qwen2_vl.load_qwen2_vl``)
            model_cfg, vcfg, params = load_qwen2_vl(pm.checkpoint, mesh=mesh)
            model_cfg = dataclasses.replace(model_cfg, name=pm.name)
            vparams = params.pop("visual")
        else:
            model_cfg = ModelConfig.tiny(
                name=pm.name, attention_bias=True, mrope_sections=(2, 3, 3),
                vocab_size=max(getattr(tokenizer, "vocab_size", 512), 512),
            )
            params = init_params(model_cfg, jax.random.PRNGKey(0))
            vcfg = VisionConfig.tiny(hidden_size=model_cfg.hidden_size)
            vparams = init_vision_params(vcfg, jax.random.PRNGKey(1))

        def special(tok, name, default):
            fn = getattr(tok, "_special", None)
            v = fn(name) if fn else None
            return v if v is not None else default

        vision_runner = VisionRunner(
            vcfg, vparams,
            image_pad_id=special(tokenizer, "<|image_pad|>", 260 + 4),
            vision_start_id=special(tokenizer, "<|vision_start|>", 260 + 5),
            vision_end_id=special(tokenizer, "<|vision_end|>", 260 + 6),
        )
    elif pm.checkpoint:
        from helix_tpu.models.loader import load_params

        # mesh-aware load: each stacked tensor is placed with its
        # NamedSharding as it is built, so host->HBM transfer is shard-wise
        # and no chip ever holds the full bf16 model
        if pm.model_overrides:
            raise ValueError(
                "model_overrides apply to random-init dev models only; "
                f"{pm.name!r} loads a checkpoint whose architecture is "
                "fixed by its config.json"
            )
        model_cfg, params = load_params(
            pm.checkpoint, mesh=mesh, quantize=streamed
        )
        model_cfg = dataclasses.replace(model_cfg, name=pm.name)
    else:
        model_cfg = CATALOG.get(pm.name)
        for key in ("rope_scaling", "window_rope_scaling"):
            if isinstance(pm.model_overrides.get(key), dict):
                # a profile writes it as a mapping; the config keeps it
                # hashable
                pm.model_overrides[key] = tuple(
                    sorted(pm.model_overrides[key].items()))
        for key in ("layer_types", "held_experts"):
            if isinstance(pm.model_overrides.get(key), list):
                pm.model_overrides[key] = tuple(pm.model_overrides[key])
        if model_cfg is not None and pm.model_overrides:
            # overrides apply to catalog configs too (shrink a catalog
            # architecture for a dev mesh) — silently ignoring them
            # would random-init the full-size model instead
            model_cfg = dataclasses.replace(
                model_cfg, **pm.model_overrides
            )
        if model_cfg is None:
            model_cfg = ModelConfig.tiny(
                name=pm.name, **pm.model_overrides
            )
        params = seeded_params(model_cfg, pm.seed, streamed, mesh)
    if mesh is not None and not pm.checkpoint and not streamed:
        # checkpoint branches place shard-wise inside the loaders; the
        # random-init branches shard here. The text tower (llama layout for
        # every kind) shards Megatron-style; a vision tower stays whole,
        # committed to the slice's first device so image encode traffic
        # never lands on another model's chips.
        from helix_tpu.models.llama import param_logical_axes
        from helix_tpu.parallel.sharding import shard_params

        params = shard_params(params, mesh, param_logical_axes(model_cfg))
        if vision_runner is not None:
            vision_runner.vparams = jax.device_put(
                vision_runner.vparams, mesh.devices.flat[0]
            )
    if want_int8 and not streamed:
        if mesh is not None:
            from helix_tpu.models.llama import param_logical_axes
            from helix_tpu.ops.quant import quantized_logical_axes
            from helix_tpu.parallel.sharding import sharding_tree

            out_sh = sharding_tree(
                mesh, quantized_logical_axes(param_logical_axes(model_cfg))
            )
            params = jax.jit(
                quantize_params, donate_argnums=0, out_shardings=out_sh
            )(params)
        else:
            params = jax.jit(quantize_params, donate_argnums=0)(params)

    if pm.adapter:
        # LoRA adapter serving: graft a trained adapter onto the base —
        # the low-rank matmul rides every projection at apply time
        # (ops/quant.py maybe_dequant_dense), so int8 bases work and the
        # adapter stays hot-swappable with the profile
        from helix_tpu.training.checkpoint import restore_checkpoint
        from helix_tpu.training.lora import (
            lora_logical_axes,
            merge_lora_into_params,
        )

        # NOTE: this restores the full checkpoint (incl. the optimizer
        # moments, ~2x adapter bytes) — orbax partial restore needs a
        # matching target tree we don't have before reading; adapters
        # are small next to base weights, so the extra I/O is accepted
        restored = restore_checkpoint(pm.adapter)
        if restored is None:
            raise ValueError(
                f"adapter checkpoint not found at {pm.adapter!r}"
            )
        lora_params = restored["lora_params"]
        # serve at the strength the adapter was TRAINED at (alpha/rank,
        # stored in the checkpoint); an explicit profile adapter_scale
        # overrides
        scaling = pm.adapter_scale
        if scaling is None:
            scaling = float(restored.get("lora_scaling") or 0) or 1.0
        if mesh is not None:
            from helix_tpu.parallel.sharding import shard_params

            lora_params = shard_params(
                lora_params, mesh, lora_logical_axes(lora_params)
            )
        params = merge_lora_into_params(
            params, lora_params, scaling=scaling
        )

    ekw = dict(pm.engine)
    if pm.context_length and "max_model_len" not in ekw:
        # honour the profile's context_length (the vLLM --max-model-len
        # analogue): cap requests there and make sure one sequence's page
        # table can actually hold that many tokens
        ekw["max_model_len"] = pm.context_length
        ps = ekw.get("page_size", 16)
        need_pages = -(-pm.context_length // ps)
        if ekw.get("max_pages_per_seq", 128) < need_pages:
            ekw["max_pages_per_seq"] = need_pages
        if ekw.get("num_pages", 2048) < need_pages + 1:
            ekw["num_pages"] = need_pages + 1
    if "decode_steps_per_sync" not in ekw and jax.default_backend() == "tpu":
        # fuse decode steps so steady-state decode fetches tokens once
        # per window, not once per token (the cost of a fetch, and so
        # this default, is not measured on the current chip)
        ekw["decode_steps_per_sync"] = 8
    import os as _os_env

    spec_env = _os_env.environ.get("HELIX_SPEC_TOKENS", "")
    if spec_env:
        # operator-level speculative-decoding override for EVERY engine
        # this node serves: >0 turns on prompt-lookup drafting with that
        # many draft tokens per slot, 0 forces it off even when the
        # profile enables it (the documented contract — so it must beat
        # profile-set spec_tokens too, not just fill the default)
        n_spec = int(spec_env)
        ekw["spec_tokens"] = max(n_spec, 1)
        ekw["enable_spec_decode"] = n_spec > 0
    from helix_tpu.engine.adapters import adapter_pool_slots_env

    adapter_slots = adapter_pool_slots_env()
    if adapter_slots is not None:
        # operator-level multi-LoRA pool override for EVERY engine this
        # node serves (the HELIX_SPEC_TOKENS contract): >=2 slots turn
        # the batched adapter path on, 0 forces it off even where a
        # profile enables it
        ekw["adapter_pool_slots"] = adapter_slots
    mpps_env = _os_env.environ.get("HELIX_MAX_PAGES_PER_SEQ", "")
    if mpps_env:
        # operator-level per-sequence page-table cap for EVERY engine
        # this node serves (same operator-beats-profile contract as
        # HELIX_SPEC_TOKENS — it must also beat the context_length
        # derived bump above).  On a tiered engine (ctx_hot_pages>0)
        # this caps DEVICE-resident pages per sequence while
        # max_model_len may exceed it; on a fully-resident engine it
        # caps the whole sequence.
        ekw["max_pages_per_seq"] = max(1, int(mpps_env))
    hot_env = _os_env.environ.get("HELIX_CTX_HOT_PAGES", "")
    if hot_env:
        # operator-level tiered-KV override for EVERY engine this node
        # serves (ISSUE 20): >0 keeps that many attention-hot tail
        # pages in HBM and streams the demoted cold middle from the
        # host pool each step; 0 forces fully-resident even where a
        # profile enables tiering
        ekw["ctx_hot_pages"] = max(0, int(hot_env))
    from helix_tpu.engine.residency import host_pool_budget_bytes

    host_budget = host_pool_budget_bytes(default=-1)
    if host_budget >= 0:
        # host-RAM KV tier budget for EVERY engine this node serves
        # (spill-instead-of-die + preemption-by-swap); same
        # operator-beats-profile contract as HELIX_SPEC_TOKENS, and 0
        # forces the tier off
        ekw["host_pool_bytes"] = host_budget
    ecfg = EngineConfig(
        eos_token_ids=tuple(tokenizer.eos_ids),
        **ekw,
    )
    jax.block_until_ready(params)
    log.info(
        "model %s: weights on device in %.1fs (%s, %.2f GB)",
        pm.name, time.monotonic() - t_load,
        "int8" if want_int8 else model_cfg.dtype,
        sum(x.nbytes for x in jax.tree.leaves(params)) / 1e9,
    )
    engine = Engine(model_cfg, params, ecfg, mesh=mesh)
    t_warm = time.monotonic()
    engine.warmup()   # compile prefill/decode before the model goes routable
    log.info(
        "model %s: warmup() in %.1fs", pm.name, time.monotonic() - t_warm
    )
    fs_dir = _os_env.environ.get("HELIX_FILESTORE_KV_DIR", "")
    if fs_dir:
        # persistent filestore KV tier (ISSUE 14): the bottom rung of
        # the residency ladder — full prefix pages persist across
        # restarts (content-addressed, checksummed, tenant-quota'd).
        # Multihost hosts arm it too: the step plan carries each
        # admission's cached_tokens and followers verify their restore
        # matched, so point every host at the SAME filestore directory
        # (the PR 14 cluster-wide tier) and disk hits stay in sync.
        from helix_tpu.serving.kv_filestore import filestore_for_engine

        engine.kv_filestore = filestore_for_engine(
            fs_dir, model_cfg, engine.cache_cfg
        )
    role = pm.multihost.get("role", "")
    if role == "leader":
        # broadcast one StepPlan per engine step for follower hosts
        # (plan-driven SPMD over DCN; serving/multihost_serving.py);
        # with HELIX_MH_CHECKPOINT_DIR set the leader also checkpoints
        # its host-side state through the filestore so a standby can
        # take over (ISSUE 17)
        from helix_tpu.serving.multihost_serving import (
            PlanLeader,
            checkpoint_store_from_env,
        )

        engine = PlanLeader(
            engine,
            checkpoint_store=checkpoint_store_from_env(),
            name=pm.name,
        )
    elif role == "follower":
        # this host executes the leader's step plans — no local HTTP
        # traffic, no local scheduler/drafter/clock
        from helix_tpu.serving.multihost_serving import (
            FollowerLoop,
            HTTPFeed,
            checkpoint_store_from_env,
        )

        follower = FollowerLoop(
            engine, HTTPFeed(pm.multihost["leader_url"], pm.name),
            name=pm.name,
            # standby followers arm auto-promotion (profile beats the
            # HELIX_MH_STANDBY env default, which FollowerLoop reads
            # when this is None)
            standby=pm.multihost.get("standby"),
            checkpoint_store=checkpoint_store_from_env(),
        )

        def _lost(err):
            # the typed resync ladder (ISSUE 17): the error already
            # carries the reason's operator action (RESYNC_ACTIONS) —
            # a leader restart wants a profile re-apply, falling off
            # the ring wants a fresh-replica restart, a rejected
            # handoff checkpoint wants the shared checkpoint dir fixed
            log.error(
                "follower %s (%s) lost plan lockstep [reason=%s]: %s",
                follower.follower_id, pm.name,
                follower.resync_reason or "fatal", err,
            )

        follower.on_lost_lockstep = _lost
        follower.start()
        return ServedModel(
            name=pm.name, loop=None, tokenizer=tokenizer, kind=pm.kind,
            context_length=(
                pm.context_length or model_cfg.max_position_embeddings
            ),
            vision=vision_runner, follower=follower,
        )
    loop = _make_engine_loop(engine, pm)
    return ServedModel(
        name=pm.name, loop=loop, tokenizer=tokenizer, kind=pm.kind,
        context_length=pm.context_length or model_cfg.max_position_embeddings,
        vision=vision_runner,
    )


def _make_engine_loop(engine, pm: ProfileModel):
    """Build + start the EngineLoop around an engine for one model.

    Shared by the profile apply path and standby promotion (ISSUE 17):
    a promoted standby wraps the same engine replica in a fresh
    PlanLeader and needs an identical loop around it — same admission
    bounds, same SLO targets, same scheduler config."""
    import os

    from helix_tpu.serving.engine_loop import EngineLoop
    from helix_tpu.serving.sched import SchedConfig

    def _bound(env_name, cast=int):
        v = os.environ.get(env_name, "")
        return cast(v) if v else None

    return EngineLoop(
        engine, name=pm.name,
        # admission bounds (shed -> 429 instead of queue-rot); unbounded
        # unless the operator sets them — see README "Robustness knobs"
        max_queue_depth=_bound("HELIX_MAX_QUEUE_DEPTH"),
        max_queued_tokens=_bound("HELIX_MAX_QUEUED_TOKENS"),
        # KV-pressure degradation ladder (ISSUE 6): queued requests shed
        # with a typed kv_exhausted 503 after this many seconds without
        # pages, and admission stalls longer than the stall threshold
        # preempt the newest decoder by swap — see README "KV tiering &
        # preemption"
        admission_timeout=_bound("HELIX_ADMISSION_TIMEOUT", float),
        preempt_stall_seconds=_bound(
            "HELIX_PREEMPT_STALL_SECONDS", float
        ),
        # per-tenant SLO observability (ISSUE 7): the profile declares
        # the targets (slo: {ttft_p95_seconds, queue_wait_p95_seconds,
        # goodput_floor_tps}); top-K bounding and burn windows are
        # operator knobs (HELIX_TENANT_TOP_K, HELIX_SLO_BURN_WINDOWS,
        # read inside obs/slo.py when left None here)
        slo_targets=pm.slo,
        tenant_top_k=_bound("HELIX_TENANT_TOP_K"),
        # the scheduler (ISSUE 9): policy, class default, per-tenant DRR
        # weights, bounded tenant queues and the adaptive prefill budget
        # come from the profile's slo.sched block; HELIX_SCHED_* env
        # knobs beat the profile (the HELIX_SPEC_TOKENS contract) — see
        # README "Scheduling"
        sched_config=SchedConfig.from_profile(pm.slo),
    ).start()


class DelegatingRegistry:
    """Stable registry handle whose backing store apply_profile can swap
    (plain ModelRegistry <-> ResidencyManager) without re-wiring the HTTP
    server that holds the reference."""

    def __init__(self, inner=None):
        self.inner = inner or ModelRegistry()

    def get(self, name):
        return self.inner.get(name)

    def names(self):
        return self.inner.names()

    def list(self):
        return self.inner.list()

    def register(self, model):
        return self.inner.register(model)

    def unregister(self, name):
        if hasattr(self.inner, "unregister"):
            return self.inner.unregister(name)
        return self.inner.evict(name)


class NodeAgent:
    """Owns the registry + apply loop + heartbeat loop for one TPU host."""

    def __init__(
        self,
        runner_id: str,
        registry: Optional[ModelRegistry] = None,
        build_model: Callable = _build_served_model,
        heartbeat_url: Optional[str] = None,
        heartbeat_interval: float = 30.0,
        address: str = "",
        runner_token: Optional[str] = None,
    ):
        import os as _os

        self.runner_id = runner_id
        self.address = address   # where the control plane can reach our OpenAI surface
        self.registry = DelegatingRegistry(registry)
        self.state = ApplyState()
        self._build = build_model
        self.heartbeat_url = heartbeat_url
        self.heartbeat_interval = heartbeat_interval
        self.runner_token = (
            runner_token
            if runner_token is not None
            else _os.environ.get("HELIX_RUNNER_TOKEN", "")
        )
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        # graceful shutdown (ISSUE 11): once draining, the heartbeat
        # advertises unroutable-for-new-work and the control plane's
        # pick_runner skips this node; drain_deadline_ts feeds the
        # honest Retry-After on a cluster-wide-drain 503
        self.draining = False
        self.drain_deadline_ts = 0.0
        # disaggregated prefill/decode pool role (ISSUE 14): declared by
        # the applied profile, heartbeat-federated; HELIX_POOL_ROLE
        # beats the profile (the HELIX_SPEC_TOKENS operator contract)
        self.profile_role = "mixed"
        self._drain_stats: dict = {}
        self._drain_thread: Optional[threading.Thread] = None
        # fired AFTER a control-plane-requested drain completes (ISSUE
        # 12 autoscale scale-down / operator drain): the CLI wires this
        # to process exit so a drained node actually releases its host
        self.on_drain: Optional[Callable[[], None]] = None
        # trace federation (ISSUE 18): completed spans buffer in the
        # process-wide trace store and ride out on each heartbeat;
        # tests swap in a per-"host" store to prove cross-host stitch
        self.trace_store = obs_trace.default_store()
        if obs_trace.federation_enabled():
            self.trace_store.enable_export()
        # correctness canaries (ISSUE 19): golden probes mint at profile
        # apply, the scheduler replays them through the real serving
        # path, health federates on the heartbeat.  Opt-in
        # (HELIX_CANARY=1) — probes consume real device steps
        self.canary = CanaryProber(
            runner_id=runner_id, models_fn=self._live_models
        )

    # ------------------------------------------------------------------
    def _teardown_all(self):
        inner = self.registry.inner
        if hasattr(inner, "resident_names"):
            for name in inner.resident_names():
                inner.evict(name)
        else:
            for name in list(inner.names()):
                inner.unregister(name)

    def apply_profile(self, profile: Optional[ServingProfile]) -> ApplyState:
        """Diff-apply: never tears down a model the new profile keeps
        (mirrors composemgr's no-prune-mid-swap rule, manager.go:1-23).
        Profiles with a ``residency`` block swap the backing store to the
        HBM-accounted ResidencyManager (lazy load, LRU-evict-idle)."""
        with self._lock:
            if profile is None:
                self._teardown_all()
                self.registry.inner = ModelRegistry()
                self.state = ApplyState(status="running", profile_name="")
                self.profile_role = "mixed"
                return self.state
            self.profile_role = getattr(profile, "role", "mixed")
            errors = profile.validate()
            if errors:
                self.state = ApplyState(
                    status="failed",
                    profile_name=profile.name,
                    error="; ".join(errors),
                )
                return self.state
            self.state = ApplyState(
                status="loading", profile_name=profile.name
            )
            try:
                want = {m.name: m for m in profile.models}
                if profile.residency:
                    self._apply_residency(profile, want)
                else:
                    if hasattr(self.registry.inner, "resident_names"):
                        self._teardown_all()
                        self.registry.inner = ModelRegistry()
                    for name in list(self.registry.names()):
                        if name not in want:
                            self.registry.unregister(name)
                    for name, pm in want.items():
                        if self.registry.get(name) is None:
                            self.state.progress[name] = "loading"
                            t0 = time.monotonic()
                            served = self._build(pm)
                            self.registry.register(served)
                            self._arm_promotion(served, pm)
                            log.info(
                                "runner %s: model %s built in %.1fs "
                                "(profile %s)",
                                self.runner_id, name,
                                time.monotonic() - t0, profile.name,
                            )
                            self.state.progress[name] = "ready"
                self.state.status = "running"
                # the apply's compile wave is over: drop any step-duration
                # samples the flight recorders banked while it ran.  Loops
                # that kept serving through a hot-swap recorded
                # compile-contended multi-second steps as "clean", which
                # would inflate the watchdog's trailing p99 until the
                # window turned over (flight.FlightRecorder.reset_baseline)
                for served in self._live_models():
                    flight = getattr(
                        getattr(served, "loop", None), "flight", None
                    )
                    if flight is not None:
                        flight.reset_baseline()
                # correctness canaries (ISSUE 19): mint golden probes
                # for the freshly built models and start the scheduler.
                # Never fails an apply — a canary bug must not take a
                # healthy runner out of service
                if canary_enabled():
                    try:
                        self.canary.mint_models(self._live_models())
                        self.canary.start()
                    except Exception:  # noqa: BLE001 — apply survives
                        log.warning(
                            "runner %s: canary minting failed",
                            self.runner_id, exc_info=True,
                        )
                # multi-host FOLLOWERS execute the leader's step plans
                # and take no HTTP traffic: keep them out of the
                # routable model list the router feeds on
                self.state.models = sorted(
                    name for name, pm in want.items()
                    if pm.multihost.get("role", "") != "follower"
                )
            except Exception as e:  # noqa: BLE001 — reported via status
                self.state.status = "failed"
                self.state.error = f"{e}\n{traceback.format_exc(limit=5)}"
                log.warning(
                    "runner %s: profile %s apply failed: %s",
                    self.runner_id, profile.name, e,
                )
            return self.state

    def _apply_residency(self, profile: ServingProfile, want: dict) -> None:
        from helix_tpu.device.detect import total_hbm_bytes
        from helix_tpu.engine.residency import (
            ResidencyManager,
            estimate_model_bytes,
        )

        budget = int(
            profile.residency.get("hbm_budget_bytes") or total_hbm_bytes()
        )

        def build(name: str):
            served = self._build(want[name])
            self._arm_promotion(served, want[name])
            return served

        def estimate(name: str) -> int:
            pm = want[name]
            if pm.kind == "embedding":
                return 1 << 28  # encoders are small; flat 256 MiB reservation
            if pm.checkpoint:
                from helix_tpu.models.loader import load_config

                model_cfg = load_config(pm.checkpoint, name=pm.name)
            else:
                from helix_tpu.models.common import CATALOG, ModelConfig

                model_cfg = CATALOG.get(pm.name) or ModelConfig.tiny(name=pm.name)
            return estimate_model_bytes(model_cfg, pm.engine, pm.quantization)

        self._teardown_all()
        mgr = ResidencyManager(budget, build, estimate=estimate)
        for name in want:
            mgr.register_name(name)
        self.registry.inner = mgr
        for name in want:
            self.state.progress[name] = "lazy"

    # ------------------------------------------------------------------
    def _arm_promotion(self, served, pm) -> None:
        """Standby failover (ISSUE 17): when a standby follower's feed
        declares the leader host dead (HELIX_MH_PROMOTE_AFTER
        consecutive transient failures, not a typed resync), promote it
        in-process: digest-verified takeover through the filestore
        checkpoint, a fresh EngineLoop around the promoted engine, and
        a registry swap so this host starts taking HTTP traffic."""
        follower = getattr(served, "follower", None)
        if follower is None or not getattr(follower, "standby", False):
            return

        def _promote(f):
            self._promote_follower(served, pm, f)

        follower.on_leader_lost = _promote

    def _promote_follower(self, served, pm, follower) -> None:
        from helix_tpu.serving.multihost_serving import (
            promote_follower,
            restore_sched_state,
        )

        t0 = time.monotonic()
        try:
            leader = promote_follower(follower, name=pm.name)
        except Exception as e:  # noqa: BLE001 — typed rungs land here
            # every refused rung degrades to today's resync ladder:
            # nothing was mutated, the operator restarts this host's
            # serving process (ring replay / checkpoint bootstrap) or
            # re-applies the serving profile across the mesh
            log.error(
                "standby promotion for %s refused, still a follower: %s",
                pm.name, e,
            )
            return
        try:
            loop = _make_engine_loop(leader, pm)
            sched_doc = getattr(leader, "_ckpt_sched", None)
            if sched_doc:
                # the checkpoint carried the dead leader's scheduler
                # state (WFQ deficits, tenant queue order); the new
                # loop's scheduler resumes from it instead of resetting
                # every tenant's debt
                restore_sched_state(loop.sched, sched_doc)
            self.registry.register(ServedModel(
                name=pm.name, loop=loop, tokenizer=served.tokenizer,
                kind=served.kind, context_length=served.context_length,
                vision=served.vision,
            ))
            with self._lock:
                if pm.name not in self.state.models:
                    self.state.models = sorted(
                        self.state.models + [pm.name]
                    )
            log.warning(
                "standby %s promoted to plan leader for %s in %.0f ms "
                "(boundary plan %d)",
                follower.follower_id, pm.name,
                (time.monotonic() - t0) * 1000.0, leader._last_plan_idx,
            )
        except Exception as e:  # noqa: BLE001 — surfaced via status
            log.exception("promotion of %s failed after takeover", pm.name)
            with self._lock:
                self.state.error = f"promotion failed: {e}"

    def _live_models(self) -> list:
        """Already-resident ServedModels, without building or blocking.

        On a ResidencyManager-backed registry, ``get()`` lazily BUILDS a
        declared model and ``list()`` waits on the lock that is held
        across whole builds — either would stall the heartbeat thread
        past the router TTL (or force every lazy model resident).
        Snapshot the resident dict lock-free instead; a racing mutation
        raises and yields an empty list for this pass (one lean
        heartbeat beats a stale-evicted runner)."""
        try:
            inner = getattr(self.registry, "inner", self.registry)
            if hasattr(inner, "_resident"):
                return [r.model for r in list(inner._resident.values())]
            return self.registry.list()
        except Exception:  # noqa: BLE001 — callers must never die
            return []

    def saturation_summary(self) -> dict:
        """The compact per-node saturation rollup heartbeated to the
        control plane: exactly the ``obs.flight.SATURATION_KEYS`` schema
        (the control plane renders one ``helix_cp_runner_saturation_*``
        gauge per key).  Aggregates every live engine on this node:
        slots/queue sum, KV occupancy and prefix hit rate pool across
        engines, tokens/s sums the per-engine goodput windows."""
        slots_busy = slots_total = queue_depth = 0
        kv_used = kv_cap = 0
        hits = misses = 0
        drafted = accepted = 0
        host_used = host_budget = 0
        preempted = 0
        prefill_budget = 0
        adapters_resident = 0
        kv_cold_pages = 0
        tps = 0.0
        for m in self._live_models():
            loop = getattr(m, "loop", None)
            if loop is None or not hasattr(loop, "saturation"):
                continue
            sat = loop.saturation()
            slots_busy += sat["slots_busy"]
            slots_total += sat["slots_total"]
            queue_depth += sat["queue_depth"]
            # per-step prefill-admission capacity sums across engines
            # (0 per engine = unbudgeted)
            prefill_budget += sat.get("prefill_budget_tokens", 0)
            tps += sat["tokens_per_sec"]
            eng = loop.engine
            kv_used += getattr(eng, "kv_pages_used", 0)
            kv_cap += getattr(eng, "kv_pages_capacity", 0)
            pc = getattr(eng, "prefix_cache", None)
            if pc is not None:
                hits += pc.hits
                misses += pc.misses
            # speculative-decoding acceptance pools across engines the
            # same way the prefix hit rate does (token-weighted)
            drafted += getattr(eng, "num_spec_drafted_tokens", 0)
            accepted += getattr(eng, "num_spec_accepted_tokens", 0)
            # host KV tier occupancy pools byte-weighted across engines;
            # parked (swapped-out) decoders sum
            hp = getattr(eng, "host_pool", None)
            if hp is not None:
                host_used += hp.used_bytes
                host_budget += hp.budget_bytes
            preempted += len(getattr(eng, "preempted", ()))
            # multi-LoRA adapters resident in HBM pools sum across
            # engines (ISSUE 15) — the router's affinity denominator
            adapters_resident += sat.get("adapters_resident", 0)
            # demoted cold-middle KV pages (tiered long-context, ISSUE
            # 20) sum across engines — host-resident history the router
            # should see as restorable pressure, not free capacity
            kv_cold_pages += sat.get("kv_cold_pages", 0)
        from helix_tpu.testing import faults

        out = {
            "kv_occupancy": round(kv_used / kv_cap, 4) if kv_cap else 0.0,
            "slots_busy": slots_busy,
            "slots_total": slots_total,
            "queue_depth": queue_depth,
            "tokens_per_sec": round(tps, 2),
            "prefix_hit_rate": (
                round(hits / (hits + misses), 4) if hits + misses else 0.0
            ),
            "spec_acceptance_ratio": (
                round(accepted / drafted, 4) if drafted else 0.0
            ),
            "kv_host_occupancy": (
                round(host_used / host_budget, 4) if host_budget else 0.0
            ),
            "preempted_requests": preempted,
            "prefill_budget_tokens": prefill_budget,
            "adapters_resident": adapters_resident,
            "kv_cold_pages": kv_cold_pages,
        }
        # in-flight canary probes ride the real queues but must not
        # look like demand to the autoscaler or the scored router —
        # subtract them from the advertised depth (ISSUE 19)
        out["queue_depth"] = max(
            0, out["queue_depth"] - self.canary.inflight
        )
        # chaos (ISSUE 12): a "saturation" fault rule overrides reported
        # keys so routing/autoscale tests can drive one runner toward
        # apparent KV exhaustion deterministically (schema-filtered —
        # an override can never mint an unknown gauge)
        inj = faults.active()
        if inj is not None:
            over = inj.saturation_override(self.runner_id)
            if over:
                out.update(
                    {k: v for k, v in over.items()
                     if k in SATURATION_KEYS}
                )
        # schema lockstep: emit exactly the shared key set
        return {k: out[k] for k in SATURATION_KEYS}

    def tenant_summary(self) -> dict:
        """The compact per-node tenants rollup heartbeated to the
        control plane: each live engine's bounded top-K block
        (``obs.slo.TENANT_KEYS`` entries) merged across engines —
        counters sum, burn rates take the worst — then re-bounded so
        the node's heartbeat stays top-K + ``__other__`` no matter how
        many engines it serves.  {} when no engine tracks tenants yet
        (a fresh/restarted node — the cp clears any stale rollup)."""
        from helix_tpu.obs.slo import merge_rollups, tenant_top_k_from_env

        rollups = []
        for m in self._live_models():
            slo = getattr(getattr(m, "loop", None), "slo", None)
            if slo is None:
                continue
            try:
                rollups.append(slo.rollup())
            except Exception:  # noqa: BLE001 — heartbeat must never die
                continue
        if not any(r.get("top") for r in rollups):
            return {}
        return merge_rollups(rollups, top_k=tenant_top_k_from_env())

    def adapter_summary(self) -> list:
        """The heartbeat adapter-residency block (ISSUE 15): bounded
        sorted ``model@adapter`` ids currently HBM-resident on this
        node (``engine.adapters.adapter_residency_summary`` over the
        lock-free live-model snapshot — the heartbeat thread never
        blocks on a build)."""
        from helix_tpu.engine.adapters import adapter_residency_summary

        try:
            return adapter_residency_summary(self._live_models())
        except Exception:  # noqa: BLE001 — heartbeat must never die
            return []

    def multihost_summary(self) -> dict:
        """The heartbeat mesh-health block (ISSUE 17): per-model role,
        follower health states / worst lag / takeover counters on
        leaders, applied-seq + resync reason on followers — rendered by
        ``multihost_serving.mh_heartbeat_block`` over the lock-free
        live-model snapshot (the heartbeat thread never blocks on a
        build)."""
        from helix_tpu.serving.multihost_serving import mh_heartbeat_block

        try:
            return mh_heartbeat_block(self._live_models())
        except Exception:  # noqa: BLE001 — heartbeat must never die
            return {}

    def trace_summary(self) -> dict:
        """The heartbeat span block (ISSUE 18): up to
        ``HELIX_TRACE_EXPORT_BATCH`` completed wire spans drained from
        the pending-export ring.  ``{}`` when federation is off or
        nothing is pending, so idle heartbeats stay small."""
        try:
            if not obs_trace.federation_enabled():
                return {}
            spans = self.trace_store.drain_export()
            return {"spans": spans} if spans else {}
        except Exception:  # noqa: BLE001 — heartbeat must never die
            return {}

    def canary_summary(self) -> dict:
        """The heartbeat canary-health block (ISSUE 19): health rung,
        round/mismatch counters and failing axes from the local prober.
        ``{}`` before any probe exists, so idle heartbeats stay small;
        validated server-side (``obs.canary.validate_canary_block``)
        like every other runner-supplied block."""
        try:
            return self.canary.summary()
        except Exception:  # noqa: BLE001 — heartbeat must never die
            return {}

    def ctx_summary(self) -> dict:
        """The heartbeat context-cache block (ISSUE 20): handle/token
        counts and create/hit/quota counters from this node's registry
        (the same per-root singleton the OpenAI surface serves, via
        ``serving.context_cache.context_cache_for``).  ``{}`` while the
        cache is empty and idle, so idle heartbeats stay small;
        validated server-side (``validate_ctx_block``) like every other
        runner-supplied block."""
        try:
            import os

            from helix_tpu.serving.context_cache import context_cache_for

            return context_cache_for(
                os.environ.get("HELIX_FILESTORE_KV_DIR", "")
            ).stats_block()
        except Exception:  # noqa: BLE001 — heartbeat must never die
            return {}

    def pool_role(self) -> str:
        """This node's disaggregation pool role: HELIX_POOL_ROLE beats
        the applied profile's ``role:`` (unknown values degrade to the
        profile's, then to mixed — the control plane re-sanitises)."""
        import os

        env = os.environ.get("HELIX_POOL_ROLE", "").strip().lower()
        if env in ("prefill", "decode", "mixed"):
            return env
        return self.profile_role or "mixed"

    def heartbeat_payload(self) -> dict:
        """Wire format mirrors the reference heartbeat body
        (``api/cmd/sandbox-heartbeat/main.go:28-60``): id + accelerator
        inventory + profile state + the saturation summary the control
        plane federates into ``helix_cp_runner_saturation_*``."""
        import os
        import shutil

        disk = shutil.disk_usage("/")
        return {
            "runner_id": self.runner_id,
            "address": self.address,
            # binds this node to its autoscaler compute row (ISSUE 12):
            # provisioned hosts export HELIX_INSTANCE_ID in their
            # startup script; the ComputeManager resolves it by row id
            # or provider id so heartbeats keep the row alive
            "instance_id": os.environ.get("HELIX_INSTANCE_ID", ""),
            "accelerators": [a.to_dict() for a in detect_accelerators()],
            "profile": {
                "name": self.state.profile_name,
                "status": self.state.status,
                "models": self.registry.names(),
                "error": self.state.error,
                "progress": self.state.progress,
            },
            "saturation": self.saturation_summary(),
            "tenants": self.tenant_summary(),
            # multi-LoRA residency federation (ISSUE 15): bounded
            # `model@adapter` ids resident in any live engine's HBM
            # pool — the scored router's adapter-affinity signal
            "adapters": self.adapter_summary(),
            # mesh health federation (ISSUE 17): leader/follower roles,
            # per-follower lag ladder states and takeover counters —
            # /v1/cluster/status renders mesh health from this
            "multihost": self.multihost_summary(),
            # disaggregation pool role (ISSUE 14): the router schedules
            # prefill and decode pools independently off this
            "role": self.pool_role(),
            # trace federation (ISSUE 18): completed spans for the cp's
            # stitched per-trace store ride the beat — bounded,
            # droppable, validated server-side like the tenant rollup
            "traces": self.trace_summary(),
            # correctness-canary health (ISSUE 19): the rung the
            # corruption-aware router steers on
            "canary": self.canary_summary(),
            # context-cache registry (ISSUE 20): pinned-prefix handle /
            # token counts for /v1/cluster/status capacity views
            "ctx": self.ctx_summary(),
            # drain state (ISSUE 11): the router stops routing NEW work
            # here the beat after this flips; in-flight work finishes or
            # migrates before the deadline
            "draining": self.draining,
            "drain_deadline_ts": self.drain_deadline_ts,
            "disk": {"total": disk.total, "used": disk.used, "free": disk.free},
            "ts": time.time(),
        }

    def _heartbeat_headers(self) -> dict:
        return (
            {"X-Runner-Token": self.runner_token} if self.runner_token else {}
        )

    def _post_heartbeat(self):
        """One heartbeat POST (used by the loop and by graceful_shutdown
        to announce the drain immediately instead of waiting out the
        interval).  Returns the response or raises."""
        import requests

        return requests.post(
            f"{self.heartbeat_url}/api/v1/runners/"
            f"{self.runner_id}/heartbeat",
            json=self.heartbeat_payload(),
            timeout=10,
            headers=self._heartbeat_headers(),
        )

    def graceful_shutdown(self, drain: Optional[float] = None) -> dict:
        """SIGTERM/rolling-restart path (ISSUE 11): announce ``draining``
        to the control plane NOW (new work reroutes immediately), let
        every engine loop drain in parallel for up to ``drain`` seconds,
        and ship whatever is still unfinished at the deadline to a peer
        runner as request snapshots (the finish -> snapshot+ship -> shed
        ladder).  Returns per-model migration stats for the exit log."""
        from helix_tpu.serving.migration import PeerShipper, drain_seconds

        if self.draining:
            # already draining (e.g. a SIGTERM lands while an
            # assignment-requested drain runs): wait for it rather than
            # double-draining stopped loops
            t = self._drain_thread
            if (
                t is not None
                and t is not threading.current_thread()
                and t.is_alive()
            ):
                t.join(timeout=120.0)
            return dict(self._drain_stats)
        if drain is None:
            drain = drain_seconds()
        self.draining = True
        self.drain_deadline_ts = time.time() + drain
        if self.heartbeat_url:
            try:
                self._post_heartbeat()
            except Exception:  # noqa: BLE001 — drain proceeds regardless
                log.warning(
                    "runner %s: could not announce drain to the "
                    "control plane", self.runner_id,
                )
        shipper = None
        if self.heartbeat_url:
            shipper = PeerShipper(
                self.heartbeat_url, self.runner_id,
                runner_token=self.runner_token,
            )
        loops = [
            (m.name, m.loop)
            for m in self._live_models()
            if getattr(m, "loop", None) is not None
        ]
        # drain every loop CONCURRENTLY (join=False: each engine thread
        # self-drains and exports its own survivors at the deadline)
        for _name, loop in loops:
            if shipper is not None:
                loop.exporter = shipper
            loop.stop(drain=drain, join=False)
        deadline = time.monotonic() + drain + 30.0
        stats = {}
        for name, loop in loops:
            t = getattr(loop, "_thread", None)
            if t is not None and t.is_alive():
                t.join(timeout=max(0.0, deadline - time.monotonic()))
            loop.stop(join=True)   # belt-and-braces: thread must be down
            st = loop.stats().get("migration", {})
            stats[name] = st
            log.info(
                "runner %s: model %s drained (exported=%s failures=%s)",
                self.runner_id, name,
                st.get("exported"), st.get("failures"),
            )
        self.stop()
        self._drain_stats = stats
        return stats

    def _drain_async(self) -> None:
        """Control-plane-requested drain (the assignment poll answered
        ``drain: true`` — autoscale scale-down or an operator POST):
        run the graceful ladder off the heartbeat thread, then hand
        control to ``on_drain`` (the CLI exits the process, releasing
        the host for the autoscaler to terminate)."""
        if self.draining or self._drain_thread is not None:
            return
        log.info(
            "runner %s: control plane requested drain — starting the "
            "graceful ladder", self.runner_id,
        )

        def run():
            try:
                self.graceful_shutdown()
            finally:
                cb = self.on_drain
                if cb is not None:
                    try:
                        cb()
                    except Exception:  # noqa: BLE001 — exit is best-effort
                        pass

        self._drain_thread = threading.Thread(
            target=run, name="helix-drain", daemon=True
        )
        self._drain_thread.start()

    def start_heartbeat(self, poll_assignment: bool = True):
        """30s heartbeat + assignment polling against the control plane
        (the pull-based loop of ``SURVEY.md`` §3.3)."""
        import requests

        headers = self._heartbeat_headers()

        def run():
            while not self._stop.is_set():
                try:
                    r = self._post_heartbeat()
                    if r.status_code != 200:
                        import logging

                        logging.getLogger(__name__).warning(
                            "heartbeat rejected (%s): %s — check "
                            "HELIX_RUNNER_TOKEN", r.status_code,
                            r.text[:200],
                        )
                    if poll_assignment:
                        a = requests.get(
                            f"{self.heartbeat_url}/api/v1/runners/"
                            f"{self.runner_id}/assignment",
                            timeout=10,
                            headers=headers,
                        )
                        if a.status_code == 200:
                            doc = a.json()
                            if doc.get("drain"):
                                # scale-down / operator drain request:
                                # run the graceful ladder; skip profile
                                # churn on a node that is leaving
                                self._drain_async()
                            else:
                                prof = (
                                    ServingProfile.from_dict(
                                        doc["profile"]
                                    )
                                    if doc.get("profile")
                                    else None
                                )
                                name = prof.name if prof else ""
                                if name != self.state.profile_name:
                                    self.apply_profile(prof)
                except Exception:  # noqa: BLE001 — keep beating
                    pass
                self._stop.wait(self.heartbeat_interval)

        self._hb_thread = threading.Thread(
            target=run, name="helix-heartbeat", daemon=True
        )
        self._hb_thread.start()

    def stop(self):
        self._stop.set()
        self.canary.stop()
        for name in list(self.registry.names()):
            self.registry.unregister(name)
