"""HBM-accounted multi-model residency: in-process hot-swap.

BASELINE.md config 3 ("Llama-3-8B + Phi-3-mini hot-swap on one chip") and
SURVEY.md §7 stage 3: where the reference swaps models by ``docker compose
down/up`` of vLLM containers (weights re-downloaded/re-loaded each time,
minutes), this build keeps models as in-process Engines and swaps by
load/evict against an HBM budget:

- every model's footprint = weight bytes (exact, from the param tree) +
  page-pool bytes (from CacheConfig) + an activation headroom margin;
- ``acquire(name)`` loads on demand, evicting least-recently-used IDLE
  models (never one with in-flight requests) until the budget fits —
  the scheduling decision ``gpu-memory-utilization`` flags approximate in
  vLLM, made exact here by the device layer's HBM numbers;
- eviction stops the engine loop and drops the param/cache references; XLA
  frees the HBM when the arrays die.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Optional

from helix_tpu.serving.registry import ModelRegistry, ServedModel


def tree_bytes(tree) -> int:
    import jax

    return sum(
        x.size * x.dtype.itemsize
        for x in jax.tree.leaves(tree)
        if hasattr(x, "size")
    )


def model_param_count(model_cfg, stored: bool = False) -> int:
    """Architectural parameter count from a config.  By default the
    ACTIVE-per-token shape (for MoE: the experts a token is routed to,
    plus the shared ones), the right numerator for decode MFU: each
    generated token moves ~2 FLOPs per active parameter through the MXU.
    ``stored=True`` counts every expert: what the weights occupy."""
    c = model_cfg
    E = c.hidden_size
    embed = c.vocab_size * E
    if c.is_mla:
        attn = (
            E * c.num_heads * (c.qk_nope_head_dim + c.qk_rope_head_dim)
            + E * (c.kv_lora_rank + c.qk_rope_head_dim)       # wkv_a
            + c.kv_lora_rank * c.num_heads * (
                c.qk_nope_head_dim + c.v_head_dim)            # wkv_b
            + c.num_heads * c.v_head_dim * E                  # wo
            + c.kv_lora_rank                                  # kv_norm
        )
    else:
        attn = (
            E * c.num_heads * c.head_dim                      # wq
            + 2 * E * c.num_kv_heads * c.head_dim             # wk, wv
            + c.num_heads * c.head_dim * E                    # wo
        )
    dense_ffn = 3 * E * c.intermediate_size                   # gate, up, down
    n_moe = c.num_moe_layers if (c.is_mla or stored) else 0
    experts = c.num_experts if stored else c.num_experts_per_tok
    moe_ffn = (
        3 * E * c.expert_width * (experts + c.num_shared_experts)
        + E * c.num_experts                                   # router
    )
    per_layer = attn + 2 * E                                  # + norms
    return embed * (1 if c.tie_word_embeddings else 2) + E + (
        c.num_layers * per_layer
        + (c.num_layers - n_moe) * dense_ffn + n_moe * moe_ffn
    )


def estimate_model_bytes(
    model_cfg,
    engine_kwargs: dict,
    quantization: Optional[str] = None,
    headroom: float = 0.10,
) -> int:
    """Predict a chat model's HBM footprint from its config BEFORE building:
    weight bytes (arch param count x itemsize) + page-pool bytes + headroom.
    The exact-accounting replacement for the reference's deleted GGUF
    memory-estimation package (``api/pkg/memory/estimate.go`` — 'should not
    be used anymore')."""
    from helix_tpu.engine.engine import EngineConfig
    from helix_tpu.engine.kv_cache import CacheConfig

    c = model_cfg
    n_params = model_param_count(c, stored=True)
    import jax.numpy as jnp

    itemsize = 1 if quantization == "int8" else jnp.dtype(c.dtype).itemsize
    weight_bytes = n_params * itemsize
    ecfg = EngineConfig(**engine_kwargs) if engine_kwargs else EngineConfig()
    cache_bytes = ecfg.cache_config(dtype=c.dtype).total_bytes(c)
    return int((weight_bytes + cache_bytes) * (1 + headroom))


def host_pool_budget_bytes(default: int = 0) -> int:
    """Operator-declared host-RAM KV tier budget
    (``HELIX_KV_HOST_POOL_BYTES``), the host-side sibling of the HBM
    budget ``CacheConfig.fit_hbm`` sizes the device pool with.  0 =
    tier disabled."""
    import os

    v = os.environ.get("HELIX_KV_HOST_POOL_BYTES", "")
    return int(v) if v else default


def served_model_bytes(m: ServedModel, headroom: float = 0.10) -> int:
    """Footprint of a live ServedModel: weights + KV pages (+headroom)."""
    total = 0
    if m.loop is not None:
        eng = m.loop.engine
        total += tree_bytes(eng.params)
        total += tree_bytes(eng.cache.carry())  # pools + int8 scale pools
    elif m.embedder is not None:
        total += tree_bytes(m.embedder.params)
    return int(total * (1 + headroom))


@dataclasses.dataclass
class Resident:
    model: ServedModel
    bytes: int
    last_used: float
    loads: int = 0


class ResidencyManager:
    """A ModelRegistry whose ``get`` faults models in against an HBM budget."""

    def __init__(
        self,
        hbm_budget_bytes: int,
        build: Callable[[str], ServedModel],
        estimate: Optional[Callable[[str], int]] = None,
        measure: Callable[[ServedModel], int] = served_model_bytes,
    ):
        """``estimate(name)`` predicts a model's footprint BEFORE building it
        so eviction happens first (mandatory on a real chip — build-then-
        evict would OOM HBM).  Without it, acquire builds first and measures
        (fine on CPU/tests, wrong on device)."""
        self.budget = hbm_budget_bytes
        self._build = build
        self._estimate = estimate
        self._measure = measure
        self._resident: dict[str, Resident] = {}
        self._known: set = set()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._loading: set = set()
        self._load_errors: dict[str, BaseException] = {}
        # bytes held for in-flight prefetches so concurrent acquires can't
        # claim the headroom the prefetch just evicted for
        self._reserved: dict[str, int] = {}
        # metrics
        self.evictions = 0
        self.loads = 0
        # model -> last acquire stall / build duration, in seconds
        self.swap_seconds: dict[str, float] = {}
        self.load_seconds: dict[str, float] = {}

    # -- registry-compatible surface --------------------------------------
    def register_name(self, name: str) -> None:
        self._known.add(name)

    def names(self) -> list:
        return sorted(self._known)

    def resident_names(self) -> list:
        return sorted(self._resident)

    def get(self, name: str) -> Optional[ServedModel]:
        if name not in self._known:
            return None
        return self.acquire(name)

    def list(self) -> list:
        with self._lock:
            return [r.model for _, r in sorted(self._resident.items())]

    def used_bytes(self) -> int:
        with self._lock:
            return self.used_bytes_locked()

    def stats(self) -> dict:
        """Consistent snapshot for /metrics (other threads mutate the dicts
        mid-scrape otherwise). used_bytes includes in-flight prefetch
        reservations — the number admission control actually sees."""
        with self._lock:
            return {
                "loads": self.loads,
                "evictions": self.evictions,
                "used_bytes": self.used_bytes_locked(),
                "budget_bytes": self.budget,
                "swap_seconds": dict(self.swap_seconds),
                "load_seconds": dict(self.load_seconds),
            }

    # -- residency ----------------------------------------------------------
    def _is_idle(self, r: Resident) -> bool:
        loop = r.model.loop
        if loop is None:
            return True
        eng = loop.engine
        return not eng.has_work()

    def _evict_until_fits(self, need: int) -> bool:
        """Evict LRU idle models until ``need`` bytes fit. Lock held."""
        while self.used_bytes_locked() + need > self.budget:
            victims = [
                r
                for r in self._resident.values()
                if self._is_idle(r)
            ]
            if not victims:
                return False
            victim = min(victims, key=lambda r: r.last_used)
            self._evict(victim.model.name)
        return True

    def used_bytes_locked(self) -> int:
        return sum(r.bytes for r in self._resident.values()) + sum(
            self._reserved.values()
        )

    def _evict(self, name: str) -> None:
        r = self._resident.pop(name, None)
        if r is None:
            return
        if r.model.loop is not None:
            r.model.loop.stop(join=False)
        self.evictions += 1

    def prefetch(self, name: str) -> bool:
        """Stage ``name``'s weights in the background so the NEXT acquire
        is (near-)free: evict idle models for headroom now, build+load on a
        daemon thread, publish as resident on completion.  The in-flight
        model keeps decoding throughout — nothing stops until an eviction
        is actually required, and busy models are never evicted (SURVEY §7
        hard part #2: swap latency is weights->HBM load time; overlap it
        with serving instead of stalling the requesting call).

        Returns False when overlap is impossible: unknown name, or the
        headroom cannot be freed without evicting a busy model (the
        subsequent ``acquire`` then does the old synchronous swap)."""
        with self._lock:
            r = self._resident.get(name)
            if r is not None:
                # already warm: refresh LRU standing so the model the
                # operator just asked to keep hot isn't the next victim
                r.last_used = time.monotonic()
                return True
            if name not in self._known or name in self._loading:
                return name in self._loading
            if self._estimate is not None:
                need = self._estimate(name)
                if not self._evict_until_fits(need):
                    return False
                self._reserved[name] = need
            self._loading.add(name)
            self._load_errors.pop(name, None)

        def run():
            t0 = time.monotonic()
            try:
                model = self._build(name)
                need = self._measure(model)
                ok = False
                with self._lock:
                    self._reserved.pop(name, None)
                    # measured > estimated: make room, idle victims only
                    ok = self._evict_until_fits(need)
                    if ok:
                        self._resident[name] = Resident(
                            model=model, bytes=need,
                            last_used=time.monotonic(), loads=1,
                        )
                        self.loads += 1
                        self.load_seconds[name] = (
                            time.monotonic() - t0
                        )
                if not ok:
                    if model.loop is not None:
                        model.loop.stop(join=False)
                    raise MemoryError(
                        f"prefetched model '{name}' ({need >> 20} MiB) no "
                        f"longer fits: resident models busy"
                    )
            except BaseException as e:  # noqa: BLE001 — delivered to waiters
                with self._lock:
                    self._load_errors[name] = e
            finally:
                with self._lock:
                    self._reserved.pop(name, None)
                    self._loading.discard(name)
                    self._cond.notify_all()

        threading.Thread(
            target=run, name=f"helix-prefetch-{name}", daemon=True
        ).start()
        return True

    def acquire(self, name: str) -> ServedModel:
        t_enter = time.monotonic()
        with self._lock:
            # a prefetch in flight for this name: wait for it instead of
            # double-building (the wait IS the swap latency)
            waited = False
            while name in self._loading:
                waited = True
                self._cond.wait(timeout=0.5)
            err = self._load_errors.pop(name, None)
            if err is not None:
                if waited:
                    raise err
                # stale failure from an unattended prefetch: a fresh build
                # may well succeed now — log and fall through to one
                import logging

                logging.getLogger(__name__).warning(
                    "dropping stale prefetch failure for %s: %s", name, err
                )
            r = self._resident.get(name)
            if r is not None:
                r.last_used = time.monotonic()
                self.swap_seconds[name] = time.monotonic() - t_enter
                return r.model
            if self._estimate is not None:
                # device path: predict footprint, evict FIRST, then build
                need = self._estimate(name)
                if not self._evict_until_fits(need):
                    raise MemoryError(
                        f"cannot fit model '{name}' ({need >> 20} MiB) in "
                        f"HBM budget {self.budget >> 20} MiB: all resident "
                        f"models busy"
                    )
                model = self._build(name)
                need = max(need, self._measure(model))
            else:
                # host/test path: build first, measure exactly, then evict
                model = self._build(name)
                need = self._measure(model)
                if not self._evict_until_fits(need):
                    if model.loop is not None:
                        model.loop.stop(join=False)
                    raise MemoryError(
                        f"cannot fit model '{name}' ({need >> 20} MiB) in "
                        f"HBM budget {self.budget >> 20} MiB: all resident "
                        f"models busy"
                    )
            self._resident[name] = Resident(
                model=model, bytes=need, last_used=time.monotonic(), loads=1
            )
            self.loads += 1
            # synchronous swap: the requesting call stalled for the whole
            # build+load — exactly the latency prefetch() exists to hide
            swap = time.monotonic() - t_enter
            self.swap_seconds[name] = swap
            self.load_seconds[name] = swap
            return model

    def evict(self, name: str) -> None:
        with self._lock:
            self._evict(name)

    def touch(self, name: str) -> None:
        with self._lock:
            r = self._resident.get(name)
            if r:
                r.last_used = time.monotonic()
