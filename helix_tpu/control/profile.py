"""Serving profiles: declarative model -> mesh-slice layout for a TPU host.

The TPU-native equivalent of the reference's Runner Profiles — "a Docker
Compose YAML of vLLM containers pinned to GPU device IDs"
(``api/pkg/types/runner_profile.go:28-62``, parsed by
``api/pkg/runner/composeparse/parse.go``).  Where a compose profile says
"vllm serve X --tensor-parallel-size 2 on device_ids [0,1]", a serving
profile says "model X on a tp=2 mesh at device offset 0"; the node agent
realises it with in-process Engines instead of ``docker compose up``.

Schema (YAML):

    name: v5e8-llama3-plus-embed
    requirement:            # operator-declared, mirrors ProfileGPURequirement
      chips: 8
      generation: v5e       # "" = any
      min_hbm_bytes: 0
    models:
      - name: meta-llama/Meta-Llama-3-8B-Instruct
        checkpoint: /models/llama3-8b
        kind: chat
        quantization: int8
        mesh: {tp: 4, device_offset: 0}
        engine: {max_decode_batch: 32, num_pages: 4096, page_size: 16}
      - name: BAAI/bge-base-en-v1.5
        kind: embedding
        mesh: {tp: 1, device_offset: 4}

``check_compatibility`` mirrors the 6-constraint check in
``api/pkg/runner/profile/compatibility.go:50-124`` (count, vendor,
architecture, model-match, min VRAM -> min HBM) against a heartbeat's
accelerator inventory, returning structured violations the control plane
surfaces as HTTP 422 detail.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import yaml

from helix_tpu.device.detect import AcceleratorStatus
from helix_tpu.device.mesh import MeshSpec


@dataclasses.dataclass(frozen=True)
class ProfileModel:
    name: str
    checkpoint: Optional[str] = None     # dir with safetensors; None = random-init
    kind: str = "chat"     # chat | embedding | vision | vision-embedding
    quantization: Optional[str] = None   # None | "int8"
    # LoRA adapter serving: an orbax checkpoint dir written by
    # `helix-tpu sft --output` — grafted onto the base weights at apply
    # (the low-rank matmul rides every projection at runtime, so int8
    # bases work too)
    adapter: Optional[str] = None
    # None = apply at the checkpoint's trained alpha/rank scaling; set a
    # number to override
    adapter_scale: Optional[float] = None
    mesh: MeshSpec = dataclasses.field(default_factory=MeshSpec)
    engine: dict = dataclasses.field(default_factory=dict)
    context_length: Optional[int] = None
    # architecture overrides for random-init dev models (no checkpoint):
    # forwarded to ModelConfig.tiny — e.g. {num_experts: 4} builds a toy
    # MoE for ep-mesh dev profiles.  Any field of ``models/common.py::
    # ModelConfig``; among them, by architecture:
    #   layer_types: one of attn | conv | retention | deltanet | window |
    #     mamba2 a layer ("window": sliding-window attention, its K/V a
    #     ring a slot)
    #   hybrid_pattern: INSTEAD of layer_types, layers of ONE branch each, a
    #     character a layer (M Mamba-2, * attention, E experts, - an MLP) with
    #     num_layers their count; mamba_heads, mamba_head_dim, mamba_groups,
    #     mamba_state_size, mamba_chunk, conv_kernel: the Mamba-2 layers'
    #   mlp_gated: false for MLPs and experts with no gate matrix (hidden_act
    #     relu2: squared ReLU); moe_latent_size: the routed experts' own
    #     width, between two projections; attn_rope: false for attention
    #     that rotates nothing
    #   sliding_window: tokens a window layer's query sees, its own among
    #     them (the ring's length)
    #   num_heads / window_num_heads: query heads of a full / a window layer
    #   rope_theta, rope_scaling, rotary_dim: the full layers' rope (a
    #     mapping for rope_scaling; rotary_dim < head_dim rotates a head's
    #     first dims only); window_rope_theta, window_rope_scaling,
    #     window_rotary_dim: the window layers'
    #   attn_gate: a sigmoid gate on the attention's output (ONE value a
    #     head on GQA layers, a value a channel on latent ones and, with
    #     attn_gate_channels, on GQA layers too)
    #   linear_gate: the delta layers' output gate, sigmoid (scaled, on a
    #     norm that takes norm_offset) or silu (on a plain-gain norm)
    #   shared_expert_gate: the shared expert times sigmoid(x w_sg), one
    #     value a token
    #   held_experts: [lo, hi), the routed experts this chip holds as one
    #     expert-parallel rank
    model_overrides: dict = dataclasses.field(default_factory=dict)
    # PRNG seed of a random-init model's weights (no checkpoint)
    seed: int = 0
    # multi-host lockstep serving over DCN (serving/multihost_serving):
    # {} = single host; {"role": "leader"} broadcasts this engine's step
    # plans; {"role": "follower", "leader_url": "http://host0:8000"}
    # executes them on this host's shards of the global mesh; add
    # "standby": true on a follower to arm auto-promotion to leader
    # when the leader host dies (ISSUE 17)
    multihost: dict = dataclasses.field(default_factory=dict)
    # declared SLO targets (obs/slo.py): {ttft_p95_seconds,
    # queue_wait_p95_seconds, goodput_floor_tps} — drives the engine
    # loop's per-model/per-tenant error-budget burn-rate gauges; {} =
    # no targets, no burn gauges
    slo: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: dict) -> "ProfileModel":
        mh = dict(d.get("multihost", {}))
        if mh and mh.get("role") not in ("leader", "follower"):
            raise ValueError(
                "multihost.role must be 'leader' or 'follower'"
            )
        if mh.get("role") == "follower" and not mh.get("leader_url"):
            raise ValueError("multihost followers need leader_url")
        if "standby" in mh:
            # standby followers (ISSUE 17): hot-spare hosts that arm
            # auto-promotion to leader; normalise truthy YAML spellings
            # to a real bool and reject leaders declaring it
            if mh.get("role") != "follower":
                raise ValueError(
                    "multihost.standby is only valid on followers"
                )
            v = mh["standby"]
            if isinstance(v, str):
                v = v.strip().lower() in ("1", "true", "yes", "on")
            mh["standby"] = bool(v)
        return cls(
            name=d["name"],
            checkpoint=d.get("checkpoint"),
            kind=d.get("kind", "chat"),
            quantization=d.get("quantization"),
            adapter=d.get("adapter"),
            adapter_scale=(
                float(d["adapter_scale"])
                if d.get("adapter_scale") is not None
                else None
            ),
            mesh=MeshSpec.from_dict(d.get("mesh", {})),
            engine=dict(d.get("engine", {})),
            context_length=d.get("context_length"),
            model_overrides=dict(d.get("model_overrides", {})),
            seed=int(d.get("seed", 0)),
            multihost=mh,
            slo=dict(d.get("slo", {})),
        )

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "checkpoint": self.checkpoint,
            "kind": self.kind,
            "quantization": self.quantization,
            "adapter": self.adapter,
            "adapter_scale": self.adapter_scale,
            "mesh": self.mesh.to_dict(),
            "engine": dict(self.engine),
            "context_length": self.context_length,
            "model_overrides": dict(self.model_overrides),
            "seed": self.seed,
            "multihost": dict(self.multihost),
            "slo": dict(self.slo),
        }


@dataclasses.dataclass(frozen=True)
class ProfileRequirement:
    chips: int = 1
    generation: str = ""          # "" = any; "v5e" | "v5p" | ...
    min_hbm_bytes: int = 0
    vendor: str = "tpu"

    @classmethod
    def from_dict(cls, d: dict) -> "ProfileRequirement":
        return cls(
            chips=int(d.get("chips", 1)),
            generation=d.get("generation", ""),
            min_hbm_bytes=int(d.get("min_hbm_bytes", 0)),
            vendor=d.get("vendor", "tpu"),
        )

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class ServingProfile:
    name: str
    models: tuple
    requirement: ProfileRequirement = ProfileRequirement()
    # hot-swap group: {"hbm_budget_bytes": N} lets the profile declare MORE
    # models than fit at once; the node agent then serves them through the
    # HBM-accounted residency manager (load-on-demand, LRU-evict-idle) —
    # the reference's multi-model story is compose down/up per swap.
    residency: Optional[dict] = None
    # disaggregated prefill/decode pool role (ISSUE 14): "prefill" nodes
    # compute prompts and ship KV snapshots to the decode pool; "decode"
    # nodes run latency-sensitive decode (and import handoffs); "mixed"
    # (the default) serves both — exactly the pre-pools behaviour.
    # Heartbeat-federated; HELIX_POOL_ROLE on the node beats the profile.
    role: str = "mixed"

    @classmethod
    def from_yaml(cls, text: str) -> "ServingProfile":
        d = yaml.safe_load(text)
        return cls.from_dict(d)

    @classmethod
    def from_dict(cls, d: dict) -> "ServingProfile":
        role = str(d.get("role", "mixed") or "mixed").strip().lower()
        if role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"profile role must be prefill|decode|mixed, got {role!r}"
            )
        return cls(
            name=d["name"],
            models=tuple(ProfileModel.from_dict(m) for m in d.get("models", [])),
            requirement=ProfileRequirement.from_dict(d.get("requirement", {})),
            residency=d.get("residency"),
            role=role,
        )

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "requirement": self.requirement.to_dict(),
            "models": [m.to_dict() for m in self.models],
            **({"residency": self.residency} if self.residency else {}),
            **({"role": self.role} if self.role != "mixed" else {}),
        }

    def to_yaml(self) -> str:
        return yaml.safe_dump(self.to_dict(), sort_keys=False)

    @property
    def model_names(self) -> list:
        return [m.name for m in self.models]

    def validate(self) -> list:
        """Static sanity: device claims within chip count, no overlap between
        models sharing a host (overlap IS allowed for hot-swap groups —
        flagged only when total concurrent footprint exceeds chips)."""
        errors = []
        seen = set()
        for m in self.models:
            lo = m.mesh.device_offset
            hi = lo + m.mesh.num_devices
            if hi > self.requirement.chips:
                errors.append(
                    f"model {m.name} claims devices [{lo},{hi}) but profile "
                    f"requires only {self.requirement.chips} chips"
                )
            if not m.name or m.name in seen:
                errors.append(f"duplicate or empty model name {m.name!r}")
            seen.add(m.name)
        return errors


@dataclasses.dataclass
class Violation:
    constraint: str
    want: str
    have: str

    def to_dict(self):
        return dataclasses.asdict(self)


def check_compatibility(
    profile: ServingProfile, inventory: list
) -> list:
    """Profile vs a heartbeat's accelerator inventory.

    Returns [] if compatible, else structured violations (mirrors
    ``profile/compatibility.go:50-124`` which 422s with constraint detail).
    ``inventory``: list of AcceleratorStatus or equivalent dicts.
    """

    def field(a, name):
        return getattr(a, name, None) if not isinstance(a, dict) else a.get(name)

    req = profile.requirement
    violations = []
    tpus = [a for a in inventory if field(a, "vendor") == req.vendor]
    if len(tpus) < req.chips:
        violations.append(
            Violation("chips", f">={req.chips} {req.vendor}", str(len(tpus)))
        )
    if req.generation:
        archs = {field(a, "arch") for a in tpus}
        if archs and archs != {req.generation}:
            violations.append(
                Violation("generation", req.generation, ",".join(sorted(archs)))
            )
    if req.min_hbm_bytes:
        have = min((field(a, "total_memory_bytes") or 0 for a in tpus), default=0)
        if have < req.min_hbm_bytes:
            violations.append(
                Violation("min_hbm_bytes", str(req.min_hbm_bytes), str(have))
            )
    return violations
