"""Model configuration shared across families.

One config dataclass covers the decoder families the reference serves via
vLLM compose profiles (Llama-3, Phi-3, Qwen-2/3 — see
``design/sample-profiles/`` and BASELINE.md configs); family-specific
behaviour is expressed as data (activation, norm offsets, qk-norm, soft
caps), not subclasses, so one compiled forward function serves them all.

A model's depth is a sequence of RUNS of one kind of layer
(``ModelConfig.layer_runs``): a kind is a token mixer (attention, a gated
short convolution with a fixed per-sequence state, power retention, whose
per-sequence state is a matrix a kv head, the gated delta rule, whose
state is a matrix a value head and a conv tail, sliding-window
attention, whose state is a ring of its last tokens' K/V, or Mamba-2,
whose state is a float32 array a head and a conv tail) times an FFN
(dense, routed experts, or none: ``hybrid_pattern``'s layers of one branch
run as blocks of a mixer and the feed-forward behind it, if one is).  What a kind with a state IS to the engine, the
cache and the serving layer (its arrays, its look-back, its refusals, its
counters) is its record in ``models/mixers.py::STATE_MIXERS``; this module
knows the kinds by name only.  A dense decoder is one run; DeepSeek-V2 is
two (the leading dense layers, then the expert layers); LFM2 interleaves
three kinds in thirteen, of which the runs that repeat back to back (the
period "attention, three convolutions" four times, "attention, two
convolutions" twice) are executed as one group each: five loop bodies a
forward pass, not thirteen.  Three routers (``models/moe.py::route``):
top-k then softmax (Mixtral), softmax then top-k (DeepSeek), and sigmoid
scores selected on score + a learned bias and weighted without it (LFM2).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

from helix_tpu.models.mixers import (
    PAGE_KINDS, STATE_MIXERS, PageKind, StateMixer,
)


@functools.lru_cache(maxsize=None)
def _pattern_blocks(pattern: str) -> tuple:
    """A pattern of one-branch layers (``ModelConfig.hybrid_pattern``) as
    blocks ``((mixer, ffn), ...)``; read at every launch through
    ``ModelConfig.mixers``, so parsed once a pattern."""
    blocks = []
    for i, ch in enumerate(pattern):
        if ch in "M*":
            blocks.append(["mamba2" if ch == "M" else "attn", "none"])
        elif ch in "E-":
            if not blocks or blocks[-1][1] != "none":
                raise ValueError(
                    f"hybrid_pattern layer {i} ({ch!r}) follows no mixer "
                    "layer: a feed-forward layer is served as the second "
                    "branch of the mixer before it")
            blocks[-1][1] = "moe" if ch == "E" else "dense"
        else:
            raise ValueError(
                f"hybrid_pattern layer {i} is {ch!r}: one of M (Mamba-2), "
                "* (attention), E (experts), - (MLP)")
    return tuple(map(tuple, blocks))


@dataclasses.dataclass(frozen=True)
class LayerRun:
    key: str        # the run's stack in the parameter tree
    mixer: str      # "attn" or a kind of ``STATE_MIXERS``
    moe: bool       # routed experts (else a dense FFN)
    count: int      # layers a repetition
    first: int      # its first layer among its mixer's layers, repetition 0
    step: int       # layers of its mixer in one repetition of its group
    ffn: bool = True    # False: the block is its mixer alone (no feed-forward,
                        # no norm and no weight of one)


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    reps: int       # times the runs are executed, one after the other
    runs: tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float = 500000.0
    # stored as a sorted tuple of (key, value) pairs so the config stays
    # hashable (it keys compiled-function caches); None = no scaling
    rope_scaling: Optional[tuple] = None
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    hidden_act: str = "silu"            # silu | gelu | gelu_tanh
    attention_bias: bool = False        # qkv bias (Qwen2)
    mlp_bias: bool = False
    qk_norm: bool = False               # per-head RMSNorm on q/k (Qwen3)
    logits_soft_cap: Optional[float] = None
    attn_logits_soft_cap: Optional[float] = None
    norm_offset: float = 0.0            # 1.0 for Gemma-style (1+w) RMSNorm
    max_position_embeddings: int = 8192
    dtype: str = "bfloat16"
    # multimodal rope sections (t, h, w) — set => Qwen2-VL-family text tower
    mrope_sections: Optional[tuple] = None
    # --- mixture of experts (Mixtral family); 0 = dense FFN ---
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # per-expert token capacity = factor * tokens * k / num_experts
    # (GShard-style dispatch; overflow tokens fall back to the residual)
    expert_capacity_factor: float = 1.5
    # 0 = dropless: tokens sorted by expert and one grouped product, at
    # every shape (DeepSeek).  > 0 keeps the GShard capacity dispatch.
    # --- DeepSeek-V2 family: fine-grained MoE with shared experts ---
    moe_intermediate_size: int = 0      # routed expert width (0: the FFN's)
    num_shared_experts: int = 0         # one shared MLP of this many widths
    # the shared expert's output times ``sigmoid(x w_sg)``, ONE value a
    # token from the layer's normed input (``w_sg [E, 1]``; Qwen3-Next)
    shared_expert_gate: bool = False
    first_k_dense: int = 0              # leading layers with a dense FFN
    # True: softmax over the top-k logits (Mixtral).  False: softmax over
    # all experts, the top-k probabilities kept as they are (DeepSeek's
    # norm_topk_prob false), times routed_scaling_factor.
    moe_renormalize: bool = True
    routed_scaling_factor: float = 1.0
    # "softmax" (above) or "sigmoid": per-expert sigmoid scores, the top-k
    # chosen on score + ``expert_bias`` (a learned [experts] vector, when
    # ``moe_expert_bias``), weighted by the unbiased scores, renormalised
    # when ``moe_renormalize`` (LFM2)
    moe_scoring: str = "softmax"
    moe_expert_bias: bool = False
    # ``(lo, hi)``: this chip is one expert-parallel rank and holds experts
    # ``[lo, hi)`` of ``num_experts``.  The router scores all of them at the
    # published top-k; the layer computes the part of the sum its own
    # experts give (``models/moe.py``).  None: every expert is here
    held_experts: Optional[tuple] = None
    # SwiGLU clamp, dense FFN and every expert: ``silu(min(gate, limit)) *
    # clip(up, -limit, limit)``; 0 = none
    swiglu_limit: float = 0.0
    # a second pair of norms a layer, on each branch's OUTPUT:
    # ``x + n_b(Mixer(n_a(x)))``, ``x + n_d(FFN(n_c(x)))``
    post_norms: bool = False
    # --- interleaved token mixers (LFM2); None = attention at every layer ---
    # one of "attn" | "conv" | "retention" | "deltanet" | "window" | "mamba2"
    # a layer.  A "conv"
    # layer is a gated short convolution of ``conv_kernel`` taps whose whole
    # state is the last ``conv_kernel - 1`` inputs of the sequence: no pages
    layer_types: Optional[tuple] = None
    conv_kernel: int = 0
    # a "retention" layer is power retention of this degree over GQA-shaped
    # q/k/v with a gate a kv head (``ops/retention.py``): its whole state is
    # a matrix a kv head, whatever the sequence's length: no pages either
    retention_degree: int = 2
    # a "deltanet" layer is the gated delta rule (``ops/deltanet.py``):
    # ``linear_key_heads`` q/k heads of ``linear_key_dim`` serve
    # ``linear_value_heads`` value heads of ``linear_value_dim``, behind a
    # causal depthwise convolution of ``conv_kernel`` taps over q|k|v.  Its
    # state is the conv's tail and a float32 matrix a value head: no pages.
    # The output gate, ``linear_gate``: "sigmoid" is ``linear_gate_scale *
    # sigmoid(z)`` on an RMSNorm a head whose gain takes ``norm_offset``
    # (GigaChat3.5); "silu" is ``silu(z)`` on an RMSNorm a head with a PLAIN
    # gain, whatever ``norm_offset`` is (Qwen3-Next)
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_gate: str = "sigmoid"
    linear_gate_scale: float = 1.0
    linear_norm_eps: float = 1e-6
    # --- multi-head latent attention (MLA); 0 = plain multi-head ---
    kv_lora_rank: int = 0               # compressed KV width, cached
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0           # shared rope key width, cached
    v_head_dim: int = 0
    # compressed query: ``q = n(h W_qa) W_qb`` through this width; 0 = the
    # direct projection (DeepSeek-V2-Lite)
    q_lora_rank: int = 0
    # ``o <- o * sigmoid(h W_g)``, a gate a head and value channel from the
    # layer's input, before the output projection
    attn_gate: bool = False
    # --- a learned sparse-attention indexer in front of MLA (DeepSeek Sparse
    # Attention, ``glm_moe_dsa``); 0 heads = every query reads every key ---
    # ``index_heads`` query heads of ``index_head_dim`` from the compressed
    # query score ONE cached key a token (a LayerNorm'd projection of the
    # layer's input, rope over its first ``qk_rope_head_dim`` dims):
    # ``I[t, s] = sum_j w[t, j] relu(q[t, j] . k[s])``; a query attends the
    # ``index_topk`` keys of largest ``I`` (all of them while it has no
    # more).  The index key is cached in a pool of its own beside the latent
    # pool, under the same page ids (``engine/kv_cache.py``)
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # --- sliding-window attention layers beside full ones (Laguna, Mellum) ---
    # a "window" layer is GQA attention whose query at position i sees keys
    # i - sliding_window < j <= i (the query's own among them): its whole
    # cache is the last ``sliding_window`` tokens' K and V, a fixed RING a
    # sequence in the state pool (a token at position p in ring row p mod
    # sliding_window): no pages.  On the GQA path ``attn_gate`` is ONE value
    # a head, ``o_h <- sigmoid(x W_g)_h o_h``, on both kinds of layer; with
    # ``attn_gate_channels`` one a head AND channel (``W_g [E, heads *
    # head_dim]``: the gate half of Qwen3-Next's doubled ``q_proj``)
    sliding_window: int = 0
    attn_gate_channels: bool = False
    # a window layer's query heads (0: ``num_heads``) and its rope: theta
    # (0: ``rope_theta``), scaling (the full layers' ``rope_scaling`` does
    # NOT carry over) and rotary width
    window_num_heads: int = 0
    window_rope_theta: float = 0.0
    window_rope_scaling: Optional[tuple] = None
    window_rotary_dim: int = 0
    # dims of a head that rope rotates on the full ("attn") layers, the rest
    # pass through; 0: all of ``head_dim``
    rotary_dim: int = 0
    # --- layers of ONE branch (nemotron_h) ---
    # the published pattern, a character a layer, each layer one branch
    # behind one norm and one residual: "M" Mamba-2, "*" attention, "E"
    # routed experts, "-" a dense MLP.  ``num_layers`` counts these.  The
    # program runs BLOCKS: a mixer and the feed-forward that follows it, if
    # one does (``mixers``, ``ffns``: as mathematics ``M`` then ``E`` is the
    # pre-norm block of the other families); a mixer that no feed-forward
    # follows is a block without one
    hybrid_pattern: Optional[str] = None
    # False: an MLP (dense, expert, shared expert) is ``W_down act(W_up x)``,
    # no gate matrix; ``hidden_act`` "relu2" is ``relu(x) ** 2``
    mlp_gated: bool = True
    # > 0: the routed experts live in a latent of this width: ``W_fc1`` before
    # the dispatch, ``W_fc2`` behind the combine; the router and the shared
    # expert read the un-projected input
    moe_latent_size: int = 0
    # False: a GQA layer rotates nothing (the Mamba-2 layers carry position)
    attn_rope: bool = True
    # a "mamba2" layer is a selective state space (``ops/ssd.py``):
    # ``mamba_heads`` heads of ``mamba_head_dim`` channels over a state of
    # ``mamba_state_size`` a channel, ``B`` and ``C`` shared by the heads of
    # one of ``mamba_groups`` groups, behind a causal depthwise convolution
    # of ``conv_kernel`` taps with a bias.  Its state is the conv's tail and
    # a float32 array ``h`` a head: no pages.  ``mamba_chunk``: the block of
    # the chunked form (not of the mathematics)
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_groups: int = 0
    mamba_state_size: int = 0
    mamba_chunk: int = 128
    # --- non-architectural serving metadata ---
    name: str = "unnamed"

    def __post_init__(self):
        if self.hybrid_pattern is not None:
            self._blocks()          # refuses what is not served, by name
            if self.layer_types is not None:
                raise ValueError(
                    f"{self.name}: hybrid_pattern and layer_types both "
                    "name the layers' kinds: give one")

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def is_dsa(self) -> bool:
        """Latent attention behind a learned indexer: a second cached array
        a token (the index key) and a key set that differs by query."""
        return self.index_heads > 0

    def _blocks(self) -> tuple:
        """``hybrid_pattern`` as the blocks the program runs: ``((mixer,
        ffn), ...)``, ``ffn`` one of ``"moe"``, ``"dense"``, ``"none"``."""
        pat = self.hybrid_pattern
        try:
            blocks = _pattern_blocks(pat)
        except ValueError as e:
            raise ValueError(f"{self.name}: {e}") from None
        if len(pat) != self.num_layers:
            raise ValueError(
                f"{self.name}: hybrid_pattern names {len(pat)} layers, "
                f"num_layers {self.num_layers}")
        if "M" in pat and (
                self.mamba_groups <= 0
                or self.mamba_heads % self.mamba_groups):
            raise ValueError(
                f"{self.name}: mamba_groups {self.mamba_groups} does not "
                f"divide mamba_heads {self.mamba_heads}: B and C are shared "
                "by the heads of a group")
        return blocks

    @property
    def mixers(self) -> tuple:
        """The token mixer of every layer (of every BLOCK under
        ``hybrid_pattern``): ``"attn"``, ``"conv"``, ``"retention"``,
        ``"deltanet"``, ``"window"`` or ``"mamba2"``."""
        if self.hybrid_pattern is not None:
            return tuple(m for m, _ in self._blocks())
        return self.layer_types or ("attn",) * self.num_layers

    @property
    def ffns(self) -> tuple:
        """The feed-forward of every layer of ``mixers``: ``"moe"``,
        ``"dense"`` or ``"none"`` (the layer is its mixer alone)."""
        if self.hybrid_pattern is not None:
            return tuple(f for _, f in self._blocks())
        return tuple(
            "moe" if self.num_experts > 0 and i >= self.first_k_dense
            else "dense" for i in range(self.num_layers))

    @property
    def num_attn_layers(self) -> int:
        """Layers with pages: what the page pool's layer axis counts."""
        return self.mixers.count("attn")

    @property
    def num_conv_layers(self) -> int:
        return self.mixers.count("conv")

    @property
    def num_retention_layers(self) -> int:
        return self.mixers.count("retention")

    @property
    def num_deltanet_layers(self) -> int:
        return self.mixers.count("deltanet")

    @property
    def num_window_layers(self) -> int:
        return self.mixers.count("window")

    def heads_of(self, mixer: str) -> int:
        """Query heads of a layer of this kind."""
        if mixer == "window" and self.window_num_heads:
            return self.window_num_heads
        return self.num_heads

    def rope_of(self, mixer: str) -> tuple:
        """``(rotary width, theta, scaling)`` of a layer of this kind: what
        ``ops.rope.rope_frequencies`` takes."""
        if self.is_mla:
            return self.qk_rope_head_dim, self.rope_theta, self.rope_scaling
        if mixer == "window":
            return (self.window_rotary_dim or self.head_dim,
                    self.window_rope_theta or self.rope_theta,
                    self.window_rope_scaling)
        return (self.rotary_dim or self.head_dim, self.rope_theta,
                self.rope_scaling)

    @property
    def mamba_inner(self) -> int:
        """Channels of a Mamba-2 layer's ``x`` (and of its gate ``z``)."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_channels(self) -> int:
        """Channels of the Mamba-2 layer's convolution: x | B | C."""
        return self.mamba_inner + 2 * self.mamba_groups * self.mamba_state_size

    @property
    def deltanet_channels(self) -> int:
        """Channels of the delta layer's convolution: q | k | v."""
        return (2 * self.linear_key_heads * self.linear_key_dim
                + self.linear_value_heads * self.linear_value_dim)

    @property
    def num_held_experts(self) -> int:
        """Routed experts whose weights are on this chip."""
        if self.held_experts is None:
            return self.num_experts
        return self.held_experts[1] - self.held_experts[0]

    @property
    def state_mixer(self) -> Optional[str]:
        """The mixer that keeps a fixed per-sequence state, a key of
        ``STATE_MIXERS`` (one kind a model:
        the state pool has one shape), ``None`` for a model whose memory is
        pages alone."""
        kinds = [m for m in STATE_MIXERS if m in self.mixers]
        if len(kinds) > 1:
            raise ValueError(
                f"{self.name}: layers of {kinds} in one model: the state "
                "pool holds one kind of recurrent state")
        return kinds[0] if kinds else None

    @property
    def num_state_layers(self) -> int:
        """Layers with a fixed per-sequence state: what the state pool's
        layer axis counts."""
        kind = self.state_mixer
        return self.mixers.count(kind) if kind else 0

    @property
    def state_kind(self) -> Optional[StateMixer]:
        """``state_mixer``'s record (``models/mixers.py``), ``None`` for a
        model whose memory is pages alone."""
        return STATE_MIXERS.get(self.state_mixer)

    @property
    def page_kind(self) -> PageKind:
        """The record of what the model's pages hold (``models/mixers.py::
        PAGE_KINDS``), never ``None``: a model with no layer that has pages
        is the K/V kind over zero layers, its page pool of no bytes."""
        return PAGE_KINDS["latent_indexed" if self.is_dsa else
                          "latent" if self.is_mla else "kv"]

    def state_arrays(self) -> tuple:
        """``((shape, dtype), ...)``: one sequence's state in one layer, by
        the mixer's kind (its record's ``arrays``); empty for a model whose
        memory is pages alone."""
        kind = self.state_kind
        return kind.arrays(self) if kind else ()

    @property
    def kv_head_pack(self) -> int:
        """KV heads that share one 128-lane tile of the page pool: a head
        width that divides 128 is stored ``[kv_heads / pack, pack * width]``
        (the same bytes, whole lane tiles), wider heads as they are."""
        d = self.head_dim
        pack = 128 // d if d < 128 and 128 % d == 0 else 1
        return pack if self.num_kv_heads % pack == 0 else 1

    def layer_runs(self) -> tuple:
        """The depth as GROUPS of runs of one kind of layer, in order: a
        group is ``LayerGroup(reps, runs)``, its runs ``LayerRun(key,
        mixer, moe, count, first, step)`` executed one after the other
        ``reps`` times.  A plain run is a group of one run done once; where
        a sequence of runs repeats back to back (a period of the layer
        pattern) it is ONE group, so a forward pass holds one loop body a
        run of the period, not one an occurrence.

        ``key`` is the run's stack in the parameter tree: ``count * reps``
        layers, the layers of repetition ``r`` at ``[r * count, (r + 1) *
        count)``.  ``first`` is the index of its first layer AMONG THE
        LAYERS OF ITS MIXER (the page pool's layer axis for an attention
        run, the state pool's for a conv run) at repetition 0, ``step`` how
        many layers of that mixer one repetition holds.  Models without
        ``layer_types`` keep the two names they always had."""
        kinds = list(zip(self.mixers, self.ffns))
        flat, i = [], 0                       # (mixer, ffn, count)
        while i < len(kinds):
            j = i
            while j < len(kinds) and kinds[j] == kinds[i]:
                j += 1
            flat.append(kinds[i] + (j - i,))
            i = j

        def key(at, moe):
            if (self.layer_types or self.hybrid_pattern) is not None:
                return f"run{at:02d}"
            return "layers" if (moe or not self.num_experts) else (
                "dense_layers")

        groups, seen, i = [], dict.fromkeys(("attn", *STATE_MIXERS), 0), 0
        while i < len(flat):
            # the period starting here that repeats over the most runs
            p, reps = 1, 1
            for q in range(1, (len(flat) - i) // 2 + 1):
                k = 1
                while flat[i + k * q:i + (k + 1) * q] == flat[i:i + q]:
                    k += 1
                if k > 1 and k * q > p * reps:
                    p, reps = q, k
            period = flat[i:i + p]
            step = {m: sum(c for mx, _, c in period if mx == m)
                    for m in seen}
            runs, at = [], dict(seen)
            for j, (mixer, ffn, count) in enumerate(period):
                moe = ffn == "moe"
                runs.append(LayerRun(key(i + j, moe), mixer, moe, count,
                                     at[mixer], step[mixer], ffn != "none"))
                at[mixer] += count
            groups.append(LayerGroup(reps, tuple(runs)))
            for m in seen:
                seen[m] += reps * step[m]
            i += p * reps
        return tuple(groups)

    @property
    def loop_bodies(self) -> int:
        """Loop bodies a forward pass traces and compiles: one a run of a
        group (1 for a dense stack, 2 for DeepSeek-V2-Lite, 5 for LFM2)."""
        return sum(len(g.runs) for g in self.layer_runs())

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def num_moe_layers(self) -> int:
        return self.ffns.count("moe")

    def kv_token_shapes(self) -> tuple:
        """Per-token shapes of the two cached arrays, as the model hands
        them to ``attn_fn``, by the kind of its pages (the record's
        ``token_arrays``): K and V ``(kv_heads, head_dim)`` each, or for
        latent attention the latent and the rope key, behind an indexer
        ``[rope key | index key]``."""
        return self.page_kind.token_arrays(self)

    @classmethod
    def from_hf_config(cls, hf: dict, name: str = "unnamed") -> "ModelConfig":
        """Build from a HuggingFace ``config.json`` dict (Llama/Qwen/Phi/
        Mistral-style decoder configs)."""
        hidden = hf["hidden_size"]
        heads = hf["num_attention_heads"]
        model_type = hf.get("model_type", "llama")
        rs = hf.get("rope_scaling") or {}
        mrope = (
            tuple(rs["mrope_section"]) if "mrope_section" in rs else None
        )
        # mrope is not a frequency scaling; store real scalings as a sorted
        # tuple so the config stays hashable
        rope_scaling = None
        if rs and mrope is None:
            rope_scaling = tuple(sorted(rs.items()))
        family = {"num_experts": hf.get("num_local_experts", 0)}
        if model_type in ("deepseek_v2", "gigachat3_5", "glm_moe_dsa"):
            # ``noaux_tc`` at one group IS the sigmoid router with a
            # selection bias over all the experts at once
            greedy = ("greedy", "noaux_tc")[:1 + (model_type == "glm_moe_dsa")]
            if (hf.get("topk_method", "greedy") not in greedy
                    or (hf.get("n_group") or 1) > 1
                    or (hf.get("topk_group") or 1) > 1
                    or hf.get("moe_layer_freq", 1) != 1):
                raise ValueError(
                    f"{model_type}: only the greedy router over all the "
                    "experts at once (no grouped top-k: topk_method greedy, "
                    "n_group 1) with an expert layer at every layer after "
                    "the dense ones is supported"
                )
            # softmax scores kept as they are, or sigmoid scores selected
            # on score + a learned bias (DeepSeek-V3's; what a config of
            # this form with no scoring_func key means in gigachat3_5)
            scoring = hf.get("scoring_func", {
                "deepseek_v2": "softmax", "gigachat3_5": "sigmoid",
                "glm_moe_dsa": "sigmoid",
            }[model_type])
            if scoring not in ("softmax", "sigmoid"):
                raise ValueError(
                    f"{model_type}: scoring_func {scoring!r} is not "
                    "supported: softmax or sigmoid")
            family = dict(
                num_experts=hf["n_routed_experts"],
                moe_intermediate_size=hf["moe_intermediate_size"],
                num_shared_experts=hf.get("n_shared_experts") or 0,
                first_k_dense=hf.get("first_k_dense_replace", 0),
                moe_renormalize=bool(hf.get("norm_topk_prob", False)),
                routed_scaling_factor=float(
                    hf.get("routed_scaling_factor", 1.0)),
                moe_scoring=scoring,
                moe_expert_bias=scoring == "sigmoid",
                expert_capacity_factor=0.0,
                kv_lora_rank=hf["kv_lora_rank"],
                q_lora_rank=hf.get("q_lora_rank") or 0,
                qk_nope_head_dim=hf["qk_nope_head_dim"],
                qk_rope_head_dim=hf["qk_rope_head_dim"],
                v_head_dim=hf["v_head_dim"],
            )
        if model_type == "gigachat3_5":
            # the hybrid: latent attention at ``full_attention_layers``, the
            # gated delta rule everywhere else; sandwich norms whose gain
            # is stored as an offset from 1; a clamped SwiGLU; a gate on the
            # attention's output.  The multi-token-prediction modules are
            # not layers of the served stack
            if hf.get("layernorm_type", "pre_post") != "pre_post":
                raise ValueError(
                    "gigachat3_5: only layernorm_type pre_post is supported")
            full = set(hf["full_attention_layers"])
            family.update(
                layer_types=tuple(
                    "attn" if i in full else "deltanet"
                    for i in range(hf["num_hidden_layers"])),
                conv_kernel=hf["linear_conv_kernel_dim"],
                linear_key_heads=hf["linear_num_key_heads"],
                linear_value_heads=hf["linear_num_value_heads"],
                linear_key_dim=hf["linear_key_head_dim"],
                linear_value_dim=hf["linear_value_head_dim"],
                linear_gate_scale=float(
                    hf.get("linear_sigmoid_gate_scale", 1.0)),
                linear_norm_eps=float(
                    hf.get("linear_attn_o_norm_eps", 1e-6)),
                attn_gate=bool(hf.get("gated_attention", False)),
                post_norms=True,
                swiglu_limit=float(hf.get("swiglu_limit") or 0.0),
                norm_offset=1.0,
            )
            family.update(cls._held_experts(hf, "gigachat3_5"))
        if model_type == "glm_moe_dsa":
            family.update(cls._glm_moe_dsa_family(hf))
        if model_type == "brumby":
            # every layer is power retention over the Qwen3 block's q/k/v
            # (per-head q/k norms, rope); the config carries no key of the
            # mixer, so the degree is the release's
            family["layer_types"] = ("retention",) * hf["num_hidden_layers"]
        if model_type == "lfm2_moe":
            if hf.get("conv_bias"):
                raise ValueError(
                    "lfm2_moe with conv_bias true is not supported: the "
                    "gated short convolution here has no bias term"
                )
            types = tuple(
                {"conv": "conv", "full_attention": "attn"}[t]
                for t in hf["layer_types"])
            family = dict(
                num_experts=hf["num_experts"],
                moe_intermediate_size=hf["moe_intermediate_size"],
                first_k_dense=hf.get("num_dense_layers", 0),
                moe_renormalize=bool(hf.get("norm_topk_prob", True)),
                routed_scaling_factor=float(
                    hf.get("routed_scaling_factor", 1.0)),
                moe_scoring="sigmoid",
                moe_expert_bias=bool(hf.get("use_expert_bias", False)),
                expert_capacity_factor=0.0,
                layer_types=types,
                conv_kernel=hf["conv_L_cache"],
            )
        if model_type == "laguna":
            family = cls._laguna_family(hf)
            heads = family.pop("num_heads")
        if model_type == "nemotron_h":
            family = cls._nemotron_h_family(hf)
        if model_type == "mellum":
            family = cls._mellum_family(hf)
        if model_type == "qwen3_next":
            family = cls._qwen3_next_family(hf)
        rope_theta = family.pop("rope_theta", None) or hf.get(
            "rope_theta", 10000.0)
        rope_scaling = family.pop("rope_scaling", rope_scaling)
        hidden_act = family.pop("hidden_act", None) or hf.get(
            "hidden_act", "silu")
        return cls(
            **family,
            mrope_sections=mrope,
            vocab_size=hf["vocab_size"],
            hidden_size=hidden,
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=hf.get("num_key_value_heads", heads),
            head_dim=(
                hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
                if "kv_lora_rank" in family
                else hf.get("head_dim") or hidden // heads),
            intermediate_size=hf["intermediate_size"],
            rope_theta=rope_theta,
            rope_scaling=rope_scaling,
            rms_norm_eps=hf.get("rms_norm_eps", hf.get("norm_eps", 1e-5)),
            tie_word_embeddings=hf.get(
                "tie_word_embeddings",
                hf.get("tie_embedding", model_type == "lfm2_moe")),
            hidden_act=hidden_act,
            attention_bias=hf.get("attention_bias", False)
            or model_type == "qwen2",
            mlp_bias=hf.get("mlp_bias", False),
            qk_norm=model_type in ("qwen3", "lfm2_moe", "brumby",
                                   "qwen3_next"),
            max_position_embeddings=hf.get("max_position_embeddings", 8192),
            num_experts_per_tok=hf.get("num_experts_per_tok", 2),
            name=name,
        )

    @staticmethod
    def _glm_moe_dsa_family(hf: dict) -> dict:
        """What ``model_type: glm_moe_dsa`` adds to the latent-attention
        expert family: the sparse-attention indexer in front of every
        layer's attention (DeepSeek Sparse Attention) and rope under
        ``rope_parameters``.  The multi-token-prediction module is not a
        layer of the served stack."""
        def refuse(why):
            raise ValueError(f"glm_moe_dsa: {why}")

        missing = [k for k in ("index_n_heads", "index_head_dim",
                               "index_topk") if not hf.get(k)]
        if missing:
            refuse(f"the indexer's {missing} are not given: model_type "
                   "glm_moe_dsa promises a sparse-attention indexer")
        rope = hf.get("rope_parameters") or {}
        kind = rope.get("rope_type", rope.get("type", "default"))
        if kind != "default" or hf.get("rope_scaling"):
            refuse(f"rope_type {kind!r} is not supported: default (no "
                   "scaling)")
        if not hf.get("rope_interleave", True):
            refuse("only rope_interleave true (pairs (2i, 2i+1)) is "
                   "supported on the latent attention's rope dims")
        if not hf.get("indexer_rope_interleave", True):
            refuse("only indexer_rope_interleave true (pairs (2i, 2i+1)) "
                   "is supported on an index head's rope dims")
        if hf["index_head_dim"] < hf["qk_rope_head_dim"]:
            refuse(f"index_head_dim {hf['index_head_dim']} is under the "
                   f"{hf['qk_rope_head_dim']} dims rope rotates")
        return dict(
            index_heads=hf["index_n_heads"],
            index_head_dim=hf["index_head_dim"],
            index_topk=hf["index_topk"],
            rope_theta=float(rope.get("rope_theta")
                             or hf.get("rope_theta", 10000.0)),
            **ModelConfig._held_experts(hf, "glm_moe_dsa"),
        )

    @staticmethod
    def _held_experts(hf: dict, family: str,
                      key: str = "n_routed_experts") -> dict:
        """The fields of ONE expert-parallel rank, where the config names
        one (``held_experts: [lo, hi]``): ``hf[key]`` is what is loaded,
        ``published_<key>`` what the router scores.  Empty without it."""
        if not hf.get("held_experts"):
            return {}
        lo, hi = hf["held_experts"]
        if hi - lo != hf[key]:
            raise ValueError(
                f"{family}: held_experts {[lo, hi]} are not the "
                f"{hf[key]} of {key}")
        return dict(held_experts=(lo, hi),
                    num_experts=hf["published_" + key])

    # a ``layer_types`` entry, as the kind of layer that serves it
    ATTENTION_KINDS = {"full_attention": "attn", "sliding_attention": "window"}

    @staticmethod
    def _leading_dense(hf: dict, family: str) -> int:
        """How many leading layers ``mlp_layer_types`` calls dense; every
        layer after them has to be sparse."""
        L = hf["num_hidden_layers"]
        mlp = list(hf.get("mlp_layer_types") or ["sparse"] * L)
        dense = 0
        while dense < L and mlp[dense] == "dense":
            dense += 1
        if any(t != "sparse" for t in mlp[dense:]):
            raise ValueError(
                f"{family}: only leading dense layers then expert layers "
                "(mlp_layer_types) are supported")
        return dense

    @staticmethod
    def _rope_of_kind(hf: dict, kind: str, head_dim: int) -> tuple:
        """``(theta, scaling, rotary width)`` of ``rope_parameters[kind]``:
        scaling None for plain rope, the width 0 for the whole head."""
        r = dict((hf.get("rope_parameters") or {}).get(kind) or {})
        theta = float(r.pop("rope_theta", hf.get("rope_theta", 10000.0)))
        width = int(head_dim * r.pop(
            "partial_rotary_factor", hf.get("partial_rotary_factor", 1)))
        plain = r.get("rope_type", "default") in ("default", None)
        return theta, (None if plain else tuple(sorted(r.items()))), (
            0 if width == head_dim else width)

    @staticmethod
    def _laguna_family(hf: dict) -> dict:
        """The fields a ``model_type: laguna`` config sets: sliding-window
        layers beside full ones (``layer_types``), each kind with its own
        query head count (``num_attention_heads_per_layer``) and rope
        (``rope_parameters``: YaRN over half a head on the full layers,
        plain rope over the whole head on the sliding ones), a sigmoid gate
        a head on the attention's output (``gating``), leading dense layers
        then routed experts behind a sigmoid router renormalised over the
        chosen and scaled, and one shared expert."""
        L = hf["num_hidden_layers"]
        kinds = ModelConfig.ATTENTION_KINDS
        types = tuple(kinds[t] for t in hf["layer_types"])
        dense = ModelConfig._leading_dense(hf, "laguna")
        if hf.get("moe_apply_router_weight_on_input"):
            raise ValueError(
                "laguna: moe_apply_router_weight_on_input true is not "
                "supported: the router's weight multiplies an expert's output")
        gating = hf.get("gating", False)
        if gating not in (True, False, None, "per-head"):
            raise ValueError(
                f"laguna: gating {gating!r} is not supported: one sigmoid "
                "gate a head (true or \"per-head\"), or none")
        per_layer = list(hf.get("num_attention_heads_per_layer")
                         or [hf["num_attention_heads"]] * L)
        by_kind = {k: sorted({h for h, t in zip(per_layer, types) if t == k})
                   for k in ("attn", "window")}
        if any(len(v) > 1 for v in by_kind.values()):
            raise ValueError(
                f"laguna: one query head count a kind of layer is "
                f"supported, not {by_kind}")
        head_dim = hf.get("head_dim") or (
            hf["hidden_size"] // hf["num_attention_heads"])
        rope_of = functools.partial(
            ModelConfig._rope_of_kind, hf, head_dim=head_dim)
        theta, scaling, width = rope_of("full_attention")
        w_theta, w_scaling, w_width = rope_of("sliding_attention")
        fx = hf["moe_intermediate_size"]
        shared = hf.get("shared_expert_intermediate_size") or 0
        if shared % fx:
            raise ValueError(
                f"laguna: a shared expert of {shared} is not a whole number "
                f"of routed experts' widths ({fx})")
        family = dict(
            num_heads=(by_kind["attn"] or [hf["num_attention_heads"]])[0],
            window_num_heads=(by_kind["window"] or [0])[0],
            layer_types=types,
            sliding_window=hf.get("sliding_window") or 0,
            rope_theta=theta, rope_scaling=scaling, rotary_dim=width,
            window_rope_theta=w_theta, window_rope_scaling=w_scaling,
            window_rotary_dim=w_width,
            attn_gate=bool(gating),
            num_experts=hf["num_experts"],
            moe_intermediate_size=fx,
            num_shared_experts=shared // fx,
            first_k_dense=dense,
            moe_renormalize=bool(hf.get("norm_topk_prob", True)),
            routed_scaling_factor=float(
                hf.get("moe_routed_scaling_factor", 1.0)),
            moe_scoring="sigmoid",
            moe_expert_bias=False,
            expert_capacity_factor=0.0,
        )
        family.update(ModelConfig._held_experts(hf, "laguna", "num_experts"))
        return family

    @staticmethod
    def _mellum_family(hf: dict) -> dict:
        """The fields a ``model_type: mellum`` config sets: sliding-window
        layers beside full ones (``layer_types``: the period may start on
        either), ONE count of query heads for both kinds, rope over the
        whole head in both at ``rope_parameters``' theta and scaling by kind
        (YaRN on the full layers, plain on the sliding ones), every layer
        after the leading dense ones sparse: a softmax router over all the
        experts, the top-k by probability, renormalised over the chosen
        (``norm_topk_prob``) with no scale, no shared expert.  No key names
        a q/k norm, so there is none (``qk_norm`` is one field away);
        ``max_window_layers`` is a key of the lineage that ``layer_types``
        overrides.  What is not served is refused by name."""
        def refuse(why):
            raise ValueError(f"mellum: {why}")

        kinds = ModelConfig.ATTENTION_KINDS
        unknown = sorted(set(hf["layer_types"]) - set(kinds))
        if unknown:
            refuse(f"layer_types {unknown} are not supported: "
                   f"{sorted(kinds)}")
        types = tuple(kinds[t] for t in hf["layer_types"])
        if len(types) != hf["num_hidden_layers"]:
            refuse("layer_types does not name num_hidden_layers layers")
        if "window" in types and not hf.get("use_sliding_window", True):
            refuse("use_sliding_window false with sliding_attention layers "
                   "in layer_types is not supported: which of the two holds "
                   "is not stated")
        if "window" in types and not hf.get("sliding_window"):
            refuse("sliding_attention layers need sliding_window")
        # a dict under rope_parameters is a kind of layer's rope
        rope = hf.get("rope_parameters") or {}
        stray = sorted(k for k, v in rope.items()
                       if isinstance(v, dict) and k not in kinds)
        if stray:
            refuse(f"rope_parameters {stray} name a kind of layer that is "
                   f"not served: {sorted(kinds)}")
        head_dim = hf.get("head_dim") or (
            hf["hidden_size"] // hf["num_attention_heads"])
        theta, scaling, width = ModelConfig._rope_of_kind(
            hf, "full_attention", head_dim)
        w_theta, w_scaling, w_width = ModelConfig._rope_of_kind(
            hf, "sliding_attention", head_dim)
        return dict(
            layer_types=types,
            sliding_window=hf.get("sliding_window") or 0,
            rope_theta=theta, rope_scaling=scaling, rotary_dim=width,
            window_rope_theta=w_theta, window_rope_scaling=w_scaling,
            window_rotary_dim=w_width,
            num_experts=hf["num_experts"],
            moe_intermediate_size=hf["moe_intermediate_size"],
            first_k_dense=ModelConfig._leading_dense(hf, "mellum"),
            moe_renormalize=bool(hf.get("norm_topk_prob", True)),
            moe_scoring="softmax",
            expert_capacity_factor=0.0,
        )

    @staticmethod
    def _qwen3_next_family(hf: dict) -> dict:
        """The fields a ``model_type: qwen3_next`` config sets: gated
        delta-rule layers (the ``linear_*`` keys) with a full-attention layer
        every ``full_attention_interval`` (layers 3, 7, ... at 4; a
        ``layer_types`` list, where given, says it outright), whose GQA
        heads rotate the first ``partial_rotary_factor`` of their dims, carry
        zero-centred q/k norms and a sigmoid gate a head and channel from a
        doubled ``q_proj``; every norm of the block zero-centred (``1 + w``)
        but the delta layer's output norm, which is plain and gated by
        ``silu(z)``; routed experts in EVERY layer behind a softmax router
        (renormalised over the chosen under ``norm_topk_prob``) beside one
        shared expert under a sigmoid gate of its own.  The multi-token-
        prediction module is not a layer of the served stack.  What is not
        served is refused by name."""
        def refuse(why):
            raise ValueError(f"qwen3_next: {why}")

        L = hf["num_hidden_layers"]
        if hf.get("decoder_sparse_step", 1) != 1:
            refuse(f"decoder_sparse_step {hf['decoder_sparse_step']} is not "
                   "supported: every layer's feed-forward is the experts (1)")
        if hf.get("mlp_only_layers"):
            refuse(f"mlp_only_layers {hf['mlp_only_layers']} is not "
                   "supported: no layer has a dense MLP in place of experts")
        if hf.get("use_sliding_window"):
            refuse("use_sliding_window true is not supported: its attention "
                   "layers are full")
        if hf.get("rope_scaling"):
            refuse(f"rope_scaling {hf['rope_scaling']} is not supported: "
                   "plain rope (null)")
        if hf.get("attention_bias"):
            refuse("attention_bias true is not supported: the gate's half of "
                   "q_proj is a matrix of its own here, with no bias")
        kinds = {"linear_attention": "deltanet", "full_attention": "attn"}
        if hf.get("layer_types"):
            unknown = sorted(set(hf["layer_types"]) - set(kinds))
            if unknown:
                refuse(f"layer_types {unknown} are not supported: "
                       f"{sorted(kinds)}")
            types = tuple(kinds[t] for t in hf["layer_types"])
            if len(types) != L:
                refuse("layer_types does not name num_hidden_layers layers")
        else:
            every = hf.get("full_attention_interval", 4)
            types = tuple("attn" if (i + 1) % every == 0 else "deltanet"
                          for i in range(L))
        head_dim = hf.get("head_dim") or (
            hf["hidden_size"] // hf["num_attention_heads"])
        width = int(head_dim * hf.get("partial_rotary_factor", 1.0))
        fx = hf["moe_intermediate_size"]
        shared = hf.get("shared_expert_intermediate_size") or 0
        if shared % fx:
            refuse(f"a shared expert of {shared} is not a whole number of "
                   f"routed experts' widths ({fx})")
        family = dict(
            layer_types=types,
            rotary_dim=0 if width == head_dim else width,
            norm_offset=1.0,
            attn_gate=True,
            attn_gate_channels=True,
            conv_kernel=hf["linear_conv_kernel_dim"],
            linear_key_heads=hf["linear_num_key_heads"],
            linear_value_heads=hf["linear_num_value_heads"],
            linear_key_dim=hf["linear_key_head_dim"],
            linear_value_dim=hf["linear_value_head_dim"],
            linear_gate="silu",
            linear_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
            num_experts=hf["num_experts"],
            moe_intermediate_size=fx,
            num_shared_experts=shared // fx,
            shared_expert_gate=shared > 0,
            moe_renormalize=bool(hf.get("norm_topk_prob", True)),
            moe_scoring="softmax",
            expert_capacity_factor=0.0,
        )
        family.update(
            ModelConfig._held_experts(hf, "qwen3_next", "num_experts"))
        return family

    # keys of a ``model_type: nemotron_h`` config that no layer of the served
    # stack reads, each with why
    NEMOTRON_H_UNREAD = {
        "rope_theta": "the family's attention applies no rotary embedding",
        "partial_rotary_factor": "no rotary embedding",
        "layer_norm_epsilon": "norm_eps is the RMSNorms' (the same value)",
        "time_step_min": "the initialiser's draw of dt_bias",
        "time_step_max": "the initialiser's draw of dt_bias",
        "time_step_floor": "the initialiser's draw of dt_bias",
        "rescale_prenorm_residual": "the initialiser's scale of out-proj",
        "use_mamba_kernels": "which CUDA kernels the modeling file calls",
        "moe_shared_expert_overlap": "a stream overlap of the training code",
        "num_logits_to_keep": "a generate() argument",
        "mtp_hybrid_override_pattern": "multi-token prediction is not loaded",
        "num_nextn_predict_layers": "multi-token prediction is not loaded",
        "model_type": "chose this branch",
    }

    @staticmethod
    def _nemotron_h_family(hf: dict) -> dict:
        """The fields a ``model_type: nemotron_h`` config sets: layers of ONE
        branch by ``hybrid_override_pattern`` (Mamba-2, attention with no
        rotary embedding, routed experts), ungated MLPs under ``relu2``,
        experts in a latent of ``moe_latent_size`` behind a sigmoid router
        with a selection bias, renormalised and scaled.  What is not served
        is refused by name."""
        def refuse(why):
            raise ValueError(f"nemotron_h: {why}")

        if (hf.get("n_group") or 1) > 1 or (hf.get("topk_group") or 1) > 1:
            refuse("only the router over all the experts at once is "
                   "supported (n_group 1, topk_group 1: no grouped top-k)")
        if hf.get("mamba_proj_bias") or hf.get("use_bias") or hf.get(
                "mlp_bias"):
            refuse("mamba_proj_bias, use_bias and mlp_bias true are not "
                   "supported: no projection here has a bias")
        if not hf.get("use_conv_bias", True):
            refuse("use_conv_bias false is not supported: the Mamba-2 "
                   "convolution here has a bias")
        if hf.get("mamba_hidden_act", "silu") != "silu":
            refuse(f"mamba_hidden_act {hf['mamba_hidden_act']!r} is not "
                   "supported: silu")
        if hf.get("residual_in_fp32"):
            refuse("residual_in_fp32 true is not supported: the residual "
                   "stream is in the model's dtype")
        if hf.get("sliding_window"):
            refuse("sliding_window is not supported with hybrid_override_"
                   "pattern: its attention layers are full")
        inner = hf["mamba_num_heads"] * hf["mamba_head_dim"]
        if "expand" in hf and hf["expand"] * hf["hidden_size"] != inner:
            refuse(f"expand {hf['expand']} x hidden_size is not "
                   f"mamba_num_heads x mamba_head_dim ({inner})")
        if hf["mamba_num_heads"] % hf["n_groups"]:
            refuse(f"n_groups {hf['n_groups']} does not divide "
                   f"mamba_num_heads {hf['mamba_num_heads']}")
        if len(hf["hybrid_override_pattern"]) != hf["num_hidden_layers"]:
            refuse("hybrid_override_pattern does not name num_hidden_layers "
                   "layers")
        fx = hf.get("moe_intermediate_size") or hf["intermediate_size"]
        shared = (hf.get("n_shared_experts") or 0) * (
            hf.get("moe_shared_expert_intermediate_size") or fx)
        if shared % fx:
            refuse(f"a shared expert of {shared} is not a whole number of "
                   f"routed experts' widths ({fx})")
        family = dict(
            hybrid_pattern=hf["hybrid_override_pattern"],
            hidden_act=hf.get("mlp_hidden_act", "relu2"),
            mlp_gated=False,
            attn_rope=False,
            conv_kernel=hf["conv_kernel"],
            mamba_heads=hf["mamba_num_heads"],
            mamba_head_dim=hf["mamba_head_dim"],
            mamba_groups=hf["n_groups"],
            mamba_state_size=hf["ssm_state_size"],
            mamba_chunk=hf.get("chunk_size", 128),
            num_experts=hf.get("n_routed_experts") or 0,
            moe_intermediate_size=fx,
            moe_latent_size=hf.get("moe_latent_size") or 0,
            num_shared_experts=shared // fx,
            moe_renormalize=bool(hf.get("norm_topk_prob", True)),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            moe_scoring="sigmoid",
            moe_expert_bias=True,
            expert_capacity_factor=0.0,
        )
        family.update(ModelConfig._held_experts(hf, "nemotron_h"))
        return family

    @classmethod
    def tiny(cls, **overrides) -> "ModelConfig":
        """A toy config for tests (fast to init/compile on one CPU core)."""
        base = dict(
            vocab_size=256,
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            intermediate_size=128,
            rope_theta=10000.0,
            max_position_embeddings=512,
            name="tiny",
        )
        base.update(overrides)
        return cls(**base)


# Canonical catalogue entries for the BASELINE.md configs — architecture
# hyperparameters only (weights come from HF checkpoints via
# ``models/loader.py``).
LLAMA3_8B = ModelConfig(
    vocab_size=128256,
    hidden_size=4096,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    intermediate_size=14336,
    rope_theta=500000.0,
    rms_norm_eps=1e-5,
    max_position_embeddings=8192,
    name="meta-llama/Meta-Llama-3-8B-Instruct",
)

PHI3_MINI = ModelConfig(
    vocab_size=32064,
    hidden_size=3072,
    num_layers=32,
    num_heads=32,
    num_kv_heads=32,
    head_dim=96,
    intermediate_size=8192,
    rope_theta=10000.0,
    max_position_embeddings=4096,
    name="microsoft/Phi-3-mini-4k-instruct",
)

QWEN2_7B = ModelConfig(
    vocab_size=152064,
    hidden_size=3584,
    num_layers=28,
    num_heads=28,
    num_kv_heads=4,
    head_dim=128,
    intermediate_size=18944,
    rope_theta=1000000.0,
    attention_bias=True,
    max_position_embeddings=32768,
    name="Qwen/Qwen2-7B-Instruct",
)

MIXTRAL_8X7B = ModelConfig(
    vocab_size=32000,
    hidden_size=4096,
    num_layers=32,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    intermediate_size=14336,
    rope_theta=1000000.0,
    max_position_embeddings=32768,
    num_experts=8,
    num_experts_per_tok=2,
    name="mistralai/Mixtral-8x7B-Instruct-v0.1",
)

# DeepSeek-V2-Lite (https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/
# blob/main/config.json): latent attention, one dense layer then 26 of
# 64 routed + 2 shared experts, YaRN rope.  Served on one device with a
# bf16/f32 latent cache; a tp/ep/sp mesh, an int8 cache, adapters and
# tiered residency are refused at engine start (models/llama.py docstring).
DEEPSEEK_V2_LITE = ModelConfig(
    vocab_size=102400,
    hidden_size=2048,
    num_layers=27,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,
    intermediate_size=10944,
    rope_theta=10000.0,
    rope_scaling=tuple(sorted({
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn",
    }.items())),
    rms_norm_eps=1e-6,
    max_position_embeddings=163840,
    num_experts=64,
    num_experts_per_tok=6,
    expert_capacity_factor=0.0,
    moe_intermediate_size=1408,
    num_shared_experts=2,
    first_k_dense=1,
    moe_renormalize=False,
    routed_scaling_factor=1.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    name="deepseek-ai/DeepSeek-V2-Lite",
)

# LFM2-8B-A1B (https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/
# config.json): 18 gated short convolutions and 6 GQA layers of head width
# 64 with q/k norms, two dense FFNs then 32 routed experts top-4 behind a
# sigmoid router with a learned bias, tied embedding.  Its conv state lives
# in a per-slot pool beside the pages; what moves a sequence's pages
# without that state is refused at engine start (engine.py's table).
LFM2_8B_A1B = ModelConfig(
    vocab_size=65536,
    hidden_size=2048,
    num_layers=24,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    intermediate_size=7168,
    rope_theta=1000000.0,
    rms_norm_eps=1e-5,
    tie_word_embeddings=True,
    qk_norm=True,
    max_position_embeddings=128000,
    num_experts=32,
    num_experts_per_tok=4,
    expert_capacity_factor=0.0,
    moe_intermediate_size=1792,
    first_k_dense=2,
    moe_renormalize=True,
    routed_scaling_factor=1.0,
    moe_scoring="sigmoid",
    moe_expert_bias=True,
    layer_types=tuple(
        "attn" if c == "A" else "conv" for c in "ccAcccAcccAcccAcccAccAcc"),
    conv_kernel=3,
    name="LiquidAI/LFM2-8B-A1B",
)

# Brumby-14B-Base (https://huggingface.co/manifestai/Brumby-14B-Base/blob/
# main/config.json): the Qwen3-14B block with every attention layer replaced
# by power retention of degree 2: no page of KV anywhere, a float32 matrix
# state a kv head, layer and slot in the state pool.  What would move or
# share that state is refused at engine start (engine.py's table).
BRUMBY_14B = ModelConfig(
    vocab_size=151936,
    hidden_size=5120,
    num_layers=40,
    num_heads=40,
    num_kv_heads=8,
    head_dim=128,
    intermediate_size=17408,
    rope_theta=1000000.0,
    rms_norm_eps=1e-6,
    qk_norm=True,
    max_position_embeddings=32768,
    layer_types=("retention",) * 40,
    retention_degree=2,
    name="manifestai/Brumby-14B-Base",
)

# GigaChat3.5-432B-A28B (https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B/
# blob/main/config.json): 30 gated delta-rule layers (32 key / 64 value
# heads of 128 behind a 4-tap convolution; a float32 matrix a value head and
# a conv tail in the state pool) beside 10 latent-attention layers with a
# compressed, gated query (a latent page pool beside the state pool), sandwich
# norms stored zero-centred, a clamped SwiGLU, three dense layers then 256
# routed experts top-8 + 1 shared behind a sigmoid router with a selection
# bias.  One chip holds a cut of it as ONE expert-parallel rank
# (``held_experts``, set by the profile); what would move or share the state
# is refused at engine start (engine.py's table).
GIGACHAT35_432B = ModelConfig(
    vocab_size=128256,
    hidden_size=7168,
    num_layers=40,
    num_heads=64,
    num_kv_heads=64,
    head_dim=192,
    intermediate_size=18432,
    rope_theta=100000.0,
    rope_scaling=tuple(sorted({
        "beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 32768,
        "type": "yarn",
    }.items())),
    rms_norm_eps=1e-6,
    norm_offset=1.0,
    post_norms=True,
    swiglu_limit=10.0,
    max_position_embeddings=262144,
    num_experts=256,
    num_experts_per_tok=8,
    expert_capacity_factor=0.0,
    moe_intermediate_size=2048,
    num_shared_experts=1,
    first_k_dense=3,
    moe_renormalize=True,
    routed_scaling_factor=2.5,
    moe_scoring="sigmoid",
    moe_expert_bias=True,
    kv_lora_rank=512,
    q_lora_rank=1536,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    attn_gate=True,
    layer_types=tuple(
        "attn" if i % 4 == 3 else "deltanet" for i in range(40)),
    conv_kernel=4,
    linear_key_heads=32,
    linear_value_heads=64,
    linear_key_dim=128,
    linear_value_dim=128,
    linear_gate_scale=2.0,
    linear_norm_eps=1e-6,
    name="ai-sage/GigaChat3.5-432B-A28B",
)

# Laguna-XS.2 (https://huggingface.co/poolside/Laguna-XS.2/blob/main/
# config.json): ten periods of one full-attention layer (48 query heads, YaRN
# over the first 64 of a head's 128 dims, pages) and three sliding-window
# layers (64 query heads, plain rope, the last 512 tokens' K/V in a ring a
# slot in the state pool: no pages), 8 kv heads of 128 everywhere, a sigmoid
# gate a head on the attention's output, one dense layer then 256 routed
# experts of width 512 top-8 + 1 shared behind a sigmoid router renormalised
# and scaled by 2.5.  One chip holds it as ONE expert-parallel rank
# (``held_experts``, set by the profile); what would move or share a ring is
# refused at engine start (engine.py's table).
LAGUNA_XS2 = ModelConfig(
    vocab_size=100352,
    hidden_size=2048,
    num_layers=40,
    num_heads=48,
    num_kv_heads=8,
    head_dim=128,
    intermediate_size=8192,
    rope_theta=500000.0,
    rope_scaling=tuple(sorted({
        "rope_type": "yarn", "factor": 64, "beta_fast": 64, "beta_slow": 1,
        "original_max_position_embeddings": 4096,
        "attention_factor": 1.4158883083359672,
    }.items())),
    rotary_dim=64,
    rms_norm_eps=1e-6,
    max_position_embeddings=262144,
    num_experts=256,
    num_experts_per_tok=8,
    expert_capacity_factor=0.0,
    moe_intermediate_size=512,
    num_shared_experts=1,
    first_k_dense=1,
    moe_renormalize=True,
    routed_scaling_factor=2.5,
    moe_scoring="sigmoid",
    attn_gate=True,
    layer_types=tuple("attn" if i % 4 == 0 else "window" for i in range(40)),
    sliding_window=512,
    window_num_heads=64,
    window_rope_theta=10000.0,
    name="poolside/Laguna-XS.2",
)

# NVIDIA-Nemotron-3-Super-120B-A12B (https://huggingface.co/nvidia/
# NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json): 88 layers of
# ONE branch each: 40 Mamba-2 mixers (128 heads of 64 over a state of 128, B
# and C shared by the 16 heads of one of 8 groups, a 4-tap convolution with a
# bias: a float32 ``h`` of 4 MB and a conv tail a layer and slot in the state
# pool), 8 attention layers of 32 query over 2 kv heads with NO rotary
# embedding (pages), 40 expert layers: 512 ungated relu2 experts of width
# 2,688 in a latent of 1,024 at top-22 + one shared expert of 5,376 behind a
# sigmoid router with a selection bias, renormalised and scaled by 5.  One
# chip holds a cut of it as ONE expert-parallel rank (``held_experts``, set by
# the profile); what would move or share the state is refused at engine start
# (the kind's record, ``models/mixers.py``).
NEMOTRON3_SUPER_120B = ModelConfig(
    vocab_size=131072,
    hidden_size=4096,
    num_layers=88,
    num_heads=32,
    num_kv_heads=2,
    head_dim=128,
    intermediate_size=2688,
    rope_theta=10000.0,         # published, and read by no layer (attn_rope)
    rms_norm_eps=1e-5,
    hidden_act="relu2",
    mlp_gated=False,
    attn_rope=False,
    max_position_embeddings=262144,
    hybrid_pattern=(
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
    conv_kernel=4,
    mamba_heads=128,
    mamba_head_dim=64,
    mamba_groups=8,
    mamba_state_size=128,
    mamba_chunk=128,
    num_experts=512,
    num_experts_per_tok=22,
    expert_capacity_factor=0.0,
    moe_intermediate_size=2688,
    moe_latent_size=1024,
    num_shared_experts=2,
    moe_renormalize=True,
    routed_scaling_factor=5.0,
    moe_scoring="sigmoid",
    moe_expert_bias=True,
    name="nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16",
)

# Mellum2-12B-A2.5B-Instruct (https://huggingface.co/JetBrains/
# Mellum2-12B-A2.5B-Instruct/blob/main/config.json): seven periods of three
# sliding-window layers (the last 1,024 tokens' K/V in a ring a slot in the
# state pool: no pages) and one full-attention layer (pages), 32 query heads
# over 4 kv heads of 128 in both kinds, the whole head rotated at theta
# 500,000 in both, YaRN on the full layers alone; every layer 64 routed
# experts of width 896 top-8 behind a softmax router renormalised over the
# chosen, no shared expert, no dense layer (``intermediate_size`` is
# published and read by no layer).  One chip holds it whole at int8; what
# would move or share a ring is refused at engine start (the kind's record,
# ``models/mixers.py``).
MELLUM2_12B = ModelConfig(
    vocab_size=98304,
    hidden_size=2304,
    num_layers=28,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    intermediate_size=7168,
    rope_theta=500000.0,
    rope_scaling=tuple(sorted({
        "rope_type": "yarn", "factor": 16, "beta_fast": 32, "beta_slow": 1,
        "original_max_position_embeddings": 8192,
        "attention_factor": 1.2772588722239782,
    }.items())),
    rms_norm_eps=1e-6,
    max_position_embeddings=131072,
    num_experts=64,
    num_experts_per_tok=8,
    expert_capacity_factor=0.0,
    moe_intermediate_size=896,
    moe_renormalize=True,
    moe_scoring="softmax",
    layer_types=tuple("attn" if i % 4 == 3 else "window" for i in range(28)),
    sliding_window=1024,
    window_rope_theta=500000.0,
    name="JetBrains/Mellum2-12B-A2.5B-Instruct",
)

# GLM-5 (https://huggingface.co/zai-org/GLM-5/blob/main/config.json,
# ``model_type: glm_moe_dsa``): 78 layers of latent attention with a
# compressed query (64 heads, nope 192 / rope 64 / value 256 over a latent of
# 512) behind a LEARNED SPARSE-ATTENTION INDEXER (``index_heads`` 32 of
# ``index_head_dim`` 128 from the compressed query against ONE cached index key
# a token; a query past ``index_topk`` 2,048 keys attends the 2,048 of largest
# index score: ``ops/dsa.py``), the index key in a pool of its own beside the
# latent pool (``engine/kv_cache.py``); three dense layers then 256 routed
# experts top-8 + 1 shared behind the sigmoid router with a selection bias
# (``noaux_tc`` at one group), renormalised, times 2.5; rope theta 1e6, pairs
# (2i, 2i+1), no scaling.  One chip holds a cut of it as ONE expert-parallel
# rank (``held_experts``, set by the profile).  Refused by name
# (``from_hf_config``): grouped top-k (``n_group`` / ``topk_group`` > 1),
# ``moe_layer_freq`` != 1, a ``rope_type`` other than default, an indexer key
# missing, ``rope_interleave`` or ``indexer_rope_interleave`` false (the
# pairs are (2i, 2i+1) on both ropes); (engine.py's table): a mesh, an int8 pool, adapters, speculation,
# tiered residency, the host tier, and by call request export / import and the
# KV filestore (the index-key pool is not carried there).  The multi-token-
# prediction module is not loaded.  On the chip: ``chip_smoke_deepseek.py
# --config glm-5-int8``.
GLM5 = ModelConfig(
    vocab_size=154880,
    hidden_size=6144,
    num_layers=78,
    num_heads=64,
    num_kv_heads=64,
    head_dim=256,
    intermediate_size=12288,
    rope_theta=1000000.0,
    rms_norm_eps=1e-5,
    max_position_embeddings=202752,
    num_experts=256,
    num_experts_per_tok=8,
    expert_capacity_factor=0.0,
    moe_intermediate_size=2048,
    num_shared_experts=1,
    first_k_dense=3,
    moe_renormalize=True,
    routed_scaling_factor=2.5,
    moe_scoring="sigmoid",
    moe_expert_bias=True,
    kv_lora_rank=512,
    q_lora_rank=2048,
    qk_nope_head_dim=192,
    qk_rope_head_dim=64,
    v_head_dim=256,
    index_heads=32,
    index_head_dim=128,
    index_topk=2048,
    name="zai-org/GLM-5",
)

# Qwen3-Next-80B-A3B-Instruct (https://huggingface.co/Qwen/
# Qwen3-Next-80B-A3B-Instruct/blob/main/config.json, ``model_type:
# qwen3_next``): twelve periods of three gated delta-rule layers (16 key / 32
# value heads of 128 behind a 4-tap convolution; a float32 matrix a value head
# and a conv tail in the state pool; the output ``silu(z)`` times a PLAIN-gain
# norm) and one gated attention layer (16 query heads over 2 kv heads of 256,
# rope over the first 64 dims at theta 1e7, zero-centred q/k norms, a sigmoid
# gate a head and channel: GQA pages beside the state pool); in EVERY layer 512
# routed experts of width 512 top-10 behind a softmax router renormalised over
# the chosen, plus one shared expert of 512 under a sigmoid gate a token;
# zero-centred norms (``1 + w``).  ``intermediate_size`` is published and read
# by no layer.  One chip holds a cut of it as ONE expert-parallel rank
# (``held_experts``, set by the profile); what would move or share the state is
# refused at engine start (the kind's record, ``models/mixers.py``).  Refused
# by name (``from_hf_config``): ``decoder_sparse_step`` != 1, ``mlp_only_
# layers``, ``use_sliding_window``, a ``rope_scaling``, ``attention_bias``.
# The multi-token-prediction module is not loaded.  On the chip:
# ``chip_smoke_deepseek.py --config qwen3-next-80b-a3b-int8``.
QWEN3_NEXT_80B = ModelConfig(
    vocab_size=151936,
    hidden_size=2048,
    num_layers=48,
    num_heads=16,
    num_kv_heads=2,
    head_dim=256,
    intermediate_size=5120,
    rope_theta=10000000.0,
    rotary_dim=64,
    rms_norm_eps=1e-6,
    norm_offset=1.0,
    qk_norm=True,
    max_position_embeddings=262144,
    num_experts=512,
    num_experts_per_tok=10,
    expert_capacity_factor=0.0,
    moe_intermediate_size=512,
    num_shared_experts=1,
    shared_expert_gate=True,
    moe_renormalize=True,
    moe_scoring="softmax",
    attn_gate=True,
    attn_gate_channels=True,
    layer_types=tuple(
        "attn" if i % 4 == 3 else "deltanet" for i in range(48)),
    conv_kernel=4,
    linear_key_heads=16,
    linear_value_heads=32,
    linear_key_dim=128,
    linear_value_dim=128,
    linear_gate="silu",
    linear_norm_eps=1e-6,
    name="Qwen/Qwen3-Next-80B-A3B-Instruct",
)

CATALOG = {
    m.name: m
    for m in (LLAMA3_8B, PHI3_MINI, QWEN2_7B, MIXTRAL_8X7B,
              DEEPSEEK_V2_LITE, LFM2_8B_A1B, BRUMBY_14B, GIGACHAT35_432B,
              LAGUNA_XS2, NEMOTRON3_SUPER_120B, MELLUM2_12B, GLM5,
              QWEN3_NEXT_80B)
}
