"""The deployment's environment-variable reference, in one place.

The reference loads one giant envconfig ``ServerConfig`` whose struct
tags generate the ``serve --help`` env reference
(``api/pkg/config/config.go:11-38``, ``serve.go:78,102``).  This module
is the same single source of truth for helix-tpu: every HELIX_* knob the
runtime reads, with description and default — rendered by
``helix-tpu config-reference`` and asserted complete by tests (a knob
read anywhere in the tree must be documented here).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class EnvVar:
    name: str
    description: str
    default: str = ""
    section: str = "general"


ENV_REFERENCE: tuple = (
    # -- server ----------------------------------------------------------
    EnvVar(
        "HELIX_DB_DSN",
        "Control-plane database location: a filesystem path to the "
        "consolidated SQLite file. A postgres:// DSN is recognised and "
        "rejected with a pointer at the SQLite deployment story (the "
        "reference runs GORM/Postgres; we run one-box SQLite with "
        "cross-entity transactions).",
        section="server",
    ),
    # -- accelerator -----------------------------------------------------
    EnvVar(
        "HELIX_PEAK_FLOPS",
        "Peak accelerator FLOP/s used as the denominator of the runner's "
        "helix_mfu_estimate gauge. Unset: the v5e bf16 peak (197e12) on "
        "TPU backends, no MFU gauge elsewhere.",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_SPEC_TOKENS",
        "Speculative decoding override for every engine this node "
        "serves: >0 enables prompt-lookup drafting with that many draft "
        "tokens per slot per verify call, 0 forces speculation off even "
        "where a profile enables it. Unset: the profile's "
        "enable_spec_decode/spec_tokens settings apply. A latent-"
        "attention model (DeepSeek-V2-Lite) is not served with "
        "speculation, nor is one whose sequences carry a state (Mamba-2 "
        "layers among them) or a ring of K/V a slot (sliding-window "
        "layers: the laguna and mellum families), nor one with a sparse-"
        "attention indexer (glm_moe_dsa: latent attention's refusal): "
        "enabling it is refused at profile apply "
        "(UnsupportedForModel).",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_TOKEN_BUCKETS",
        "Comma-separated token-bucket ladder for the unified ragged "
        "device step's prefill segment (e.g. '64,192,512,2048'). Each "
        "admission wave / prefill chunk pads its flat token axis up to "
        "the smallest rung that fits, so the ladder trades compiled "
        "step shapes (one per rung used, watch "
        "helix_compiled_step_shapes) against padding waste (watch "
        "helix_prefill_padding_ratio). The top rung is always clamped "
        "to max_prefill_len. Unset: powers of two from page_size to "
        "max_prefill_len.",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_KV_HOST_POOL_BYTES",
        "Host-RAM KV tier budget (bytes) for every engine this node "
        "serves: prefix-cache evictions spill page contents to pinned "
        "host buffers instead of dying (restored + re-adopted when a "
        "later prompt shares the prefix), and running decoders become "
        "preemptible by page swap (Engine.preempt). Overrides a "
        "profile's engine.host_pool_bytes; 0 forces the tier off. "
        "Unset: the profile setting applies (default off).",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_ADMISSION_TIMEOUT",
        "Seconds a request may wait for KV pages before it is shed with "
        "a typed 503 (code kv_exhausted, Retry-After) instead of aging "
        "silently in the queue. While admission has been starved longer "
        "than this, NEW arrivals fast-fail the same way before SSE "
        "headers commit. Applies to queued and preempted-parked "
        "requests. Unset: no deadline (requests wait up to the 600 s "
        "queue reaper).",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_PREEMPT_STALL_SECONDS",
        "Admission stall threshold for preemption-by-swap: when the "
        "wait queue has been KV-starved this long, the engine loop "
        "swaps the newest/largest running decoder out to the host KV "
        "tier (exact resume later) instead of letting the whole queue "
        "age out. Needs HELIX_KV_HOST_POOL_BYTES > 0. Unset: never "
        "preempt.",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_DRAIN_SECONDS",
        "Graceful-shutdown drain window (node agent SIGTERM/SIGINT "
        "path): the heartbeat flips to draining immediately (the router "
        "stops sending new work), in-flight requests keep generating "
        "this many seconds, and whatever is still unfinished at the "
        "deadline is exported as request snapshots to a peer runner "
        "instead of shed (finish -> snapshot+ship -> shed ladder).",
        default="10",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MIGRATION_TIMEOUT",
        "Cross-runner migration timeout in seconds: bounds each "
        "snapshot ship during drain AND how long an imported request "
        "waits for its stream to be claimed via /v1/migrate/resume "
        "before the peer aborts the orphan.",
        default="30",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MIDSTREAM_FAILOVER",
        "Set to 1 to arm the control plane's SSE-aware dispatch path: "
        "a runner death PAST the first streamed byte continues the "
        "client stream on a surviving runner (resume-from-snapshot "
        "after a clean drain, else deterministic replay-from-prompt "
        "with already-delivered text elided) with exactly-once token "
        "delivery for greedy/seeded requests. Unset/0: mid-stream "
        "death surfaces as an in-band error frame (the PR 2 "
        "behaviour).",
        section="server",
    ),
    EnvVar(
        "HELIX_POOL_DISAGG",
        "Set to 1 to enable disaggregated prefill/decode at the control "
        "plane: streaming prompts dispatch to a prefill-pool runner "
        "that computes the prompt, ships the KV snapshot + sampler "
        "state to a decode-pool peer, and the stream resumes there "
        "(greedy and seeded outputs bit-identical to colocated "
        "serving). Every failure rung falls back toward colocated "
        "serving — prefill runner serves locally on a failed ship, the "
        "decode pool re-prefills on a failed handoff. Needs runners "
        "declaring role: prefill and decode (profile role: or "
        "HELIX_POOL_ROLE). Unset/0: colocated serving.",
        section="server",
    ),
    EnvVar(
        "HELIX_POOL_ROLE",
        "This node's disaggregation pool role (prefill | decode | "
        "mixed), heartbeat-federated to the control plane. Beats the "
        "applied profile's role: declaration (the HELIX_SPEC_TOKENS "
        "operator contract). Ordinary traffic avoids prefill-pool "
        "runners while any decode/mixed runner serves the model; the "
        "prefill handoff picks strictly from the prefill pool. Unset: "
        "the profile's role (default mixed).",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_XFER_ATTEMPT_TIMEOUT",
        "Per-attempt timeout in seconds for one KV snapshot ship (a "
        "POST /v1/migrate/import to a peer runner) — drain migration "
        "and disaggregated prefill handoffs both obey it, so one slow "
        "peer cannot wedge a drain.",
        default="10",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_XFER_MAX_ATTEMPTS",
        "Rounds over the candidate peer set a KV snapshot ship makes "
        "before giving up (each round tries every model-matching "
        "target once; rounds back off exponentially).",
        default="3",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_XFER_BACKOFF_BASE",
        "Base seconds of the capped exponential backoff between KV "
        "ship rounds (round n sleeps min(base * 2^n, cap)).",
        default="0.1",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_XFER_BACKOFF_CAP",
        "Cap seconds of the KV ship backoff.",
        default="2.0",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_XFER_DEADLINE",
        "Hard total deadline in seconds for one KV snapshot transfer "
        "(all attempts + backoffs + the disagg handler's wait for "
        "prefill completion). Past it the ship is abandoned "
        "(helix_xfer_deadline_exceeded_total) and the request degrades "
        "to local serving. Unset: HELIX_MIGRATION_TIMEOUT.",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_ADAPTER_POOL_SLOTS",
        "Continuous multi-LoRA serving override for every engine this "
        "node serves (the HELIX_SPEC_TOKENS contract — beats the "
        "profile's engine.adapter_pool_slots): >=2 slots arm the "
        "batched adapter path (one resident base model serves many "
        "`model@adapter` tenants through a stacked HBM pool, slot 0 "
        "reserved for the zero identity adapter; the pool shape "
        "compiles once at warmup, so publishing an adapter later "
        "needs no restart or recompile), 0 forces it off even where a "
        "profile enables it. Unset: the profile setting applies "
        "(default off). Not supported for mrope (VL) engines; on "
        "multi-host meshes the pool runs on every host (adapter ids "
        "ride the step plan and followers stage residency before the "
        "step), so publish adapters to the leader and followers as a "
        "pair. A latent-attention model (DeepSeek-V2-Lite) and a model "
        "with sliding-window or Mamba-2 layers refuse a pool at profile "
        "apply (UnsupportedForModel).",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_ADAPTER_HOST_POOL_BYTES",
        "Byte budget for the host rung of the adapter residency "
        "ladder (decoded LoRA adapter trees awaiting an HBM pool "
        "slot; LRU over filestore-backed entries — an adapter whose "
        "only copy is the host one is never evicted). Cold adapters "
        "promote filestore -> host on the async prefetch worker and "
        "host -> HBM at admission. Default 268435456 (256 MiB); 0 "
        "disables the bound.",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_ADAPTER_PREFETCH",
        "Async adapter prefetch (ISSUE 15): on (default), a cold "
        "adapter's filestore->host load runs on a background worker "
        "kicked at submit/admission, overlapping the request's queue "
        "wait — an engine step never blocks on an adapter load. "
        "0/false forces synchronous loads (debug/tests).",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_FILESTORE_KV_DIR",
        "Root directory of the persistent filestore KV tier (the "
        "bottom rung of the residency ladder: HBM -> host RAM -> peer "
        "-> filestore). Freshly prefilled full prefix pages persist "
        "here (content-addressed by prefix-chain digest, namespaced by "
        "model + KV geometry, blake2b-checksummed) and restore across "
        "process restarts — an agent fleet's shared system prompt "
        "survives a rolling deploy without recomputing. Corrupt or "
        "missing blobs degrade to recompute with a typed counter "
        "(helix_filestore_kv_corrupt_total), never an error. Point it "
        "at a shared filesystem to share prefixes across runners. "
        "Unset: tier off. Multi-host meshes arm it too: point the "
        "leader and every follower at the SAME directory — the step "
        "plan carries each admission's cached_tokens and followers "
        "verify their restore matched the leader's.",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_FILESTORE_KV_QUOTA_BYTES",
        "Per-tenant write quota for the filestore KV tier in bytes "
        "(the PR 7 tenant identity is charged at write-through). Past "
        "it new blobs are rejected with a typed counter "
        "(helix_filestore_kv_quota_rejects_total); reads are never "
        "gated. 0/unset: unlimited.",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MAX_PAGES_PER_SEQ",
        "Per-sequence page-table capacity for EVERY engine this node "
        "serves (operator-beats-profile, the HELIX_SPEC_TOKENS "
        "contract — it also beats the bump derived from a profile's "
        "context_length). On a tiered engine (ctx_hot_pages > 0) this "
        "caps the DEVICE-resident pages one sequence may hold while "
        "max_model_len can exceed it — the demoted cold middle lives "
        "in the host pool; on a fully-resident engine it caps the "
        "whole sequence. Unset: the profile's engine block (default "
        "128; the widest a benchmark profile sets is 1,056, 16,896 tokens "
        "a sequence, for Qwen3-Next-80B-A3B (model_type qwen3_next; a page "
        "is 98,304 bytes over its 3 attention layers of 2 kv heads of 256, "
        "beside 19,316,736 bytes of delta-rule state a slot whatever the "
        "length; its new ModelConfig fields are linear_gate, "
        "attn_gate_channels and shared_expert_gate, its catalog entry "
        "Qwen/Qwen3-Next-80B-A3B-Instruct, its cell "
        "qwen3-next-80b-a3b.saturated-16k) and "
        "for GLM-5 (model_type glm_moe_dsa), where a page is a "
        "page of TWO pools under one id, the latent pool and the index-key "
        "pool of the sparse-attention indexer, ModelConfig.index_heads / "
        "index_head_dim / index_topk: 196,608 bytes over its 8 layers; "
        "then 544, 8,704 tokens, for Mellum2-12B-A2.5B, of whose 28 "
        "layers only the 7 full-attention ones keep pages: a page is "
        "229,376 bytes there and the 21 sliding-window layers keep a ring "
        "a slot, ModelConfig.sliding_window 1,024 keys, whatever the "
        "length).",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_CTX_HOT_PAGES",
        "Tiered KV residency for million-token contexts (ISSUE 20): "
        "> 0 keeps that many attention-hot TAIL pages of each long "
        "sequence in HBM and demotes the cold middle to the host pool "
        "(requires HELIX_KV_HOST_POOL_BYTES), streaming it back "
        "through fixed-size chunks folded into the same online-softmax "
        "merge as ring attention — outputs stay bit-identical to fully "
        "resident while peak HBM pages stay bounded. Every restored "
        "page re-verifies its blake2b checksum; a corrupt page is a "
        "typed error, never wrong attention. Applies to every engine "
        "this node serves (operator-beats-profile); 0 forces fully-"
        "resident even where a profile enables tiering. Unset: the "
        "profile's engine block (default 0 = off). A latent-attention "
        "model (DeepSeek-V2-Lite) and a model with sliding-window or "
        "Mamba-2 layers (a demoted middle would come back without the "
        "ring or the state) refuse tiering at profile apply "
        "(UnsupportedForModel).",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_CTX_TENANT_TOKENS",
        "Per-tenant quota for the context-caching API (ISSUE 20): the "
        "total prompt tokens one tenant may hold across its POST "
        "/v1/context handles. Past it new creations are rejected 429 "
        "with a typed counter (helix_ctx_quota_rejects_total); "
        "resolving existing handles is never gated. 0/unset: "
        "unlimited.",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_EXACT_SAMPLING",
        "Set to 1 to force the exact full-vocab top-p sampling path for "
        "every request (default: auto — the 64-candidate MXU fast path "
        "when the nucleus provably fits, exact fallback otherwise).",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_SEARCH_ENGINES",
        "JSON list of metasearch engine specs for the bundled searx-"
        "compatible /search endpoint, e.g. "
        '[{"kind": "searx", "name": "sx", "url": "http://host"}, '
        '{"kind": "mediawiki"}, {"kind": "ddg"}]. Empty (default): '
        "/search returns 503 instead of hanging on missing egress.",
        section="knowledge",
    ),
    EnvVar(
        "HELIX_BROWSER_POOL_SIZE",
        "Instances in the crawling/browsing pool (default 2). Each is an "
        "HTTP fetcher + readability extractor; with HELIX_CHROME_BIN set "
        "the pool seam can hold real Chromium sessions instead.",
        section="knowledge",
    ),
    EnvVar(
        "HELIX_CHROME_BIN",
        "Path to a Chromium binary for the CDP browser seam (JS-rendered "
        "crawling). Unset: the JS-less HttpBrowser serves the pool.",
        section="knowledge",
    ),
    EnvVar(
        "HELIX_FILESTORE",
        "Blob store backend: 'local' (default, rooted FS under the data "
        "dir) or 'gcs' (Google Cloud Storage over the JSON API).",
        section="server",
    ),
    EnvVar(
        "HELIX_GCS_BUCKET",
        "Bucket for HELIX_FILESTORE=gcs (required in that mode).",
        section="server",
    ),
    EnvVar(
        "HELIX_GCS_PREFIX",
        "Optional object-key prefix for the GCS filestore.",
        section="server",
    ),
    EnvVar(
        "HELIX_GCS_ENDPOINT",
        "GCS API endpoint override (default "
        "https://storage.googleapis.com); point at fake-gcs-server or an "
        "emulator in tests/dev.",
        section="server",
    ),
    EnvVar(
        "HELIX_GCS_TOKEN",
        "Static bearer token for GCS requests. Unset: the GCE metadata "
        "server is tried (2 s budget), else anonymous (emulators).",
        section="server",
    ),
    EnvVar(
        "HELIX_LICENSE_KEY",
        "Offline-verifiable ed25519-signed license key (HELIX-... "
        "format). Absent or invalid: the deployment runs the community "
        "tier; /api/v1/config/license reports the reason.",
        section="server",
    ),
    EnvVar(
        "HELIX_LICENSE_PUBKEY",
        "Hex ed25519 public key that license signatures must verify "
        "against (default: the built-in issuer key). Self-licensing "
        "deployments run their own issuer with helix_tpu.control.license.",
        section="server",
    ),
    EnvVar(
        "HELIX_PUBLIC_DOMAINS",
        "Comma-separated domains this deployment itself fronts. The "
        "/.well-known/helix-domain-verify route only answers for claims "
        "on these domains — unset (default), it answers for none, so a "
        "user can never self-verify the deployment's own domain and "
        "hijack email auto-join.",
        section="auth",
    ),
    EnvVar(
        "HELIX_DOMAIN_CLAIM_TTL_S",
        "Seconds an UNVERIFIED org-domain claim blocks competing claims "
        "(default 259200 = 72h). Verified claims never expire.",
        section="auth",
    ),
    # -- auth ------------------------------------------------------------
    EnvVar(
        "HELIX_MASTER_KEY",
        "Envelope-encryption master key for user secrets and OAuth "
        "tokens. Unset: a random key is generated and persisted next to "
        "the auth DB (set explicitly in production).",
        section="auth",
    ),
    EnvVar(
        "HELIX_RUNNER_TOKEN",
        "Shared token nodes present on the runner control loop "
        "(heartbeat, assignment poll, reverse-tunnel dial). Empty + "
        "auth_required: runner endpoints fail closed to admin-only.",
        section="auth",
    ),
    EnvVar(
        "HELIX_API_KEY",
        "Bearer key used by the admin CLI verbs (org/knowledge/secret/"
        "runner) when --api-key is not passed; also injected into "
        "sandboxed agent children as their control-plane credential.",
        section="auth",
    ),
    EnvVar(
        "HELIX_API_BASE",
        "Control-plane base URL injected into sandboxed agent children "
        "(their only egress).",
        section="auth",
    ),
    EnvVar(
        "HELIX_OIDC_ISSUER",
        "OIDC issuer URL; set to enable JWT bearer auth (discovery + "
        "JWKS RS256 verification).",
        section="auth",
    ),
    EnvVar(
        "HELIX_OIDC_CLIENT_ID",
        "Audience expected in OIDC tokens.",
        default="helix",
        section="auth",
    ),
    EnvVar(
        "HELIX_OIDC_ADMIN_EMAILS",
        "Comma-separated emails granted platform admin on OIDC "
        "provision (a pure-OIDC deployment's only admin path).",
        section="auth",
    ),
    # -- integrations -----------------------------------------------------
    EnvVar(
        "HELIX_GITHUB_CLIENT_ID",
        "GitHub OAuth app client id (enables the GitHub agent skill).",
        section="integrations",
    ),
    EnvVar(
        "HELIX_GITHUB_CLIENT_SECRET",
        "GitHub OAuth app client secret.",
        section="integrations",
    ),
    EnvVar(
        "HELIX_SLACK_WEBHOOK_URL",
        "Slack incoming-webhook URL for lifecycle notifications.",
        section="integrations",
    ),
    EnvVar(
        "HELIX_DISCORD_WEBHOOK_URL",
        "Discord webhook URL for lifecycle notifications.",
        section="integrations",
    ),
    EnvVar(
        "HELIX_SMTP_HOST",
        "SMTP host for email notifications (enables the email sink).",
        section="integrations",
    ),
    EnvVar("HELIX_SMTP_PORT", "SMTP port.", default="587",
           section="integrations"),
    EnvVar("HELIX_SMTP_FROM", "Email sender.", default="helix@localhost",
           section="integrations"),
    EnvVar("HELIX_SMTP_TO", "Notification recipient.",
           section="integrations"),
    EnvVar("HELIX_SMTP_USER", "SMTP username.", section="integrations"),
    EnvVar("HELIX_SMTP_PASSWORD", "SMTP password.",
           section="integrations"),
    # -- observability ----------------------------------------------------
    EnvVar(
        "HELIX_PING_URL",
        "Version-ping beacon endpoint (anonymous {product, version, ts} "
        "POST, hourly). Unset: no beacon (the default).",
        section="observability",
    ),
    EnvVar(
        "HELIX_PROFILER_DIR",
        "Directory for on-demand jax.profiler captures written by the "
        "runner's POST /admin/profiler (the server picks the filename; "
        "clients never choose paths). Unset: a fresh tempdir per "
        "capture.",
        section="observability",
    ),
    EnvVar(
        "HELIX_TENANT_TOP_K",
        "How many tenants get their own label series per engine in the "
        "per-tenant SLO accounting (helix_tenant_* metrics and the "
        "heartbeat tenants rollup); everyone else folds into one "
        "__other__ bucket via LRU demotion, so /metrics cardinality is "
        "constant under tenant churn.",
        default="8",
        section="observability",
    ),
    EnvVar(
        "HELIX_SLO_BURN_WINDOWS",
        "Fast,slow window seconds for the SLO error-budget burn-rate "
        "gauges (helix_slo_burn_rate / helix_tenant_slo_burn_rate), "
        "e.g. '300,3600'. Burn rate 1.0 = the error budget is spent "
        "exactly as fast as it accrues; >1.0 = the SLO is being "
        "violated.",
        default="300,3600",
        section="observability",
    ),
    EnvVar(
        "HELIX_TRACEMALLOC",
        "Set to 1 to arm tracemalloc at import so the control plane's "
        "heap-profile endpoint sees allocations from process start. "
        "Costs 2-7x on every later jax compile — diagnostics only, "
        "never in production serving.",
        default="0",
        section="observability",
    ),
    # trace federation (ISSUE 18): the push cadence is the heartbeat
    # interval — spans ride the existing beat, so there is no separate
    # interval knob to tune (or forget)
    EnvVar(
        "HELIX_TRACE_FEDERATION",
        "Set to 0/false/off to stop runners pushing completed trace "
        "spans to the control plane inside the heartbeat payload. On "
        "(the default) the cp stitches every host's spans per trace id "
        "and serves the cluster-wide timeline at /v1/debug/traces/"
        "{id}; off, each host only answers for its own spans.",
        default="1",
        section="observability",
    ),
    EnvVar(
        "HELIX_TRACE_EXPORT_BATCH",
        "Maximum spans one heartbeat may carry (and the control "
        "plane's per-batch ingest clamp). Spans beyond the batch wait "
        "for the next beat; the export ring bounds how many can wait.",
        default="256",
        section="observability",
    ),
    EnvVar(
        "HELIX_TRACE_BUFFER",
        "Runner-side pending-export ring size. When the heartbeat "
        "falls behind span production, the OLDEST unsent span is "
        "dropped and counted in helix_trace_dropped_spans_total — "
        "memory stays bounded, loss stays visible.",
        default="2048",
        section="observability",
    ),
    EnvVar(
        "HELIX_TRACE_CP_TRACES",
        "How many federated traces the control plane retains (LRU "
        "beyond that; a dead runner's spans are pruned with the "
        "runner regardless).",
        default="2048",
        section="observability",
    ),
    EnvVar(
        "HELIX_CANARY",
        "Set to 1 to run the continuous correctness-canary scheduler "
        "(obs/canary.py): golden greedy probes mint per serving axis "
        "at profile apply and replay through the real serving path "
        "under the reserved __canary__ tenant, verifying token-level "
        "bit-identity. Off by default — probes consume real device "
        "steps, so the operator opts in the way scored routing is "
        "opted into.",
        default="0",
        section="observability",
    ),
    EnvVar(
        "HELIX_CANARY_INTERVAL",
        "Seconds between canary probe rounds while the runner's "
        "canary health is ok (failing runners reprobe on "
        "HELIX_CANARY_REPROBE_BACKOFF instead).",
        default="60",
        section="observability",
    ),
    EnvVar(
        "HELIX_CANARY_AXES",
        "Comma list restricting which serving axes mint golden probes "
        "(decode, prefix, spec, adapter, int8, resume). Unset: every "
        "axis the engine actually exercises, EXCEPT resume — the "
        "post-migration replay axis only mints when listed "
        "explicitly.",
        section="observability",
    ),
    EnvVar(
        "HELIX_CANARY_FAILURES",
        "Consecutive mismatched probe rounds before the runner's "
        "canary health flips to 'failing' (and the consecutive clean "
        "rounds required to recover from 'reprobing' back to 'ok'). "
        "Latency deviations and probe sheds/timeouts never count — "
        "only token-level bit-identity failures move the rungs.",
        default="2",
        section="observability",
    ),
    EnvVar(
        "HELIX_CANARY_REPROBE_BACKOFF",
        "Seconds a canary-failing runner waits between recovery probe "
        "rounds, so a transiently corrupted runner re-earns 'ok' "
        "without waiting out the full probe interval.",
        default="30",
        section="observability",
    ),
    # -- scheduler (serving/sched.py; README "Scheduling") ---------------
    # HELIX_SCHED_* knobs beat the profile's slo.sched block (the
    # HELIX_SPEC_TOKENS operator-override contract)
    EnvVar(
        "HELIX_SCHED_POLICY",
        "Scheduler policy for every engine this node serves: 'wfq' "
        "turns on strict interactive/batch priority tiers + per-tenant "
        "deficit-weighted fair queueing; 'fifo' forces the baseline "
        "FIFO ordering even where a profile enables wfq. Unset: the "
        "profile's slo.sched.policy applies (default fifo).",
        section="scheduler",
    ),
    EnvVar(
        "HELIX_SCHED_DEFAULT_CLASS",
        "Priority class assumed for requests that carry no (or an "
        "unauthenticated) X-Helix-Class header: 'interactive' or "
        "'batch'. Unset: the profile's slo.sched.default_class "
        "(default interactive).",
        section="scheduler",
    ),
    EnvVar(
        "HELIX_SCHED_TENANT_QUEUE_DEPTH",
        "Bounded per-tenant queues: max queued requests one tenant may "
        "hold before ITS submissions get 429s (per-tenant queue_full), "
        "so a flooding tenant cannot fill the global admission bound "
        "and starve everyone else. Unset: the profile's "
        "slo.sched.max_tenant_queue_depth (default unbounded).",
        section="scheduler",
    ),
    EnvVar(
        "HELIX_SCHED_PREFILL_BUDGET",
        "Adaptive per-step prefill-admission token budget (cap and "
        "initial value) under the wfq policy: halves toward the floor "
        "while the fast-window TTFT/queue-wait burn rate exceeds 1.0, "
        "grows back 1.25x once healthy. Unset: the profile's "
        "slo.sched.prefill_budget_tokens (default unbudgeted).",
        section="scheduler",
    ),
    EnvVar(
        "HELIX_SCHED_PREFILL_BUDGET_MIN",
        "Floor the TTFT-burn feedback loop may shrink the prefill "
        "budget to; admission always makes progress (>= 1 admission "
        "per step) regardless. Unset: the profile's "
        "slo.sched.prefill_budget_min_tokens.",
        default="256",
        section="scheduler",
    ),
    # -- routing (control/router.py; README "Routing & autoscaling") -----
    EnvVar(
        "HELIX_ROUTER_POLICY",
        "Control-plane placement policy: 'scored' closes the loop from "
        "federated heartbeat saturation (hard-avoid runners near KV/"
        "host-pool exhaustion or with a squeezed prefill budget, "
        "soft-prefer low queue depth / occupancy / warm spec "
        "acceptance, steer batch-class traffic off runners whose "
        "tenants are burning SLO budget; stale or missing saturation "
        "scores neutral, never best). Unset or 'rr': the seed "
        "least-loaded/round-robin baseline, bit-for-bit.",
        default="rr",
        section="router",
    ),
    EnvVar(
        "HELIX_ROUTER_KV_AVOID_THRESHOLD",
        "KV occupancy (0..1) at which the scored policy hard-avoids a "
        "runner — routed to only when no alternative exists.",
        default="0.85",
        section="router",
    ),
    EnvVar(
        "HELIX_ROUTER_KV_FULL_THRESHOLD",
        "KV occupancy (0..1) past which a runner is treated as FULL: a "
        "new dispatch there is a guaranteed typed kv_exhausted, so "
        "when EVERY candidate is full the control plane sheds with a "
        "503 code=kv_saturated and an honest Retry-After instead of "
        "dispatching into certain failure.",
        default="0.98",
        section="router",
    ),
    EnvVar(
        "HELIX_ROUTER_HOST_AVOID_THRESHOLD",
        "Host KV tier occupancy (0..1) at which the scored policy "
        "hard-avoids a runner (its spill headroom is nearly gone).",
        default="0.92",
        section="router",
    ),
    EnvVar(
        "HELIX_ROUTER_PREFILL_AVOID_TOKENS",
        "A runner reporting a prefill-admission budget in (0, this] is "
        "hard-avoided: the scheduler's SLO-burn feedback has squeezed "
        "admission to the floor there. 0 in the heartbeat always means "
        "unbudgeted and never triggers the avoid.",
        default="256",
        section="router",
    ),
    EnvVar(
        "HELIX_ROUTER_BURN_STEER_THRESHOLD",
        "Worst-tenant fast-window SLO burn rate above which batch-class "
        "(X-Helix-Class) traffic is steered away from a runner (soft "
        "score penalty, not an avoid).",
        default="1.0",
        section="router",
    ),
    EnvVar(
        "HELIX_PREFIX_AFFINITY",
        "Set to 1 to route requests sharing a prompt head (system "
        "prompt) to the runner whose PrefixCache/host tier already "
        "holds those pages (cp-side bounded LRU of prefix digest -> "
        "runner). Affinity is a hint, not a pin: under the scored "
        "policy it yields to saturation, breakers and drain; under rr "
        "it yields whenever the hinted runner is no longer among the "
        "least-loaded. Unset/0: off.",
        section="router",
    ),
    EnvVar(
        "HELIX_PREFIX_AFFINITY_ENTRIES",
        "Bound on the prefix-affinity LRU (distinct prompt heads "
        "remembered cluster-wide).",
        default="2048",
        section="router",
    ),
    EnvVar(
        "HELIX_ROUTER_CANARY_AVOID",
        "Set to 1 to hard-avoid runners whose federated correctness-"
        "canary health is failing or reprobing (wrong tokens are worse "
        "than slow ones) — under BOTH routing policies. The LAST "
        "runner serving a model is never stranded: it serves with a "
        "warning (counted in "
        "the cp canary route counters, logged with the trace id) "
        "rather than shedding a whole model on a possibly-false-"
        "positive probe. Unset/0: canary health is reported but never "
        "steers.",
        default="0",
        section="router",
    ),
    # -- dispatch robustness (control plane -> runner) -------------------
    EnvVar(
        "HELIX_DISPATCH_MAX_ATTEMPTS",
        "Max runner candidates one inference dispatch tries before "
        "returning 503 runners_exhausted (connect errors and 5xx "
        "received before the first streamed byte fail over to the next "
        "candidate).",
        default="3",
        section="server",
    ),
    EnvVar(
        "HELIX_DISPATCH_BACKOFF_BASE",
        "Base seconds for the capped exponential backoff (with jitter) "
        "between dispatch failover attempts.",
        default="0.05",
        section="server",
    ),
    EnvVar(
        "HELIX_DISPATCH_BACKOFF_CAP",
        "Upper bound in seconds on the per-attempt dispatch backoff.",
        default="1.0",
        section="server",
    ),
    EnvVar(
        "HELIX_DISPATCH_TIMEOUT",
        "Total deadline in seconds for one inference dispatch across "
        "all failover attempts (the remaining budget shrinks with each "
        "retry).",
        default="300",
        section="server",
    ),
    EnvVar(
        "HELIX_INTER_TOKEN_TIMEOUT",
        "Runner-side ceiling in seconds on the gap between consecutive "
        "streamed tokens of one response; a stall past it aborts the "
        "request with a typed 504 (SSE clients get an in-band error "
        "frame).",
        default="300",
        section="server",
    ),
    # -- knowledge --------------------------------------------------------
    EnvVar(
        "HELIX_CRAWLER_ALLOW_PRIVATE",
        "Set to 1 to let the knowledge crawler fetch private/loopback "
        "addresses (intranet docs). Default: refused (SSRF guard).",
        default="0",
        section="knowledge",
    ),
    # -- accelerator ------------------------------------------------------
    EnvVar(
        "JAX_PLATFORMS",
        "JAX platform selection; the control plane and sandbox children "
        "pin 'cpu' (they never touch chips). Serving nodes inherit the "
        "deployment default (tpu).",
        section="accelerator",
    ),
    # -- multi-host (DCN) serving (serving/multihost_serving.py) ---------
    EnvVar(
        "HELIX_MH_DIGEST",
        "Follower-side emission-digest verification mode for multi-host "
        "plan-broadcast serving: 'strict' (default) treats a rolling "
        "per-step digest mismatch against the leader's plans as lost "
        "lockstep (the follower stops and surfaces the restart ladder), "
        "'warn' logs and counts it (helix-side stats "
        "digest_mismatches), 'off' skips the check.",
        default="strict",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MH_RING",
        "Capacity (records) of the leader's plan ring buffer. A "
        "follower that falls more than this many records behind cannot "
        "rejoin by replay and must restart from a profile re-apply; "
        "bigger rings buy crash-recovery window at the cost of leader "
        "memory (plans are compact JSON, typically <1 KiB/step).",
        default="4096",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MH_BACKOFF_BASE",
        "Base seconds of a follower's capped exponential backoff (with "
        "jitter) between retries after a transient plan-feed error "
        "(retry n sleeps ~min(base * 2^n, cap)); fatal conditions "
        "(ring fall-behind, leader restart, divergence) never retry.",
        default="0.05",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MH_BACKOFF_CAP",
        "Cap seconds of the follower plan-feed retry backoff.",
        default="5.0",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MH_LAG_STEPS",
        "Leader-side lag ladder threshold (steps): a follower whose "
        "applied step sustains more than this many steps behind the "
        "published plan enters the typed 'lagging' state and the "
        "leader throttles admission (prefill budget pinned to 0, the "
        "PR 8 discipline) until it catches back up to half the "
        "threshold — back-pressure instead of ring overflow.",
        default="64",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MH_MAX_FOLLOWERS",
        "Bound on follower health entries the leader tracks (and the "
        "size of the helix_mh_follower_* metric family); polls beyond "
        "it are served but not registered (followers_dropped counts "
        "them).",
        default="16",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MH_FOLLOWER_TTL",
        "Seconds without a poll before the leader marks a registered "
        "follower 'lost' (it stops feeding the lag throttle; a "
        "rejoining poll re-registers it).",
        default="15",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MH_FOLLOWER_ID",
        "Stable id this follower registers with the leader's health "
        "registry (default: follower-<pid>). Set it per host so lag / "
        "digest telemetry survives process restarts under one name.",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MH_CHECKPOINT_DIR",
        "Shared filestore directory for leader-state checkpoints "
        "(ISSUE 17 failover). Point every host of the mesh at the SAME "
        "path (the PR 14 cluster filestore tier): the leader "
        "checkpoints its host-side queue state there and a standby "
        "promotes from the newest checkpoint. Empty = no "
        "checkpointing, failover degrades to the full resync ladder.",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MH_CHECKPOINT_SECONDS",
        "Seconds between leader-state checkpoints (captured on the "
        "engine thread at a step boundary, written off-thread through "
        "the filestore). Smaller = fresher takeover boundary, more "
        "filestore writes.",
        default="5",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MH_CHECKPOINT_KEEP",
        "Newest leader-state checkpoints retained per model; older "
        "ones are pruned after each write.",
        default="3",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MH_STANDBY",
        "Set to 1 on a follower host to mark it a hot standby (the "
        "profile's multihost.standby beats this): standbys keep a "
        "digest-verified replica and are the preferred "
        "promote_follower target when the leader dies.",
        default="0",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_MH_PROMOTE_AFTER",
        "Standby auto-promotion trigger: after this many CONSECUTIVE "
        "transient plan-feed failures (the leader host is gone, not a "
        "blip) a standby stops retrying and fires its promotion hook. "
        "0 (default) = never self-trigger; promotion is operator- or "
        "node-agent-driven.",
        default="0",
        section="accelerator",
    ),
    # -- multi-host (DCN) training ---------------------------------------
    EnvVar(
        "HELIX_COORDINATOR",
        "Multi-host training: process 0's host:port for the jax "
        "distributed world (gradient all-reduce rides DCN between "
        "hosts).",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_NUM_HOSTS",
        "Multi-host training: total participating host processes.",
        default="1",
        section="accelerator",
    ),
    EnvVar(
        "HELIX_HOST_RANK",
        "Multi-host training: this host's process rank (0-based).",
        default="0",
        section="accelerator",
    ),
    # -- compute autoscaler (GCE provider) -------------------------------
    EnvVar(
        "HELIX_GCE_PROJECT",
        "GCP project for the pool autoscaler's GCE provider. Setting "
        "this together with HELIX_GCE_ZONE switches the autoscaler from "
        "the stub to real instances.",
        section="compute",
    ),
    EnvVar(
        "HELIX_GCE_ZONE",
        "GCE zone runner instances are provisioned in.",
        section="compute",
    ),
    EnvVar(
        "HELIX_GCE_MACHINE_TYPE",
        "Machine type for provisioned runner hosts.",
        default="n2-standard-8",
        section="compute",
    ),
    EnvVar(
        "HELIX_GCE_IMAGE",
        "Boot image for provisioned runner hosts.",
        default="projects/debian-cloud/global/images/family/debian-12",
        section="compute",
    ),
    EnvVar(
        "HELIX_GCE_CONTROL_PLANE",
        "Control-plane URL baked into the instance startup script "
        "(serve-node dials back here over the reverse tunnel).",
        section="compute",
    ),
    EnvVar(
        "GCE_TOKEN",
        "Static OAuth bearer for the GCE API; falls back to the "
        "instance metadata server when unset.",
        section="compute",
    ),
    EnvVar(
        "HELIX_INSTANCE_ID",
        "Compute-row identity an autoscaled host includes in its "
        "heartbeats so the pool manager can bind them to its instance "
        "row (matched by row id or provider id; the GCE startup script "
        "exports the instance hostname). Unset on hand-managed nodes.",
        section="compute",
    ),
    EnvVar(
        "HELIX_AUTOSCALE_FLOOR",
        "Override for the autoscaler's floor (healthy hosts kept alive "
        "at all times); beats the supplied ManagerConfig.",
        section="compute",
    ),
    EnvVar(
        "HELIX_AUTOSCALE_MAX",
        "Override for the autoscaler's max owned hosts (hard ceiling; "
        "0 disables demand/saturation bursts).",
        section="compute",
    ),
    EnvVar(
        "HELIX_AUTOSCALE_QUEUE_HIGH",
        "Cluster-wide queued-request depth (summed over runner "
        "heartbeats) that, sustained for HELIX_AUTOSCALE_SUSTAIN_"
        "SECONDS, provisions another host (0 disables the queue "
        "trigger).",
        section="compute",
    ),
    EnvVar(
        "HELIX_AUTOSCALE_BURN_HIGH",
        "Worst-tenant fast-window SLO burn rate that, sustained, "
        "provisions another host (0 disables the burn trigger).",
        section="compute",
    ),
    EnvVar(
        "HELIX_AUTOSCALE_SUSTAIN_SECONDS",
        "How long a scale-up trigger (and the idle condition for "
        "scale-down victim selection) must hold before the autoscaler "
        "acts — one hot scrape must not provision.",
        default="60",
        section="compute",
    ),
    EnvVar(
        "HELIX_AUTOSCALE_IDLE_SECONDS",
        "Cluster idle duration (zero queued work, tenant burn healthy) "
        "after which the autoscaler drains ONE runner at a time down "
        "toward the floor — announce draining, migrate in-flight "
        "requests to peers (ISSUE 11 ladder), then terminate the host. "
        "0 disables saturation-driven scale-down.",
        section="compute",
    ),
    EnvVar(
        "HELIX_AUTOSCALE_DRAIN_GRACE",
        "Seconds a drain-requested host may linger before it is "
        "terminated anyway (0 = HELIX_DRAIN_SECONDS + 30). Normal "
        "completion is earlier: the host is reclaimed as soon as its "
        "runner leaves the router.",
        section="compute",
    ),
    EnvVar(
        "HELIX_GIT_TOKEN",
        "Internal: carries the forge token from GitHubSync to git's "
        "credential helper via the child environment (never on the "
        "command line).",
        section="integrations",
    ),
    # -- CLI --------------------------------------------------------------
    EnvVar(
        "HELIX_API_URL",
        "Control-plane base URL the CLI verbs talk to when --url is not "
        "passed.",
        default="http://localhost:8080",
        section="cli",
    ),
    EnvVar(
        "HELIX_API_TOKEN",
        "Bearer token the CLI presents to the control plane when "
        "--api-key is not passed.",
        section="cli",
    ),
    # -- server -----------------------------------------------------------
    EnvVar(
        "HELIX_PUBLIC_URL",
        "Externally-reachable base URL of this control plane (used in "
        "links the server hands out: OAuth callbacks, runner dial-back).",
        default="http://localhost:8080",
        section="server",
    ),
    EnvVar(
        "HELIX_EXECUTOR",
        "Spec-task executor backend: empty = in-process sandbox agent; "
        "'ws' = dispatch implementation work to an external runner over "
        "the /ws/external-runner websocket.",
        section="server",
    ),
    EnvVar(
        "HELIX_WS_AGENT",
        "With HELIX_EXECUTOR=ws: agent type requested from external "
        "runners (e.g. claude-code, zed, goose).",
        section="server",
    ),
    # -- knowledge --------------------------------------------------------
    EnvVar(
        "HELIX_ANN_THRESHOLD",
        "Vector-store size (rows) above which similarity search switches "
        "from exact cosine scan to the native HNSW ANN index.",
        default="5000",
        section="knowledge",
    ),
    # -- billing (Stripe rails) ------------------------------------------
    EnvVar(
        "HELIX_STRIPE_SECRET_KEY",
        "Stripe API secret key; setting it enables the billing rails "
        "(checkout sessions, subscriptions, webhooks).",
        section="billing",
    ),
    EnvVar(
        "HELIX_STRIPE_WEBHOOK_SECRET",
        "Stripe webhook signing secret used to verify "
        "/api/v1/stripe/webhook payloads.",
        section="billing",
    ),
    EnvVar(
        "HELIX_STRIPE_PRICE_ID_PRO",
        "Stripe price id for the pro-tier subscription checkout.",
        section="billing",
    ),
    EnvVar(
        "HELIX_STRIPE_API_URL",
        "Stripe API base (tests point it at a fake).",
        default="https://api.stripe.com",
        section="billing",
    ),
    EnvVar(
        "HELIX_APP_URL",
        "User-facing app URL Stripe checkout redirects back to.",
        default="http://localhost:8080",
        section="billing",
    ),
    # -- Anthropic gateway ------------------------------------------------
    EnvVar(
        "HELIX_ANTHROPIC_PROXY_KEY",
        "Upstream Anthropic API key for the native /v1/messages gateway.",
        section="anthropic",
    ),
    EnvVar(
        "HELIX_ANTHROPIC_OAUTH_TOKEN",
        "Claude-subscription OAuth bearer; preferred over the API key "
        "when present (the gateway probes which auth the account has).",
        section="anthropic",
    ),
    EnvVar(
        "HELIX_ANTHROPIC_BASE_URL",
        "Anthropic API base for the direct gateway backend.",
        default="https://api.anthropic.com",
        section="anthropic",
    ),
    EnvVar(
        "HELIX_VERTEX_PROJECT",
        "GCP project id; setting it routes the Anthropic gateway "
        "through Vertex AI model endpoints.",
        section="anthropic",
    ),
    EnvVar(
        "HELIX_VERTEX_REGION",
        "Vertex AI region for Anthropic models.",
        default="us-east5",
        section="anthropic",
    ),
    EnvVar(
        "HELIX_VERTEX_CREDENTIALS",
        "Service-account credentials JSON (inline) for Vertex auth; "
        "falls back to metadata-server tokens when unset.",
        section="anthropic",
    ),
    EnvVar(
        "HELIX_VERTEX_BASE_URL",
        "Override for the Vertex endpoint base (tests point it at a "
        "fake).",
        section="anthropic",
    ),
    EnvVar(
        "HELIX_BEDROCK_ACCESS_KEY",
        "AWS access key id; setting it routes the Anthropic gateway "
        "through Bedrock invoke endpoints.",
        section="anthropic",
    ),
    EnvVar(
        "HELIX_BEDROCK_SECRET_KEY",
        "AWS secret access key for Bedrock SigV4 signing.",
        section="anthropic",
    ),
    EnvVar(
        "HELIX_BEDROCK_SESSION_TOKEN",
        "Optional AWS STS session token for Bedrock.",
        section="anthropic",
    ),
    EnvVar(
        "HELIX_BEDROCK_REGION",
        "AWS region for Bedrock Anthropic models.",
        default="us-east-1",
        section="anthropic",
    ),
    EnvVar(
        "HELIX_BEDROCK_BASE_URL",
        "Override for the Bedrock endpoint base (tests point it at a "
        "fake).",
        section="anthropic",
    ),
)


def render(sections: bool = True) -> str:
    out = []
    cur = None
    for var in ENV_REFERENCE:
        if sections and var.section != cur:
            cur = var.section
            out.append(f"\n[{cur}]")
        default = f" (default: {var.default})" if var.default else ""
        out.append(f"  {var.name}{default}\n      {var.description}")
    return "\n".join(out).strip()
