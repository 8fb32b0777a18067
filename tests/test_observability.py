"""Observability spine (ISSUE 3): shared metrics registry + end-to-end
request tracing.

- A mini Prometheus text parser asserts name/type/label well-formedness
  and histogram invariants on BOTH /metrics planes (control plane and
  runner render through the same helix_tpu.obs registry).
- Counter monotonicity across requests.
- One request through the full stack (control plane -> dispatch with one
  injected failover retry -> runner -> engine) yields a single trace
  with >= 6 spans across all three planes, retrievable from
  /v1/debug/traces/{id} on either plane.
- tools/lint_metrics.py (no ad-hoc exposition outside helix_tpu/obs/)
  runs as a tier-1 test so drift fails fast.
"""

import asyncio
import os
import re
import threading
import time
from types import SimpleNamespace

import pytest
import requests

from helix_tpu.control.server import ControlPlane
from helix_tpu.obs.metrics import METRIC_NAME_RE
from helix_tpu.testing import faults

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.disarm()


# ---------------------------------------------------------------------------
# mini Prometheus text parser
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(.*)\})?"
    r" (-?(?:[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)|NaN|[+-]Inf)$"
)
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def parse_prometheus(text: str):
    """Parse + validate an exposition document.  Returns (types, samples)
    where samples = [(name, labels_dict, value)].  Raises AssertionError
    on any malformed line."""
    types: dict = {}
    samples: list = []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            parts = line.split(" ")
            assert len(parts) == 4, f"malformed TYPE line: {line!r}"
            _, _, name, mtype = parts
            assert mtype in ("counter", "gauge", "histogram", "untyped"), (
                f"unknown metric type in {line!r}"
            )
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = mtype
            continue
        if line.startswith("#"):
            continue   # HELP / comments
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        name, labelstr, value = m.group(1), m.group(2), m.group(3)
        labels: dict = {}
        if labelstr is not None:
            consumed = []
            for lm in _LABEL_RE.finditer(labelstr):
                labels[lm.group(1)] = lm.group(2)
                consumed.append(lm.group(0))
            assert ",".join(consumed) == labelstr, (
                f"malformed labels in {line!r}"
            )
        samples.append((name, labels, float(value)))
    return types, samples


def assert_wellformed(text: str):
    """Full well-formedness: every sample belongs to a TYPE'd family,
    family names obey the helix naming contract, histograms are
    internally consistent."""
    types, samples = parse_prometheus(text)

    def family_of(name: str):
        if name in types:
            return name
        for suf in _HIST_SUFFIXES:
            if name.endswith(suf) and name[: -len(suf)] in types:
                base = name[: -len(suf)]
                assert types[base] == "histogram", (
                    f"{name} uses a histogram suffix but {base} is "
                    f"{types[base]}"
                )
                return base
        raise AssertionError(f"sample {name} has no # TYPE family")

    hist: dict = {}
    for name, labels, value in samples:
        fam = family_of(name)
        assert METRIC_NAME_RE.fullmatch(fam), (
            f"family {fam} violates the helix naming contract"
        )
        if types[fam] == "histogram":
            key = (fam, tuple(sorted(
                (k, v) for k, v in labels.items() if k != "le"
            )))
            h = hist.setdefault(
                key, {"buckets": [], "sum": None, "count": None}
            )
            if name.endswith("_bucket"):
                assert "le" in labels, f"bucket without le: {name}{labels}"
                h["buckets"].append((labels["le"], value))
            elif name.endswith("_sum"):
                h["sum"] = value
            elif name.endswith("_count"):
                h["count"] = value
    for (fam, key), h in hist.items():
        assert h["sum"] is not None and h["count"] is not None, (
            f"histogram {fam}{dict(key)} missing _sum/_count"
        )
        assert h["buckets"], f"histogram {fam}{dict(key)} has no buckets"
        les = [le for le, _ in h["buckets"]]
        assert les[-1] == "+Inf", f"{fam}: last bucket must be +Inf"
        bounds = [float("inf") if le == "+Inf" else float(le) for le in les]
        assert bounds == sorted(bounds), f"{fam}: le not ascending"
        counts = [c for _, c in h["buckets"]]
        assert counts == sorted(counts), (
            f"{fam}: bucket counts not cumulative"
        )
        assert counts[-1] == h["count"], (
            f"{fam}: +Inf bucket != _count"
        )
    return types, samples


def counter_values(text: str) -> dict:
    types, samples = parse_prometheus(text)
    out = {}
    for name, labels, value in samples:
        fam = name
        for suf in _HIST_SUFFIXES:
            if name.endswith(suf) and name[: -len(suf)] in types:
                fam = name[: -len(suf)]
        if types.get(fam) in ("counter", "histogram"):
            out[(name, tuple(sorted(labels.items())))] = value
    return out


# ---------------------------------------------------------------------------
# full-stack fixture: control plane + one REAL runner (tiny engine)
# ---------------------------------------------------------------------------

def _serve_app(app, holder):
    started = threading.Event()
    box = {}

    def run():
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        from aiohttp import web

        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        loop.run_until_complete(site.start())
        box["port"] = site._server.sockets[0].getsockname()[1]
        holder.setdefault("loops", []).append(loop)
        started.set()
        loop.run_forever()

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(10)
    return box["port"]


@pytest.fixture(scope="module")
def spine():
    """Control plane + one real runner serving a tiny engine as 'm1'."""
    import jax

    from helix_tpu.engine.engine import Engine, EngineConfig
    from helix_tpu.models.common import ModelConfig
    from helix_tpu.models.llama import init_params
    from helix_tpu.serving.engine_loop import EngineLoop
    from helix_tpu.serving.openai_api import OpenAIServer
    from helix_tpu.serving.registry import ModelRegistry, ServedModel
    from helix_tpu.serving.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    cfg = ModelConfig.tiny(vocab_size=512, dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(7))
    engine = Engine(
        cfg, params,
        EngineConfig(
            max_decode_batch=4, page_size=4, num_pages=256,
            max_pages_per_seq=32, max_prefill_len=64,
            attn_backend="reference", eos_token_ids=tok.eos_ids,
        ),
    )
    loop = EngineLoop(engine, name="m1").start()
    registry = ModelRegistry()
    registry.register(
        ServedModel(name="m1", loop=loop, tokenizer=tok, context_length=128)
    )
    api = OpenAIServer(registry)
    holder: dict = {}
    runner_port = _serve_app(api.build_app(), holder)
    cp = ControlPlane()
    cp.dispatch_backoff_base = 0.001
    cp.dispatch_backoff_cap = 0.002
    cp_port = _serve_app(cp.build_app(), holder)
    cp.router.upsert_from_heartbeat(
        "real", models=["m1"], profile_name="p", profile_status="running",
        meta={"address": f"http://127.0.0.1:{runner_port}"},
    )
    yield SimpleNamespace(
        cp=cp,
        cp_url=f"http://127.0.0.1:{cp_port}",
        runner_url=f"http://127.0.0.1:{runner_port}",
        api=api,
        loop=loop,
    )
    cp.stop()
    loop.stop(join=False)
    for lp in holder.get("loops", []):
        lp.call_soon_threadsafe(lp.stop)


def _chat(url, max_tokens=6, stream=False, timeout=30):
    return requests.post(
        f"{url}/v1/chat/completions",
        json={
            "model": "m1", "max_tokens": max_tokens, "temperature": 0,
            "stream": stream,
            "messages": [{"role": "user", "content": "observe me"}],
        },
        timeout=timeout,
    )


# ---------------------------------------------------------------------------
# metrics exposition
# ---------------------------------------------------------------------------

class TestMetricsExposition:
    def test_runner_metrics_wellformed(self, spine):
        assert _chat(spine.runner_url).status_code == 200
        text = requests.get(f"{spine.runner_url}/metrics", timeout=10).text
        types, samples = assert_wellformed(text)
        names = {n for n, _, _ in samples}
        # engine series carry the model label
        assert any(
            n == "helix_decode_tokens_total" and l.get("model") == "m1"
            for n, l, _ in samples
        )
        # latency histograms emitted by the shared registry
        assert types.get("helix_ttft_seconds") == "histogram"
        assert types.get("helix_queue_wait_seconds") == "histogram"
        assert types.get("helix_inter_token_seconds") == "histogram"
        assert types.get("helix_engine_step_seconds") == "histogram"
        assert "helix_ttft_seconds_bucket" in names

    def test_control_plane_metrics_wellformed(self, spine):
        assert _chat(spine.cp_url).status_code == 200
        text = requests.get(f"{spine.cp_url}/metrics", timeout=10).text
        types, samples = assert_wellformed(text)
        assert types.get("helix_cp_dispatch_retries_total") == "counter"
        # dispatch-attempt latency histogram from the shared registry
        assert types.get("helix_cp_dispatch_attempt_seconds") == "histogram"
        assert any(
            n == "helix_cp_dispatch_attempt_seconds_count" and v >= 1
            for n, _, v in samples
        )
        # per-runner breaker series with runner labels
        assert any(
            n == "helix_cp_runner_breaker_state"
            and l.get("runner") == "real"
            for n, l, _ in samples
        )

    def test_both_planes_share_registry_format(self, spine):
        """Control-plane and runner /metrics are the same exposition
        dialect: every family TYPE'd, same sample grammar, and between
        them the TTFT + queue-wait + dispatch-attempt histograms."""
        cp_text = requests.get(f"{spine.cp_url}/metrics", timeout=10).text
        rn_text = requests.get(
            f"{spine.runner_url}/metrics", timeout=10
        ).text
        cp_types, _ = assert_wellformed(cp_text)
        rn_types, _ = assert_wellformed(rn_text)
        histos = {
            n for t in (cp_types, rn_types)
            for n, k in t.items() if k == "histogram"
        }
        assert {
            "helix_ttft_seconds", "helix_queue_wait_seconds",
            "helix_cp_dispatch_attempt_seconds",
        } <= histos

    def test_counters_monotonic_across_requests(self, spine):
        before_text = requests.get(
            f"{spine.runner_url}/metrics", timeout=10
        ).text
        before = counter_values(before_text)
        for _ in range(2):
            assert _chat(spine.runner_url).status_code == 200
        after_text = requests.get(
            f"{spine.runner_url}/metrics", timeout=10
        ).text
        after = counter_values(after_text)
        for key, v0 in before.items():
            if key in after:
                assert after[key] >= v0, f"counter went backwards: {key}"
        key = ("helix_ttft_seconds_count", (("model", "m1"),))
        assert after.get(key, 0) >= before.get(key, 0) + 2

    def test_no_adhoc_exposition_lint(self):
        import sys

        sys.path.insert(
            0, os.path.join(os.path.dirname(__file__), "..", "tools")
        )
        import lint_metrics

        root = os.path.join(os.path.dirname(__file__), "..")
        violations = lint_metrics.run(os.path.abspath(root))
        assert violations == [], "\n".join(violations)


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

class TestTracePropagation:
    def test_one_trace_spans_failover_retry_to_engine(self, spine):
        """One request, one injected pre-stream dispatch fault: the SAME
        trace id covers the failed attempt, the retry, the runner HTTP
        handling and the engine phases — >= 6 spans, retrievable from
        both planes."""
        faults.arm(
            seed=11,
            rules=[{"point": "dispatch", "runner": "real",
                    "mode": "connect_error", "times": 1}],
        )
        r = _chat(spine.cp_url)
        faults.disarm()
        assert r.status_code == 200, r.text
        tid = r.headers.get("X-Helix-Trace-Id")
        assert tid, "trace id must be echoed in response headers"
        assert spine.cp.dispatch_retries >= 1

        doc = requests.get(
            f"{spine.cp_url}/v1/debug/traces/{tid}", timeout=10
        ).json()
        assert doc["trace_id"] == tid
        spans = doc["spans"]
        assert len(spans) >= 6, spans
        names = [s["name"] for s in spans]
        planes = {s["plane"] for s in spans}
        assert {"control", "runner", "engine"} <= planes
        attempts = [s for s in spans if s["name"] == "dispatch_attempt"]
        assert len(attempts) == 2   # injected failure + the retry
        outcomes = sorted(a["attrs"]["outcome"] for a in attempts)
        assert outcomes[-1] == "ok" and outcomes[0].startswith("failed")
        for expected in ("queue", "prefill", "decode", "admit", "request"):
            assert expected in names, f"missing span {expected}: {names}"
        # same trace visible on the runner plane
        rdoc = requests.get(
            f"{spine.runner_url}/v1/debug/traces/{tid}", timeout=10
        ).json()
        assert rdoc["trace_id"] == tid
        # chrome trace_event export on both planes
        for base in (spine.cp_url, spine.runner_url):
            chrome = requests.get(
                f"{base}/v1/debug/traces/{tid}?format=chrome", timeout=10
            ).json()
            assert chrome["traceEvents"], base
            assert any(
                e.get("ph") == "X" for e in chrome["traceEvents"]
            )

    def test_caller_supplied_trace_id_adopted(self, spine):
        tid = "cafe" * 8
        r = requests.post(
            f"{spine.runner_url}/v1/chat/completions",
            json={"model": "m1", "max_tokens": 4, "temperature": 0,
                  "messages": [{"role": "user", "content": "hi"}]},
            headers={"X-Helix-Trace-Id": tid},
            timeout=30,
        )
        assert r.status_code == 200
        assert r.headers.get("X-Helix-Trace-Id") == tid
        doc = requests.get(
            f"{spine.runner_url}/v1/debug/traces/{tid}", timeout=10
        ).json()
        assert any(s["plane"] == "engine" for s in doc["spans"])

    def test_exhausted_503_carries_trace_id(self, spine):
        spine.cp.dispatch_max_attempts = 2
        try:
            faults.arm(
                seed=3,
                rules=[{"point": "dispatch", "runner": "*",
                        "mode": "connect_error", "p": 1.0}],
            )
            r = _chat(spine.cp_url)
        finally:
            faults.disarm()
            spine.cp.dispatch_max_attempts = 3
        assert r.status_code == 503
        body = r.json()["error"]
        assert body["code"] == "runners_exhausted"
        assert body["trace_id"]
        assert r.headers.get("X-Helix-Trace-Id") == body["trace_id"]

    def test_unknown_trace_404(self, spine):
        for base in (spine.cp_url, spine.runner_url):
            r = requests.get(
                f"{base}/v1/debug/traces/nope", timeout=10
            )
            assert r.status_code == 404


# ---------------------------------------------------------------------------
# satellites: trace store bounds, heap profile, profiler hook
# ---------------------------------------------------------------------------

class TestSatellites:
    def test_trace_store_bounded(self):
        from helix_tpu.obs import TraceStore

        st = TraceStore(max_traces=4, max_spans_per_trace=3)
        for i in range(10):
            for j in range(5):
                st.record(f"t{i}", f"s{j}", 0.0, 1.0, plane="x")
        assert len(st) == 4
        assert st.get("t0") is None          # LRU-evicted
        assert len(st.get("t9")["spans"]) == 3   # span cap
        assert st.dropped_spans > 0

    def test_heap_profile_never_empty(self):
        import tracemalloc

        from helix_tpu.control import debug_profile as dp

        was_tracing = tracemalloc.is_tracing()
        try:
            first = dp.heap_profile()
            assert "sampling since" in first
            assert "total tracked" in first   # a real snapshot, not a stub
            second = dp.heap_profile()
            assert "sampling since" in second
            assert "KiB" in second or "total tracked" in second
        finally:
            if not was_tracing:
                # tracemalloc taxes EVERY allocation (2-4x on jax compile
                # paths) — never leave it armed for the rest of the suite
                tracemalloc.stop()
                dp._tracemalloc_started_at = 0.0

    @pytest.mark.slow   # jax profiler session init costs ~45s on CPU
    def test_profiler_capture_endpoint(self, spine):
        r = requests.post(
            f"{spine.runner_url}/admin/profiler",
            json={"seconds": 0.05},
            timeout=60,
        )
        assert r.status_code in (200, 501), r.text
        if r.status_code == 200:
            assert os.path.isdir(r.json()["log_dir"])
