#!/usr/bin/env python3
"""The quickest proof that helix-tpu still starts on the chip.

    python3 chip_smoke.py             # one TPU chip: kernels, then serve-node
    python3 chip_smoke.py --chips 4   # four chips: the tp path only

One chip.  This parent is plain Python and never imports JAX, so the chip
has one owner at a time; its phases are children, one after another:

1. kernels — ``ragged_paged_attention(backend="pallas")`` against the XLA
   reference at Qwen2-7B geometry (decode and prefill-with-history shapes,
   bf16 and int8 KV pools from ``--seed``), and ``flash_attention`` against
   ``mha_reference``; prints the max abs error per case.
2. server — ``python -m helix_tpu serve-node --profile
   profiles/v5e1-qwen2-7b.yaml``: Qwen2-7B at published widths, int8 weights
   from the profile's seed, bf16 KV.  Over HTTP: models, state (must be one
   TPU v5e), a plain and a streamed chat completion, a burst of eight, the
   same greedy request twice, metrics, SIGTERM (must exit 0).
3. int8-KV server pass — the same server with ``kv_cache_dtype: int8`` and
   the two single requests, when the time limit leaves room for a second
   load and warm-up (a warm compile cache); otherwise a line says that the
   kernel phase is the int8-KV evidence.

Any failed phase ends the script non-zero at once.  The last line is
``{"ok": true, "device": {...}}`` with the device as the server child saw
it — printed only if that device is a TPU.

Four chips (``--chips 4``).  One child drives all four devices and runs
only this: Llama-3-8B int8 from ``--seed`` under ``mesh: {tp: 4}`` and on
device 0 alone, the same three prompts through prefill and 16 decode steps
(tokens drawn from seeded noise, so both engines walk the same history),
next-token logits compared with a tolerance at every step.  (Qwen2-7B has 4 kv heads: at
tp=4 one bf16 kv head a chip does not fill a sublane pack and the kernel
refuses it by name — the child checks that too.  Llama-3-8B's 8 kv heads
give 2 a chip.)

``--rehearse`` walks the same control flow on the CPU at a tiny size
(``profiles/dev-tiny.yaml``, kernels in interpret mode).  A rehearsal never
ends in ``"ok": true`` and never exits 0.
"""

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out")
PROFILE = os.path.join("profiles", "v5e1-qwen2-7b.yaml")
REHEARSAL_PROFILE = os.path.join("profiles", "dev-tiny.yaml")
TIME_LIMIT_S = 1200          # the contract's; compilation included
SAFETY_S = 150               # what the last steps need
TP_MODEL = "meta-llama/Meta-Llama-3-8B-Instruct"
TOL_BF16, TOL_INT8 = 1.6e-2, 2e-2     # BASELINE.md


def say(**obj):
    print(json.dumps(obj), flush=True)


def fail(msg, code=1):
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


# ---------------------------------------------------------------------------
# children that hold the chip (these import JAX; the parent never does)
# ---------------------------------------------------------------------------


def _device_or_die(rehearse, want_count):
    import jax

    from helix_tpu.device.compile_cache import configure_compile_cache

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not rehearse and info["platform"] != "tpu":
        fail(f"JAX found no TPU (devices: {info})", 3)
    if len(devs) != want_count:
        fail(f"need {want_count} device(s), JAX sees {len(devs)}", 3)
    say(phase="device", cache=configure_compile_cache(), **info)
    return info


def phase_kernels(seed, rehearse):
    import jax
    import jax.numpy as jnp
    import numpy as np

    _device_or_die(rehearse, 1)
    from helix_tpu.ops.attention import flash_attention, mha_reference
    from helix_tpu.ops.paged import (
        ragged_paged_attention,
        ragged_paged_attention_reference,
    )
    from helix_tpu.ops.paged_kernel import ragged_paged_attention_tpu
    from helix_tpu.ops.quant import pack_scale_pages, quantize_kv

    H, KVH, D = 28, 4, 128                # Qwen2-7B
    L, P = 2, 16
    if rehearse:
        N, maxP, B, S = 64, 8, 4, 32
    else:
        N, maxP, B, S = 1024, 64, 32, 512
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    kk, kv, kq, kn = jax.random.split(key, 4)
    k_f = jax.random.normal(kk, (L, N, P, KVH, D), jnp.float32)
    v_f = jax.random.normal(kv, (L, N, P, KVH, D), jnp.float32)
    pools = {"bf16": (k_f.astype(jnp.bfloat16), v_f.astype(jnp.bfloat16),
                      None, None)}
    kq8, ks8 = quantize_kv(k_f)
    vq8, vs8 = quantize_kv(v_f)
    pools["int8"] = (kq8, vq8, pack_scale_pages(ks8), pack_scale_pages(vs8))

    def layout(shape):
        """(T, t0, q_len, hist, tables, a row's most fresh tokens): decode =
        B one-token rows over ragged histories; prefill = one S-token row
        over a history that ends mid-page.  The last is the static bound
        the engine passes (the page kind's ``attend``, ``models/mixers.py``),
        which sizes the kernel's query blocks."""
        pages = rng.permutation(np.arange(1, N))
        if shape == "decode":
            hist = rng.integers(1, maxP * P - 1, size=B)
            tables = np.resize(pages, (B, maxP))
            return (B, np.arange(B), np.ones(B, int), hist, tables, 1)
        hist = np.array([(maxP * P) // 2 - 5])
        return (S, np.zeros(1, int), np.array([S]), hist,
                pages[:maxP][None], S)

    ok = True
    for shape in ("decode", "prefill_with_history"):
        T, t0, q_len, hist, tables, bound = layout(shape)
        q = jax.random.normal(kq, (T, H, D), jnp.float32).astype(jnp.bfloat16)
        k_new = jax.random.normal(kn, (T, KVH, D)).astype(jnp.bfloat16)
        v_new = (k_new * 0.5 + 0.25).astype(jnp.bfloat16)
        meta = [jnp.asarray(x, jnp.int32) for x in (t0, q_len, hist, tables)]
        for kvname, (kp, vp, ks, vs) in pools.items():
            args = (q, k_new, v_new, kp, vp, jnp.int32(1), *meta)
            if rehearse:
                got = ragged_paged_attention_tpu(
                    *args, max_q_len=bound, interpret=True,
                    k_scale=ks, v_scale=vs)
            else:
                got = ragged_paged_attention(
                    *args, backend="pallas", max_q_len=bound,
                    k_scale=ks, v_scale=vs)
            with jax.default_matmul_precision("highest"):
                want = ragged_paged_attention_reference(
                    *args, k_scale=ks, v_scale=vs)
            got, want = (np.asarray(x, np.float32) for x in (got, want))
            err = float(np.abs(got - want).max())
            tol = TOL_INT8 if kvname == "int8" else TOL_BF16
            good = bool(np.isfinite(got).all() and err <= tol)
            ok &= good
            say(phase="kernel", op="ragged_paged_attention", geometry=[H, KVH, D],
                shape=shape, tokens=T, max_q_len=bound, kv=kvname,
                max_abs_err=err, tol=tol, ok=good)

    q = jax.random.normal(kq, (1, S, H, D), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(kn, (1, S, KVH, D), jnp.float32).astype(jnp.bfloat16)
    v = (k * 0.5 + 0.25).astype(jnp.bfloat16)
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    seg = (pos >= S // 3).astype(jnp.int32) + 1     # two packed prompts
    kw = dict(causal=True, q_positions=pos, kv_positions=pos,
              q_segment_ids=seg, kv_segment_ids=seg)
    got = flash_attention(q, k, v, interpret=rehearse, **kw)
    with jax.default_matmul_precision("highest"):
        want = mha_reference(q, k, v, **kw)
    err = float(np.abs(np.asarray(got, np.float32)
                       - np.asarray(want, np.float32)).max())
    good = err <= TOL_BF16
    ok &= good
    say(phase="kernel", op="flash_attention", geometry=[H, KVH, D], tokens=S,
        max_abs_err=err, tol=TOL_BF16, ok=good)
    if not ok:
        fail("a kernel disagrees with its reference")


def phase_tp4(seed, rehearse):
    """tp=4 against one chip, by logits.  See the module docstring."""
    import dataclasses

    import jax
    import numpy as np

    info = _device_or_die(rehearse, 4)
    import helix_tpu.engine.engine as E
    from helix_tpu.control.node_agent import seeded_params
    from helix_tpu.device.mesh import MeshSpec, build_mesh
    from helix_tpu.engine.sampling import SamplingParams
    from helix_tpu.models.common import CATALOG
    from helix_tpu.ops.paged_kernel import (
        UnsupportedKernelGeometry,
        check_geometry,
    )

    try:
        check_geometry(28 // 4, 4 // 4, 128, 2)     # Qwen2-7B at tp=4
        fail("Qwen2-7B at tp=4 should be refused by the kernel by name")
    except UnsupportedKernelGeometry as e:
        say(phase="tp4", note="Qwen2-7B tp=4 is refused, Llama-3-8B runs",
            refused=str(e)[:160])

    cfg = CATALOG[TP_MODEL]
    if rehearse:
        cfg = dataclasses.replace(
            cfg, num_layers=2, hidden_size=256, num_heads=8, num_kv_heads=8,
            head_dim=32, intermediate_size=512, vocab_size=1024)
    ecfg = E.EngineConfig(
        max_decode_batch=8, page_size=16, num_pages=512, max_prefill_len=512,
        kv_cache_dtype="auto", decode_steps_per_sync=1,
        enable_prefix_cache=False,
    )
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (37, 150, 301)]
    STEPS = 16
    # worst |tp4 - tp1| logit over the logits' range.  The first four-chip
    # run saw 2.5e-2 over 14 points (bf16 partial sums meet in a different
    # order under tp; random weights give a narrow range): twice that.
    TOL = 5e-2

    # the compiled text of every step the tp engine runs (the spy compiles
    # each new shape once more to read it; the persistent cache pays)
    texts = {}
    real_build = E._build_ragged_step_fn

    def build(*a, **kw):
        fn = real_build(*a, **kw)
        if a[3] is None:            # the one-chip engine
            return fn

        def call(*args):
            if a[4:7] not in texts:
                texts[a[4:7]] = fn.lower(*args).compile().as_text()
            return fn(*args)

        return call

    E._build_ragged_step_fn = build

    def engine(mesh):
        t0 = time.monotonic()
        params = seeded_params(cfg, seed, True, mesh)
        jax.block_until_ready(params)
        eng = E.Engine(cfg, params, ecfg, mesh=mesh)
        say(phase="tp4", mesh="tp=4" if mesh is not None else "tp=1",
            load_quantize_s=round(time.monotonic() - t0, 2))
        # Random weights give near-flat logits, and greedy ids part on a
        # near-tie within a few tokens (the first four-chip run: at tokens
        # 3, 5 and 9), after which there is nothing left to compare.  So
        # the tokens are drawn at a temperature where the seeded noise
        # decides them, the same on both engines; the check is on logits.
        reqs = [E.Request(id=f"p{i}", prompt_tokens=list(p),
                          sampling=SamplingParams(temperature=1000.0,
                                                  seed=1000 * seed + i,
                                                  max_tokens=STEPS + 1))
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.add_request(r)
        return eng, reqs

    mesh = build_mesh(MeshSpec(tp=4))
    e4, r4 = engine(mesh)
    e1, r1 = engine(None)

    # --- placement: a quarter of the bytes on each of four devices -------
    def shard_report(x):
        shards = x.addressable_shards
        return ({s.device.id for s in shards},
                {s.data.nbytes for s in shards}, x.nbytes)

    for name, x in (("w_gate", e4.params["layers"]["w_gate"]["weight"]),
                    ("wq", e4.params["layers"]["wq"]["weight"]),
                    ("k_pages", e4.cache.k_pages),
                    ("v_pages", e4.cache.v_pages)):
        devs, sizes, total = shard_report(x)
        good = len(devs) == 4 and sizes == {total // 4}
        say(phase="tp4", check="shards", tensor=name, devices=sorted(devs),
            shard_bytes=sorted(sizes), total_bytes=total, ok=good)
        if not good:
            fail(f"{name} is not a quarter on each of four devices")
    per_dev = {}
    for leaf in jax.tree.leaves(e4.params):
        for s in leaf.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
    total = sum(x.nbytes for x in jax.tree.leaves(e4.params))
    say(phase="tp4", check="param_bytes", per_device=per_dev, total=total)
    if max(per_dev.values()) > 0.27 * total:
        fail("a device holds more than its quarter of the parameters")

    # --- same prompts through both, compared where histories agree -------
    def slot_of(eng, req):
        return next(i for i, r in enumerate(eng.slots) if r is req)

    compared, worst, diverged, last = 0, 0.0, {}, {}
    for _ in range(STEPS + 8):
        e4.step()
        e1.step()
        done = True
        la = lb = None
        for i, (a, b) in enumerate(zip(r4, r1)):
            if i in diverged or not a.output_tokens:
                done &= i in diverged
                continue
            if a.output_tokens != b.output_tokens:
                # ids may part only on a near-tie of logits that agreed
                k = next(j for j, (x, y) in enumerate(
                    zip(a.output_tokens, b.output_tokens)) if x != y)
                diverged[i] = k
                if i in last and last[i][0] == k:
                    _, y, span = last[i]
                    gap = abs(float(y[a.output_tokens[k]]
                                    - y[b.output_tokens[k]])) / span
                    if gap > 2 * TOL:
                        fail(f"prompt {i}: ids part at token {k} where the "
                             f"logits differ by {gap} of their range")
                continue
            if a.finished or b.finished:
                continue
            done = False
            if la is None:
                la = np.asarray(e4.next_token_logits())
                lb = np.asarray(e1.next_token_logits())
            x, y = la[slot_of(e4, a)], lb[slot_of(e1, b)]
            span = max(float(y.max() - y.min()), 1e-9)
            err = float(np.abs(x - y).max()) / span
            last[i] = (len(b.output_tokens), y, span)
            worst = max(worst, err)
            compared += 1
            if not np.isfinite(x).all():
                fail("tp=4 logits are not finite")
        if done:
            break
    say(phase="tp4", check="logits", points_compared=compared,
        worst_err_over_logit_range=worst, tol=TOL,
        ids_tp4=[r.output_tokens for r in r4],
        ids_tp1=[r.output_tokens for r in r1],
        diverged_at=diverged)
    if compared < 2 * STEPS or worst > TOL:
        fail(f"tp=4 and tp=1 logits: {compared} points, worst {worst}")

    # --- the compiled tp step: kernel in, pool never gathered ------------
    pool_shard = e4.cache.k_pages.addressable_shards[0].data.nbytes
    for shape, text in sorted(texts.items()):
        gathers = [
            int(np.prod([int(d) for d in dims.split(",") if d]))
            for dims in re.findall(
                r"= \w+\[([\d,]*)\][^=]*? all-gather(?:-start)?\(", text)
        ]
        biggest = max(gathers or [0])
        n_ar = len(re.findall(r" all-reduce(?:-start)?\(", text))
        kernels = text.count("tpu_custom_call")
        good = biggest * 2 < pool_shard // 4 and n_ar >= 2 and (
            rehearse or kernels >= 1)
        say(phase="tp4", check="compiled_step", step=list(shape),
            tpu_custom_calls=kernels, all_reduces=n_ar,
            largest_all_gather_elems=biggest, pool_shard_bytes=pool_shard,
            ok=good)
        if not good:
            fail(f"compiled tp step {shape}: kernel/all-reduce/all-gather")
    if not texts:
        fail("no tp step was compiled")
    say(phase="tp4", check="bytes_in_use", per_device={
        d.id: (d.memory_stats() or {}).get("bytes_in_use")
        for d in jax.devices()})
    say(phase="tp4", device=info, ok=True)


# ---------------------------------------------------------------------------
# the parent: plain Python
# ---------------------------------------------------------------------------


def child_env(rehearse, devices=1):
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices}"
        ).strip()
    return env


def run_child(phase, seed, rehearse, devices=1):
    """Run one phase of this file as a child; echo its lines; return its
    JSON lines.  A child that fails ends the script with its code."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--seed", str(seed)] + (["--rehearse"] if rehearse else [])
    proc = subprocess.Popen(cmd, cwd=HERE, env=child_env(rehearse, devices),
                            stdout=subprocess.PIPE, text=True)
    lines = []
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.startswith("{"):
                lines.append(json.loads(line))
        code = proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
    if code != 0:
        fail(f"phase {phase} exited {code}", code if code > 0 else 1)
    return lines


def http(url, body=None, timeout=300):
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read().decode()


def free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def words(n_bytes, salt):
    """A deterministic prompt of exactly ``n_bytes`` bytes (the byte
    tokenizer of a checkpoint-less model makes that many tokens)."""
    out, x = [], (salt * 2654435761 + 12345) % 2**32
    while sum(len(w) + 1 for w in out) < n_bytes:
        x = (x * 1103515245 + 12345) % 2**31
        out.append("".join(chr(97 + (x >> s) % 26) for s in (3, 8, 13, 18, 23))
                   [: 2 + x % 4])
    return " ".join(out)[:n_bytes]


class Server:
    """``serve-node`` as a child, and the requests the smoke sends it."""

    def __init__(self, profile, rehearse, tag):
        self.rehearse, self.tag = rehearse, tag
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        os.makedirs(OUT, exist_ok=True)
        self.log_path = os.path.join(OUT, f"chip_smoke_server_{tag}.log")
        self.log = open(self.log_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "helix_tpu", "serve-node", "--profile",
             profile, "--host", "127.0.0.1", "--port", str(self.port)],
            cwd=HERE, env=child_env(rehearse), stdout=self.log,
            stderr=subprocess.STDOUT)

    def log_text(self):
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def die(self, msg):
        tail = self.log_text()[-3000:]
        self.kill()
        fail(f"server[{self.tag}]: {msg}\n--- server log tail ---\n{tail}")

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()

    def wait_running(self, deadline):
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                self.die(f"exited {self.proc.returncode} before it served")
            try:
                state = json.loads(http(self.url + "/api/v1/state", timeout=5))
                if state["profile"]["status"] == "running":
                    self.ready_s = time.monotonic() - self.t0
                    return state
                if state["profile"]["status"] == "failed":
                    self.die(f"profile failed: {state['profile']['error']}")
            except OSError:
                pass
            time.sleep(2)
        self.die("not running before the time limit")

    def chat(self, model, text, max_tokens=64, stream=False, **extra):
        """One chat completion -> (n_tokens, finish_reason, ids, ttft)."""
        body = {"model": model, "max_tokens": max_tokens, "stream": stream,
                "messages": [{"role": "user", "content": text}], **extra}
        t0 = time.monotonic()
        if not stream:
            out = json.loads(http(self.url + "/v1/chat/completions", body))
            ch = out["choices"][0]
            return (out["usage"]["completion_tokens"], ch["finish_reason"],
                    ch.get("token_ids"), None)
        req = urllib.request.Request(
            self.url + "/v1/chat/completions", json.dumps(body).encode(),
            {"Content-Type": "application/json"})
        n, finish, ttft = 0, None, None
        with urllib.request.urlopen(req, timeout=300) as r:
            for raw in r:
                line = raw.decode().strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                chunk = json.loads(line[6:])
                if "error" in chunk:
                    self.die(f"stream error: {chunk}")
                if ttft is None:
                    ttft = time.monotonic() - t0
                n += 1
                finish = chunk["choices"][0]["finish_reason"] or finish
        return n, finish, None, ttft

    def answered(self, what, n, finish):
        if n < 1 or not finish:
            self.die(f"{what}: {n} tokens, finish_reason {finish!r}")

    def stop(self):
        self.proc.send_signal(signal.SIGTERM)
        try:
            code = self.proc.wait(timeout=180)
        except subprocess.TimeoutExpired:
            self.die("did not exit within 180 s of SIGTERM")
        if code != 0:
            self.die(f"exit code {code} after SIGTERM, want 0")
        return code


def metric(text, name):
    vals = [float(m.group(1)) for m in re.finditer(
        rf"^{name}(?:{{[^}}]*}})? ([0-9.eE+-]+)$", text, re.M)]
    return max(vals) if vals else None


def log_seconds(log, what):
    m = re.search(rf"{what} in ([0-9.]+)s", log)
    return float(m.group(1)) if m else None


def server_phase(profile, rehearse, tag, full, deadline):
    srv = Server(profile, rehearse, tag)
    try:
        state = srv.wait_running(deadline)
        acc = state["accelerators"]
        dev = {"platform": "tpu" if acc[0]["vendor"] == "tpu" else
               acc[0]["vendor"], "kind": acc[0]["device_kind"],
               "count": len(acc)}
        if not rehearse and not (
                acc[0]["vendor"] == "tpu" and acc[0]["arch"] == "v5e"
                and len(acc) == 1):
            srv.die(f"state is not one TPU v5e: {acc}")
        model = json.loads(http(srv.url + "/v1/models"))["data"][0]["id"]
        log = srv.log_text()
        backend = re.search(
            r"attention backend (\w+) on platform (\w+), device_kind (.+?),",
            log)
        if not backend:
            srv.die("the log does not name the attention backend")
        if not rehearse and (backend[1], backend[2]) != ("pallas", "tpu"):
            srv.die(f"backend {backend[1]} on {backend[2]}, want pallas/tpu")
        say(phase="server", kv=tag, model=model, device=dev,
            attention_backend=backend[1], log_device_kind=backend[3],
            ready_s=round(srv.ready_s, 1),
            load_quantize_s=log_seconds(log, "weights on device"),
            warmup_s=log_seconds(log, r"warmup\(\)"))

        n, fin, _, _ = srv.chat(model, words(120, 1))
        srv.answered("plain request", n, fin)
        ns, fins, _, ttft = srv.chat(model, words(150, 2), stream=True)
        srv.answered("streamed request", ns, fins)
        say(phase="server", kv=tag, plain_tokens=n, plain_finish=fin,
            streamed_tokens=ns, streamed_finish=fins,
            first_token_s=round(ttft, 3))
        if full:
            # eight at once, 100-400 token prompts: packed prefill, the
            # mixed step and a full decode window all run
            lens = [100, 140, 190, 230, 280, 320, 360, 400]
            if rehearse:        # dev-tiny holds 256 tokens a sequence
                lens = [n // 4 for n in lens]
            results, errors = [None] * 8, []

            def one(i):
                try:
                    results[i] = srv.chat(model, words(lens[i], 10 + i))
                except Exception as e:     # surfaced below, never dropped
                    errors.append(repr(e))

            t0 = time.monotonic()
            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.monotonic() - t0
            if errors:
                srv.die(f"burst: {errors}")
            for i, (bn, bfin, _, _) in enumerate(results):
                srv.answered(f"burst request {i}", bn, bfin)
            toks = sum(r[0] for r in results)
            say(phase="server", kv=tag, burst_requests=8,
                burst_prompt_tokens=lens, burst_tokens=toks,
                burst_seconds=round(wall, 2),
                smoke_tokens_per_s_not_a_benchmark=round(toks / wall, 1))
            # the same greedy request twice: the second is served partly
            # from the prefix cache.  Random weights give near-flat logits,
            # so ids may part after the first token without failing.
            hits0 = metric(http(srv.url + "/metrics"),
                           "helix_prefix_cache_hits_total") or 0
            runs = [srv.chat(model, words(65 if rehearse else 260, 99),
                             temperature=0.0,
                             return_token_ids=True) for _ in range(2)]
            for gn, gfin, _, _ in runs:
                srv.answered("greedy request", gn, gfin)
            hits1 = metric(http(srv.url + "/metrics"),
                           "helix_prefix_cache_hits_total") or 0
            say(phase="server", kv=tag, greedy_ids_first=runs[0][2],
                greedy_ids_second=runs[1][2],
                greedy_agree=runs[0][2] == runs[1][2],
                prefix_cache_hits_gained=hits1 - hits0)
        text = http(srv.url + "/metrics")
        shapes = metric(text, "helix_compiled_step_shapes")
        decoded = metric(text, "helix_decode_tokens_total")
        peak = json.loads(http(srv.url + "/api/v1/state"))[
            "accelerators"][0].get("peak_memory_bytes")
        say(phase="server", kv=tag, compiled_step_shapes=shapes,
            decode_tokens=decoded, peak_bytes_in_use=peak or None)
        if not shapes or shapes < 1 or not decoded:
            srv.die(f"metrics: step shapes {shapes}, decode tokens {decoded}")
        code = srv.stop()
        say(phase="server", kv=tag, sigterm_exit_code=code)
        return dev, time.monotonic() - srv.t0
    finally:
        srv.kill()


def int8_profile(profile):
    """The same profile with an int8 KV pool, beside the server logs."""
    with open(os.path.join(HERE, profile)) as f:
        text = f.read()
    if "kv_cache_dtype: auto" in text:
        text = text.replace("kv_cache_dtype: auto", "kv_cache_dtype: int8")
    else:
        text = text.replace("engine: {", "engine: {kv_cache_dtype: int8, ", 1)
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "chip_smoke_int8kv_profile.yaml")
    with open(path, "w") as f:
        f.write(text)
    return path


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes; never ok, never exit 0")
    ap.add_argument("--phase", choices=("kernels", "tp4"),
                    help="(internal) run one chip-holding phase")
    args = ap.parse_args()
    start = time.monotonic()
    profile = REHEARSAL_PROFILE if args.rehearse else PROFILE
    if not (os.path.isdir(os.path.join(HERE, "helix_tpu"))
            and os.path.isfile(os.path.join(HERE, profile))):
        fail("chip_smoke.py must sit at the root of a helix-tpu checkout "
             "(helix_tpu/ and profiles/ beside it)", 2)
    if args.phase:
        sys.path.insert(0, HERE)
        {"kernels": phase_kernels, "tp4": phase_tp4}[args.phase](
            args.seed, args.rehearse)
        return

    if args.chips == 4:
        lines = run_child("tp4", args.seed, args.rehearse, devices=4)
        device = lines[-1]["device"]
    else:
        run_child("kernels", args.seed, args.rehearse)
        deadline = start + TIME_LIMIT_S - SAFETY_S
        device, took = server_phase(profile, args.rehearse, "bf16", True,
                                    deadline)
        left = start + TIME_LIMIT_S - SAFETY_S - time.monotonic()
        if left > 1.15 * took:
            server_phase(int8_profile(profile), args.rehearse, "int8", False,
                         time.monotonic() + left)
        else:
            say(phase="server", kv="int8", skipped=True,
                seconds_left=round(left), first_pass_seconds=round(took),
                note="no room in the time limit for a second load and "
                     "warm-up: the kernel phase's int8 cases are this run's "
                     "int8-KV evidence")
    if args.rehearse:
        say(ok=False, rehearsal=True, device=device)
        sys.exit(4)
    if device.get("platform") != "tpu" or device.get("count") != args.chips:
        fail(f"the children saw {device}, not {args.chips} TPU chip(s)", 3)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)


if __name__ == "__main__":
    main()
