"""Per-layer metric readers.  Each metric has a file of its own,
``benchmark/metrics/<name>.json``, that names a reduction below and gives it
its parameters (a series, a name pattern).  A reader that finds nothing to
read returns None and the harness leaves the metric out of the line.

``ctx`` keys: ``scrapes`` {edge: parsed /metrics}, ``flight`` (step records
inside the window), ``trace`` (``benchmark.lib.xplane`` summary or None),
``log`` (server log text), ``recs`` (client records of the window),
``config`` (the configuration's JSON), ``device_kind``.
"""

import re

from benchmark.lib import model_bytes, peaks, prom, stats

READERS = {}


def reader(fn):
    READERS[fn.__name__] = fn
    return fn


@reader
def histogram_mean_ms(ctx, spec):
    """Mean of a server histogram's observations inside the window."""
    return prom.mean_of_histogram_ms(
        ctx["scrapes"]["w0"], ctx["scrapes"]["w1"], spec["series"])


@reader
def send_lag_p95_ms(ctx, spec):
    """How late the generator ran: send time - due time, open loop only."""
    lags = [(r.sent - r.due) * 1e3 for r in ctx["recs"]
            if r.due is not None and r.sent is not None]
    return stats.percentile(lags, 95) if lags else None


@reader
def ttft_percentile_ms(ctx, spec):
    """A percentile of time to first token: from due time in an open loop,
    from send time in a closed one."""
    ttft = [(r.first - (r.due if r.due is not None else r.sent)) * 1e3
            for r in ctx["recs"] if r.first is not None]
    return stats.percentile(ttft, spec["q"])


@reader
def flight_slot_occupancy(ctx, spec):
    """Mean busy decode slots over capacity, per engine step, in %."""
    steps = [s for s in ctx["flight"] if s.get("slots_total")]
    if not steps:
        return None
    return 100.0 * sum(s["slots_busy"] / s["slots_total"]
                       for s in steps) / len(steps)


@reader
def log_seconds(ctx, spec):
    m = re.search(spec["pattern"], ctx["log"])
    return float(m.group(1)) if m else None


def _device(ctx):
    tr = ctx.get("trace")
    if not tr or not tr.get("devices"):
        return None
    return tr["devices"][0]


def _programs(dev, spec, ctx):
    """Executed programs whose name matches ``program`` and whose operations
    include one matching ``with_op`` (if given) and none matching
    ``without_op`` (if given).  ``whole_op`` names an operation that runs
    once a layer: a program whose count of it is not a multiple of the layer
    count was cut by the capture's edge and is left out."""
    out = []
    layers = ctx["config"]["num_hidden_layers"]
    for m in dev["modules"]:
        if not re.search(spec["program"], m["name"]):
            continue
        names = list(m["ops"])
        if spec.get("with_op") and not any(
                re.search(spec["with_op"], n) for n in names):
            continue
        if spec.get("without_op") and any(
                re.search(spec["without_op"], n) for n in names):
            continue
        if spec.get("whole_op"):
            calls = sum(c for n, c in m["ops"].items()
                        if re.search(spec["whole_op"], n))
            if calls == 0 or calls % layers:
                continue
        out.append(m)
    return out


def _steps_in(program, spec, ctx):
    """Model steps one program execution ran: calls of the operation that
    runs once a layer a step (``per_op``), over the layer count.  The fused
    decode window's length is a dynamic argument, so one program name covers
    1 to ``decode_steps_per_sync`` steps; the trace shows how many."""
    if not spec.get("per_op"):
        return 1
    calls = sum(c for n, c in program["ops"].items()
                if re.search(spec["per_op"], n))
    layers = ctx["config"]["num_hidden_layers"]
    return calls / (layers * spec.get("per_op_calls_per_layer", 1))


@reader
def trace_program_ms(ctx, spec):
    """Mean device time of a kind of program, in ms a model step."""
    dev = _device(ctx)
    if dev is None:
        return None
    progs = _programs(dev, spec, ctx)
    steps = sum(_steps_in(p, spec, ctx) for p in progs)
    if not progs or steps <= 0:
        return None
    return sum(p["dur_s"] for p in progs) * 1e3 / steps


@reader
def trace_op_share(ctx, spec):
    """Self time of the operations matching ``op`` over device busy time."""
    dev = _device(ctx)
    if dev is None or not dev["busy_s"]:
        return None
    t = sum(v[1] for n, v in dev["ops"].items() if re.search(spec["op"], n))
    return 100.0 * t / dev["busy_s"] if t else None


@reader
def trace_idle_share(ctx, spec):
    tr = ctx.get("trace")
    dev = _device(ctx)
    if dev is None or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - dev["busy_s"] / tr["window_s"])


@reader
def decode_hbm_share(ctx, spec):
    """Least bytes a decode step reads (int8 weights + the K and V of the
    live contexts) over the step's device time, over the chip's HBM peak.
    Live context: the mean over the window's flight records of KV pages in
    use, times the page size (pages are whole, so a little over the tokens
    held: the share leans high by under one page a sequence)."""
    ms = trace_program_ms(ctx, spec)
    pages = [s["kv_pages_used"] for s in ctx["flight"]
             if s.get("kind") in ("decode", "mixed")]
    if not ms or not pages:
        return None
    cfg, srv = ctx["config"], ctx["config"]["serving"]
    live = sum(pages) / len(pages) * srv["page_size"]
    need = model_bytes.decode_step_bytes(
        cfg, live, srv["weight_dtype"], srv["kv_dtype"],
        embed_rows=srv["max_decode_batch"])
    peak = peaks.chip_peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * need / (ms / 1e3) / peak
