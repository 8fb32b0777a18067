"""Read Prometheus text exposition (the server's ``/metrics``) into numbers
and take deltas over a window."""

import re

_LINE = re.compile(r"^([A-Za-z_:][\w:]*)(\{[^}]*\})? (\S+)$")


def parse(text, model=None):
    """{series name: value}.  A series with labels is kept when it carries
    ``model="<model>"`` (or when ``model`` is None) and has no other label
    that splits it (``le`` buckets are dropped); a series without labels is
    always kept."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if not m:
            continue
        name, labels, raw = m.groups()
        if labels:
            pairs = dict(re.findall(r'(\w+)="([^"]*)"', labels))
            if model is not None and pairs.get("model") not in (None, model):
                continue
            if set(pairs) - {"model"}:
                continue
        try:
            out[name] = float(raw)
        except ValueError:
            continue
    return out


def delta(before, after, name):
    """after - before of one series; None where either side lacks it."""
    if name not in before or name not in after:
        return None
    return after[name] - before[name]


def mean_of_histogram_ms(before, after, base):
    """Mean of a histogram's observations inside the window, in ms, from its
    ``_sum`` and ``_count`` deltas.  None with no observation."""
    s = delta(before, after, base + "_sum")
    c = delta(before, after, base + "_count")
    if not c or s is None:
        return None
    return s / c * 1e3
