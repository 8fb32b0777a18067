"""Trim an xplane.pb: keep the device planes (and the metadata they need),
and of each line only the events that start inside the first KEEP_S seconds
of the earliest kept event.  Plain protobuf wire format, no schema files."""
import sys
KEEP_S = float(sys.argv[3]) if len(sys.argv) > 3 else 0.25

def varint(b, i):
    x = s = 0
    while True:
        c = b[i]; i += 1
        x |= (c & 0x7f) << s; s += 7
        if not c & 0x80: return x, i

def enc(x):
    out = bytearray()
    while True:
        c = x & 0x7f; x >>= 7
        if x: out.append(c | 0x80)
        else: out.append(c); return bytes(out)

def fields(b):
    i = 0
    while i < len(b):
        tag, i = varint(b, i)
        f, w = tag >> 3, tag & 7
        if w == 0: v, j = varint(b, i); yield f, w, v, b[i:j]; i = j
        elif w == 2: n, i2 = varint(b, i); yield f, w, b[i2:i2+n], None; i = i2 + n
        elif w == 1: yield f, w, b[i:i+8], None; i += 8
        elif w == 5: yield f, w, b[i:i+4], None; i += 4
        else: raise ValueError(w)

def put(f, w, v):
    if w == 0: return enc(f << 3) + enc(v)
    if w == 2: return enc(f << 3 | 2) + enc(len(v)) + v
    return enc(f << 3 | w) + v

data = open(sys.argv[1], 'rb').read()
out = bytearray()
for f, w, v, _ in fields(data):
    if f != 1:                      # XSpace: errors, warnings, hostnames
        out += put(f, w, v); continue
    name = next((x for ff, ww, x, _ in fields(v) if ff == 2), b'').decode()
    if not name.startswith('/device:TPU:'):
        continue
    # first pass: earliest line timestamp
    lines = [x for ff, ww, x, _ in fields(v) if ff == 3]
    t0 = min(next((x for ff, ww, x, _ in fields(l) if ff == 3), 0) for l in lines)
    plane = bytearray()
    kept = dropped = 0
    for ff, ww, x, _ in fields(v):
        if ff == 4:     # event_metadata entry: keep id and name only
            key = next(y for f3, w3, y, _ in fields(x) if f3 == 1)
            val = next(y for f3, w3, y, _ in fields(x) if f3 == 2)
            slim = b''.join(put(f4, w4, (z[:160] if f4 == 2 else z))
                            for f4, w4, z, _ in fields(val) if f4 in (1, 2))
            plane += put(4, 2, put(1, 0, key) + put(2, 2, slim)); continue
        if ff == 5:     # stat_metadata: the events' stats are dropped
            continue
        if ff != 3:
            plane += put(ff, ww, x); continue
        ts = next((y for f3, w3, y, _ in fields(x) if f3 == 3), 0)
        lname = next((y for f3, w3, y, _ in fields(x) if f3 == 2), b'').decode()
        if lname not in ('XLA Modules', 'XLA Ops'):
            continue
        line = bytearray()
        for f3, w3, y, _ in fields(x):
            if f3 != 4:
                line += put(f3, w3, y); continue
            off = next((z for f4, w4, z, _ in fields(y) if f4 == 2), 0)
            # drop the event's stats (field 4): the reduction reads none
            if (ts - t0) * 1000 + off < KEEP_S * 1e12:
                ev = b''.join(put(f4, w4, z) for f4, w4, z, _ in fields(y) if f4 != 4)
                line += put(4, 2, ev); kept += 1
            else:
                dropped += 1
        plane += put(3, 2, bytes(line))
    print(name, 'events kept', kept, 'dropped', dropped)
    out += put(1, 2, bytes(plane))
open(sys.argv[2], 'wb').write(out)
print(len(data), '->', len(out))
