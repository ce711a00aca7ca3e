"""Writes small.xplane.pb: a hand-made XSpace in the profiler's wire
format (tsl/profiler/protobuf/xplane.proto), shaped like what a TPU v5e
trace holds — one device plane with `XLA Modules` and `XLA Ops` lines,
one host plane with an annotation span — small enough to work out by
hand. Run it again only to change the fixture:

    python tests/benchmark/data/make_small_xplane.py
"""

import os


def varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def field(num, wtype, payload):
    key = varint((num << 3) | wtype)
    if wtype == 0:
        return key + varint(payload)
    return key + varint(len(payload)) + payload


def event(meta_id, offset_ps, duration_ps):
    return (field(1, 0, meta_id) + field(2, 0, offset_ps)
            + field(3, 0, duration_ps))


def line(name, timestamp_ns, events):
    return (field(2, 2, name.encode()) + field(3, 0, timestamp_ns)
            + b"".join(field(4, 2, e) for e in events))


def metadata(mid, name):
    return field(1, 0, mid) + field(2, 2, field(1, 0, mid)
                                    + field(2, 2, name.encode()))


def plane(name, lines, names):
    return (field(2, 2, name.encode())
            + b"".join(field(3, 2, ln) for ln in lines)
            + b"".join(field(4, 2, metadata(i, n)) for i, n in names.items()))


US = 1_000_000  # picoseconds in a microsecond

# Device: two executions of the decode program (100 us each) and one of
# prefill (300 us) inside a 1000 us window that starts at T0.
T0 = 1_700_000_000_000_000_000  # ns
names = {1: "jit_paged_decode_chunk(123)", 2: "jit_paged_prefill(77)",
         3: "fusion.1", 4: "_ragged_paged.8", 5: "_mha_forward.7",
         6: "while.1"}
modules = line("XLA Modules", T0, [
    event(1, 0 * US, 100 * US), event(2, 200 * US, 300 * US),
    event(1, 700 * US, 100 * US),
])
ops = line("XLA Ops", T0, [
    event(3, 0 * US, 60 * US), event(4, 60 * US, 40 * US),       # decode 1
    # prefill: a while loop that holds the kernel and a fusion (nested
    # events: busy time is a union, op time is SELF time)
    event(6, 200 * US, 300 * US),
    event(5, 200 * US, 250 * US), event(3, 450 * US, 50 * US),
    event(3, 700 * US, 70 * US), event(4, 770 * US, 30 * US),    # decode 2
])
device = plane("/device:TPU:0", [modules, ops], names)
host_names = {1: "harvest", 2: "engine_loop"}
host = plane("/host:CPU", [line("engine", T0, [
    event(2, 0, 1000 * US), event(1, 100 * US, 100 * US),
])], host_names)
space = field(1, 2, device) + field(1, 2, host)

if __name__ == "__main__":
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "small.xplane.pb")
    with open(path, "wb") as f:
        f.write(space)
    print(path, len(space), "bytes")
