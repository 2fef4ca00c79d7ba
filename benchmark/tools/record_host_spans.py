#!/usr/bin/env python3
"""Record ``benchmark/tests/data/host-spans.xplane.pb`` and
``host-spans-cycles.json``: a jax trace around a few cycles of the tiny
fused engine (one wave with a prompt longer than the chunk budget, so the
slice holds chunk cycles and plain decode cycles) and two steps of the
tiny trainer, on whatever device jax has, with the flight recorder's
cycle records beside it. The trace is cut down to the host-plane events
the readers look for (XLA's own thread-pool lines make a CPU trace a
megabyte). A one-off: nothing at run time, no reader and no test, uses
the wire codec below; it only trims the data file before it is committed.
``python3 benchmark/tools/record_host_spans.py <dir>``"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.lib import host_spans as HS  # noqa: E402
from benchmark.lib import system as SUT  # noqa: E402
from benchmark.lib import trace_reduce as TR  # noqa: E402
from benchmark.lib import traffic as T  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "tests", "data")
SEED = 27


# -- the protobuf wire format, as far as an XSpace needs it ----------------
def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _encode_varint(value):
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        if not value:
            out.append(byte)
            return bytes(out)
        out.append(byte | 0x80)


def _fields(buf):
    """[(field number, value, the field's own bytes)] of one message."""
    i, out = 0, []
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:
            n = {1: 8, 5: 4}[wire]
            value, i = buf[i:i + n], i + n
        out.append((key >> 3, value, buf[start:i]))
    return out


def _message(number, payload):
    return _encode_varint(number << 3 | 2) + _encode_varint(len(payload)) \
        + payload


def keep_events(xspace: bytes, keep) -> bytes:
    """The XSpace with only the events whose name ``keep`` accepts: lines
    left empty and event names left unused are dropped. XSpace.planes = 1;
    XPlane.lines = 3, .event_metadata = 4 (map: key 1, value 2 with
    .name = 2); XLine.events = 4; XEvent.metadata_id = 1."""
    out = bytearray()
    for number, value, raw in _fields(xspace):
        if number != 1:
            out += raw
            continue
        plane_fields = _fields(value)
        kept = set()
        for n, v, _ in plane_fields:
            if n == 4:
                entry = {a: b for a, b, _ in _fields(v)}
                meta = {a: b for a, b, _ in _fields(entry[2])}
                if keep(bytes(meta.get(2, b"")).decode()):
                    kept.add(entry[1])
        plane = bytearray()
        for n, v, r in plane_fields:
            if n == 3:
                line, events = bytearray(), 0
                for a, b, rr in _fields(v):
                    if a != 4:
                        line += rr
                    elif dict((x, y) for x, y, _ in _fields(b)).get(1) in kept:
                        line += rr
                        events += 1
                if events:
                    plane += _message(3, bytes(line))
            elif n != 4 or {a: b for a, b, _ in _fields(v)}[1] in kept:
                plane += r
        out += _message(1, bytes(plane))
    return bytes(out)


def main(out_dir: str) -> int:
    import jax
    with open(os.path.join(DATA, "tiny-config.json")) as f:
        config = json.load(f)
    model, serving = config["model"], config["serving"]
    vocab = int(model["vocab_size"])
    served = SUT.Served(SUT.build_lm(model, SEED, serving["dtype"]), model,
                        serving, slots=4)
    trainer, _, _ = SUT.build_trainer(model, config["training"], SEED)
    rng = T.seed_rng(SEED, 6)
    batch = rng.integers(0, vocab, size=(4, 33), dtype="int32")
    ids, labels = batch[:, :-1].copy(), batch[:, 1:].copy()

    def wave(first):
        lengths = (40, 10, 10)      # 40 > the 32-token chunk budget
        served.generate([T.prompt_tokens(SEED, first + i, n, vocab, stream=7)
                         for i, n in enumerate(lengths)], [4] * len(lengths))

    def steps():
        for _ in range(2):
            loss = trainer.train_batch([ids, labels], return_numpy=False)
        loss.block_until_ready()

    wave(0)                         # compile every program the slice uses
    steps()
    jax.profiler.start_trace(out_dir)
    wave(10)
    steps()
    jax.profiler.stop_trace()
    cycles = served.engine.flight_recorder.snapshot()["cycles"]
    served.close()

    path = TR.latest_xplane(out_dir)
    with open(path, "rb") as f:
        whole = f.read()
    cut = keep_events(whole, lambda name: name.startswith(HS.PREFIXES))
    small = os.path.join(out_dir, "host-spans.xplane.pb")
    with open(small, "wb") as f:
        f.write(cut)
    spans = HS.read_trace(small)[0]
    assert spans == HS.read_trace(path)[0], "the cut changed a span"
    traced = {int(a["cycle"]) for _, _, n, a in spans if n == HS.CYCLE}
    with open(os.path.join(out_dir, "host-spans-cycles.json"), "w") as f:
        json.dump([c for c in cycles if c["cycle"] in traced], f, indent=1)
    print(f"{len(spans)} spans over cycles {sorted(traced)}; wrote "
          f"{small} ({len(cut):,} of {len(whole):,} bytes) and "
          f"host-spans-cycles.json")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
