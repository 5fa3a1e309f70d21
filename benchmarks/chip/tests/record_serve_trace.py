#!/usr/bin/env python3
"""Record ``data/serve.xplane.pb``: a few traced steps of ``Server`` on a TPU.

  python3 benchmarks/chip/tests/record_serve_trace.py [--out PATH]

Run from the root of a checkout, on one chip.  qwen3-1.7b at its published
widths with 2 of its 28 layers, random bfloat16 weights, 4 slots of 256
positions.  Three requests of 32 prompt tokens fill three slots and every
shape is compiled before the trace starts; inside the traced window
(``bench.window``) the harness-style ``bench.step`` spans wrap 4 steps, and a
fourth request, submitted before the second of them, is admitted into the
free slot.  ``test_phases.py`` reads the file.

The recorded ``.xplane.pb`` is trimmed to what ``reduce`` and ``phases``
read, to keep it small: the ``/host:metadata`` plane (HLO protos) goes, so
do the Python tracer's ``$``-events, and of each device operation's metadata
stats only ``program_id`` and ``tf_op`` stay.
"""
import sys
import tempfile
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(_ROOT / "src"), str(_ROOT / "benchmarks")]

LAYERS, SLOTS, CACHE_LEN, PROMPT, MAX_NEW, STEPS = 2, 4, 256, 32, 64, 4


KEEP_STATS = {"program_id", "tf_op"}
DROP_PLANES = {"/host:metadata"}


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _raw(b):
    """(field, value, raw bytes) of one protobuf message, field by field."""
    from chip.phases import _varint as read_varint
    i = 0
    while i < len(b):
        start = i
        key, i = read_varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = read_varint(b, i)
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            v = None
        else:
            n, i = read_varint(b, i)
            v, i = b[i:i + n], i + n
        yield key >> 3, v, bytes(b[start:i])


def _msg(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _names(plane, field: int) -> dict:
    """id -> name of a plane's event (4) or stat (5) metadata map."""
    out = {}
    for f, entry, _ in _raw(plane):
        if f == field:
            d = {k: v for k, v, _ in _raw(entry)}
            meta = {k: v for k, v, _ in _raw(d.get(2, b""))}
            out[d.get(1, 0)] = bytes(meta.get(2, b"")).decode()
    return out


def _trim_event_metadata(entry, keep_stat) -> bytes:
    out = b""
    for f, v, raw in _raw(entry):
        if f == 2:          # the XEventMetadata: drop stats not kept
            v = b"".join(r for k, val, r in _raw(v)
                         if k != 5 or keep_stat(val))
            raw = _msg(2, v)
        out += raw
    return out


def trim(space: bytes) -> bytes:
    """The trace without what the reduction does not read (see above)."""
    out = b""
    for f, plane, raw in _raw(memoryview(space)):
        if f != 1:
            out += raw
            continue
        name = next(bytes(v).decode() for k, v, _ in _raw(plane) if k == 2)
        if name in DROP_PLANES:
            continue
        stats = _names(plane, 5)
        events = _names(plane, 4)
        device = name.startswith("/device:")

        def keep_stat(stat):
            sid = next((v for k, v, _ in _raw(stat) if k == 1), None)
            return stats.get(sid) in KEEP_STATS

        def keep_event(event):
            mid = next((v for k, v, _ in _raw(event) if k == 1), None)
            return not events.get(mid, "").startswith("$")

        body = b""
        for k, v, r in _raw(plane):
            if k == 4 and device:
                r = _msg(4, _trim_event_metadata(v, keep_stat))
            elif k == 3 and not device:
                r = _msg(3, b"".join(rr for kk, vv, rr in _raw(v)
                                     if kk != 4 or keep_event(vv)))
            body += r
        out += _msg(1, body)
    return out


def requests(vocab: int, n: int, first_uid: int = 0):
    import numpy as np
    from repro.serve.loop import Request
    rng = np.random.default_rng(first_uid)
    return [Request(uid=first_uid + i, max_new=MAX_NEW,
                    prompt=rng.integers(0, vocab, PROMPT).astype(np.int32))
            for i in range(n)]


def main(argv=None) -> int:
    import argparse
    import dataclasses
    import shutil

    import jax
    from chip import reduce
    from repro.configs import get
    from repro.models import model as lm
    from repro.serve.loop import Server
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=str(Path(__file__).parent / "data"
                                         / "serve.xplane.pb"))
    a = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_serve_trace: needs a TPU")
    base = get("qwen3-1.7b")
    cfg = dataclasses.replace(
        base, n_layers=LAYERS,
        policy=dataclasses.replace(base.policy, param_dtype="bfloat16"))
    params = lm.init(cfg, jax.random.key(0))
    server = Server(cfg, params, slots=SLOTS, cache_len=CACHE_LEN, wall=True)
    for r in requests(cfg.vocab_size, SLOTS - 1):
        server.submit(r)
    server.step()                       # compiles prefill and decode
    server.step()
    late = requests(cfg.vocab_size, 1, first_uid=SLOTS)
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    try:
        with jax.profiler.TraceAnnotation(reduce.WINDOW):
            for i in range(STEPS):
                if i == 1:
                    server.submit(late[0])
                with jax.profiler.TraceAnnotation(reduce.STEP):
                    server.step()
    finally:
        jax.profiler.stop_trace()
    src = reduce.find_xplane(Path(d))
    Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    Path(a.out).write_bytes(trim(src.read_bytes()))
    shutil.rmtree(d, ignore_errors=True)
    print(f"{a.out}: {Path(a.out).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
