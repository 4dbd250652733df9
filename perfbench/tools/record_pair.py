"""Record a small pair for the tests: a few whole steps of a traced cell, and
the scope table the program gave for the executable that ran them.

    python3 perfbench/tools/record_pair.py --workload <cell> --seed <n> \\
        --seconds 12 --steps 2 --out chiprun_out/pair/train_scoped

runs the cell once with ``--trace 1`` as ``run.py`` does, keeps the profiler's
file, and writes ``<out>.xplane.pb.gz`` (the device planes' ``XLA Ops`` and
``XLA Modules`` lines and the host spans the loader keeps, cut to the first
``--steps`` whole executions of ``jit_train_step``) and
``<out>.scopes.json.gz`` (what ``scopes.table_for`` returned in that run).
``--raw <file.xplane.pb>`` cuts a file recorded earlier instead of running.
Not part of a benchmark run.

The cut works on the protobuf's wire format (the container has no generated
classes for it): XSpace.planes=1; XPlane.name=2 .lines=3 .event_metadata=4
(map: key=1, value=2 with XEventMetadata.name=2); XLine.name=2 .timestamp_ns=3
.events=4; XEvent.metadata_id=1 .offset_ps=2 .duration_ps=3. Every field it
does not cut is copied as it was.
"""
import argparse
import glob
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(HERE)]

KEPT_LINES = ("XLA Ops", "XLA Modules")
HOST_SPANS = ("hb.", "prefill", "decode")
VARINT, LEN = 0, 2


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _enc_varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def fields(buf):
    """(field number, wire type, value, the field's own bytes) of a message."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        no, wt = key >> 3, key & 7
        if wt == VARINT:
            value, i = _varint(buf, i)
        elif wt == LEN:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wt in (1, 5):
            n = 8 if wt == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wt}")
        yield no, wt, value, buf[start:i]


def _len_field(no, payload):
    return _enc_varint(no << 3 | LEN) + _enc_varint(len(payload)) + payload


def _first(buf, no, default=None):
    return next((v for n, _, v, _ in fields(buf) if n == no), default)


def _event_span(event):
    offset = duration = 0
    meta = None
    for no, _, v, _ in fields(event):
        if no == 1:
            meta = v
        elif no == 2:
            offset = v
        elif no == 3:
            duration = v
    return meta, offset, duration


def _cut_plane(plane, window_ps, is_device, steps, module):
    """The plane with its lines cut to ``window_ps`` (found here, from the
    module's executions, where it is ``None`` and the plane is a device's);
    ``(bytes or None, window)``."""
    names = {}
    for no, _, entry, _ in fields(plane):
        if no == 4:
            meta = _first(entry, 2, b"")
            names[_first(entry, 1, 0)] = bytes(_first(meta, 2, b"")).decode("utf8", "replace")
    lines = [v for no, _, v, _ in fields(plane) if no == 3]
    if is_device and window_ps is None:
        for line in lines:
            if bytes(_first(line, 2, b"")) != b"XLA Modules":
                continue
            t0 = _first(line, 3, 0) * 1000
            runs = []
            for no, _, ev, _ in fields(line):
                if no == 4:
                    meta, off, dur = _event_span(ev)
                    if names.get(meta, "").startswith(module + "("):
                        runs.append((t0 + off, dur))
            if not runs:
                return None, None
            median = sorted(d for _, d in runs)[len(runs) // 2]
            whole = [r for r in runs if r[1] >= 0.98 * median][:steps]
            window_ps = (whole[0][0], whole[-1][0] + whole[-1][1])
    if window_ps is None:
        return None, None
    lo, hi = window_ps
    used, out_lines = set(), []
    for line in lines:
        name = bytes(_first(line, 2, b"")).decode()
        if is_device and name not in KEPT_LINES:
            continue
        t0 = _first(line, 3, 0) * 1000
        kept, rest = [], []
        for no, _, v, raw in fields(line):
            if no != 4:
                rest.append(raw)
                continue
            meta, off, dur = _event_span(v)
            s, e = t0 + off, t0 + off + dur
            if is_device:
                keep = lo <= s and e <= hi
            else:       # a host span the loader keeps, overlapping the window
                keep = names.get(meta, "").startswith(HOST_SPANS) and s < hi and e > lo
            if keep:
                kept.append(raw)
                used.add(meta)
        if kept:
            out_lines.append(_len_field(3, b"".join(rest + kept)))
    if not out_lines:
        return None, window_ps
    out = []
    for no, _, v, raw in fields(plane):
        if no == 3:
            continue
        if no == 4 and _first(v, 1, 0) not in used:
            continue
        out.append(raw)
    return b"".join(out + out_lines), window_ps


def cut(raw: bytes, steps: int, module: str = "jit_train_step") -> bytes:
    planes = [v for no, _, v, _ in fields(raw) if no == 1]
    named = [(bytes(_first(p, 2, b"")).decode(), p) for p in planes]
    out, window = [], None
    for name, plane in named:
        if name.startswith("/device:TPU:"):
            got, w = _cut_plane(plane, None, True, steps, module)
            if got:
                out.append(_len_field(1, got))
                window = w if window is None else (min(window[0], w[0]), max(window[1], w[1]))
    for name, plane in named:
        if name.startswith("/host:") and window:
            got, _ = _cut_plane(plane, window, False, steps, module)
            if got:
                out.append(_len_field(1, got))
    return b"".join(out)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--raw", default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    if args.raw:
        with open(args.raw, "rb") as f:
            raw = f.read()
    else:
        import harness
        import run as runner
        import scopes

        kept = {}
        reduce_, table_for = harness.TraceWindow.reduce, scopes.table_for

        def reduce(self):
            for p in glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")):
                with open(p, "rb") as f:
                    kept["raw"] = f.read()
            return reduce_(self)

        def keeping(facts, module):
            got = table_for(facts, module)
            if got is not None:
                kept["table"] = got[0]
            return got

        harness.TraceWindow.reduce, scopes.table_for = reduce, keeping
        result = runner.run_cell(args.workload, args.seed, args.seconds, True)
        print(json.dumps(result), flush=True)
        raw = kept["raw"]
        with gzip.open(args.out + ".scopes.json.gz", "wt") as f:
            json.dump(kept["table"], f, separators=(",", ":"))
    with gzip.open(args.out + ".xplane.pb.gz", "wb") as f:
        f.write(cut(raw, args.steps))
    return 0


if __name__ == "__main__":
    sys.exit(main())
