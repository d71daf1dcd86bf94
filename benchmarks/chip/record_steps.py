"""Record a profiler trace of a few engine steps of one benchmark cell,
for the tests of the trace reduction (``testdata/``).

    python3 benchmarks/chip/record_steps.py --workload <cell> --seed <n> \
        --out <file.xplane.pb> [--running 2] [--steps 4]

Builds the cell's program as `run.py` does and warms it up, starts
``--running`` requests and serves them until each has its first token,
then traces, inside a ``bench.traced`` span, the arrival of one more
request and ``--steps`` engine steps: a prefill beside the running
decodes, then decode steps over all of them. Needs the chip.

The file keeps what `trace_reduce.load` and `program_spans.load` read
(`strip`): a few steps hold tens of thousands of device operations, and
their stats and the other lines and planes would multiply its size.
"""

import argparse
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent


# field numbers of the profile's protobuf (tsl/profiler/protobuf/
# xplane.proto), which `strip` rewrites on the wire with no generated
# module: XSpace.planes; XPlane.name, .lines, .event_metadata (a map:
# key 1, value 2); XLine.name, .events; XEvent.metadata_id, .stats;
# XEventMetadata.name, .stats
SPACE_PLANES = 1
PLANE_NAME, PLANE_LINES, PLANE_EVENT_METADATA = 2, 3, 4
MAP_KEY, MAP_VALUE = 1, 2
LINE_NAME, LINE_EVENTS = 2, 4
EVENT_METADATA_ID, EVENT_STATS = 1, 4
METADATA_NAME, METADATA_STATS = 2, 5


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _encode_varint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append(v & 0x7F | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _fields(buf: bytes):
    """(number, value, the field's own bytes) of each field of the
    message ``buf``: the value is an int for a varint, bytes else."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {start}")
        yield key >> 3, val, buf[start:i]


def _message(number: int, payload: bytes) -> bytes:
    return _encode_varint(number << 3 | 2) + _encode_varint(len(payload)) \
        + payload


def _first(buf: bytes, number: int, default=b""):
    return next((v for n, v, _ in _fields(buf) if n == number), default)


def _without(buf: bytes, number: int) -> bytes:
    return b"".join(raw for n, _, raw in _fields(buf) if n != number)


def _map_values(buf: bytes, number: int, fn) -> bytes:
    """``buf`` with ``fn`` applied to the payload of every field
    ``number`` whose result is not None; the others are left out."""
    out = []
    for n, val, raw in _fields(buf):
        if n != number:
            out.append(raw)
        elif (new := fn(val)) is not None:
            out.append(_message(n, new))
    return b"".join(out)


def _strip_plane(plane: bytes, keep_host) -> bytes | None:
    import trace_reduce as TR

    name = _first(plane, PLANE_NAME).decode()
    if name.startswith("/device:"):
        def line(buf):
            if _first(buf, LINE_NAME).decode() not in (TR.OPS_LINE,
                                                      TR.MODULES_LINE):
                return None
            return _map_values(buf, LINE_EVENTS,
                               lambda e: _without(e, EVENT_STATS))

        plane = _map_values(plane, PLANE_LINES, line)
        return _map_values(
            plane, PLANE_EVENT_METADATA,
            lambda kv: _map_values(kv, MAP_VALUE,
                                   lambda md: _without(md, METADATA_STATS)))
    if not name.startswith("/host:") or not any(
            n == PLANE_LINES for n, _, _ in _fields(plane)):
        return None
    names = {}
    for n, kv, _ in _fields(plane):
        if n == PLANE_EVENT_METADATA:
            md = _first(kv, MAP_VALUE)
            names[_first(kv, MAP_KEY, 0)] = _first(md, METADATA_NAME).decode()

    def event(buf):
        return buf if keep_host(names.get(_first(buf, EVENT_METADATA_ID, 0),
                                          "")) else None

    return _map_values(plane, PLANE_LINES,
                       lambda buf: _map_values(buf, LINE_EVENTS, event))


def strip(src: pathlib.Path, dst: pathlib.Path):
    """Write to ``dst`` the profile ``src`` less what the trace readers
    do not read: the device planes keep their `XLA Ops` and `XLA
    Modules` lines and the operations' names, with no stats (which hold
    the source paths of every operation), the host planes the
    benchmark's and the program's spans and the runtime's program
    issues; other planes go."""
    import program_spans as PS
    import trace_reduce as TR

    def keep_host(name):
        return name.startswith((TR.SPAN_PREFIX, PS.PREFIX)) \
            or name == TR.ISSUE_EVENT

    dst.write_bytes(_map_values(src.read_bytes(), SPACE_PLANES,
                                lambda p: _strip_plane(p, keep_host)))


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--running", type=int, default=2)
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import harness as H
    import trace_reduce as TR

    H.strip_program_env()
    sys.path.insert(0, str(H.REPO / "src"))
    import jax

    H.enable_compile_cache()
    if jax.devices()[0].platform == "cpu":
        print("[record] JAX finds no accelerator", file=sys.stderr)
        return 1
    h = H.Harness(H.load_cell(args.workload), args.seed, tracing=True)
    h.warm_up()
    h.prepare(60.0)
    for _ in range(args.running):
        h.submit()
    while any(r.decoded == 0 for r in h.records.values()):
        h.step(record=False)
    trace_dir = H.REPO / ".bench_trace" / "record_steps"
    with TR.capture(trace_dir), h.span("traced"):
        h.submit()
        for _ in range(args.steps):
            h.step(record=False)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    strip(pathlib.Path(TR.find_xplane(trace_dir)), out)
    print(f"[record] {args.steps} steps of {args.workload} to {out} "
          f"({out.stat().st_size} bytes)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
