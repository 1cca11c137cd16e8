"""Scoped reading of one profiler capture: device ops by their name stack, and the engine's own spans.

`jax.profiler.ProfileData`, which `bench/xplane.py` reads, gives an event
its name and times but not the stats of its metadata, and the op's name
stack (`tf_op`, e.g. `jit(decode)/decode_loop/while/body/.../attn_core/
bhqk,bhkd->bhqd/dot_general`) is one of those. This module walks the
`.xplane.pb` wire format itself, with the standard library only, and keeps:

* device ops: the "XLA Ops" line of each TPU plane, one event per op run,
  as (HLO text, start_ns, end_ns, tf_op) on `xplane.load`'s clock;
* engine spans: host events named `serve.*`, which the program opens
  around its own phases (`serve.step`, `serve.admit`, ...), for reading
  by hand and for tests; no metric reads them yet.

`SCOPES` is the benchmark's own copy of the program's scope contract (the
program states it in `repro.instrument.SCOPES`), so a change to the program
cannot move what a name counts. An op belongs to the innermost contract
name on its `tf_op` path, and to none ("") when no component is one.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench import xplane
from bench.context import ABSENT, DECODE_PROGRAM

SCOPES = ("attn_core", "kv_write", "layer_scan", "decode_loop", "gemm",
          "norm", "rope", "mlp_act", "embed", "sample")
SPAN_PREFIX = "serve."

Op = Tuple[str, int, int, str]        # (HLO text, start_ns, end_ns, tf_op)

# XSpace / XPlane / XLine / XEvent / XEventMetadata / XStat / XStatMetadata
# field numbers (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 3, 4, 5
_LINE_NAME, _LINE_TIMESTAMP_NS, _LINE_EVENTS = 2, 3, 4
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DURATION_PS = 1, 2, 3
_EVENT_MD_NAME, _EVENT_MD_STATS = 2, 5
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_STAT_MD_NAME = 2


@dataclasses.dataclass
class Scoped:
    ops: Dict[str, List[Op]]                  # device plane -> op events
    spans: List[xplane.Event]                 # the engine's serve.* spans


def _varint(buf, i: int) -> Tuple[int, int]:
    out, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for varint and fixed
    fields, a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, v


def _map_entries(entries) -> Iterator[Tuple[int, object]]:
    for entry in entries:
        key = value = None
        for f, v in _fields(entry):
            if f == 1:
                key = v
            elif f == 2:
                value = v
        yield key, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _event_metadata(entries, stat_names: Dict[int, str]
                    ) -> Dict[int, Tuple[str, str]]:
    """metadata id -> (name, tf_op or "")."""
    tf_ids = {k for k, name in stat_names.items() if name == "tf_op"}
    out = {}
    for key, value in _map_entries(entries):
        name, tf = "", ""
        for f, v in _fields(value):
            if f == _EVENT_MD_NAME:
                name = _text(v)
            elif f == _EVENT_MD_STATS:
                stat = dict(_fields(v))
                if stat.get(_STAT_MD_ID) in tf_ids:
                    tf = _text(stat[_STAT_STR]) if _STAT_STR in stat \
                        else stat_names.get(stat.get(_STAT_REF), "")
        # the stat reads "<name stack>:<op type>"
        out[key] = (name, tf.rsplit(":", 1)[0] if ":" in tf else tf)
    return out


def _events(buf, timestamp_ns: int) -> List[Tuple[int, int, int]]:
    """(metadata id, start_ns, end_ns) of a line's events, timed as
    `ProfileData` times them. The hot loop of a reading: a 20 s capture
    holds about a million op runs, so the walk is inlined here."""
    out = []
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire != 2:
            i = _skip(buf, i, wire)
            continue
        size, i = _varint(buf, i)
        if key >> 3 != _LINE_EVENTS:
            i += size
            continue
        end, mid, off, dur = i + size, 0, 0, 0
        while i < end:
            k, i = _varint(buf, i)
            if k & 7 != 0:
                i = _skip(buf, i, k & 7)
                continue
            v, i = _varint(buf, i)
            f = k >> 3
            if f == _EVENT_MD_ID:
                mid = v
            elif f == _EVENT_OFFSET_PS:
                off = _signed(v)
            elif f == _EVENT_DURATION_PS:
                dur = v
        start = int(timestamp_ns + off / 1e3)
        out.append((mid, start, start + int(dur / 1e3)))
    return out


def _skip(buf, i: int, wire: int) -> int:
    """Index past one field value of wire type `wire` starting at i."""
    if wire == 0:
        return _varint(buf, i)[1]
    if wire == 2:
        size, i = _varint(buf, i)
        return i + size
    return i + (8 if wire == 1 else 4)


def load(path: str) -> Scoped:
    with open(path, "rb") as f:
        data = f.read()
    ops: Dict[str, List[Op]] = {}
    spans: List[xplane.Event] = []
    for f, plane in _fields(data):
        if f != _SPACE_PLANES:
            continue
        name, lines, event_md, stat_md = "", [], [], []
        for g, v in _fields(plane):
            if g == _PLANE_NAME:
                name = _text(v)
            elif g == _PLANE_LINES:
                lines.append(v)
            elif g == _PLANE_EVENT_MD:
                event_md.append(v)
            elif g == _PLANE_STAT_MD:
                stat_md.append(v)
        device = name.startswith("/device:TPU:")
        if not (device or name.startswith("/host:")):
            continue
        stat_names = {}
        for key, value in _map_entries(stat_md):
            for g, v in _fields(value):
                if g == _STAT_MD_NAME:
                    stat_names[key] = _text(v)
        meta = _event_metadata(event_md, stat_names)
        for line in lines:
            lname, ts = "", 0
            for g, v in _fields(line):
                if g == _LINE_NAME:
                    lname = _text(v)
                elif g == _LINE_TIMESTAMP_NS:
                    ts = _signed(v)
            if device and lname == "XLA Ops":
                ops[name] = [(meta[m][0], s, e, meta[m][1])
                             for m, s, e in _events(line, ts)]
            elif not device:
                spans.extend((meta[m][0], s, e)
                             for m, s, e in _events(line, ts)
                             if meta.get(m, ("",))[0].startswith(SPAN_PREFIX))
    spans.sort(key=lambda x: x[1])
    return Scoped(ops, spans)


def _memo(ctx) -> Dict:
    """What the readers of one run share, kept on that run's reader
    context: the capture is walked once for all of them."""
    return vars(ctx).setdefault("_opscope", {})


def _capture_path() -> Optional[str]:
    """The capture of this run, found as `bench/cell.py` finds it."""
    from bench import cell
    try:
        return xplane.find_xplane(str(cell.TRACE_DIR))
    except FileNotFoundError:
        return None


def for_ctx(ctx) -> Optional[Scoped]:
    """The scoped reading of the run's capture (None without one)."""
    if ctx.capture is None:
        return None
    memo = _memo(ctx)
    if "scoped" not in memo:
        path = _capture_path()
        memo["scoped"] = None if path is None else load(path)
    return memo["scoped"]


def scope_of(tf_op: str) -> str:
    """The innermost contract scope on an op's name stack, else "". A
    fusion's stat may list the names of the ops fused into it after its
    own, joined by ";": the op's own path, the first, decides."""
    for part in reversed(tf_op.split(";", 1)[0].split("/")):
        if part in SCOPES:
            return part
    return ""


def split_ns(ops: Sequence[Op]) -> Dict[str, int]:
    """Device time of `ops` by innermost contract scope ("" for none),
    leaving out the ops that only contain others (a loop's event spans the
    ops it runs), as `xplane.breakdown` counts them."""
    out = dict.fromkeys(SCOPES + ("",), 0)
    for name, s, e, tf in ops:
        if not xplane._CONTAINER.match(name):
            out[scope_of(tf)] += e - s
    return out


def decode_split_ms(ctx):
    """Milliseconds per decode micro-step of each scope, over the ops of the
    decode programs that ran within traced engine steps (`Ctx.programs`):
    device time / (runs x `decode_chunk`). None without a device capture
    or decode runs; ABSENT when no op of those runs names a contract scope,
    as on a program older than the scopes."""
    if not ctx.plane:
        return None
    scoped = for_ctx(ctx)
    runs = ctx.programs(DECODE_PROGRAM)
    if scoped is None or not runs:
        return None
    memo = _memo(ctx)
    if "split" not in memo:
        per = len(runs) * ctx.engine["decode_chunk"]
        ops = xplane.inside(scoped.ops.get(ctx.plane, []), runs)
        if not ops:
            memo["split"] = None
        elif not any(scope_of(op[3]) for op in ops):
            memo["split"] = ABSENT
        else:
            memo["split"] = {k: v / 1e6 / per
                             for k, v in split_ns(ops).items()}
    return memo["split"]


def idle_ns(events: Sequence[xplane.Event], spans: Sequence[xplane.Event]
            ) -> int:
    """Time inside `spans` in which none of `events` runs (spans disjoint)."""
    if not spans:
        return 0
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    busy = xplane.merge(((s, e) for _, s, e, *_ in events), lo, hi)
    starts = [s for s, _ in busy]
    idle = 0
    for _, s, e in spans:
        covered = 0
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(busy) and busy[i][0] < e:
            covered += max(0, min(e, busy[i][1]) - max(s, busy[i][0]))
            i += 1
        idle += (e - s) - covered
    return idle
