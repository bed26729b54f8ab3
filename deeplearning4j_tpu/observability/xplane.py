"""Stdlib-only XPlane (``.xplane.pb``) parser -> per-op attribution summary.

``jax.profiler.start_trace`` writes its artifact as an XSpace protobuf
(``plugins/profile/<ts>/<host>.xplane.pb``). This module walks the protobuf
wire format directly: varints and length-delimited submessages, nothing
else, no generated bindings, no third-party deps. It was written when the
installed JAX shipped no reader; JAX 0.9.0's ``jax.profiler.ProfileData``
now reads the same file, and replacing this hand parser with it is a later
``simplicity`` PR (noted, not done, in PR 21).

Only the fields attribution needs are decoded (verified against traces from
this jax build; the numbers are the upstream tsl/profiler field ids):

    XSpace      { repeated XPlane planes = 1; }
    XPlane      { string name = 2; repeated XLine lines = 3;
                  map<int64, XEventMetadata> event_metadata = 4; }
    XEventMetadata { int64 id = 1; string name = 2; }
    XLine       { string name = 2; repeated XEvent events = 4;
                  string display_name = 11; }
    XEvent      { int64 metadata_id = 1; int64 duration_ps = 3; }

Unknown fields are skipped (forward-compatible); *structural* damage — a
truncated varint, a length running past the buffer — raises
:class:`XPlaneParseError`, which :func:`summarize` converts into an
``{"error": ...}`` record so a half-written trace can never crash a fit
loop or a bench row.

The bucketing rules are lifted from scripts/profile_flagship.py (which now
delegates here): classify by the defining HLO opcode, never by substring
search over the whole HLO string — operand text routinely contains
``transpose``/``reshape``, which round 4's parser misread as ~38%
"datamovement" on every model.
"""
from __future__ import annotations

import glob
import os
import re
import struct
from typing import Dict, Iterator, List, Optional, Tuple


class XPlaneParseError(ValueError):
    """Structurally invalid protobuf wire data (truncated / malformed)."""


# protobuf wire types
_VARINT, _I64, _LEN, _I32 = 0, 1, 2, 5


def _read_varint(buf: bytes, i: int) -> Tuple[int, int]:
    result = shift = 0
    n = len(buf)
    while True:
        if i >= n:
            raise XPlaneParseError("truncated varint")
        b = buf[i]
        result |= (b & 0x7F) << shift
        i += 1
        if not b & 0x80:
            return result, i
        shift += 7
        if shift > 63:
            raise XPlaneParseError("varint longer than 64 bits")


def _walk(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """Yield ``(field_number, wire_type, value)`` over one message's bytes.

    Values are ints for varints, raw bytes for everything else; nested
    messages are the caller's job (feed the bytes back through _walk).
    """
    i, n = 0, len(buf)
    while i < n:
        tag, i = _read_varint(buf, i)
        field, wt = tag >> 3, tag & 7
        if field == 0:
            raise XPlaneParseError("field number 0")
        if wt == _VARINT:
            v, i = _read_varint(buf, i)
        elif wt == _I64:
            if i + 8 > n:
                raise XPlaneParseError("truncated 64-bit field")
            v, i = buf[i:i + 8], i + 8
        elif wt == _LEN:
            ln, i = _read_varint(buf, i)
            if i + ln > n:
                raise XPlaneParseError(
                    "length-delimited field overruns buffer")
            v, i = buf[i:i + ln], i + ln
        elif wt == _I32:
            if i + 4 > n:
                raise XPlaneParseError("truncated 32-bit field")
            v, i = buf[i:i + 4], i + 4
        else:
            raise XPlaneParseError(f"unsupported wire type {wt}")
        yield field, wt, v


def _utf8(v: object) -> str:
    return v.decode("utf-8", "replace") if isinstance(v, bytes) else str(v)


def _parse_event_metadata(buf: bytes) -> Tuple[Optional[int], str]:
    """One event_metadata map entry: key=1 (id), value=2 (XEventMetadata)."""
    eid: Optional[int] = None
    name = ""
    for field, wt, v in _walk(buf):
        if field == 1 and wt == _VARINT:
            eid = v
        elif field == 2 and wt == _LEN:
            for f2, w2, v2 in _walk(v):
                if f2 == 1 and w2 == _VARINT:
                    eid = v2  # XEventMetadata.id is authoritative
                elif f2 == 2 and w2 == _LEN:
                    name = _utf8(v2)
    return eid, name


def _parse_event(buf: bytes) -> Tuple[int, int]:
    """(metadata_id, duration_ps) of one XEvent."""
    mid = dur_ps = 0
    for field, wt, v in _walk(buf):
        if field == 1 and wt == _VARINT:
            mid = v
        elif field == 3 and wt == _VARINT:
            dur_ps = v
    return mid, dur_ps


def _parse_line(buf: bytes, names: Dict[int, str]) -> dict:
    name = display = ""
    events: List[Tuple[str, int]] = []
    for field, wt, v in _walk(buf):
        if field == 2 and wt == _LEN:
            name = _utf8(v)
        elif field == 11 and wt == _LEN:
            display = _utf8(v)
        elif field == 4 and wt == _LEN:
            mid, dur_ps = _parse_event(v)
            events.append((names.get(mid, f"<metadata {mid}>"), dur_ps))
    return {"name": name, "display_name": display, "events": events}


def _parse_plane(buf: bytes) -> dict:
    name = ""
    line_bufs: List[bytes] = []
    event_names: Dict[int, str] = {}
    for field, wt, v in _walk(buf):
        if field == 2 and wt == _LEN:
            name = _utf8(v)
        elif field == 3 and wt == _LEN:
            line_bufs.append(v)  # defer: event_metadata may come after lines
        elif field == 4 and wt == _LEN:
            eid, enm = _parse_event_metadata(v)
            if eid is not None:
                event_names[eid] = enm
    return {"name": name,
            "lines": [_parse_line(b, event_names) for b in line_bufs]}


def parse_planes(data: bytes) -> List[dict]:
    """Decode an XSpace buffer into plane dicts (name, lines->events).

    Raises :class:`XPlaneParseError` on structural damage; use
    :func:`summarize` for the never-raises entry point.
    """
    return [_parse_plane(v) for field, wt, v in _walk(data)
            if field == 1 and wt == _LEN]


# --------------------------------------------------------------- attribution
def opcode(nm: str) -> str:
    """The defining HLO opcode of ``%name = type opcode(args)``. Bucketing
    must use THIS, not substring search over the whole HLO string (see the
    module docstring for the round-4 misattribution that rule fixed)."""
    m = re.search(r"=\s*(?:\([^=]*?\)\s*|\S+\s+)?([a-z][a-z0-9\-_.]*)\(", nm)
    return m.group(1) if m else nm.split(".")[0].lstrip("%")


def bucket(nm: str) -> str:
    """Category of one op event: matmul / conv / collective / datamovement /
    reduce-vs-compute fusion, else the opcode itself (long tail)."""
    op = opcode(nm)
    # fusions: classify by the name prefix XLA gives them (it encodes the
    # fused ops: transpose_..., convert_reduce_..., maximum_add_...)
    label = nm.lstrip("%").split(" ")[0].split(".")[0].lower()
    if "conv" in op or label.startswith("convolution"):
        return "conv"
    if op in ("dot", "custom-call") or "matmul" in label:
        return "matmul/custom"
    if any(t in op for t in ("all-reduce", "all-gather", "collective",
                             "reduce-scatter", "permute")):
        return "collective"
    if op in ("copy", "transpose", "reshape", "bitcast",
              "dynamic-slice", "dynamic-update-slice") \
            or label.startswith(("copy", "transpose", "bitcast")):
        return "datamovement"
    if op == "fusion":
        # TPU traces do not expose fusion bodies; the big kOutput fusions
        # CONTAIN the convolutions/matmuls plus their elementwise epilogues,
        # so this bucket is "compute", not "elementwise overhead"
        if label.startswith(("convert_reduce", "multiply_reduce", "reduce")):
            return "fusion:reduce"
        return "fusion:compute"
    return op


#: control-flow wrappers (the K-step scan loop) span their whole body and
#: would double-count every inner op
_CONTROL_FLOW = ("while", "conditional", "call")

#: the profiler's own bookkeeping shows up as giant host events (e.g.
#: ``$profiler.py:91 start_trace`` spans the whole capture) — pure noise
_BOOKKEEPING = ("start_trace", "stop_trace")

_PJIT_RE = re.compile(r"PjitFunction\((.*)\)")


def _is_host_python_line(line: dict) -> bool:
    nm = (line.get("display_name") or line.get("name") or "").strip().lower()
    return nm == "python"


def find_trace(logdir: str) -> Optional[str]:
    """Newest ``*.xplane.pb`` under a trace directory (or the file itself)."""
    if os.path.isfile(logdir):
        return logdir
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def summarize(logdir: str, top: int = 25) -> dict:
    """Attribution summary of the newest trace under ``logdir`` — top
    self-time ops, category split (sums to ~100%% of counted time), per-fn
    share from the host pjit spans. Never raises: every failure mode comes
    back as ``{"error": ...}`` so callers inside fit loops / bench rows can
    attach the record verbatim.
    """
    try:
        path = find_trace(logdir)
        if path is None:
            return {"error": f"no xplane.pb under {logdir}"}
        with open(path, "rb") as f:
            data = f.read()
        planes = parse_planes(data)
    except (OSError, XPlaneParseError) as e:
        return {"error": f"unreadable xplane trace: {e!r}", "trace": logdir}

    out: dict = {"trace": path, "planes": [p["name"] for p in planes]}
    # device planes only ("/device:TPU:0" etc.); fall back to host planes so
    # the pipeline still summarizes something on CPU-only runs, also where
    # the process has described a TPU it does not run on and its planes are
    # there, empty
    device = [p for p in planes
              if any(t in p["name"].lower() for t in ("tpu", "gpu", "device"))
              and any(line["events"] for line in p["lines"])]
    summarized = device or planes
    out["summarized_planes"] = [p["name"] for p in summarized]

    op_time: Dict[str, int] = {}
    cat_time: Dict[str, int] = {}
    total_ps = 0
    for plane in summarized:
        lines = plane["lines"]
        # device planes carry container lines ("XLA Modules", "Steps",
        # "Framework Name Scope") spanning the same wall time as the per-op
        # line — summing every line double-counts. Keep exactly the XLA
        # per-op line when present.
        op_lines = [l for l in lines
                    if (l["name"] or "").strip().lower() in ("xla ops", "ops")]
        for line in (op_lines or lines):
            host_line = _is_host_python_line(line)
            for nm, dur_ps in line["events"]:
                if any(b in nm for b in _BOOKKEEPING) or nm.startswith("$"):
                    continue
                if not host_line and opcode(nm) in _CONTROL_FLOW:
                    continue
                cat = "host" if host_line else bucket(nm)
                op_time[nm] = op_time.get(nm, 0) + dur_ps
                cat_time[cat] = cat_time.get(cat, 0) + dur_ps
                total_ps += dur_ps

    out["total_device_ns"] = total_ps // 1000
    ranked = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    out["top_ops"] = [
        {"op": k, "ns": v // 1000,
         "pct": round(100.0 * v / total_ps, 2) if total_ps else 0.0}
        for k, v in ranked]

    ranked_cats = sorted(cat_time.items(), key=lambda kv: -kv[1])
    head, tail = ranked_cats[:11], ranked_cats[11:]
    if tail:  # roll the long tail up so the split still sums to ~100%
        head.append((f"other({len(tail)} buckets)", sum(v for _, v in tail)))
    out["categories_pct"] = {
        k: round(100.0 * v / total_ps, 2) if total_ps else 0.0
        for k, v in head}

    # per-fn share: the host "python" line's PjitFunction(...) spans say
    # which jitted program owned the window, whichever planes held the ops
    fn_time: Dict[str, int] = {}
    for plane in planes:
        for line in plane["lines"]:
            if not _is_host_python_line(line):
                continue
            for nm, dur_ps in line["events"]:
                m = _PJIT_RE.search(nm)
                if m:
                    fn_time[m.group(1)] = fn_time.get(m.group(1), 0) + dur_ps
    fn_total = sum(fn_time.values())
    if fn_total:
        out["fn_pct"] = {
            k: round(100.0 * v / fn_total, 2)
            for k, v in sorted(fn_time.items(), key=lambda kv: -kv[1])[:top]}
    return out


# ------------------------------------------------------------------ encoding
# Minimal writers, used by tests/golden/make_xplane_golden.py to build the
# committed fixture with the same field layout the parser reads. Living here
# keeps encoder and parser in one reviewable file.
def encode_varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def encode_field(field: int, wt: int, payload) -> bytes:
    tag = encode_varint((field << 3) | wt)
    if wt == _VARINT:
        return tag + encode_varint(payload)
    if wt == _LEN:
        return tag + encode_varint(len(payload)) + payload
    if wt == _I64:
        return tag + struct.pack("<q", payload)
    if wt == _I32:
        return tag + struct.pack("<i", payload)
    raise ValueError(f"wire type {wt}")


def encode_message(*fields: bytes) -> bytes:
    return b"".join(fields)
