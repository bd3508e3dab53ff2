"""A minimal reader of the profiler's ``.xplane.pb`` (protobuf wire format,
``tsl/profiler/protobuf/xplane.proto``), in plain Python.

``jax.profiler.ProfileData`` gives an event's HLO text and times but not
the statistics kept on its *metadata* — among them ``tf_op``, the JAX name
stack (``jit(step)/sparse_tables/apply/scatter-add``) that names a layer
whatever kernel implements it.  This reader decodes only what the
reduction needs: per plane and line, each event's display name, its
metadata's ``tf_op`` and ``hlo_category``, its start and its duration.
"""

from __future__ import annotations

from typing import Callable, Dict, Iterator, List, Optional, Tuple


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message; a length-
    delimited value is a memoryview slice."""
    view = memoryview(buf)
    pos, end = 0, len(buf)
    while pos < end:
        tag, pos = _varint(buf, pos)
        num, wt = tag >> 3, tag & 7
        if wt == 0:
            val, pos = _varint(buf, pos)
        elif wt == 2:
            n, pos = _varint(buf, pos)
            val = view[pos:pos + n]
            pos += n
        elif wt == 1:
            val = bytes(view[pos:pos + 8])
            pos += 8
        elif wt == 5:
            val = bytes(view[pos:pos + 4])
            pos += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield num, wt, val


def _stat(buf, stat_names: Dict[int, str]) -> Tuple[str, Optional[str]]:
    """``(stat name, string value or None)`` of one XStat."""
    name, value = "", None
    for num, _, val in fields(bytes(buf)):
        if num == 1:
            name = stat_names.get(val, "")
        elif num == 5:
            value = bytes(val).decode("utf-8", "replace")
        elif num == 7:
            value = stat_names.get(val, "")
    return name, value


def read_planes(path: str, want_plane: Callable[[str], bool],
                want_line: Callable[[str, str], bool],
                keep_stats: Tuple[str, ...] = ("tf_op", "hlo_category")
                ) -> Dict[str, Dict[str, List[Tuple]]]:
    """``{plane: {line: [(display_name, stats, start_ns, dur_ns)]}}`` for the
    planes and lines asked for; ``stats`` holds the metadata's ``keep_stats``.
    A host plane's lines are threads and may share a name (every Python
    thread is ``python3``): a name seen before gets ``#<n>`` appended, so
    that two threads' events stay apart."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, List[Tuple]]] = {}
    for num, _, plane_buf in fields(space):
        if num != 1:
            continue
        plane_buf = bytes(plane_buf)
        name, lines, ev_meta, stat_meta = "", [], [], {}
        for n, _, v in fields(plane_buf):
            if n == 2:
                name = bytes(v).decode()
            elif n == 3:
                lines.append(v)
            elif n == 4:
                ev_meta.append(v)
            elif n == 5:
                key, label = 0, ""
                for a, _, b in fields(bytes(v)):
                    if a == 1:
                        key = b
                    elif a == 2:
                        for c, _, d in fields(bytes(b)):
                            if c == 2:
                                label = bytes(d).decode()
                stat_meta[key] = label
        if not want_plane(name):
            continue
        meta: Dict[int, Tuple[str, Dict[str, str]]] = {}
        for v in ev_meta:
            key, ev_name, display, stats = 0, "", "", {}
            for a, _, b in fields(bytes(v)):
                if a == 1:
                    key = b
                elif a == 2:
                    for c, _, d in fields(bytes(b)):
                        if c == 2:
                            ev_name = bytes(d).decode("utf-8", "replace")
                        elif c == 4:
                            display = bytes(d).decode("utf-8", "replace")
                        elif c == 5:
                            s_name, s_val = _stat(d, stat_meta)
                            if s_name in keep_stats and s_val is not None:
                                stats[s_name] = s_val
            meta[key] = (display or ev_name, stats)
        plane_out: Dict[str, List[Tuple]] = {}
        for line_buf in lines:
            line_buf = bytes(line_buf)
            line_name, t0_ns, events = "", 0, []
            for n, _, v in fields(line_buf):
                if n == 2:
                    line_name = bytes(v).decode()
                elif n == 3:
                    t0_ns = v
                elif n == 4:
                    events.append(v)
            if not want_line(name, line_name):
                continue
            if line_name in plane_out:
                line_name = f"{line_name}#{len(plane_out)}"
            rows = plane_out.setdefault(line_name, [])
            for ev in events:
                mid = off_ps = dur_ps = 0
                for n, _, v in fields(bytes(ev)):
                    if n == 1:
                        mid = v
                    elif n == 2:
                        off_ps = v
                    elif n == 3:
                        dur_ps = v
                label, stats = meta.get(mid, ("", {}))
                rows.append((label, stats, t0_ns + off_ps / 1e3, dur_ps / 1e3))
        out[name] = plane_out
    return out
