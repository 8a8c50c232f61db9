"""The JAX op name of every device op in a profile, and device time by the
program's named scopes.

``jax.profiler.ProfileData`` gives a device op only its HLO instruction
text; the op name that JAX wrote into the op's metadata (e.g.
``jit(step)/vmap()/while/body/closed_call/transpose(jvp(layer2))/nbr_gather/scatter-add``)
is a ``tf_op`` stat of the event's metadata in the raw ``XSpace`` protobuf.
JAX ships no generated ``xplane_pb2``, so the messages are described here,
with only the fields read.

``read(path)`` gives, per chip, ``{(start_ns, instruction text): op name}``
for every ``XLA Ops`` event, keyed as ``bench.xplane.load`` makes a
``DeviceOp`` (its start before clipping to the window, its ``name``), and
``named_ops(trace, table)`` pairs a loaded trace's ops with their op names.
The sums below read the program's scopes (``layer1``, ``layer2``,
``nbr_gather``, ``loss``, ``adam``, ``fold``) and jitted programs (``step``,
``evaluate``, ``server_apply``) from those names.

    python3 -m bench.opnames <profile dir or .xplane.pb[.gz]> --rounds R [--trim OUT.xplane.pb.gz]

prints the device time per round by scope and program, the ops that took
most time with their op names, and the decode time; ``--trim`` writes the
profile cut to what the benchmark's readers and these sums read.
"""
from __future__ import annotations

import argparse
import functools
import gzip
import json
import re
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from bench import xplane

TF_OP = "tf_op"
# Program scopes in the order the breakdown lists them.
SCOPES = ("layer1", "layer2", "loss", "adam", "fold")
PROGRAMS = ("step", "evaluate", "server_apply")


@functools.lru_cache(maxsize=None)
def _messages():
    """Message classes for the fields of ``tsl/profiler/protobuf/xplane.proto``
    that are read (the parser skips the others): ``XSpace`` with its planes
    left as bytes, ``PlaneName`` to read a plane's name alone, ``XPlane``."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    I64, U64, STR, BYTES, MSG = (F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING,
                                 F.TYPE_BYTES, F.TYPE_MESSAGE)
    fp = descriptor_pb2.FileDescriptorProto(name="bench_xplane.proto", package="bench_xplane")

    def message(name, fields, parent=None):
        """``fields``: (name, number, type, message type or None, repeated)."""
        m = (parent.nested_type if parent else fp.message_type).add(name=name)
        for fname, number, kind, ref, repeated in fields:
            f = m.field.add(name=fname, number=number, type=kind,
                            label=F.LABEL_REPEATED if repeated else F.LABEL_OPTIONAL)
            if ref:
                f.type_name = ".bench_xplane." + ref
        return m

    message("XStat", [("metadata_id", 1, I64, None, False), ("str_value", 5, STR, None, False),
                      ("ref_value", 7, U64, None, False)])
    message("XEvent", [("metadata_id", 1, I64, None, False), ("offset_ps", 2, I64, None, False),
                       ("duration_ps", 3, I64, None, False)])
    message("XLine", [("name", 2, STR, None, False), ("timestamp_ns", 3, I64, None, False),
                      ("events", 4, MSG, "XEvent", True)])
    message("XEventMetadata", [("id", 1, I64, None, False), ("name", 2, STR, None, False),
                               ("stats", 5, MSG, "XStat", True)])
    message("XStatMetadata", [("id", 1, I64, None, False), ("name", 2, STR, None, False)])
    plane = message("XPlane", [
        ("name", 2, STR, None, False), ("lines", 3, MSG, "XLine", True),
        ("event_metadata", 4, MSG, "XPlane.EventMetadataEntry", True),
        ("stat_metadata", 5, MSG, "XPlane.StatMetadataEntry", True)])
    # The two map<int64, ...> fields in their wire form: repeated entries.
    message("EventMetadataEntry", [("key", 1, I64, None, False),
                                   ("value", 2, MSG, "XEventMetadata", False)], plane)
    message("StatMetadataEntry", [("key", 1, I64, None, False),
                                  ("value", 2, MSG, "XStatMetadata", False)], plane)
    message("XSpace", [("planes", 1, BYTES, None, True)])
    message("PlaneName", [("name", 2, STR, None, False)])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fp)
    return tuple(message_factory.GetMessageClass(pool.FindMessageTypeByName(f"bench_xplane.{n}"))
                 for n in ("XSpace", "PlaneName", "XPlane"))


def _read_bytes(path: str) -> bytes:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


def _device_planes(raw: bytes):
    """(chip, parsed XPlane) of each ``/device:TPU:n`` plane."""
    XSpace, PlaneName, XPlane = _messages()
    space = XSpace.FromString(raw)
    for plane_raw in space.planes:
        chip = xplane._chip_index(PlaneName.FromString(plane_raw).name)
        if chip is not None:
            yield chip, XPlane.FromString(plane_raw)


def _op_name(meta, stat_names: Dict[int, str], tf_op_id: Optional[int]) -> str:
    """The ``tf_op`` stat of an event's metadata, less its ``:<type>`` tail."""
    for st in meta.stats:
        if st.metadata_id == tf_op_id:
            value = st.str_value or stat_names.get(st.ref_value, "")
            return value.rpartition(":")[0] if ":" in value else value
    return ""


def read(path: str) -> Dict[int, Dict[Tuple[int, str], str]]:
    """chip -> {(start_ns, instruction text): op name} of every ``XLA Ops``
    event ("" where the event's metadata has no ``tf_op``). ``start_ns`` is
    the line's ``timestamp_ns`` plus ``offset_ps // 1000``, as
    ``ProfileData`` gives it."""
    table: Dict[int, Dict[Tuple[int, str], str]] = {}
    for chip, plane in _device_planes(_read_bytes(path)):
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op_id = next((k for k, v in stat_names.items() if v == TF_OP), None)
        metas = {e.key: e.value for e in plane.event_metadata}
        names = {k: (m.name, _op_name(m, stat_names, tf_op_id)) for k, m in metas.items()}
        out = table.setdefault(chip, {})
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for ev in line.events:
                text, op = names.get(ev.metadata_id, ("", ""))
                out[(line.timestamp_ns + ev.offset_ps // 1000, text)] = op
    return table


NamedOps = Dict[int, List[Tuple[xplane.DeviceOp, str]]]


def named_ops(trace: xplane.Trace, table: Dict[int, Dict[Tuple[int, str], str]]) -> NamedOps:
    """Each chip's ops of ``trace`` with their op names ("" if unknown). An op
    that began before the window was clipped to it: it is found by name as
    the latest event of that name that began before the window."""
    out: NamedOps = {}
    for chip, ops in trace.ops.items():
        names = table.get(chip, {})
        pairs = []
        for op in ops:
            name = names.get((op.start_ns, op.name))
            if name is None and op.start_ns == trace.window[0]:
                earlier = [k for k in names if k[1] == op.name and k[0] < op.start_ns]
                name = names[max(earlier)] if earlier else None
            pairs.append((op, name or ""))
        out[chip] = pairs
    return out


# -- reading an op name -------------------------------------------------------

_WRAPPER = re.compile(r"^([\w-]+)\((.*)\)$", re.S)


def _split(path: str) -> List[str]:
    """``path`` split at each ``/`` outside parentheses."""
    parts, depth, cur = [], 0, []
    for ch in path:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    return [p for p in parts if p]


def scopes(op_name: str) -> List[Tuple[str, bool]]:
    """The names along an op name's path, each with whether it lies under a
    ``transpose(`` (a backward op): transform wrappers (``jit(...)``,
    ``jvp(...)``, ``transpose(...)``, ``vmap(...)``) are opened, so
    ``jit(step)/transpose(jvp(layer2))/nbr_gather/scatter-add`` gives
    step, layer2, nbr_gather and scatter-add, the last three backward."""
    out: List[Tuple[str, bool]] = []
    backward = False
    for part in _split(op_name):
        m = _WRAPPER.match(part)
        while m:
            backward = backward or m.group(1) == "transpose"
            part = m.group(2)
            m = _WRAPPER.match(part)
        if "/" in part:
            out.extend((n, backward or b) for n, b in scopes(part))
        elif part:
            out.append((part, backward))
    return out


def program(op_name: str) -> str:
    """The jitted program an op belongs to: ``step`` for ``jit(step)/...``;
    "" for an op name that does not start with ``jit(``."""
    parts = _split(op_name)
    if not parts or not parts[0].startswith("jit("):
        return ""
    return parts[0][len("jit("):-1]


def in_scope(op_name: str, scope: str) -> bool:
    return any(n == scope for n, _ in scopes(op_name))


# -- device time --------------------------------------------------------------

def device_ms(named: NamedOps, rounds: int, keep: Callable[[str], bool]) -> Optional[float]:
    """Summed device time of the non-container ops whose op name ``keep``
    accepts, per chip and round, in ms; None where no op is accepted."""
    total, found = 0, False
    for pairs in named.values():
        for op, name in pairs:
            if keep(name) and not xplane.is_container(op.name):
                total += op.end_ns - op.start_ns
                found = True
    return total / 1e6 / len(named) / rounds if found else None


def layer_ms(named: NamedOps, rounds: int, layer: str) -> Optional[float]:
    """One layer of the training step, forward and backward."""
    return device_ms(named, rounds, lambda n: program(n) == "step" and in_scope(n, layer))


def nbr_gather_bwd_ms(named: NamedOps, rounds: int) -> Optional[float]:
    """The neighbour gathers' backward (their scatter-adds), all layers."""
    return device_ms(named, rounds, lambda n: any(s == "nbr_gather" and bwd for s, bwd in scopes(n)))


def evaluate_ms(named: NamedOps, rounds: int) -> Optional[float]:
    return device_ms(named, rounds, lambda n: program(n) == "evaluate")


def part_of(op_name: str) -> str:
    """Where an op's time goes in the breakdown: the first of the step's
    scopes on its path (``step:unscoped`` where none is), else its program
    (``other`` for JAX's own programs, ``no op name`` where there is none)."""
    if not op_name:
        return "no op name"
    prog = program(op_name)
    if prog == "step":
        names = {n for n, _ in scopes(op_name)}
        return next((f"step:{s}" for s in SCOPES if s in names), "step:unscoped")
    return prog if prog in PROGRAMS else "other"


def breakdown(named: NamedOps, rounds: int, top: int = 12) -> Dict[str, object]:
    """Device ms per round by part (``part_of``), and the ``top`` ops by
    time with their op names and parts, per chip."""
    chips = len(named)
    parts: Dict[str, float] = {}
    ops: Dict[str, List] = {}
    for pairs in named.values():
        for op, name in pairs:
            if xplane.is_container(op.name):
                continue
            ms = (op.end_ns - op.start_ns) / 1e6 / chips / rounds
            part = part_of(name)
            parts[part] = parts.get(part, 0.0) + ms
            key = xplane.short_name(op.name)
            row = ops.setdefault(key, [key, 0.0, name, part])
            row[1] += ms
    return {
        "ms_per_round": dict(sorted(parts.items(), key=lambda kv: -kv[1])),
        "top_ops": sorted(ops.values(), key=lambda r: -r[1])[:top],
    }


def metrics(named: NamedOps, rounds: int) -> Dict[str, Optional[float]]:
    """The per-layer quantities the scopes make readable, ms per round."""
    return {
        "layer1_device_ms": layer_ms(named, rounds, "layer1"),
        "layer2_device_ms": layer_ms(named, rounds, "layer2"),
        "nbr_gather_bwd_ms": nbr_gather_bwd_ms(named, rounds),
        "evaluate_device_ms": evaluate_ms(named, rounds),
    }


# -- trimming a profile for a test record ---------------------------------------

def trim(raw: bytes, window: Tuple[int, int], out: str) -> None:
    """Write to ``out``, gzipped, the profile ``raw`` cut to the chips'
    ``XLA Ops`` events that overlap ``window``, their metadata's instruction
    text and ``tf_op``, and the host's ``bench.marker``/``bench.window``
    events: what ``xplane.load`` and :func:`read` read."""
    XSpace, _, XPlane = _messages()
    lo, hi = window
    space = XSpace()
    for plane_raw in XSpace.FromString(raw).planes:
        plane = XPlane.FromString(plane_raw)
        chip = xplane._chip_index(plane.name)
        marks = {e.key for e in plane.event_metadata
                 if e.value.name in (xplane.MARKER, xplane.WINDOW)}
        keep = XPlane(name=plane.name)
        used = set()
        for line in plane.lines:
            if chip is None:
                events = [ev for ev in line.events if ev.metadata_id in marks]
            elif line.name == xplane.OPS_LINE:
                starts = [line.timestamp_ns + ev.offset_ps // 1000 for ev in line.events]
                events = [ev for ev, s in zip(line.events, starts)
                          if s < hi and s + ev.duration_ps // 1000 > lo]
            else:
                events = []
            if events:
                kept = keep.lines.add(name=line.name, timestamp_ns=line.timestamp_ns)
                kept.events.extend(events)
                used.update(ev.metadata_id for ev in events)
        if not keep.lines:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        tf_op_id = next((k for k, v in stat_names.items() if v == TF_OP), None)
        if tf_op_id is not None:
            entry = keep.stat_metadata.add(key=tf_op_id)
            entry.value.id, entry.value.name = tf_op_id, TF_OP
        for e in plane.event_metadata:
            if e.key in used:
                meta = keep.event_metadata.add(key=e.key).value
                meta.id, meta.name = e.value.id, e.value.name
                op = _op_name(e.value, stat_names, tf_op_id)
                if op:
                    meta.stats.add(metadata_id=tf_op_id, str_value=op + ":")
        space.planes.append(keep.SerializeToString())
    with gzip.open(out, "wb", compresslevel=9) as f:
        f.write(space.SerializeToString())


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("profile", help="a profile directory or an .xplane.pb[.gz] file")
    ap.add_argument("--rounds", type=int, required=True, help="rounds in the traced window")
    ap.add_argument("--trim", default=None, help="also write the trimmed profile here (.gz)")
    args = ap.parse_args(argv)
    path = args.profile
    if not path.endswith((".pb", ".gz")):
        path = xplane.find_xplane(path)
    trace = xplane.load(path)
    t0 = time.perf_counter()
    table = read(path)
    decode_s = time.perf_counter() - t0
    named = named_ops(trace, table)
    busy = sum(xplane.busy_ns(o) for o in trace.ops.values()) / len(trace.ops) / 1e6 / args.rounds
    print(json.dumps({
        "decode_s": decode_s,
        "busy_ms_per_round": busy,
        "window_s": trace.window_s,
        **metrics(named, args.rounds),
        **breakdown(named, args.rounds),
    }, indent=1))
    if args.trim:
        trim(_read_bytes(path), trace.window, args.trim)
    return 0


if __name__ == "__main__":
    sys.exit(main())
