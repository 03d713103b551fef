"""A traced run's device time by part of the model (PR 36).

The program opens ``jax.named_scope(<part>)`` where each part's work
happens and turns a compiled program into a table ``{operation: (part,
phase)}`` (``paddle_tpu/obs/parts.py``); a device trace names the same
operations.  :func:`by_part` sums device 0's ``XLA Ops`` events inside
the programs of one name by that table.

Where the table comes from.  Serving: the engine records one
``program.parts`` span per program it compiled, with the table of THAT
program, and the run's saved spans carry it (:func:`span_tables`).
Training: the driver keeps no span, but the ``.xplane.pb`` itself holds
every operation's ``op_name``, as the ``tf_op`` stat of the event's
METADATA (``jax.profiler.ProfileData`` shows an event's own stats only,
which hold no name: PERF.md section 3), so :func:`train_row`
decodes the metadata from the file the run wrote.  Either way a run on a
tree without the scopes or the span gives no table, and a reader
returns nothing.
"""

import glob
import json
import os

from benchmarks.lib import xplane

try:
    from paddle_tpu.obs.parts import UNSCOPED, operation_key, part_of
except ImportError:             # a tree from before the scopes
    UNSCOPED = operation_key = part_of = None

SPAN = "program.parts"
# where benchmarks/run.py keeps every cell's trace (``out/trace/<cell>``)
TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "out", "trace")


def leaves(events) -> list:
    """The events that hold no other event of their line: a ``while``,
    ``conditional`` or ``call`` is on the line over its whole run AND its
    body's operations are on it one by one, so a container's time is its
    leaves' and counts once.  Events of no duration hold nothing and
    take nothing: dropped."""
    events = sorted((e for e in events if e[2] > 0),
                    key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(events, events[1:] + [None])
            if nxt is None or nxt[1] >= e[1] + e[2]]


def _choose(keys: frozenset, names: dict, tables: list) -> dict:
    """The table among ``tables`` that holds every one of ``keys``."""
    fits = [t for t in tables if all(k in t for k in keys)]
    if not fits:
        near = min(tables, key=lambda t: sum(k not in t for k in keys))
        lacking = sorted(names[k] for k in keys if k not in near)
        raise ValueError(
            f"{len(lacking)} traced operations are in no table of the "
            f"program (the trace is another tree's, or a compile was not "
            f"recorded): {[n[:120] for n in lacking[:5]]}")
    for other in fits[1:]:
        differ = [names[k] for k in keys
                  if tuple(other[k]) != tuple(fits[0][k])]
        if differ:
            raise ValueError(
                f"two compiles of the program both hold every traced "
                f"operation and disagree on {len(differ)} of them: "
                f"{[n[:120] for n in differ[:5]]}")
    return fits[0]


def program_events(trace: dict, prefix: str, t0: float = float("-inf"),
                   t1: float = float("inf")) -> list:
    """``[(start, end, leaf events), ...]`` for the programs named
    ``prefix`` on device 0's ``XLA Modules`` line that ran wholly inside
    ``[t0, t1]``: the events of its ``XLA Ops`` line inside each, the
    containers dropped (:func:`leaves`)."""
    dev = trace["devices"][0]
    spans = sorted((s, s + d) for name, s, d in dev["modules"]
                   if name.startswith(prefix) and t0 <= s and s + d <= t1)
    inside = [[] for _ in spans]
    i = 0
    for e in sorted(dev["ops"], key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] <= e[1]:
            i += 1
        if i < len(spans) and spans[i][0] <= e[1] \
                and e[1] + e[2] <= spans[i][1]:
            inside[i].append(e)
    return [(s, e, leaves(events))
            for (s, e), events in zip(spans, inside)]


def by_part(trace: dict, prefix: str, tables, t0: float = float("-inf"),
            t1: float = float("inf")) -> dict:
    """Device 0's time inside the programs named ``prefix`` that ran
    wholly inside ``[t0, t1]``, by part: ``{"programs": n, "mean_ms":
    the programs' mean device time, "ops_ms": their operations' (the
    rest is seams between operations), "parts": {(part, phase): ms per
    program}, "unscoped_ms": ms per program under no scope, "unscoped":
    [[name, ms per program], ...] its largest three}``.

    ``tables``: one ``{operation_key: (part, phase)}`` per compile of
    the program (or one table).  A function compiled at several shapes
    has a table per compile under one name and ``%fusion.12`` means
    something else in each, so each program event is paired with the
    table that holds EVERY operation inside it; none, or two that
    disagree on a part, raises, and so a traced name the tables lack
    raises."""
    if isinstance(tables, dict):
        tables = [tables]
    programs = program_events(trace, prefix, t0, t1)
    out = {"programs": len(programs), "mean_ms": None, "ops_ms": None,
           "parts": {}, "unscoped_ms": None, "unscoped": []}
    if not programs:
        return out
    parts, unscoped, chosen, keyed = {}, {}, {}, {}
    for _, _, events in programs:
        for name, _, _ in events:
            if name not in keyed:
                keyed[name] = operation_key(name)
        names = {keyed[name]: name for name, _, _ in events}
        keys = frozenset(names)
        if keys not in chosen:
            chosen[keys] = _choose(keys, names, tables)
        table = chosen[keys]
        for name, _, dur in events:
            part = tuple(table[keyed[name]])
            parts[part] = parts.get(part, 0.0) + dur
            if part[0] == UNSCOPED:
                unscoped[name] = unscoped.get(name, 0.0) + dur
    n = len(programs)
    out.update(
        mean_ms=sum(e - s for s, e, _ in programs) / n / 1e6,
        ops_ms=sum(parts.values()) / n / 1e6,
        parts={k: ns / n / 1e6 for k, ns in
               sorted(parts.items(), key=lambda kv: -kv[1])},
        unscoped_ms=sum(unscoped.values()) / n / 1e6,
        unscoped=[[name[:160], ns / n / 1e6] for name, ns in sorted(
            unscoped.items(), key=lambda kv: -kv[1])[:3]])
    return out


def part_ms(row: dict, *names, phase=None) -> float:
    """Milliseconds per program of the parts ``names`` (every part where
    none is given), in ``phase`` (every phase where None)."""
    return sum(ms for (part, ph), ms in row["parts"].items()
               if (not names or part in names)
               and (phase is None or ph == phase))


def coverage_percent(rows) -> float:
    """100 x (1 - time under no scope / time in operations) over the
    programs of ``rows`` together, each weighted by its runs."""
    ops = sum(r["ops_ms"] * r["programs"] for r in rows)
    bare = sum(r["unscoped_ms"] * r["programs"] for r in rows)
    return 100.0 * (1.0 - bare / ops)


def log_row(run: dict, metric: str, prefix: str, row: dict) -> None:
    """The whole table of one program on an earlier line."""
    run["log"](f"{metric}: {prefix} " + json.dumps({
        "programs": row["programs"], "mean_ms": row["mean_ms"],
        "ops_ms": row["ops_ms"], "unscoped_ms": row["unscoped_ms"],
        "parts": {f"{part}.{phase}": round(ms, 4)
                  for (part, phase), ms in row["parts"].items()},
        "largest_unscoped": row["unscoped"]}))


# ------------------------------------------------------------- serving
def span_tables(spans, prefix: str) -> list:
    """The tables of the ``program.parts`` spans of the program named
    ``prefix``, one per compile."""
    return [a["parts"] for name, _, _, a in spans or ()
            if name == SPAN and a.get("program") == prefix]


def serve_row(run: dict, prefix: str):
    """:func:`by_part` of a serving run's traced slice for the program
    named ``prefix`` (computed once a run); None where the run has no
    device trace, the slice no such program, or the engine recorded no
    table for it."""
    cache = run.setdefault("parts_rows", {})
    if prefix not in cache:
        trace = run.get("trace")
        tables = span_tables(run.get("spans"), prefix) \
            if operation_key is not None else []
        row = None
        if trace and tables and 0 in trace["devices"]:
            row = by_part(trace, prefix, tables, *run["trace_window_ns"])
            if not row["programs"]:
                row = None
        cache[prefix] = row
    return cache[prefix]


# ------------------------------------------------------------ training
def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, bytes for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield tag >> 3, value


def _map_entries(plane, field):
    """``(key, message bytes)`` of a ``map<int64, Message>`` field."""
    for number, entry in _fields(plane):
        if number == field:
            kv = dict(_fields(entry))
            yield kv.get(1, 0), kv.get(2, b"")


def event_op_names(path: str, plane_name: str = xplane.DEVICE_PREFIX + "0"
                   ) -> dict:
    """``{program id: {event name: op_name}}`` of one plane of an
    ``.xplane.pb``: the ``tf_op`` and ``program_id`` stats of each
    ``XEventMetadata`` (tsl's ``xplane.proto``: ``XSpace.planes = 1``;
    ``XPlane.name = 2, event_metadata = 4, stat_metadata = 5``;
    ``XEventMetadata.name = 2, stats = 5``; ``XStat.metadata_id = 1,
    uint64_value = 3, str_value = 5``; ``XStatMetadata.name = 2``).  The
    program id is the number in a program event's name
    (``jit_step(1552344013168649686)``).  Only the two metadata maps are
    decoded: the events stay unread, and ``xplane.load`` has them."""
    with open(path, "rb") as f:
        data = f.read()
    for number, plane in _fields(data):
        if number != 1 or not any(
                n == 2 and v.decode() == plane_name
                for n, v in _fields(plane)):
            continue
        stat_ids = {dict(_fields(meta)).get(2): key
                    for key, meta in _map_entries(plane, 5)}
        tf_op, program = stat_ids.get(b"tf_op"), stat_ids.get(b"program_id")
        programs = {}
        for _, meta in _map_entries(plane, 4):
            name, op_name, pid = None, "", None
            for n, v in _fields(meta):
                if n == 2:
                    name = v.decode()
                elif n == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op:
                        op_name = stat.get(5, b"").decode().rstrip(":")
                    elif stat.get(1) == program:
                        pid = stat.get(3)
            if name is not None:
                programs.setdefault(pid, {})[name] = op_name
        return programs
    return {}


def trace_file(run: dict):
    """The ``.xplane.pb`` this run wrote, found WITHOUT guessing a
    directory's name: ``benchmarks/run.py`` writes every cell's trace
    under its own ``out/trace/<cell>/``, and the file it loaded into
    ``run["trace"]`` is the one whose ``bench.window`` mark has this
    run's window bounds (``run["trace_window_ns"]``, read off that mark)
    to the nanosecond.  None where no file under ``out/trace`` does."""
    found = glob.glob(os.path.join(TRACE_ROOT, "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    t0, t1 = run["trace_window_ns"]
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        marks = [e for e in xplane.load(path)["host"]
                 if e[0] == xplane.WINDOW_MARK]
        if marks and marks[0][1] == t0 and marks[0][1] + marks[0][2] == t1:
            return path
    return None


def file_tables(path: str) -> list:
    """One table ``{operation_key: (part, phase)}`` per program of the
    trace file at ``path``, from its own ``op_name``s."""
    return [{operation_key(name): part_of(op_name)
             for name, op_name in names.items()}
            for names in event_op_names(path).values()]


def train_row(run: dict):
    """:func:`by_part` of a training run's traced steps (computed once a
    run), the table decoded from the trace's own file; None where the
    run has no device trace, the program side has no parser (the
    parent), the file cannot be told, or no operation of the step sits
    under a scope."""
    if "parts_row" not in run:
        row, trace = None, run.get("trace")
        if trace and part_of is not None and 0 in trace["devices"] \
                and trace["devices"][0]["modules"]:
            path = trace_file(run)
            if path is not None:
                row = by_part(trace, run["step_module_prefix"],
                              file_tables(path), *run["trace_window_ns"])
                if not row["programs"] \
                        or row["unscoped_ms"] >= row["ops_ms"]:
                    row = None
        run["parts_row"] = row
    return run["parts_row"]
