"""The program's own spans and named scopes in a profiler trace.

The engine's ``repro.obs`` tracer opens a ``jax.profiler.TraceAnnotation``
for each of its spans whenever a profiler session records, and the model
names device work with ``jax.named_scope`` (``embed_quant``, ``lm_head``).
``load`` reduces a trace to those, whatever their names: as ``spans``,
every host event ``[name, start_ns, dur_ns]`` on the thread that opened
the harness's window (the serving thread: the program's spans, and the
runtime's own events under them, such as ``PjitFunction(step)``), the
harness's own annotations left out; as ``scoped``, each device operation
once under each named scope of its path, ``[scope, start_ns, dur_ns]``.
Both lie on the one clock the profiler gives the device's operations and
the host's annotations.  The reduction below works on those lists and on
``trace.load``'s, so a recorded trace can be kept as a JSON fixture.

An operation's scopes are in the ``tf_op`` stat of its *event metadata*
(``jit(step)/embed_quant/reduce_max:`` on a TPU v5e), which
``jax.profiler.ProfileData`` does not expose; ``_scoped_ops`` reads it
from the ``XSpace`` protobuf with the few fields it needs declared here.
"""
from __future__ import annotations

import functools
import glob
import os

from . import trace as tracemod

# the spans of the admission path: the scheduler's pop of queued requests
# (prefix-trie lookup, block allocation and eviction) and each admission
ADMIT_SPANS = ("admit.schedule", "admit")
# the event-metadata stat of an "XLA Ops" event that holds the HLO op's
# framework name: the path of named scopes it was traced under
SCOPE_STAT = "tf_op"


def scopes_of(framework_name: str) -> list[str]:
    """The named scopes a framework name lies under, outermost first: its
    path's components but the last (the operation) and those that name a
    transformation (``jit(step)``).  ``jit(step)/jit(main)/embed_quant/abs``
    lies under ``embed_quant``."""
    parts = framework_name.split("/")[:-1]
    return list(dict.fromkeys(p for p in parts if p and "(" not in p))


def _trace_file(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {logdir}, found {paths}")
    return paths[0]


@functools.cache
def _xspace():
    """The message class of an ``XSpace`` (tsl/profiler/protobuf/
    xplane.proto) with only the fields ``_scoped_ops`` reads, under their
    field numbers there; the parser skips every other field."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    I64, U64, STR, MSG = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING, F.TYPE_MESSAGE
    fdp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench_xplane",
        syntax="proto3")
    # (field, number, type, message type, repeated); a map<int64, ...> is
    # read as its repeated key/value entries
    for name, fields in (
            ("XStat", (("metadata_id", 1, I64, None, False),
                       ("str_value", 5, STR, None, False),
                       ("ref_value", 7, U64, None, False))),
            ("XEvent", (("metadata_id", 1, I64, None, False),
                        ("offset_ps", 2, I64, None, False),
                        ("duration_ps", 3, I64, None, False))),
            ("XLine", (("name", 2, STR, None, False),
                       ("timestamp_ns", 3, I64, None, False),
                       ("events", 4, MSG, "XEvent", True))),
            ("XEventMetadata", (("stats", 5, MSG, "XStat", True),)),
            ("XStatMetadata", (("name", 2, STR, None, False),)),
            ("EventMetadataEntry", (("key", 1, I64, None, False),
                                    ("value", 2, MSG, "XEventMetadata", False))),
            ("StatMetadataEntry", (("key", 1, I64, None, False),
                                   ("value", 2, MSG, "XStatMetadata", False))),
            ("XPlane", (("name", 2, STR, None, False),
                        ("lines", 3, MSG, "XLine", True),
                        ("event_metadata", 4, MSG, "EventMetadataEntry", True),
                        ("stat_metadata", 5, MSG, "StatMetadataEntry", True))),
            ("XSpace", (("planes", 1, MSG, "XPlane", True),))):
        msg = fdp.message_type.add(name=name)
        for fname, number, ftype, tname, repeated in fields:
            f = msg.field.add(name=fname, number=number, type=ftype,
                              label=(F.LABEL_REPEATED if repeated
                                     else F.LABEL_OPTIONAL))
            if tname:
                f.type_name = f".chipbench_xplane.{tname}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench_xplane.XSpace"))


def _scoped_ops(path: str, device_plane: str) -> list[list]:
    """``[scope, start_ns, dur_ns]`` of the "XLA Ops" events on
    ``device_plane``, once for each named scope their framework name lies
    under, timed as ``jax.profiler.ProfileData`` times them (whole ns,
    rounded down)."""
    space = _xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        if plane.name != device_plane:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        scopes_of_md = {}
        for e in plane.event_metadata:
            for st in e.value.stats:
                if stat_names.get(st.metadata_id) == SCOPE_STAT:
                    scopes = scopes_of(st.str_value
                                       or stat_names.get(st.ref_value, ""))
                    if scopes:
                        scopes_of_md[e.key] = scopes
                    break
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                start = line.timestamp_ns + ev.offset_ps // 1000
                for scope in scopes_of_md.get(ev.metadata_id, ()):
                    out.append([scope, start, ev.duration_ps // 1000])
    return out


def load(logdir: str, device_index: int = 0) -> dict:
    """Reduce the trace under ``logdir`` to ``{"window", "spans",
    "scoped"}``."""
    import jax

    path = _trace_file(logdir)
    out = {"window": None, "spans": [],
           "scoped": _scoped_ops(path, f"/device:TPU:{device_index}")}
    harness_spans = set(tracemod.HOST_SPANS) | {tracemod.WINDOW_SPAN}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [[e.name, e.start_ns, e.duration_ns] for e in line.events]
            window = [e for e in events if e[0] == tracemod.WINDOW_SPAN]
            if window:           # the serving thread
                out["window"] = [window[0][1], window[0][1] + window[0][2]]
                out["spans"] += [e for e in events if e[0] not in harness_spans]
    for key in ("spans", "scoped"):
        out[key].sort(key=lambda e: e[1])
    return out


def idle_intervals(trace) -> list[tuple[float, float]]:
    """The stretches of the window in which no operation ran on the device."""
    lo, hi = trace["window"]
    edges = [lo] + [x for iv in tracemod.busy_intervals(trace) for x in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _intersect(a, b) -> list[tuple[float, float]]:
    """The intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _open(trace, *names: str) -> list[tuple[float, float]]:
    """The stretches of the window in which an event named in ``names``
    was open, from the program's spans and the harness's annotations."""
    events = [e for e in trace.get("spans", []) + trace.get("host", [])
              if e[0] in names]
    return tracemod.busy_intervals({"window": trace["window"], "ops": events})


def idle_under(trace, *names: str) -> float:
    """Seconds of the window in which no operation ran on the device while
    a span named in ``names`` was open."""
    return sum(e - s for s, e in _intersect(idle_intervals(trace),
                                            _open(trace, *names))) / 1e9


def innermost(trace) -> list[tuple[float, float, str]]:
    """The window cut into stretches, each named by the innermost span open
    over it (the serving thread's events nest), or ``none``."""
    lo, hi = trace["window"]
    segs, stack, t = [], [], lo      # stack: (end, name), innermost last

    def close_until(x):
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                segs.append((t, end, name))
                t = end

    for name, s, e in sorted(tracemod._clip(trace["spans"], lo, hi),
                             key=lambda x: (x[1], -x[2])):
        close_until(s)
        if s > t:
            segs.append((t, s, stack[-1][1] if stack else "none"))
            t = s
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    close_until(hi)
    if hi > t:
        segs.append((t, hi, "none"))
    return segs


def idle_by_innermost(trace, within: str | None = None) -> dict[str, float]:
    """Seconds of device idle time in the window, each charged to the
    innermost span open over it (``none`` where none was open);
    with ``within``, only idle time while that span or harness
    annotation was open."""
    idle = idle_intervals(trace)
    if within is not None:
        idle = _intersect(idle, _open(trace, within))
    segs = innermost(trace)
    out: dict[str, float] = {}
    i = j = 0
    while i < len(idle) and j < len(segs):
        s, e = max(idle[i][0], segs[j][0]), min(idle[i][1], segs[j][1])
        if e > s:
            out[segs[j][2]] = out.get(segs[j][2], 0.0) + (e - s) / 1e9
        if idle[i][1] < segs[j][1]:
            i += 1
        else:
            j += 1
    return out


def scoped_within(trace, executions, scope: str) -> float:
    """Seconds of the operations under ``scope`` that start inside one of
    ``executions`` (``(start_ns, end_ns)`` of one executable's runs)."""
    ops = [e for e in trace["scoped"] if e[0] == scope]
    return tracemod.ops_within({"window": trace["window"], "ops": ops},
                               executions, (scope,))
