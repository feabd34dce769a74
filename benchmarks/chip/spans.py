"""The program's own spans and named scopes in a profiler trace.

The engine's ``repro.obs`` tracer opens a ``jax.profiler.TraceAnnotation``
for each of its spans whenever a profiler session records, and the model
names two device costs with ``jax.named_scope`` (``embed_quant``,
``lm_head``).  ``load`` reduces a trace to those: the program's spans as
``[name, start_ns, dur_ns]`` (host events named in ``PROGRAM_SPANS``) and
the device operations under a scope as ``[scope, start_ns, dur_ns]``, on
the one clock the profiler gives the device's operations and the host's
annotations.  The reduction below works on those lists and on
``trace.load``'s, so a recorded trace can be kept as a JSON fixture.

An operation's scope is in the ``tf_op`` stat of its *event metadata*
(``jit(step)/embed_quant/reduce_max:`` on a TPU v5e), which
``jax.profiler.ProfileData`` does not expose; ``_scoped_ops`` reads it
from the ``XSpace`` protobuf with the few fields it needs declared here.

``attach`` adds the two lists to a reduction that ``trace.load`` made,
reading them from the same profile: the newest in the harness's temporary
directory, while the harness still holds it, if its traced window is the
reduction's.
"""
from __future__ import annotations

import functools
import glob
import os
import tempfile

from . import trace as tracemod

# the engine's spans (serve/engine.py, docs/metrics.md), outermost first
PROGRAM_SPANS = ("serve.step", "admit.schedule", "admit", "prefill.chunk",
                 "prefill.full", "admit.fetch", "admit.sample",
                 "decode.reserve", "decode.step", "decode.dispatch",
                 "decode.wait", "decode.host")
# the spans of the admission path: the scheduler's pop of queued requests
# (prefix-trie lookup, block allocation and eviction) and each admission
ADMIT_SPANS = ("admit.schedule", "admit")
SCOPES = ("embed_quant", "lm_head")
# the event-metadata stat of an "XLA Ops" event that holds the HLO op's
# framework name: the path of named scopes it was traced under
SCOPE_STAT = "tf_op"
TRACE_DIR_PREFIX = "chipbench-trace-"      # the harness's trace directories


def scope_of(framework_name: str) -> str | None:
    """The scope of ``SCOPES`` that a framework name lies under:
    ``jit(step)/jit(main)/embed_quant/abs`` is ``embed_quant``."""
    parts = framework_name.split("/")
    for scope in SCOPES:
        if scope in parts:
            return scope
    return None


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
    ``device_plane`` whose framework name lies under one of ``SCOPES``,
    timed as ``jax.profiler.ProfileData`` times them (whole ns, rounded
    down)."""
    space = _xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = []
    for plane in space.planes:
        if plane.name != device_plane:
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        scope_of_md = {}
        for e in plane.event_metadata:
            for st in e.value.stats:
                if stat_names.get(st.metadata_id) == SCOPE_STAT:
                    scope = scope_of(st.str_value
                                     or stat_names.get(st.ref_value, ""))
                    if scope:
                        scope_of_md[e.key] = scope
                    break
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                scope = scope_of_md.get(ev.metadata_id)
                if scope:
                    out.append([scope, line.timestamp_ns + ev.offset_ps // 1000,
                                ev.duration_ps // 1000])
    return out


def load(logdir: str, device_index: int = 0) -> dict:
    """Reduce the trace under ``logdir`` to ``{"window", "spans",
    "scoped"}``."""
    import jax

    path = _trace_file(logdir)
    out = {"window": None, "spans": [],
           "scoped": _scoped_ops(path, f"/device:TPU:{device_index}")}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in PROGRAM_SPANS:
                        out["spans"].append([e.name, e.start_ns, e.duration_ns])
                    elif e.name == tracemod.WINDOW_SPAN:
                        out["window"] = [e.start_ns, e.start_ns + e.duration_ns]
    for key in ("spans", "scoped"):
        out[key].sort(key=lambda e: e[1])
    return out


def attach(trace: dict) -> dict:
    """``trace`` (a ``trace.load`` reduction) with ``spans`` and ``scoped``
    added from the profile it was reduced from: the newest of the harness's
    trace directories, if its ``bench.window`` is ``trace["window"]``.
    Left as it is when it holds them already, or when the newest profile
    is another.  A stopgap until ``trace.load`` fills both keys itself."""
    if "spans" in trace and "scoped" in trace:
        return trace
    pattern = os.path.join(tempfile.gettempdir(), TRACE_DIR_PREFIX + "*",
                           "**", "*.xplane.pb")
    paths = glob.glob(pattern, recursive=True)
    if paths:
        red = load(os.path.dirname(max(paths, key=os.path.getmtime)))
        if red["window"] == trace["window"]:
            trace["spans"], trace["scoped"] = red["spans"], red["scoped"]
    return trace


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
    """The window cut into stretches, each named by the innermost program
    span open over it (the engine's spans nest on its one thread), or
    ``none``."""
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
    innermost program span open over it (``none`` where none was open);
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
