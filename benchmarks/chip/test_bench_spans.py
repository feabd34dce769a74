"""The reduction of the program's spans and named scopes (``spans.py``) and
the three metrics that read it, against a short trace recorded on one TPU
v5e while the harness served glm4-chat (``fixtures/glm4-chat-spans.json``:
0.3 s of a traced window, reduced by ``trace.load`` and ``spans.load``).
Each number is checked against a second, plainer computation over the same
events, on a timeline of ``NS``-long bins.  ``spans.load`` itself is
checked on a profile recorded on the CPU and on a device plane written
here, with a span and a scope that no list names."""
import glob
import json
import os
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from chip import harness, spans, trace

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "glm4-chat-spans.json"
NS = 100          # resolution of the plain timeline, ns
READERS = ("admit_idle_share", "embed_quant_ms", "lm_head_ms")


@pytest.fixture(scope="module")
def red():
    return json.loads(FIXTURE.read_text())


SCOPES = ("embed_quant", "lm_head")       # the model's named scopes


def ctx_for(red):
    return SimpleNamespace(trace=red, tracemod=trace, spans=spans,
                           modules={"decode": "step", "prefill": "pre"})


def bins(red, events):
    """Flags of the window in NS-long bins, set where one of ``events``
    (``[name, start_ns, dur_ns]``) is open."""
    lo, hi = red["window"]
    flags = np.zeros(int((hi - lo) // NS) + 1, bool)
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            flags[int(round((a - lo) / NS)):int(round((b - lo) / NS))] = True
    return flags


def test_fixture_is_a_real_trace(red):
    names = {n for n, _, _ in red["spans"]}
    for want in ("serve.step", "admit.schedule", "admit", "admit.fetch",
                 "admit.sample", "decode.reserve", "decode.step",
                 "decode.dispatch", "decode.wait", "decode.host"):
        assert want in names, want
    assert {n for n, _, _ in red["scoped"]} == set(SCOPES)
    assert red["ops"] and red["modules"] and red["host"]


def test_admit_idle_share_matches_a_plain_timeline(red):
    idle = ~bins(red, red["ops"])
    admit = bins(red, [e for e in red["spans"]
                       if e[0] in ("admit.schedule", "admit")])
    plain = 100.0 * (idle & admit).mean()
    got = harness.metric_reader("admit_idle_share")(ctx_for(red))
    assert got == pytest.approx(plain, abs=0.2) and got > 0


@pytest.mark.parametrize("scope", SCOPES)
def test_scoped_decode_time_matches_a_plain_sum(red, scope):
    lo, hi = red["window"]
    dec = [(s, s + d) for n, s, d in red["modules"]
           if n.startswith("jit_step(") and s < hi and s + d > lo]
    plain = sum(min(s + d, hi) - max(s, lo) for n, s, d in red["scoped"]
                if n == scope and s < hi and s + d > lo
                and any(a <= max(s, lo) < b for a, b in dec)) / 1e6 / len(dec)
    got = harness.metric_reader(f"{scope}_ms")(ctx_for(red))
    assert got == pytest.approx(plain, rel=1e-9) and got > 0


def test_idle_is_charged_to_the_innermost_span(red):
    """Each idle bin, charged by brute force to the span opened last among
    those open over it, sums to the sweep's table."""
    lo, hi = red["window"]
    idle = ~bins(red, red["ops"])
    label = np.full(idle.size, "none", dtype=object)
    for name, s, d in sorted(red["spans"], key=lambda e: (e[1], -e[2])):
        a, b = max(s, lo), min(s + d, hi)
        if b > a:     # later-opened spans overwrite the ones open around them
            label[int(round((a - lo) / NS)):int(round((b - lo) / NS))] = name
    table = spans.idle_by_innermost(red)
    assert sum(table.values()) == pytest.approx(
        trace.window_s(red) - trace.busy_s(red), rel=1e-9)
    for name in set(label[idle]) | set(table):
        plain = (label[idle] == name).sum() * NS / 1e9
        assert table.get(name, 0.0) == pytest.approx(plain, abs=2e-5), name
    within = spans.idle_by_innermost(red, within="serve.step")
    assert "none" not in within
    assert sum(within.values()) == pytest.approx(
        spans.idle_under(red, "serve.step"), rel=1e-9)
    harness_step = spans.idle_by_innermost(red, within="engine.step")
    assert sum(harness_step.values()) == pytest.approx(
        spans.idle_under(red, "engine.step"), rel=1e-9)
    assert 0 < sum(harness_step.values()) <= sum(table.values())


def test_open_stretches_join_program_spans_and_harness_annotations():
    red = {"window": [0, 100],
           "spans": [["admit", 10, 20], ["admit", 25, 10], ["serve.step", 0, 5]],
           "host": [["engine.step", 50, 60], ["client.wait", 0, 100]]}
    assert spans._open(red, "admit") == [(10, 35)]
    assert spans._open(red, "engine.step") == [(50, 100)]
    assert spans._open(red, "admit", "engine.step") == [(10, 35), (50, 100)]
    assert spans._open(red, "engine.step", "admit") == [(10, 35), (50, 100)]
    assert spans._open({"window": [0, 100], "host": []}, "admit") == []


def test_innermost_stretches_tile_the_window():
    red = {"window": [0, 100], "spans": [["serve.step", 10, 80],
                                         ["admit", 20, 30],
                                         ["admit.sample", 40, 10],
                                         ["decode.step", 60, 20]]}
    assert spans.innermost(red) == [
        (0, 10, "none"), (10, 20, "serve.step"), (20, 40, "admit"),
        (40, 50, "admit.sample"), (50, 60, "serve.step"),
        (60, 80, "decode.step"), (80, 90, "serve.step"), (90, 100, "none")]


def test_scoped_time_counts_only_ops_inside_the_executions():
    red = {"window": [0, 100], "scoped": [
        ["embed_quant", 5, 5], ["embed_quant", 20, 5], ["lm_head", 30, 5],
        ["embed_quant", 45, 5], ["embed_quant", 60, 5], ["lm_head", 98, 5]]}
    decode = [(15, 40), (55, 99)]
    assert spans.scoped_within(red, decode, "embed_quant") == 10e-9
    assert spans.scoped_within(red, decode, "lm_head") == 7e-9   # clipped
    assert spans.scoped_within(red, [], "lm_head") == 0.0


def test_scope_of_reads_path_components():
    assert spans.scopes_of("jit(step)/jit(main)/embed_quant/abs") == ["embed_quant"]
    assert spans.scopes_of("jit(step)/lm_head/dot_general") == ["lm_head"]
    assert spans.scopes_of("jit(step)/my_lm_head_x/dot") == ["my_lm_head_x"]
    assert spans.scopes_of("jit(step)/moe/route/moe/top_k:") == ["moe", "route"]
    assert spans.scopes_of("jit(step)/jit(main)/reduce_max:") == []
    assert spans.scopes_of("embed_quant") == []      # an operation, no scope


def _record_profile(logdir):
    """A CPU profile holding the harness's window annotation around a few
    of the engine's spans, from the program's disabled default tracer, a
    span that no list names, and a harness annotation."""
    import jax

    from repro.obs.trace import NULL_TRACER

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(logdir), profiler_options=opts):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("engine.step"):
                with NULL_TRACER.span("serve.step", step=0):
                    with NULL_TRACER.span("admit", request=0):
                        with NULL_TRACER.span("admit.sample", request=0):
                            jax.numpy.ones(8).block_until_ready()
                    with NULL_TRACER.span("toy.route", experts=8):
                        jax.numpy.zeros(8).block_until_ready()


def _add_device_plane(logdir, events):
    """Write into the profile under ``logdir`` a TPU plane whose "XLA Ops"
    line holds ``events``: ``(framework name, start_ns, dur_ns)``, the
    framework name in the ``tf_op`` stat of each event's metadata (every
    other one by reference to a stat metadata entry, as the profiler may
    store it)."""
    (path,) = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                        recursive=True)
    space = spans._xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())     # unknown fields are kept
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata.add(key=1).value.name = spans.SCOPE_STAT
    line = plane.lines.add(name="XLA Ops", timestamp_ns=0)
    for i, (name, start, dur) in enumerate(events):
        md = plane.event_metadata.add(key=100 + i).value
        if i % 2:
            plane.stat_metadata.add(key=200 + i).value.name = name
            md.stats.add(metadata_id=1, ref_value=200 + i)
        else:
            md.stats.add(metadata_id=1, str_value=name)
        line.events.add(metadata_id=100 + i, offset_ps=start * 1000,
                        duration_ps=dur * 1000)
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


def test_load_keeps_every_span_of_the_serving_thread(tmp_path):
    _record_profile(tmp_path)
    red = spans.load(str(tmp_path))
    names = [n for n, _, _ in red["spans"]]
    program = [n for n in names if n in ("serve.step", "admit", "admit.sample",
                                         "toy.route")]
    assert program == ["serve.step", "admit", "admit.sample", "toy.route"]
    assert "engine.step" not in names and trace.WINDOW_SPAN not in names
    lo, hi = red["window"]
    assert all(lo <= s and s + d <= hi for _, s, d in red["spans"])
    assert red["scoped"] == []
    # no device operation: the toy span's whole stretch is idle under it
    (toy,) = [e for e in red["spans"] if e[0] == "toy.route"]
    assert spans.idle_under({**red, "ops": []}, "toy.route") == toy[2] / 1e9


def test_load_keeps_every_named_scope(tmp_path):
    _record_profile(tmp_path)
    _add_device_plane(tmp_path, [
        ("jit(step)/jit(main)/embed_quant/reduce_max:", 10, 5),
        ("jit(step)/jit(main)/moe_dispatch/lm_head/dot:", 20, 7),
        ("jit(step)/jit(main)/add:", 30, 3),
        ("jit(step)/toy_scope/moe_dispatch/toy_scope/abs:", 40, 2)])
    red = spans.load(str(tmp_path))
    assert red["scoped"] == [["embed_quant", 10, 5], ["moe_dispatch", 20, 7],
                             ["lm_head", 20, 7], ["toy_scope", 40, 2],
                             ["moe_dispatch", 40, 2]]
    assert "toy.route" in {n for n, _, _ in red["spans"]}
    window = {"window": [0, 100], "scoped": red["scoped"]}
    assert spans.scoped_within(window, [(15, 45)], "moe_dispatch") == 9e-9
    assert spans.scoped_within(window, [(15, 45)], "toy_scope") == 2e-9


def test_any_span_or_scope_name_is_read(red):
    """A span and a scope that no list names, laid into the recorded trace,
    read as a plain timeline and a plain sum read them."""
    lo, hi = red["window"]
    dec = [(s, s + d) for n, s, d in red["modules"]
           if n.startswith("jit_step(") and s < hi and s + d > lo]
    span = ["moe.route", lo + (hi - lo) // 3, (hi - lo) // 4]
    ops = [["moe_dispatch", s + 1000, 500] for s, _ in dec[:3]]
    toy = dict(red, spans=red["spans"] + [span], scoped=red["scoped"] + ops)
    plain = (~bins(toy, toy["ops"]) & bins(toy, [span])).sum() * NS / 1e9
    assert spans.idle_under(toy, "moe.route") == pytest.approx(plain, abs=2e-5)
    assert spans.idle_under(toy, "moe.route") > 0
    assert spans.scoped_within(toy, dec, "moe_dispatch") == pytest.approx(
        len(ops) * 500e-9, rel=1e-9)


def test_readers_read_nothing_without_program_spans(red):
    """A program that opens no spans and names no scopes (the parent of
    this reduction) leaves every new metric out, and raises nothing; so
    does a trace whose serving thread holds only the runtime's events."""
    bare = {k: red[k] for k in ("ops", "modules", "host", "window")}
    runtime = dict(bare, scoped=[], spans=[["PjitFunction(step)", s, d]
                                           for _, s, d in red["spans"][:20]])
    for name in READERS:
        for tr in (bare, runtime):
            assert harness.metric_reader(name)(ctx_for(dict(tr))) is None, name
