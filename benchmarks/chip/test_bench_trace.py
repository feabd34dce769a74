"""The trace reduction against a short trace recorded on one TPU v5e while
the harness served glm4-chat (``fixtures/glm4-chat-trace.json``: 0.3 s of
a traced window, reduced by ``trace.load``).  Each number is checked
against a second, plainer computation over the same events."""
import json
from pathlib import Path

import numpy as np
import pytest

from chip import trace

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "glm4-chat-trace.json"
NS = 100          # resolution of the plain timeline, ns


@pytest.fixture(scope="module")
def red():
    return json.loads(FIXTURE.read_text())


def timeline(red, events):
    """Busy flags of the window in NS-long bins (event times are floats in ns)."""
    lo, hi = red["window"]
    busy = np.zeros(int((hi - lo) // NS) + 1, bool)
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            busy[int(round((a - lo) / NS)):int(round((b - lo) / NS))] = True
    return busy


def test_fixture_is_a_real_trace(red):
    assert red["ops"] and red["modules"] and red["host"]
    names = {trace.kind(n) for n, _, _ in red["ops"]}
    assert any(n.startswith("qmm_") for n in names)
    assert any(n.startswith("paged_decode_attention") for n in names)
    assert {n for n, _, _ in red["host"]} <= set(trace.HOST_SPANS)


def test_idle_share_matches_a_plain_timeline(red):
    busy = timeline(red, red["ops"])
    plain = busy.mean()
    assert trace.busy_s(red) / trace.window_s(red) == pytest.approx(plain, abs=2e-3)
    assert 0.0 < trace.busy_s(red) <= trace.window_s(red)


def test_executable_time_and_kernel_sums(red):
    lo, hi = red["window"]
    dec = trace.modules(red, "step")
    want = sum(min(s + d, hi) - max(s, lo) for n, s, d in red["modules"]
               if n.startswith("jit_step(") and s < hi and s + d > lo)
    assert sum(e - s for s, e in dec) == want and dec
    # every qmm and attention kernel runs inside some executable
    for prefix in ("qmm_", "paged_decode_attention"):
        inside = trace.ops_within(red, dec + trace.modules(red, "pre"), (prefix,))
        plain = sum(min(s + d, hi) - max(s, lo) for n, s, d in red["ops"]
                    if n.startswith(prefix) and s < hi and s + d > lo
                    and any(a <= max(s, lo) < b for a, b in
                            dec + trace.modules(red, "pre"))) / 1e9
        assert inside == pytest.approx(plain, rel=1e-9) and inside > 0


def test_idle_gaps_are_charged_to_the_open_annotation(red):
    gaps = trace.idle_gaps(red)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    idle = ~timeline(red, red["ops"])
    # the longest gap, found again on the plain timeline, and the annotation
    # open over most of it
    runs, start = [], None
    for i, v in enumerate(np.append(idle, False)):
        if v and start is None:
            start = i
        elif not v and start is not None:
            runs.append((i - start, start, i))
            start = None
    n, a, b = max(runs)
    assert gaps[0][1] == pytest.approx(n * NS / 1e9, abs=2 * NS / 1e9)
    lo = red["window"][0]
    cover = {}
    for name, s, d in red["host"]:
        o = min(s + d, lo + b * NS) - max(s, lo + a * NS)
        if o > 0:
            cover[name] = cover.get(name, 0) + o
    assert gaps[0][0] == (max(cover, key=cover.get) if cover else "none")


def test_top_ops_sum_instances(red):
    top = trace.top_ops(red)
    assert len(top) <= 10 and len({n for n, _ in top}) == len(top)
    assert all("." not in n or not n.rsplit(".", 1)[1].isdigit() for n, _ in top)
    assert top[0][1] >= top[-1][1] > 0
