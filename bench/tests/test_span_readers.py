"""The per-layer readers of the program's own spans (``repro.obs``),
on hand-made span lists."""
import os

import pytest

import harness
from repro.obs import SpanRecord


def _reader(name):
    return harness.load_module(
        os.path.join(harness.BENCH, "metrics", f"{name}.py"),
        "test_metric_" + name.replace(".", "_"))


def _span(sid, name, start, end, parent=None, **kw):
    return SpanRecord(name, start, end, sid, parent, 1, {}, **kw)


def _sweep_spans():
    """A Study of two chunks: prepare, two dispatches (each with a pull of
    0.1 s inside), two materializes (each with a pull of 0.2 s), two
    fills; times in seconds."""
    return [
        _span(1, "repro.study.run", 0.0, 10.0),
        _span(2, "repro.stream.prepare", 0.0, 0.5, 1),
        _span(3, "repro.stream.dispatch", 0.5, 1.5, 1),
        _span(4, "repro.engine.stack_mits", 0.6, 1.0, 3,
              own_compiles=1, own_compile_s=0.25, compiles=1,
              compile_s=0.25),
        _span(5, "repro.stream.pull", 1.4, 1.5, 3),
        _span(6, "repro.stream.dispatch", 1.5, 2.5, 1),
        _span(7, "repro.stream.pull", 2.4, 2.5, 6),
        _span(8, "repro.stream.materialize", 2.5, 3.5, 1),
        _span(9, "repro.stream.pull", 2.5, 2.7, 8),
        _span(10, "repro.study.fill_chunk", 3.5, 4.0, 1),
        _span(11, "repro.stream.materialize", 4.0, 5.0, 1),
        _span(12, "repro.stream.pull", 4.0, 4.2, 11),
        _span(13, "repro.study.fill_chunk", 5.0, 5.5, 1),
        # a pull outside the executor's host steps is not subtracted
        _span(14, "repro.stream.pull", 6.0, 7.0, 1),
    ]


def test_host_ms_per_chunk_excludes_nested_pulls():
    value, note = _reader("host_ms_per_chunk.sweep").reduce(_sweep_spans())
    # host spans 0.5 + 2 * 1.0 + 2 * 1.0 + 2 * 0.5 = 5.5 s, less the
    # pulls inside them 2 * 0.1 + 2 * 0.2 = 0.6 s, over two chunks
    assert value == pytest.approx(1e3 * 4.9 / 2)
    assert note["chunks"] == 2
    assert note["pull_ms_per_chunk"] == pytest.approx(1e3 * 0.6 / 2)
    self_ms = note["self_ms_per_chunk"]
    assert self_ms["repro.stream.dispatch"] == pytest.approx(
        1e3 * (2.0 - 0.4 - 0.2) / 2)
    assert self_ms["repro.study.run"] == pytest.approx(
        1e3 * (10.0 - 5.5 - 1.0) / 2)
    assert (note["compiles"], note["compile_s"]) == (1, 0.25)


def _tick_spans():
    """Two ticks of 0.1 s; the second dispatches and compiles twice."""
    return [
        _span(1, "repro.control.tick", 0.0, 0.1),
        _span(2, "repro.source.next", 0.0, 0.01, 1),
        _span(3, "repro.detector.step", 0.01, 0.03, 1),
        _span(4, "repro.controller.decide", 0.03, 0.09, 1),
        _span(5, "repro.control.tick", 1.0, 2.0),
        _span(6, "repro.source.next", 1.0, 1.01, 5),
        _span(7, "repro.detector.step", 1.01, 1.03, 5),
        _span(8, "repro.controller.decide", 1.03, 1.07, 5),
        _span(9, "repro.control.dispatch", 1.07, 1.97, 5,
              compiles=2, compile_s=0.6, own_compiles=0),
        _span(10, "repro.ladder.build", 1.07, 1.5, 9,
              compiles=1, compile_s=0.4, own_compiles=1, own_compile_s=0.4),
        _span(11, "repro.source.apply", 1.5, 1.97, 9,
              compiles=1, compile_s=0.2, own_compiles=1, own_compile_s=0.2),
    ]


def test_decide_ms_per_tick_over_ticks():
    value, note = _reader("decide_ms_per_tick.control").reduce(_tick_spans())
    assert value == pytest.approx(1e3 * 0.10 / 2)
    assert note["ticks"] == 2
    child = note["child_ms_per_tick"]
    assert child["repro.controller.decide"] == pytest.approx(value)
    assert child["repro.control.dispatch"] == pytest.approx(1e3 * 0.9 / 2)
    # (0.1 - 0.09) + (1.0 - 0.97) s of the ticks outside their children
    assert note["tick_self_ms"] == pytest.approx(1e3 * 0.04 / 2)


def test_compile_ms_per_dispatch_over_dispatches():
    value, note = _reader("compile_ms_per_dispatch.control").reduce(
        _tick_spans())
    assert value == pytest.approx(600.0)
    assert note["dispatches"] == 1
    assert note["compiles_by_span"] == {
        "repro.ladder.build": {"count": 1, "s": 0.4},
        "repro.source.apply": {"count": 1, "s": 0.2}}


@pytest.mark.parametrize("name", ["host_ms_per_chunk.sweep",
                                  "decide_ms_per_tick.control",
                                  "compile_ms_per_dispatch.control"])
def test_no_spans_read_nothing(name):
    mod = _reader(name)
    assert mod.reduce([]) is None

    class Ctx:
        notes = {}

        def note(self, k, v):
            self.notes[k] = v

    from repro import obs
    obs.clear()
    assert mod.read(Ctx()) is None


def test_each_reader_finds_only_its_own_spans():
    assert _reader("host_ms_per_chunk.sweep").reduce(_tick_spans()) is None
    for name in ("decide_ms_per_tick.control",
                 "compile_ms_per_dispatch.control"):
        assert _reader(name).reduce(_sweep_spans()) is None
