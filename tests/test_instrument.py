"""Host spans and counters (``repro.core.instrument``): what a span log
keeps, how spans nest, how counters sum, and that logs stay apart."""
import threading

from repro.core import instrument
from repro.core.instrument import add, span, span_log


def test_span_keeps_nothing_with_no_log_open():
    with span("repro.test.outer", window=3):
        add("bytes", 10)
    assert instrument._LOG.get() is None
    with span_log() as log:
        pass
    assert log.spans == [] and log.counts == {}


def test_nested_spans_record_their_parent():
    with span_log() as log:
        with span("a"):
            with span("b", kind="fixed", lanes=2):
                with span("c"):
                    pass
            with span("d"):
                pass
        with span("e"):
            pass
    assert [s.name for s in log.spans] == ["a", "b", "c", "d", "e"]
    assert [s.parent for s in log.spans] == [None, 0, 1, 0, None]
    for s in log.spans:
        assert 0 <= s.start_ns <= s.end_ns
        if s.parent is not None:
            p = log.spans[s.parent]
            assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns
    a = log.spans[0]
    assert log.ms("a") == 1e-6 * (a.end_ns - a.start_ns)
    b, d = log.spans[1], log.spans[3]
    assert log.ms("b", "d") == 1e-6 * ((b.end_ns - b.start_ns)
                                       + (d.end_ns - d.start_ns))


def test_span_closes_when_its_body_raises():
    with span_log() as log:
        try:
            with span("a"):
                raise ValueError
        except ValueError:
            pass
        with span("b"):
            pass
    assert log.spans[0].end_ns >= log.spans[0].start_ns
    assert log.spans[1].parent is None


def test_counters_add():
    with span_log() as log:
        add("readback_bytes", 8)
        with span("a"):
            add("readback_bytes", 24)
            add("placed_bytes", 5)
    assert log.counts == {"readback_bytes": 32, "placed_bytes": 5}


def test_two_logs_do_not_mix():
    # nested: the inner log takes what happens while it is open
    with span_log() as outer:
        with span("before"):
            add("n", 1)
        with span_log() as inner:
            with span("inside"):
                add("n", 2)
        with span("after"):
            add("n", 4)
    assert [s.name for s in outer.spans] == ["before", "after"]
    assert [s.name for s in inner.spans] == ["inside"]
    assert outer.counts == {"n": 5} and inner.counts == {"n": 2}

    # threads: each caller's log is its own
    logs, go = {}, threading.Barrier(2)

    def work(k):
        with span_log() as log:
            go.wait(timeout=30)
            for _ in range(50):
                with span(k):
                    add(k, 1)
        logs[k] = log

    threads = [threading.Thread(target=work, args=(k,)) for k in "xy"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    for k in "xy":
        assert {s.name for s in logs[k].spans} == {k}
        assert len(logs[k].spans) == 50 and logs[k].counts == {k: 50}
