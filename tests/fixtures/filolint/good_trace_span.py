"""Fixture twin: every opened span is a declared TRACE_SPEC constant and
every declared span is opened somewhere."""

SPAN_GOOD = "fixture.good"
SPAN_OTHER = "fixture.other"
SPAN_LATE = "fixture.late"

TRACE_SPEC = {
    SPAN_GOOD: "a span the code opens",
    SPAN_OTHER: "opened by the tracer-attribute call form",
    SPAN_LATE: "an interval handed to tracer.record() after the fact",
}


class _T:
    def span(self, name, **tags):
        return name

    def record(self, name, t0_ns, t1_ns, **tags):
        return name


def work(span, hist):
    with span(SPAN_GOOD):
        pass
    t = tracer = _T()
    t.span(SPAN_OTHER)
    tracer.record(SPAN_LATE, 0, 1)
    hist.record("not a span: the receiver is no tracer")
