"""Fixture: span-surface violations. The literal span name and the
undeclared SPAN_ constant must trip surface-trace-undeclared; the declared
span nothing ever opens must trip surface-trace-unused."""

SPAN_GOOD = "fixture.good"
SPAN_DEAD = "fixture.dead"
SPAN_ROGUE = "fixture.rogue"         # defined but NOT a TRACE_SPEC key

TRACE_SPEC = {
    SPAN_GOOD: "a span the code opens",
    SPAN_DEAD: "declared but never opened anywhere",
}


def work(span, tracer):
    tracer.record("fixture.late", 0, 1)     # literal name through record()
    with span(SPAN_GOOD):
        pass
    with span("fixture.literal"):    # literal name: one-spelling rule
        pass
    with span(SPAN_ROGUE):           # constant exists, spec entry doesn't
        pass
