"""Helpers for the per-layer readers that read the program's event
ring (``paddle_tpu.observability.tracez``): events are tuples
``(phase, name, start, duration, thread, args)`` on ``perf_counter``."""

from . import stats


def spans(ring, name):
    """[(start, duration)] of the complete ("X") events called `name`."""
    return [(e[2], e[3]) for e in ring or [] if e[0] == "X" and e[1] == name]


def span_args(ring, name):
    return [e[5] or {} for e in ring or [] if e[0] == "X" and e[1] == name]


def instants(ring, name):
    """[(time, args)] of the instant ("i") events called `name`."""
    return [(e[2], e[5] or {}) for e in ring or []
            if e[0] == "i" and e[1] == name]


def self_ms(ring, outer, inner_names):
    """Per `outer` span, its duration less the `inner_names` events
    that start inside it, in milliseconds."""
    inner = [s for n in inner_names for s in spans(ring, n)]
    return [1e3 * (dur - covered)
            for _, dur, covered in stats.spans_inside(spans(ring, outer),
                                                      inner)]
