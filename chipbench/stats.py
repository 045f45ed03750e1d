"""Window and percentile arithmetic on plain lists of timestamps.

Every deciding number of a serving cell is counted per token event
between the window's two marks, never per completed request: a request
that straddles a mark contributes the tokens, gaps and first token that
fall inside, and nothing else.
"""
import math


def percentile(values, q):
    """The q-th percentile (0..100) with linear interpolation between
    order statistics (numpy's default); None for an empty list."""
    vals = sorted(values)
    if not vals:
        return None
    pos = (len(vals) - 1) * (float(q) / 100.0)
    lo = math.floor(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50)


def mean(values):
    vals = list(values)
    return sum(vals) / len(vals) if vals else None


def in_window(t, t_open, t_close):
    """An event belongs to the window when it happened after the opening
    mark and not after the closing one."""
    return t_open < t <= t_close


def count_in_window(times, t_open, t_close):
    return sum(1 for t in times if in_window(t, t_open, t_close))


def token_gaps(streams, t_open, t_close):
    """Gaps between consecutive tokens of one stream, over all gaps that
    END inside the window. `streams` is an iterable of per-stream lists
    of token timestamps, each in emission order."""
    gaps = []
    for times in streams:
        for a, b in zip(times, times[1:]):
            if in_window(b, t_open, t_close):
                gaps.append(b - a)
    return gaps


def first_token_latencies(requests, t_open, t_close):
    """Submit -> first token, over requests SUBMITTED inside the window
    whose first token has arrived. `requests` yields (t_submit,
    t_first_or_None)."""
    return [t_first - t_submit for t_submit, t_first in requests
            if in_window(t_submit, t_open, t_close) and t_first is not None]


def rate(count, t_open, t_close):
    """Events per second over the whole window."""
    span = t_close - t_open
    if span <= 0:
        raise ValueError(f"empty window: {t_open} .. {t_close}")
    return count / span


def spans_inside(outer, inner):
    """For each (start, dur) in `outer`, the summed duration of the
    `inner` spans that start inside it. Both lists sorted by start."""
    out, j = [], 0
    inner = sorted(inner)
    for start, dur in sorted(outer):
        end = start + dur
        while j < len(inner) and inner[j][0] < start:
            j += 1
        k, covered = j, 0.0
        while k < len(inner) and inner[k][0] < end:
            covered += inner[k][1]
            k += 1
        out.append((start, dur, covered))
    return out
