"""Helpers for the readers of the decode scheduler's tiling spans
(``decode.loop`` and what it contains, all written on the engine's
thread; ``docs/observability.md`` lists them). Beside ``ringread``,
which these readers use as it is. Ring events are tuples ``(phase,
name, start, duration, thread, args)`` on ``perf_counter``."""
import bisect
import collections
import itertools
import operator

from . import ringread, stats

ENGINE_SPANS = ("decode.loop", "decode.step", "decode.admit")
TOUCH_S = 1e-9          # spans closer than this touch, they do not nest


def durations_ms(ring, name):
    return [1e3 * d for _, d in ringread.spans(ring, name)]


def inside_ms(ring, outer, inner_names):
    """Per `outer` span that holds any of them, the summed duration of
    the `inner_names` spans that start inside it, in milliseconds."""
    inner = [s for n in inner_names for s in ringread.spans(ring, n)]
    return [1e3 * covered for _, _, covered in
            stats.spans_inside(ringread.spans(ring, outer), inner)
            if covered > 0]


def arg_values(ring, name, key):
    return [a[key] for a in ringread.span_args(ring, name) if key in a]


def engine_thread(ring):
    """The thread that wrote the scheduler's spans (None without any)."""
    tids = collections.Counter(e[4] for e in ring or []
                               if e[0] == "X" and e[1] in ENGINE_SPANS)
    return tids.most_common(1)[0][0] if tids else None


def segments(ring, tid):
    """The thread's time cut at every span boundary: disjoint, sorted
    ``(start, end, names, leaf)`` where `names` are the spans that
    cover the piece, outermost first, and `leaf` says that the
    innermost of them holds no other span (its whole extent is then one
    piece). Time inside no span at all yields no piece."""
    events = sorted(((e[2], e[2] + e[3], e[1]) for e in ring or []
                     if e[0] == "X" and e[4] == tid),
                    key=lambda e: (e[0], -e[1]))
    pieces, stack, cursor = [], [], None   # stack of [name, end, leaf]

    def emit(upto):
        if upto > cursor:
            pieces.append((cursor, upto, tuple(s[0] for s in stack),
                           stack[-1]))

    for start, end, name in events:
        while stack and start >= stack[-1][1] - TOUCH_S:
            emit(stack[-1][1])
            cursor = max(cursor, stack.pop()[1])
        if stack:
            emit(start)
            stack[-1][2] = False
            end = min(end, stack[-1][1])    # a span written by hand
        cursor = start
        stack.append([name, end, True])
    while stack:
        emit(stack[-1][1])
        cursor = max(cursor, stack.pop()[1])
    return [(a, b, names, top[2]) for a, b, names, top in pieces]


def overlap(pieces, lo, hi):
    """[(seconds, names, leaf)] of the parts inside [lo, hi) of
    `segments`' pieces (sorted and disjoint, so their ends are sorted
    too)."""
    out = []
    first = bisect.bisect_right(pieces, lo, key=operator.itemgetter(1))
    for a, b, names, leaf in itertools.islice(pieces, first, None):
        if a >= hi:
            break
        out.append((min(b, hi) - max(a, lo), names, leaf))
    return out


def unspanned_share(ring, t_open, t_close):
    """Share (%) of the window that the engine's thread spent inside no
    leaf span: the self time of the spans that hold others, and the time
    outside every span. None without a ``decode.loop`` in the ring."""
    if not ringread.spans(ring, "decode.loop") or t_close <= t_open:
        return None
    pieces = segments(ring, engine_thread(ring))
    in_leaf = sum(sec for sec, _, leaf in overlap(pieces, t_open, t_close)
                  if leaf)
    return 100.0 * (1.0 - in_leaf / (t_close - t_open))
