"""Share of token gaps that contain another request's prefill: a gap
between two ``decode.emit`` events of one request inside which an
``exec:decode.prefill`` event starts (a request's own prefill precedes
its first token, so any prefill inside a gap is another's)."""
import bisect

from chipbench import ringread


def read(ctx):
    ring = ctx["ring"]
    if ring is None:
        return None
    by_req = {}
    for t, args in ringread.instants(ring, "decode.emit"):
        by_req.setdefault(args.get("req"), []).append(t)
    starts = sorted(s for s, _ in ringread.spans(ring, "exec:decode.prefill"))
    gaps = stalled = 0
    for times in by_req.values():
        for a, b in zip(times, times[1:]):
            gaps += 1
            if bisect.bisect_right(starts, a) < bisect.bisect_left(starts, b):
                stalled += 1
    return 100.0 * stalled / gaps if gaps else None
