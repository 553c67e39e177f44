"""Interval arithmetic for the trace reduction. Intervals are (start, end)
pairs in any one unit; nothing here knows about a trace."""


def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(disjoint):
    return sum(e - s for s, e in disjoint)


def clip(disjoint, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in disjoint
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The part of disjoint, sorted `a` that disjoint, sorted `b` leaves."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(disjoint, lo, hi):
    """The idle intervals of [lo, hi] that `disjoint` leaves."""
    return subtract([(lo, hi)], clip(disjoint, lo, hi))


def self_times(events):
    """[(event, self time)]: each event's duration minus what the events
    nested inside it cover. `events` are (start, end, payload); siblings
    do not overlap (one device queue), children lie inside their parent."""
    out = []
    stack = []                       # [start, end, payload, covered]
    for s, e, payload in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((done[2], (done[1] - done[0]) - done[3]))
        if stack:
            stack[-1][3] += min(e, stack[-1][1]) - s
        stack.append([s, e, payload, 0])
    while stack:
        done = stack.pop()
        out.append((done[2], (done[1] - done[0]) - done[3]))
    return out
