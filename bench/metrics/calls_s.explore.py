"""Mean seconds per exploration inside device calls, as the host sees
them: dispatch, copies in, the run and the fetch back of every stage-2
and stage-4 call (``spac.stage2.scan``, ``spac.stage4.round1``,
``.replay``, ``.scan``), over the completed ``spac.explore`` roots of the
traced window."""

CALLS = ("spac.stage2.scan", "spac.stage4.round1", "spac.stage4.replay",
         "spac.stage4.scan")


def read(ctx):
    try:
        from repro.analysis import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    roots = {r.id for r in recs if r.name == "spac.explore" and r.parent is None}
    if not roots or spans.dropped():
        return None
    ns = sum(r.end_ns - r.start_ns for r in recs
             if r.root in roots and r.name in CALLS)
    return ns * 1e-9 / len(roots)
