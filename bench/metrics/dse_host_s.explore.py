"""Mean seconds per exploration in the host stages of Algorithm 1 around
the two batched calls: stage 1, the stage-2 screen, stage-3 sizing and
the stage-4 ranking with the report (``spac.stage1``, ``spac.screen``,
``spac.stage3``, ``spac.finalize``), over the completed ``spac.explore``
roots of the traced window."""

NAMES = ("spac.stage1", "spac.screen", "spac.stage3", "spac.finalize")


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
             if r.root in roots and r.name in NAMES)
    return ns * 1e-9 / len(roots)
