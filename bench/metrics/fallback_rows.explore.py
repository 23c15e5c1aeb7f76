"""Stage-4 rows per exploration that left the batched verifier for the
serial oracle (degenerate depth, a binding shared cap, no convergence):
the ``fallback_rows`` counter on the ``spac.stage4`` spans, over the
completed ``spac.explore`` roots of the traced window."""


def read(ctx):
    try:
        from repro.analysis import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    roots = {r.id for r in recs if r.name == "spac.explore" and r.parent is None}
    if not roots or spans.dropped():
        return None
    rows = sum(r.attrs.get("fallback_rows", 0) for r in recs
               if r.root in roots and r.name == "spac.stage4")
    return float(rows) / len(roots)
