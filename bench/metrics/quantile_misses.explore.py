"""Latency percentiles per exploration that the host took anew over a
stage-2 row's samples instead of reading the quantiles stage 2 carried:
the ``quantile_misses`` counter that ``SurrogateResult.p`` notes on the
innermost span (``spac.screen``, ``spac.stage3``, ``spac.search.tell``),
over the completed ``spac.explore`` roots of the traced window.  A program
that notes no ``quantile_reads`` (one whose rows carry no quantiles) reads
nothing."""


def read(ctx):
    try:
        from repro.analysis import spans
    except ImportError:             # a program without spans
        return None
    recs = spans.records()
    roots = {r.id for r in recs if r.name == "spac.explore" and r.parent is None}
    if not roots or spans.dropped():
        return None
    noted = [r.attrs for r in recs
             if r.root in roots and "quantile_reads" in r.attrs]
    if not noted:
        return None
    return float(sum(a.get("quantile_misses", 0) for a in noted)) / len(roots)
