"""Mean seconds per exploration in the NSGA-II search (host ask/tell plus
one stage-2 call per generation), from ``ScenarioReport.stage2_time_s``."""


def read(ctx):
    ex = ctx["explorations"]
    if not ctx["search"] or not ex:
        return None
    return sum(e["stage2_s"] for e in ex) / len(ex)
