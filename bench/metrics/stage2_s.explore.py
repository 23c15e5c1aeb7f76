"""Mean seconds per exploration in stage 2 (the batched surrogate call),
from the program's own ``ScenarioReport.stage2_time_s``; exhaustive
explorations only (under a search see ``search_s.explore``)."""


def read(ctx):
    ex = ctx["explorations"]
    if ctx["search"] or not ex:
        return None
    return sum(e["stage2_s"] for e in ex) / len(ex)
